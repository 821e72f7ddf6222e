"""One tick block, one table of cache kinds (``models/paged.forward_paged``):
what a dense-attention model gets from sharing the skeleton the latent tick
brought (``cfg.segments``, the whole-stack expert call, ``with_stats``), the
one function that says which attention a tick runs, what is frozen of a
pool (keys, shapes, dtypes, bytes, walks, for every serving cell), that the
engine books a pool by the table's classes, and that every family's tick is
one ``wo`` product a layer under ``T.scan_periods``. Toy width, float32.
"""
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import family_harness
from deepspeed_tpu.inference.fastgen import FastGenEngine
from deepspeed_tpu.inference.ragged import RaggedInferenceEngine
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas.paged_attention import (latent_paged_attention,
                                                      paged_attention,
                                                      tile_rows)

BS, MB = 8, 16
E, H, F = 4, 64, 32

DENSE = dict(vocab_size=256, hidden_size=H, num_layers=3, num_heads=4,
             num_kv_heads=2, pos_emb="rope", norm="rmsnorm",
             activation="swiglu", use_bias=False, dtype="float32",
             max_seq_len=128,
             # untied and wide: a tied toy model only echoes its last token
             tie_embeddings=False, init_std=0.1)
EXPERTS = dict(n_experts=E, moe_top_k=2, moe_ffn_size=F, moe_shared_size=F,
               moe_dispatch="ragged")
LATENT = dict(mla=True, kv_lora_rank=16, qk_nope_head_dim=16,
              qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=0)

MODELS = {
    # Mixtral / Qwen-MoE / OLMoE: per-head K/V pools, every layer experts
    "dense-attention-experts": dict(DENSE, **EXPERTS),
    # a leading dense layer ahead of the expert stack, on K/V pools
    "dense-attention-leading-dense-layer": dict(
        DENSE, **EXPERTS, first_dense_layers=1),
    # the latent cache kind on the same skeleton
    "latent-attention-experts": dict(
        DENSE, **EXPERTS, **LATENT, num_kv_heads=None, first_dense_layers=1),
}


@pytest.fixture(scope="module")
def models():
    out = {}
    for name, kw in MODELS.items():
        cfg = T.TransformerConfig(**kw)
        out[name] = cfg, family_harness.init_params(
            cfg, jax.random.PRNGKey(3))
    return out


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@functools.lru_cache(maxsize=None)
def _jitted(cfg, attention_fn, with_stats):
    return jax.jit(lambda params, pool, t, pos, tb: PG.forward_paged(
        params, t, pos, tb, pool, cfg, attention_fn=attention_fn,
        with_stats=with_stats))


def _tick(cfg, params, pool, rows, Tn=32, attention_fn=None,
          with_stats=False):
    """One tick over ``rows`` = (token, position, table) triples, padded to
    ``Tn`` with rows of the trash block."""
    t = np.zeros((Tn,), np.int32)
    pos = np.zeros((Tn,), np.int32)
    tb = np.zeros((Tn, MB), np.int32)
    for r, (tok, p, table) in enumerate(rows):
        t[r], pos[r], tb[r] = tok, p, table
    return _jitted(cfg, attention_fn, with_stats)(
        params, pool, jnp.asarray(t), jnp.asarray(pos), jnp.asarray(tb))


def _serve_through_pool(cfg, params, toks, n_prompt, chunk=24, **kw):
    """Chunked prefill, then decode steps, of one sequence: logits at every
    position."""
    pool = PG.init_paged_kv(cfg, 32, BS)
    table = np.zeros((MB,), np.int32)
    table[:len(toks) // BS + 1] = np.arange(1, len(toks) // BS + 2)
    out = {}
    ticks = [list(range(lo, min(lo + chunk, n_prompt)))
             for lo in range(0, n_prompt, chunk)]
    ticks += [[p] for p in range(n_prompt, len(toks))]
    for ps in ticks:
        logits, pool = _tick(cfg, params, pool,
                             [(toks[p], p, table) for p in ps], **kw)
        for r, p in enumerate(ps):
            out[p] = logits[r]
    return jnp.stack([out[p] for p in range(len(toks))])


# ------------------------------------------------------------------ #
# a dense-attention expert model on the shared skeleton
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("model", sorted(MODELS))
def test_chunked_prefill_and_decode_match_forward(models, model):
    cfg, params = models[model]
    toks = np.random.default_rng(1).integers(
        0, cfg.vocab_size, 44).astype(np.int32)
    want = T.forward(params, jnp.asarray(toks[None]), cfg)[0]
    got = _serve_through_pool(cfg, params, toks, 36)
    assert _rel(got, want) < 1e-5


def _greedy_by_forward(cfg, params, prompts, new):
    """One program for every prompt and length: the model is causal, so
    what follows a position does not reach it."""
    fwd = jax.jit(lambda p, t: T.forward(p, t, cfg))
    out = []
    for prompt in prompts:
        seq = np.zeros((1, 48), np.int32)
        seq[0, :len(prompt)] = prompt
        for n in range(len(prompt), len(prompt) + new):
            seq[0, n] = int(jnp.argmax(
                fwd(params, jnp.asarray(seq))[0, n - 1]))
        out.append(seq[0, len(prompt):len(prompt) + new].tolist())
    return out


@pytest.mark.parametrize("model", sorted(MODELS))
def test_fastgen_greedy_stream_matches_the_reference_engine(models, model):
    """The slot engine's stream where it serves the model; it refuses a
    stack of two segments, where greedy decoding by ``T.forward`` stands
    in."""
    cfg, params = models[model]
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (5, 19, 33)]
    uids, new = [1, 2, 3], 6
    if cfg.first_dense_layers:
        want = dict(zip(uids, _greedy_by_forward(cfg, params, prompts, new)))
    else:
        want = RaggedInferenceEngine(
            cfg, params, max_slots=4, max_len=128, temperature=0.0,
            seed=0).generate_all(uids, prompts, max_new_tokens=new)
    fg = FastGenEngine(cfg, params, n_blocks=32, block_size=BS,
                       max_blocks_per_seq=MB, token_budget=32,
                       temperature=0.0, seed=0)
    assert fg._expert_layers == sum(
        c.num_layers for _, c in cfg.segments if c.n_experts)
    got = fg.generate_all(uids, prompts, max_new_tokens=new)
    assert got == want
    assert fg.allocator.free_blocks == 31


@pytest.mark.parametrize("model", sorted(MODELS))
def test_expert_tick_takes_the_layer_stack_whole(models, model):
    """No layer's expert matrices are sliced out of the stack before the
    grouped matmuls (a slice is a copy of every expert of the layer:
    1.1 GB at Moonlight's widths): the scan's operands hold no ``[L, E,
    ...]`` leaf and the lowered tick no dynamic slice that yields one
    layer's ``[E, ., .]``."""
    cfg, params = models[model]
    pool = PG.init_paged_kv(cfg, 8, BS)
    z = jnp.zeros((8,), jnp.int32)
    text = jax.jit(lambda p, pool: PG.forward_paged(
        p, z, z, jnp.zeros((8, MB), jnp.int32), pool, cfg)).lower(
            params, pool).as_text()
    sliced = re.findall(r"dynamic_slice.*-> tensor<1x(\d+)x(\d+)x(\d+)x",
                        text)
    assert (str(E), str(H), str(F)) not in sliced
    assert (str(E), str(F), str(H)) not in sliced
    # the layer's small leaves are still the scan's sliced operands
    assert re.search(rf"dynamic_slice.*-> tensor<1x{H}x{E}x", text)  # gate_w


@pytest.mark.parametrize("model", sorted(MODELS))
def test_stats_count_the_real_rows_of_each_expert_layer(models, model):
    cfg, params = models[model]
    table = np.zeros((MB,), np.int32)
    table[0] = 1
    rows = [(7 + r, r, table) for r in range(5)]       # 27 pad rows
    logits, _, stats = _tick(cfg, params, PG.init_paged_kv(cfg, 8, BS),
                             rows, with_stats=True)
    n_layers = cfg.num_layers - cfg.first_dense_layers
    got = np.asarray(stats["expert_rows"])
    assert got.shape == (n_layers, E)
    assert got.sum(axis=1).tolist() == [5 * cfg.moe_top_k] * n_layers
    assert logits.shape == (32, cfg.vocab_size)


def test_stats_of_a_model_without_experts_are_empty():
    cfg = T.TransformerConfig(**DENSE)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    out = _tick(cfg, params, PG.init_paged_kv(cfg, 8, BS), [],
                with_stats=True)
    assert len(out) == 3 and out[2] == {}


# ------------------------------------------------------------------ #
# one function chooses a tick's attention
# ------------------------------------------------------------------ #
CHOICES = {
    # (model, kernels wanted) -> (function, tile rows)
    "alibi-takes-the-reference": (
        dict(DENSE, pos_emb="alibi"), True,
        PG.paged_attention_reference, 0),
    "kernels-off-dense": (DENSE, False, PG.paged_attention_reference, 0),
    "kernels-off-latent": (
        dict(DENSE, **LATENT, num_kv_heads=None), False,
        PG.latent_attention_reference, 0),
    "dense-kernel": (DENSE, True, paged_attention, tile_rows(4, H // 4)),
    "latent-kernel": (
        dict(DENSE, **LATENT, num_kv_heads=None), True,
        latent_paged_attention, tile_rows(4, LATENT["kv_lora_rank"])),
}


@pytest.mark.parametrize("choice", sorted(CHOICES))
def test_tick_attention_and_the_engines_tile_rows_agree(choice):
    kw, use_kernel, fn, rows = CHOICES[choice]
    cfg = T.TransformerConfig(**kw)
    assert PG.tick_attention(cfg, use_kernel) == (fn, rows)
    eng = FastGenEngine(cfg, n_blocks=8, block_size=BS,
                        max_blocks_per_seq=MB, token_budget=32,
                        use_pallas_kernel=use_kernel, seed=0)
    assert (eng._attention, eng._tile_rows) == (fn, rows)
    assert eng._use_kernel is use_kernel
    # what the engine hands forward_paged resolves to itself
    assert PG.tick_attention(cfg, fn not in PG._REFERENCES)[0] is fn


@pytest.mark.parametrize("model", sorted(MODELS))
def test_no_attention_fn_means_the_reference(models, model):
    cfg, params = models[model]
    table = np.zeros((MB,), np.int32)
    table[:2] = [1, 2]
    rows = [(3 + r, r, table) for r in range(11)]
    want, _ = _tick(cfg, params, PG.init_paged_kv(cfg, 8, BS), rows)
    for fn in (PG.paged_attention_reference,
               PG.tick_attention(cfg, False)[0]):
        got, _ = _tick(cfg, params, PG.init_paged_kv(cfg, 8, BS), rows,
                       attention_fn=fn)
        assert bool(jnp.all(got == want))


def test_a_kernel_handed_in_selects_the_pools_own(models):
    """The benchmark's runner passes ``paged_attention`` whatever the
    model: on a latent pool that means the latent instantiation."""
    cfg, params = models["latent-attention-experts"]
    pool = PG.init_paged_kv(cfg, 8, BS)
    z = jnp.zeros((8,), jnp.int32)
    text = jax.jit(lambda p, pool: PG.forward_paged(
        p, z, z, jnp.zeros((8, MB), jnp.int32), pool, cfg,
        attention_fn=paged_attention)).lower(params, pool).as_text(
            debug_info=True)
    assert "attn/latent_paged_attention/" in text
    assert "attn/paged_attention/" not in text


# ------------------------------------------------------------------ #
# what is frozen of a pool: keys, shapes, dtypes, bytes, walks
# ------------------------------------------------------------------ #
#: serving cell -> family
CELLS = {
    "serve-mistral7b-chat-steady-v2": "grouped-query",
    "serve-pythia69b-decode-closed": "parallel-block",
    "serve-moonlight16b-longdoc-closed": "latent",
    "serve-phi4flash-reason-closed": "state-space",
    "serve-trinity-large-agentctx-closed": "window-and-full",
    "serve-lfm2-24b-concurrent-closed": "short-convolution",
    "serve-kimi-linear-48b-rollout-closed": "delta-rule",
    "serve-keye-vl2-30b-longctx-closed": "learned-sparse",
    "serve-nemotron3-super-120b-agents-closed": "single-sublayers",
    "serve-ouro-2.6b-cot-closed": "looped",
    "serve-granite4-hsmall-rag-closed": "paired-mamba2-blocks",
}
#: what a toy pool is built with: blocks, block size, slots, longest run
TOY = (64, 8, 3, 16)
BF, F32 = "bfloat16", "float32"
#: ``init_paged_kv`` of each cell's configuration at its published widths
#: with the cell's own sizes, and (``toy:``) of the configuration's
#: rehearsal widths with ``TOY``: written from the tree of PR 43. The
#: benchmark's readers read a pool operand's shape; they are frozen.
POOLS = {
    "serve-mistral7b-chat-steady-v2": {
        "k": ((16, 2400, 32, 8, 128), BF), "v": ((16, 2400, 32, 8, 128), BF)},
    "toy:serve-mistral7b-chat-steady-v2": {
        "k": ((2, 64, 8, 2, 16), BF), "v": ((2, 64, 8, 2, 16), BF)},
    "serve-pythia69b-decode-closed": {
        "k": ((16, 640, 32, 32, 128), BF), "v": ((16, 640, 32, 32, 128), BF)},
    "toy:serve-pythia69b-decode-closed": {
        "k": ((2, 64, 8, 4, 16), BF), "v": ((2, 64, 8, 4, 16), BF)},
    "serve-moonlight16b-longdoc-closed": {
        "latent": ((9, 4352, 32, 640), BF)},
    "toy:serve-moonlight16b-longdoc-closed": {
        "latent": ((3, 64, 8, 128), BF)},
    "serve-phi4flash-reason-closed": {
        "conv": ((1971, 5120), BF), "k": ((1, 10900, 10, 32, 128), BF),
        "ssm": ((9, 73, 16, 5120), F32), "v": ((1, 10900, 10, 32, 128), BF),
        "wk": ((8, 2336, 10, 32, 128), BF),
        "wv": ((8, 2336, 10, 32, 128), BF)},
    "toy:serve-phi4flash-reason-closed": {
        "conv": ((36, 128), BF), "k": ((1, 64, 2, 8, 16), BF),
        "ssm": ((3, 4, 16, 128), F32), "v": ((1, 64, 2, 8, 16), BF),
        "wk": ((2, 16, 2, 8, 16), BF), "wv": ((2, 16, 2, 8, 16), BF)},
    "serve-trinity-large-agentctx-closed": {
        "k": ((1, 12288, 32, 8, 128), BF), "v": ((1, 12288, 32, 8, 128), BF),
        "wk": ((4, 29, 192, 32, 8, 128), BF),
        "wv": ((4, 29, 192, 32, 8, 128), BF)},
    "toy:serve-trinity-large-agentctx-closed": {
        "k": ((1, 64, 8, 2, 16), BF), "v": ((1, 64, 8, 2, 16), BF),
        "wk": ((4, 4, 4, 8, 2, 16), BF), "wv": ((4, 4, 4, 8, 2, 16), BF)},
    "serve-lfm2-24b-concurrent-closed": {
        "conv": ((4368, 2048), BF), "k": ((2, 20480, 32, 4, 128), BF),
        "v": ((2, 20480, 32, 4, 128), BF)},
    "toy:serve-lfm2-24b-concurrent-closed": {
        "conv": ((64, 256), BF), "k": ((2, 64, 8, 1, 128), BF),
        "v": ((2, 64, 8, 1, 128), BF)},
    "serve-kimi-linear-48b-rollout-closed": {
        "kda": ((6, 273, 32, 128, 128), F32),
        "kda_conv": ((4914, 12288), BF),
        "latent": ((2, 40960, 32, 640), BF)},
    "toy:serve-kimi-linear-48b-rollout-closed": {
        "kda": ((6, 4, 2, 128, 128), F32), "kda_conv": ((72, 768), BF),
        "latent": ((2, 64, 8, 128), BF)},
    # (PR 46) keys, values and, a third store of a layer's block range,
    # the indexer's key of every position, padded to the lanes; blocks
    # alone: nothing a sequence slot
    "serve-keye-vl2-30b-longctx-closed": {
        "idx": ((6, 4353, 128, 128), BF), "k": ((6, 4353, 128, 4, 128), BF),
        "v": ((6, 4353, 128, 4, 128), BF)},
    "toy:serve-keye-vl2-30b-longctx-closed": {
        "idx": ((6, 64, 8, 128), BF), "k": ((6, 64, 8, 2, 16), BF),
        "v": ((6, 64, 8, 2, 16), BF)},
    # (PR 53) two key-value heads of 128: a block heads first; a Mamba-2
    # layer's matrices with the state values down a tile's rows and two
    # heads' channels along its lanes, and its convolution's inputs
    "serve-nemotron3-super-120b-agents-closed": {
        "k": ((1, 12288, 2, 32, 128), BF), "v": ((1, 12288, 2, 32, 128), BF),
        "ssd": ((5, 137, 64, 128, 128), F32),
        "ssd_conv": ((2055, 10240), BF)},
    "toy:serve-nemotron3-super-120b-agents-closed": {
        "k": ((1, 64, 2, 8, 128), BF), "v": ((1, 64, 2, 8, 128), BF),
        "ssd": ((5, 4, 8, 128, 16), F32), "ssd_conv": ((60, 2176), BF)},
    # (PR 55) a looped stack: a cache layer a (pass, layer), 4 x 48 (the
    # toy: 4 x 3) of a grouped-query stack's blocks
    "serve-ouro-2.6b-cot-closed": {
        "k": ((192, 193, 32, 16, 128), BF),
        "v": ((192, 193, 32, 16, 128), BF)},
    "toy:serve-ouro-2.6b-cot-closed": {
        "k": ((12, 64, 8, 4, 16), BF), "v": ((12, 64, 8, 4, 16), BF)},
    # (PR 60) eight key-value heads of 128 in the ONE attention layer: a
    # block positions first; nine Mamba-2 layers' matrices (ONE group: two
    # heads' channels along a tile's lanes at the published widths, all
    # eight at the toy's) and their convolutions' inputs
    "serve-granite4-hsmall-rag-closed": {
        "k": ((1, 11520, 32, 8, 128), BF), "v": ((1, 11520, 32, 8, 128), BF),
        "ssd": ((9, 37, 64, 128, 128), F32), "ssd_conv": ((999, 8448), BF)},
    "toy:serve-granite4-hsmall-rag-closed": {
        "k": ((1, 64, 8, 2, 16), BF), "v": ((1, 64, 8, 2, 16), BF),
        "ssd": ((9, 4, 1, 128, 128), F32), "ssd_conv": ((108, 384), BF)},
}
#: ``FastGenEngine._pool_bytes``: (block stores, per-slot state stores)
BYTES = {
    "serve-mistral7b-chat-steady-v2": (5033164800, 0),
    "toy:serve-mistral7b-chat-steady-v2": (131072, 0),
    "serve-pythia69b-decode-closed": (5368709120, 0),
    "toy:serve-pythia69b-decode-closed": (262144, 0),
    "serve-moonlight16b-longdoc-closed": (1604321280, 0),
    "toy:serve-moonlight16b-longdoc-closed": (393216, 0),
    "serve-phi4flash-reason-closed": (1785856000, 3297310720),
    "toy:serve-phi4flash-reason-closed": (65536, 140288),
    "serve-trinity-large-agentctx-closed": (1610612736, 2919235584),
    "toy:serve-trinity-large-agentctx-closed": (65536, 65536),
    "serve-lfm2-24b-concurrent-closed": (2684354560, 17891328),
    "toy:serve-lfm2-24b-concurrent-closed": (524288, 32768),
    "serve-kimi-linear-48b-rollout-closed": (3355443200, 3555901440),
    "toy:serve-kimi-linear-48b-rollout-closed": (262144, 3256320),
    "serve-keye-vl2-30b-longctx-closed": (7702511616, 0),
    "toy:serve-keye-vl2-30b-longctx-closed": (1179648, 0),
    "serve-nemotron3-super-120b-agents-closed": (402653184, 2915184640),
    "toy:serve-nemotron3-super-120b-agents-closed": (524288, 1571840),
    # 192 x 193 blocks of 32 x 16 x 128 x 2 B, keys and values: 1,572,864 B
    # a token
    "serve-ouro-2.6b-cot-closed": (9714008064, 0),
    "toy:serve-ouro-2.6b-cot-closed": (1572864, 0),
    "serve-granite4-hsmall-rag-closed": (1509949440, 1413582336),
    "toy:serve-granite4-hsmall-rag-closed": (65536, 2442240),
}
#: ``tick_walks``: (layers, window, cache positions a fetch step) a kind
#: of kernel call
WALKS = {
    "serve-mistral7b-chat-steady-v2": [(16, None, 256)],
    "toy:serve-mistral7b-chat-steady-v2": [(2, None, 128)],
    "serve-pythia69b-decode-closed": [(16, None, 64)],
    "toy:serve-pythia69b-decode-closed": [(2, None, 128)],
    "serve-moonlight16b-longdoc-closed": [(9, None, 512)],
    "toy:serve-moonlight16b-longdoc-closed": [(3, None, 2048)],
    "serve-phi4flash-reason-closed": [(8, 512, 128), (8, None, 128)],
    "toy:serve-phi4flash-reason-closed": [(2, 16, 128), (2, None, 128)],
    "serve-trinity-large-agentctx-closed": [(4, 4096, 128), (1, None, 128)],
    "toy:serve-trinity-large-agentctx-closed": [(4, 16, 128),
                                                (1, None, 128)],
    "serve-lfm2-24b-concurrent-closed": [(2, None, 256)],
    "toy:serve-lfm2-24b-concurrent-closed": [(2, None, 128)],
    "serve-kimi-linear-48b-rollout-closed": [(2, None, 512)],
    "toy:serve-kimi-linear-48b-rollout-closed": [(2, None, 2048)],
    # two blocks of 128 a step, as Mistral's and LFM2's eight of 32 (the
    # toys' blocks of 8 keep one lane width)
    "serve-keye-vl2-30b-longctx-closed": [(6, None, 256)],
    "toy:serve-keye-vl2-30b-longctx-closed": [(6, None, 128)],
    "serve-nemotron3-super-120b-agents-closed": [(1, None, 256)],
    "toy:serve-nemotron3-super-120b-agents-closed": [(1, None, 128)],
    # 192 calls a tick: a walk a (pass, layer), four blocks of 32 a step
    "serve-ouro-2.6b-cot-closed": [(192, None, 128)],
    "toy:serve-ouro-2.6b-cot-closed": [(12, None, 128)],
    "serve-granite4-hsmall-rag-closed": [(1, None, 256)],
    "toy:serve-granite4-hsmall-rag-closed": [(1, None, 128)],
}


@functools.lru_cache(maxsize=None)
def _sized(case: str):
    """(config, blocks, block size, slots, longest run) of a case of
    ``POOLS``: a cell's own, or its configuration's toy widths."""
    from benchmarks import model_config
    from benchmarks.manifest import load_cell

    toy, name = case.startswith("toy:"), case.split(":")[-1]
    cell = load_cell(name)
    cfg = model_config.build(cell.config, "serve", rehearse=toy)
    eng = cell.deploy["engine"]
    nb, bs, slots, run = TOY if toy else (
        eng["n_blocks"], eng["block_size"], eng.get("state_slots", 0),
        eng["token_budget"])
    return cfg, nb, bs, slots if cfg.layer_kinds else 0, run


def _pool_shapes(case: str):
    cfg, nb, bs, slots, run = _sized(case)
    return jax.eval_shape(lambda: PG.init_paged_kv(
        cfg, nb, bs, state_slots=slots, max_run=run))


def test_the_cases_cover_every_serving_cell():
    from benchmarks.manifest import load_manifest

    serving = {w["name"] for w in load_manifest()["workloads"]
               if w["name"].startswith("serve-")}
    assert serving == set(CELLS)
    assert set(POOLS) == set(BYTES) == set(WALKS) == {
        p + c for c in CELLS for p in ("", "toy:")}


@pytest.mark.parametrize("case", sorted(POOLS))
def test_a_pool_has_its_frozen_keys_shapes_and_dtypes(case):
    got = {k: (tuple(v.shape), str(v.dtype))
           for k, v in _pool_shapes(case).items()}
    assert got == POOLS[case]


@pytest.mark.parametrize("case", sorted(BYTES))
def test_the_engine_books_a_pools_bytes_as_blocks_or_state(case):
    cfg, nb, bs, slots, run = _sized(case)
    assert FastGenEngine._pool_bytes(cfg, nb, bs, slots, run) == BYTES[case]


@pytest.mark.parametrize("case", sorted(WALKS))
def test_tick_walks_of_a_pool(case, monkeypatch):
    cfg = _sized(case)[0]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert PG.tick_walks(cfg, _pool_shapes(case)) == WALKS[case]


# ------------------------------------------------------------------ #
# one table of cache kinds: the engine books by class, names no store
# ------------------------------------------------------------------ #
def _bytes(x):
    return int(np.prod(x.shape)) * x.dtype.itemsize


@pytest.mark.parametrize("case", sorted(BYTES))
def test_pool_bytes_are_the_tables_by_class(case):
    cfg, nb, bs, slots, run = _sized(case)
    pool = _pool_shapes(case)
    stores = [s for kind in PG.cache_kinds(cfg).values()
              for s in kind.stores]
    # every array of the pool is one entry's, once
    assert sorted(s.name for s in stores) == sorted(pool)
    blocks = sum(_bytes(pool[s.name]) for s in stores if s.cls == PG.BLOCKS)
    state = sum(_bytes(pool[s.name]) for s in stores if s.cls != PG.BLOCKS)
    assert FastGenEngine._pool_bytes(cfg, nb, bs, slots, run) == (
        blocks, state)
    # a homogeneous stack is its one entry ``num_layers`` times; a looped
    # one: a cache layer a (pass, layer)
    if not cfg.layer_kinds:
        (entry,) = PG.cache_kinds(cfg).values()
        assert entry.layers == cfg.loop_passes * cfg.num_layers


def test_a_made_up_kind_with_a_slot_store_is_booked_as_state(monkeypatch):
    """A kind the engine never heard of: its SLOT store is built, booked
    as state by ``_pool_bytes`` and shown by the gauge of bytes a slot,
    with no edit to ``inference/fastgen.py``."""
    import dataclasses
    import inspect

    import deepspeed_tpu.inference.fastgen as FG
    from deepspeed_tpu import telemetry

    case = "toy:serve-lfm2-24b-concurrent-closed"
    cfg, nb, bs, slots, run = _sized(case)
    real = PG.cache_kinds

    def with_more(c):
        return {**real(c), "made_up": PG.CacheKind(
            2, "made_up", (PG.Store("made_up", PG.SLOT, lambda bs: (5, 7),
                                    jnp.float32, holds="made_up"),),
            None, lambda *a: None)}

    monkeypatch.setattr(PG, "cache_kinds", with_more)
    more = 2 * (slots + 1) * 5 * 7 * 4
    assert FastGenEngine._pool_bytes(cfg, nb, bs, slots, run) == (
        BYTES[case][0], BYTES[case][1] + more)
    eng = FastGenEngine(dataclasses.replace(cfg, dtype="float32"),
                        n_blocks=nb, block_size=bs, max_blocks_per_seq=8,
                        token_budget=run, state_slots=slots,
                        use_pallas_kernel=False, seed=0)
    assert eng.pool["made_up"].shape == (2, slots + 1, 5, 7)
    per_slot = telemetry.gauge("fastgen_state_bytes_per_slot")
    assert per_slot.value(kind="made_up") == 2 * 5 * 7 * 4
    assert telemetry.gauge("fastgen_state_bytes").value() == sum(
        _bytes(eng.pool[s.name]) for kind in with_more(cfg).values()
        for s in kind.stores if s.cls != PG.BLOCKS)
    # the engine names no store and no kind of its model
    source = inspect.getsource(FG)
    for name in ("kda_conv", "wk", "wv", "ssm", "kda", "conv"):
        assert f'"{name}"' not in source


# ------------------------------------------------------------------ #
# one block, one driver
# ------------------------------------------------------------------ #
#: what hands an array on as it is (a slice of it, a cast, a reshape)
_PASS = {"reshape", "slice", "dynamic_slice", "squeeze", "gather",
         "convert_element_type", "copy", "broadcast_in_dim"}


def _count_products(jaxpr, tainted):
    """dot_generals of ``jaxpr`` (and the scans and calls inside it) that
    take an operand handed down from a ``tainted`` variable."""
    tainted, n = set(tainted), 0
    for eqn in jaxpr.eqns:
        hit = [i for i, v in enumerate(eqn.invars)
               if not hasattr(v, "val") and v in tainted]
        name = eqn.primitive.name
        if name == "dot_general":
            n += bool(hit)
        elif name in _PASS and 0 in hit:
            tainted.update(eqn.outvars)
        elif hit and any(k in eqn.params for k in ("jaxpr", "call_jaxpr")):
            inner = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
            inner = getattr(inner, "jaxpr", inner)
            n += _count_products(inner, [inner.invars[i] for i in hit])
    return n


@functools.lru_cache(maxsize=None)
def _traced(case: str, attention_fn=None):
    """(the float32 paged forward of a toy case traced, its config, its
    flat parameters' paths)."""
    import dataclasses

    cfg, nb, bs, slots, run = _sized(case)
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = jax.eval_shape(lambda k: T.init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    pool = jax.eval_shape(lambda: PG.init_paged_kv(
        cfg, nb, bs, state_slots=slots, max_run=run))
    ints = jax.ShapeDtypeStruct((run,), jnp.int32)
    closed = jax.make_jaxpr(
        lambda p, pool, t, pos, tb: PG.forward_paged(
            p, t, pos, tb, pool, cfg, attention_fn=attention_fn))(
        params, pool, ints, ints, jax.ShapeDtypeStruct((run, 8), jnp.int32))
    paths = [path for path, _ in jax.tree_util.tree_flatten_with_path(
        params)[0]]
    return closed, cfg, paths


@pytest.mark.parametrize("case", sorted(c for c in POOLS if "toy:" in c))
def test_a_tick_holds_one_wo_product_a_layer_and_one_scan_a_run(case):
    """The lowered tick of every family: the only scans of the skeleton
    are ``T.scan_periods``', one a run of a period of kinds, and a step
    multiplies by ``wo`` once a layer of its period that has a mixer (a
    stack of single sublayers' ``ffn`` layers have none; ``paged.py``
    names the leaf once and scans nothing itself); a looped stack: all of
    it once a pass."""
    import inspect

    closed, cfg, paths = _traced(case)
    runs = [run for _, seg in cfg.segments
            for run in T.kind_runs(PG.stack_kinds(cfg, seg))]
    assert sum(steps * len(period) for _, period, steps in runs) \
        == cfg.num_layers
    scans = [e for e in closed.jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] \
        == [s for _, _, s in runs] * cfg.loop_passes
    wo = [v for v, path in zip(closed.jaxpr.invars, paths)
          if getattr(path[-1], "key", None) == "wo"]
    assert wo
    assert _count_products(closed.jaxpr, wo) == cfg.loop_passes * sum(
        sum(kind != "ffn" for kind in period) for _, period, _ in runs)
    source = inspect.getsource(PG)
    assert source.count('lp["wo"]') == 1 and "lax.scan" not in source


@pytest.mark.parametrize("case", sorted(c for c in POOLS if "toy:" in c))
def test_no_run_of_several_steps_is_cut_ahead_of_its_scan(case):
    """The traced tick, not its result: no scan of more than one step takes
    an operand that is a ``slice`` / ``dynamic_slice`` / ``gather`` of a
    stacked leaf made OUTSIDE it. Such a slice is the operand of a
    ``while`` and XLA materialises it: a copy of the run's weights every
    tick (0.97 GB in ``serve-granite4-hsmall-rag-closed`` before PR 62);
    ``T.scan_periods`` reads a run of several steps out of the whole leaf
    inside the loop's body. (A loop of one trip is inlined: its static
    slice feeds its reader.)"""
    closed, cfg, paths = _traced(case)
    stacked = {key for key, _ in cfg.segments}
    # a variable handed down from a stacked leaf -> (the leaf, cut from it?)
    seen = {v: (path, False) for v, path in zip(closed.jaxpr.invars, paths)
            if getattr(path[0], "key", None) in stacked}
    for eqn in closed.jaxpr.eqns:
        name, taken = eqn.primitive.name, [
            v for v in eqn.invars if not hasattr(v, "val")]
        if name in _PASS and taken and taken[0] in seen:
            path, cut = seen[taken[0]]
            seen.update(dict.fromkeys(eqn.outvars, (path, cut or name in (
                "slice", "dynamic_slice", "gather"))))
        elif name == "scan" and eqn.params["length"] > 1:
            assert [jax.tree_util.keystr(seen[v][0]) for v in taken
                    if v in seen and seen[v][1]] == []
    if "granite4" in case:    # the case that has such a run
        assert [(steps, len(period)) for _, seg in cfg.segments
                for _, period, steps in T.kind_runs(
                    PG.stack_kinds(cfg, seg))] == [(1, 6), (4, 1)]


@pytest.mark.parametrize("case", sorted(c for c in POOLS if "toy:" in c))
def test_no_attention_fn_and_a_reference_are_one_program(case):
    """``attention_fn=None`` and a reference trace to the same program in
    float32, operation for operation: their logits are bit-equal."""
    ref = PG.tick_attention(_sized(case)[0], False)[0]
    assert ref in PG._REFERENCES
    assert str(_traced(case)[0]) == str(_traced(case, ref)[0])
