"""One tick skeleton, two cache kinds (``models/paged.forward_paged``): what
a dense-attention model gets from sharing the skeleton the latent tick
brought (``cfg.segments``, the whole-stack expert call, ``with_stats``), and
the one function that says which attention a tick runs. Toy width, float32.
"""
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

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
        out[name] = cfg, T.init_params(cfg, jax.random.PRNGKey(3))
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
