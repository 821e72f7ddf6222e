"""The head of a serving tick runs for the rows it samples
(``models/paged.forward_paged(head_rows=)``; ``FastGenEngine._build_tick``
chooses between the gathered head and the all-rows head from the count the
packed array carries), for every cache kind the tick skeleton serves. Toy
widths, float32.
"""
import contextlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.fastgen import FastGenEngine
from deepspeed_tpu.models import paged as PG
import family_harness as H
from family_harness import rel, tick_program
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import config_from_hf

_DENSE = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
              num_kv_heads=2, pos_emb="rope", norm="rmsnorm",
              activation="swiglu", use_bias=False, dtype="float32",
              max_seq_len=128, tie_embeddings=False, init_std=0.1)
_HF = dict(hidden_size=64, intermediate_size=96, vocab_size=128,
           max_position_embeddings=512, tie_word_embeddings=False)


def _configs():
    return {
        # per-head K/V pools, a head with a bias
        "dense": T.TransformerConfig(**dict(_DENSE, lm_head_bias=True)),
        # a latent pool, a leading dense layer, routed experts (their row
        # counts ride back behind the sampled tokens)
        "latent": T.TransformerConfig(**dict(
            _DENSE, num_kv_heads=None, mla=True, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            q_lora_rank=0, n_experts=4, moe_top_k=2, moe_ffn_size=32,
            moe_shared_size=32, moe_dispatch="ragged",
            first_dense_layers=1)),
        # a period of kinds: state-space layers, window rings, one shared
        # cache, gated memory units; tied embeddings
        "hybrid-period": config_from_hf(types.SimpleNamespace(**dict(
            _HF, model_type="phi4flash", layer_norm_eps=1e-5,
            mb_per_layer=2, num_attention_heads=8, num_hidden_layers=8,
            num_key_value_heads=4, sliding_window=16,
            tie_word_embeddings=True, hidden_act="silu"))),
        # standard blocks: window layers over a ring beside a full layer,
        # over a share of the experts
        "standard-blocks-ring": config_from_hf(types.SimpleNamespace(**dict(
            _HF, model_type="afmoe", moe_intermediate_size=32, head_dim=16,
            num_attention_heads=6, num_key_value_heads=2,
            num_hidden_layers=5, num_dense_layers=1,
            layer_types=["sliding_attention"] * 4 + ["full_attention"],
            num_experts=4, router_experts=16, num_experts_per_tok=4,
            num_shared_experts=1, rms_norm_eps=1e-5, rope_theta=10000,
            route_norm=True, route_scale=2.448, sliding_window=16,
            mup_enabled=True))),
        # standard blocks with a conv kind that keeps a state row a slot
        "conv": config_from_hf(types.SimpleNamespace(**dict(
            _HF, model_type="lfm2_moe", moe_intermediate_size=32,
            num_attention_heads=4, num_key_value_heads=2,
            num_hidden_layers=6, num_dense_layers=2,
            layer_types=["conv", "conv", "full_attention", "conv", "conv",
                         "conv"], num_experts=8, num_experts_per_tok=4,
            norm_eps=1e-5, norm_topk_prob=True, routed_scaling_factor=1,
            use_expert_bias=True, conv_L_cache=3, conv_bias=False,
            rope_parameters={"rope_theta": 1000000,
                             "rope_type": "default"}))),
    }


KINDS = sorted(_configs())
BUDGET, SMALL = 32, 8          # the two tick buckets of the engines below


@pytest.fixture(scope="module")
def models():
    out = {}
    for name, cfg in _configs().items():
        params = H.init_params(cfg, jax.random.PRNGKey(7))
        # wide enough that the argmax is not the last token's echo, and
        # biases off zero
        leaves, tree = jax.tree_util.tree_flatten(params)
        keys = jax.random.split(jax.random.PRNGKey(8), len(leaves))
        with H.drawn_whole():
            out[name] = cfg, tree.unflatten(
                [x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
                 for x, k in zip(leaves, keys)])
    return out


@pytest.fixture(scope="module")
def engine_of(models):
    """One engine a kind for the file: the first test borrows its allocator
    and its (untouched) pool, the second serves through it."""
    engines = {}

    def get(kind):
        if kind not in engines:
            cfg, params = models[kind]
            engines[kind] = FastGenEngine(
                cfg, params, n_blocks=96, block_size=4, max_blocks_per_seq=16,
                token_budget=BUDGET, state_slots=14, temperature=0.0,
                use_pallas_kernel=False, seed=0)
        return engines[kind]
    return get


# ------------------------------------------------------------------ #
# the model: logits of the rows asked for
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("kind", KINDS)
def test_head_rows_gives_those_rows_of_the_all_rows_head(engine_of, kind):
    eng = engine_of(kind)
    cfg, mb, held = eng.cfg, eng.max_blocks_per_seq, []
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, cfg.vocab_size, BUDGET).astype(np.int32)
    positions = np.zeros((BUDGET,), np.int32)
    tables = np.zeros((BUDGET, mb), np.int32)
    # two decode rows first, then a chunk of 20 rows, then pads
    for row, pos in ((0, 5), (1, 9)):
        blocks = eng.allocator.allocate(pos // eng.block_size + 1)
        held.append(blocks)
        positions[row] = pos
        tables[row, :len(blocks)] = blocks
    blocks = eng.allocator.allocate(20 // eng.block_size + 1)
    held.append(blocks)
    positions[2:22] = np.arange(20)
    tables[2:22, :len(blocks)] = blocks
    for blocks in held:             # the tables stay: nothing else runs here
        eng.allocator.free(blocks)
    operands = (eng.params, eng.pool, jnp.asarray(tokens),
                jnp.asarray(positions), jnp.asarray(tables))

    def fwd(params, pool, t, pos, tb, head_rows):
        return PG.forward_paged(params, t, pos, tb, pool, cfg,
                                head_rows=head_rows)

    # the all-rows program is the one the next test checks ticks against
    every, pool = tick_program(cfg, None, BUDGET, mb)(*operands)
    assert every.shape == (BUDGET, cfg.vocab_size)
    assert every.dtype == jnp.float32
    # the decode rows, the chunk's last row, and that one again as a pad
    r = np.array([0, 1, 21, 21, 21], np.int32)
    some, pool_some = jax.jit(fwd)(*operands, jnp.asarray(r))
    assert some.shape == (len(r), cfg.vocab_size)
    assert some.dtype == jnp.float32
    assert rel(some, every[r]) < 1e-5
    np.testing.assert_array_equal(np.argmax(some, -1),
                                  np.argmax(every, -1)[r])
    # the cache is written for every row whatever the head runs for
    for k in pool:
        np.testing.assert_array_equal(np.asarray(pool_some[k]),
                                      np.asarray(pool[k]))


# ------------------------------------------------------------------ #
# the engine: greedy tokens through step() are the all-rows head's
# ------------------------------------------------------------------ #
def _all_rows_tokens(eng, calls):
    """What the all-rows head gives for each recorded tick: the argmax of
    ``forward_paged``'s full logits over a pool of its own, fed the
    packed arrays in order. Returns (tick bucket, sampled rows' count or
    None in the small bucket, tokens the engine read, tokens wanted)."""
    assert eng._attention in PG._REFERENCES       # no kernel: ``None``'s
    pool = jax.tree.map(jnp.zeros_like, eng.pool)
    out = []
    for c in calls:
        Tn, mb, packed = c["Tn"], c["mb"], c["packed"]
        n = Tn * mb
        logits, pool = tick_program(eng.cfg, None, Tn, mb)(
            eng.params, pool, packed[n:n + Tn], packed[n + Tn:n + 2 * Tn],
            packed[:n].reshape(Tn, mb))
        want = np.argmax(np.asarray(logits), -1)
        got = np.asarray(c["sampled"])[:Tn]
        head = packed[n + 2 * Tn:-2]
        if Tn == SMALL:
            assert head.size == 0
            out.append((Tn, None, got, want))
        elif head[SMALL] <= SMALL:
            count = int(head[SMALL])
            # the pad repeats the last sampled row
            assert (head[count:SMALL] == head[max(count - 1, 0)]).all()
            out.append((Tn, count, got[:count], want[head[:count]]))
        else:
            out.append((Tn, int(head[SMALL]), got, want))
    return out


@contextlib.contextmanager
def _recorded_ticks(eng):
    """Every tick ``eng`` runs inside the block, with what it was handed
    and what it sampled; the engine's programs are its own again after."""
    calls = []
    build = eng._build_tick

    def _build_tick(Tn, mb):
        fn = build(Tn, mb)

        def tick(params, pool, packed):
            sampled, pool = fn(params, pool, packed)
            calls.append({"Tn": Tn, "mb": mb, "packed": packed,
                          "sampled": sampled})
            return sampled, pool
        tick.fn = fn
        return tick
    eng._build_tick = _build_tick
    try:
        yield calls
    finally:
        del eng._build_tick
        eng._ticks = {k: getattr(t, "fn", t) for k, t in eng._ticks.items()}


@pytest.mark.parametrize("kind", KINDS)
def test_greedy_step_tokens_are_the_all_rows_heads(engine_of, kind):
    """Chunk ticks and decode ticks alternate; a late long prompt meets
    more decoding sequences than the small bucket has rows, so its chunk
    ticks take the all-rows branch of the same program."""
    eng = engine_of(kind)
    cfg = eng.cfg
    rng = np.random.default_rng(4)

    def prompts(lens):
        return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lens]

    stream = {}

    def run(ticks):
        for _ in range(ticks):
            for uid, tok in eng.step().items():
                stream.setdefault(uid, []).append(tok)

    with _recorded_ticks(eng) as calls:
        eng.put([1, 2, 3], prompts([40, 5, 11]))
        run(4)
        eng.put([4], prompts([45]))            # chunks beside 3 decode rows
        run(3)
        eng.put(list(range(5, 12)), prompts([3, 4, 2, 5, 3, 2, 4]))
        run(2)
        eng.put([12], prompts([50]))           # chunks beside 11 decode rows
        run(4)
    eng.flush(list(range(1, 13)))
    ticks = _all_rows_tokens(eng, calls)
    for Tn, count, got, want in ticks:
        np.testing.assert_array_equal(got, want)
    gathered = [c for Tn, c, _, _ in ticks if Tn == BUDGET and c <= SMALL]
    every = [c for Tn, c, _, _ in ticks if Tn == BUDGET and c > SMALL]
    assert gathered and max(gathered) >= 3 and every
    assert any(Tn == SMALL for Tn, _, _, _ in ticks)
    # one program a (rows, table width) key: the branch is not a program
    assert len(eng._ticks) == len({(c["Tn"], c["mb"]) for c in calls})
    # and the streams are what each tick sampled, in order
    assert all(len(v) >= 2 for v in stream.values()) and len(stream) == 12


# ------------------------------------------------------------------ #
# the tests' driver is a loop of ticks
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("kind", ["dense", "latent", "conv"])
def test_generate_all_is_a_hand_loop_of_put_step_query_flush(engine_of, kind):
    """``generate_all`` against ``put`` / ``step()`` / ``query`` / ``flush``
    written out, on the engine the tests above compiled: token for token
    (greedy, so the same engine gives both), every block and slot back, and
    nothing in ``_ticks`` but ``(rows, table width)`` tick programs."""
    eng = engine_of(kind)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, eng.cfg.vocab_size, n).tolist()
               for n in (40, 5, 11)]
    new, free = 7, (eng.allocator.free_blocks, eng.allocator.free_slots)
    got = eng.generate_all([21, 22, 23], prompts, max_new_tokens=new)
    assert (eng.allocator.free_blocks, eng.allocator.free_slots) == free
    assert not eng.seqs
    uids = [31, 32, 33]
    eng.put(uids, prompts)
    for _ in range(40):
        eng.step()
        for u in uids:
            seq = eng.seqs[u]
            if not seq.done and len(seq.generated) >= new:
                eng._finish(seq)
        if all(eng.seqs[u].done for u in uids):
            break
    want = [eng.query(u)[1][:new] for u in uids]
    eng.flush(uids)
    assert [got[u] for u in (21, 22, 23)] == want
    assert all(len(toks) == new for toks in want)
    assert (eng.allocator.free_blocks, eng.allocator.free_slots) == free
    assert all(isinstance(k, tuple) and len(k) == 2
               and all(isinstance(n, int) for n in k) for k in eng._ticks)
    assert {Tn for Tn, _ in eng._ticks} <= {BUDGET, SMALL}
