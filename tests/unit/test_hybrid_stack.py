"""A stack of layer kinds (``TransformerConfig.layer_kinds``; the
``phi4flash`` family): state-space layers with per-sequence state beside the
block pool, windowed attention over a ring, one full-attention layer whose
blocks the cross-attention layers read, gated memory units.

Toy widths, float32, matmul precision "highest": the paged tick
(``models/paged.forward_paged``, the engine's pools and allocator), the
whole-sequence forward (``T.forward``) and the plain reference
(``benchmarks/reference/sambay_lm.py``, which imports nothing of the
program) are three implementations of the same equations and agree to
rounding, ~1e-6 relative; the tolerance 2e-5 leaves room for the order of
float32 sums and none for a wrong mask, state or layer index (the
smallest such fault measured while writing this, a wrong lambda_init
index, read 0.12).
"""
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmarks.reference import sambay_lm as R
from deepspeed_tpu.inference.fastgen import BlockAllocator
from deepspeed_tpu.models import hybrid as HY
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import config_from_hf
from deepspeed_tpu.ops.pallas.paged_attention import paged_attention
from family_harness import CATALOG, TOL

HF = dict(model_type="phi4flash", hidden_size=64, intermediate_size=96,
          layer_norm_eps=1e-5, max_position_embeddings=4096, mb_per_layer=2,
          num_attention_heads=8, num_hidden_layers=8, num_key_value_heads=4,
          sliding_window=16, tie_word_embeddings=True, vocab_size=128,
          hidden_act="silu")
FAMILY = H.Family(R, {"model": HF}, tokens=(2, 60))


@pytest.fixture(scope="module")
def model():
    m = FAMILY.model("model")
    return m.cfg, m.params, m.toks, H.whole_forward(FAMILY, m), m.arch


def _rings_and_blocks(eng):
    assert eng.pool["wk"].shape[1] == 4 * 8      # (3 slots + trash) x 8
    assert eng.allocator.free_blocks == 63


test_whole_forward_matches_the_reference = H.whole_forward_test(
    FAMILY, ["model"])
# 60 positions under a window of 16 and a ring of 32: the ring wraps, the
# window's edge falls inside chunks, and every pool starts full of garbage
# (a slot's last tenant): state is zero at position 0 whatever is there
test_paged_ticks_match_whole_forward_and_reference = H.paged_ticks_test(
    FAMILY, ["model"], n_prompt=57, garbage=1.0, also=_rings_and_blocks,
    cases=[
        (None, 13, TOL, {}),      # chunk and sequence boundaries fall mid-tick
        (paged_attention, 13, TOL, {}),   # the kernels (interpret mode)
        (None, 16, TOL, {}),      # a full tick: the ring holds window + run
    ])


def test_engine_serves_two_interleaved_and_reuses_a_slot():
    """Through ``FastGenEngine`` itself: token budget under the prompts'
    length, two sequences interleaved, a third admitted into the slot the
    first left (two slots only, so it waits for one). Greedy tokens against
    the reference's logits, teacher-forced on the engine's own output."""
    m = FAMILY.model("model")
    eng = H.engine(FAMILY, m.cfg, m.params, state_slots=2)
    # 47 positions each: the reference compiles one length
    prompts = {1: m.toks[0, :41].tolist(), 2: m.toks[1, :35].tolist(),
               3: m.toks[0, 5:47].tolist()}
    want = {1: 6, 2: 12, 3: 5}
    waits = eng._tm_slot_waits.total()
    slots_seen, _ = H.serve_greedy(eng, prompts, want)
    assert eng._tm_slot_waits.total() - waits == 1
    assert all(b in (1, 2) for b in slots_seen.values())
    assert slots_seen[3] == slots_seen[1]     # handed on by the first to end
    H.assert_greedy_tokens_are_the_reference_s(FAMILY, m, eng, prompts, want)
    eng.flush([1, 2, 3])
    assert eng.allocator.free_slots == 2 and eng.allocator.free_blocks == 63


def test_state_outside_the_block_pool_does_not_grow(model):
    """Per sequence the rings and the state are a slot's, whatever the
    length: after 3 x the ring's positions the sequence holds one slot and
    only the one ``full`` layer's blocks; the other layers wrote no block."""
    cfg, params, toks, _, _ = model
    eng = H.engine(FAMILY, cfg, params, n_blocks=40, max_blocks_per_seq=32)
    shapes = {k: v.shape for k, v in eng.pool.items()}
    assert shapes["k"] == shapes["v"] == (1, 40, 2, 4, 16)
    assert shapes["wk"] == (2, 4 * 8, 2, 4, 16)    # 2 window layers
    # a mamba layer's three stored inputs a row each, inputs-major: a
    # slot keeps 3 layers x 3 x 128 values of them
    assert shapes["conv"] == (3 * 3 * 4, 128) and shapes["ssm"] == (3, 4, 16, 128)
    assert eng.pool["conv"].nbytes // 4 == 3 * 3 * 128 * 4
    assert eng.pool["ssm"].dtype == jnp.float32
    ring = 8 * 4
    eng.put([7], [toks[0, :50].tolist()])
    while eng.seqs[7].pos < 3 * ring:
        eng.step()
    seq = eng.seqs[7]
    assert len(seq.blocks) == (seq.pos - 1) // 4 + 1 and seq.blocks[0] == 1
    assert eng.allocator.free_slots == 2
    written = np.asarray(jnp.any(eng.pool["k"][0] != 0, axis=(1, 2, 3)))
    assert set(np.flatnonzero(written)) <= set(seq.blocks) | {0}
    # the rings of the other two slots were never touched
    wk = np.asarray(jnp.any(eng.pool["wk"] != 0, axis=(2, 3, 4)))
    assert wk[:, 8:16].all() and not wk[:, 16:].any()


def test_failed_tick_leaves_slots_and_state_as_they_were(model):
    cfg, params, toks, _, _ = model
    eng = H.engine(FAMILY, cfg, params)
    eng.put([1], [toks[0, :20].tolist()])
    eng.step()
    eng.put([2], [toks[1, :9].tolist()])
    before = (eng.allocator.snapshot(), eng.seqs[1].pos,
              jax.tree.map(np.asarray, eng.pool))
    good = eng._ticks

    class Boom(dict):
        def __getitem__(self, key):
            raise RuntimeError("injected")

    eng._ticks = Boom(good)
    with pytest.raises(RuntimeError, match="injected"):
        eng.step()
    eng._ticks = good
    assert eng.allocator.snapshot() == before[0]
    assert eng.seqs[1].pos == before[1] and not eng.seqs[2].blocks
    for k, v in before[2].items():
        np.testing.assert_array_equal(np.asarray(eng.pool[k]), v)
    eng.step()
    assert eng.seqs[2].blocks[0] == 2


def test_allocator_hands_first_blocks_out_of_the_slots():
    a = BlockAllocator(12, state_slots=3)
    assert (a.free_blocks, a.free_slots) == (11, 3)
    s1, s2 = a.allocate(3), a.allocate(1)
    assert s1[0] == 1 and s2 == [2] and all(b > 3 for b in s1[1:])
    assert all(b > 3 for b in a.grow(2))
    assert a.available(starting=True) == 1 + a.available(starting=False)
    s3 = a.allocate(1)
    assert a.free_slots == 0 and a.available(starting=True) == 0
    with pytest.raises(RuntimeError, match="slot"):
        a.allocate(1)
    snap = a.snapshot()
    a.begin()
    a.free(s1)
    assert a.free_slots == 1 and a.allocate(1) == [1]
    a.rollback()
    assert a.free_slots == 0 and s3 == [3] and a.snapshot() == snap
    # without slots the two calls are one free list
    b = BlockAllocator(6)
    assert b.allocate(2) == [1, 2] and b.grow(1) == [3] and b.free_slots == 0


# (layers, stored inputs a layer, channels) in the proportions of the three
# convolution-state stores: the delta-rule layers' q | k | v, the gated short
# convolution's, the state-space layers'
CONV_STORES = {"kda_conv": (2, 3, 96), "conv": (3, 2, 32), "mamba": (2, 3, 40)}


@pytest.mark.parametrize("name", sorted(CONV_STORES))
def test_what_a_tick_writes_is_what_the_next_tick_reads(name):
    """A store of ``paged._conv_store`` through ``paged._conv_rows``: the
    rows that close a run write their slot's row of every stored input,
    the next tick's rows read them back in the order written, and nothing
    else moves: not the other layers' rows, not a slot that had no row,
    not slot 0 under any number of pad rows."""
    layers, inputs, channels = CONV_STORES[name]
    slots, layer = 3, 1
    rng = np.random.default_rng(5)
    store = PG._conv_store(layers, slots, (inputs, channels), jnp.float32)
    assert store.shape == (layers * inputs * (slots + 1), channels)
    store = store + 7.0                             # whatever was there
    # a tick: a run of three rows of slot 2, a pad, slot 1 alone, two pads
    slot = jnp.asarray([2, 2, 2, 0, 1, 0, 0], jnp.int32)
    runs = HY.runs_of(slot, jnp.asarray([4, 5, 6, 0, 9, 0, 0], jnp.int32))
    read, write = PG._conv_rows(slots + 1, slot, runs.last & (slot > 0))
    assert all(np.all(np.asarray(x) == 7.0) and x.shape == (7, channels)
               for x in read(store, layer, inputs))
    new = tuple(jnp.asarray(rng.normal(size=(7, channels)), jnp.float32)
                for _ in range(inputs))
    after = write(store, jnp.int32(layer), new)
    # the next tick: one row a slot, pads among them
    slot2 = jnp.asarray([0, 1, 2, 3, 0], jnp.int32)
    read2, _ = PG._conv_rows(slots + 1, slot2, slot2 > 0)
    got = read2(after, jnp.int32(layer), inputs)
    assert len(got) == inputs
    for k in range(inputs):
        np.testing.assert_array_equal(got[k][1], new[k][4])   # slot 1
        np.testing.assert_array_equal(got[k][2], new[k][2])   # slot 2
        assert np.all(np.asarray(got[k][3]) == 7.0)           # no row
        assert np.all(np.asarray(got[k][0]) == 7.0)           # the pads'
    by_row = np.asarray(after).reshape(layers, inputs, slots + 1, channels)
    assert np.all(np.delete(by_row, layer, axis=0) == 7.0)
    assert int((by_row != 7.0).any(axis=-1).sum()) == 2 * inputs


@pytest.mark.parametrize("case", ["mid-tick", "decode", "one-run"])
def test_segmented_scan_and_conv_against_a_sequential_scan(case):
    """Runs that start mid-tick, from stored state or from position 0,
    against one sequence at a time, one position at a time."""
    rng = np.random.default_rng(3)
    n, di, c = 4, 8, 4
    # (owner, first position, rows): the layout of one tick
    layout = {"mid-tick": [(2, 7, 5), (1, 0, 6), (3, 11, 1), (0, 0, 1)],
              "decode": [(1, 4, 1), (2, 9, 1), (3, 1, 1)],
              "one-run": [(1, 0, 19)]}[case]
    owner = np.concatenate([[o] * r for o, _, r in layout]).astype(np.int32)
    pos = np.concatenate([np.arange(p, p + r) for _, p, r in layout]
                         ).astype(np.int32)
    Tn = len(owner)
    runs = HY.runs_of(jnp.asarray(owner), jnp.asarray(pos))
    assert int(runs.start.sum()) == len(layout)

    def draw(*shape):
        return rng.normal(size=shape).astype(np.float32)

    delta, xc = np.abs(draw(Tn, di)), draw(Tn, di)
    bm, cm, a_neg = draw(Tn, n), draw(Tn, n), -np.abs(draw(n, di))
    s0, x, taps, c0 = draw(4, n, di), draw(Tn, di), draw(c, di), \
        draw(4, c - 1, di)
    got_s, got_y = HY._selective_scan(*map(jnp.asarray, (
        delta, xc, bm, cm, a_neg)), runs, jnp.asarray(s0[owner]))
    # the stored inputs a tuple of rows, oldest first; a run from position
    # 0 starts from zeros whatever its slot held
    got_c, got_w = HY._segmented_conv(
        jnp.asarray(x), jnp.asarray(taps), runs,
        tuple(jnp.asarray(c0[owner, k]) for k in range(c - 1)))
    assert len(got_w) == c - 1
    got_w = np.stack(got_w, axis=1)
    t = 0
    for o, first, rows in layout:
        s, hist = s0[o], list(c0[o] * (first > 0))
        for _ in range(rows):
            s = np.exp(delta[t][None] * a_neg) * s \
                + (delta[t] * xc[t])[None] * bm[t][:, None]
            hist.append(x[t])
            np.testing.assert_allclose(got_s[t], s, rtol=2e-5, atol=1e-6)
            np.testing.assert_allclose(got_y[t], cm[t] @ s, rtol=2e-5,
                                       atol=1e-5)
            np.testing.assert_allclose(
                got_c[t], sum(taps[k] * hist[-c + k] for k in range(c)),
                rtol=2e-5, atol=1e-5)
            np.testing.assert_allclose(got_w[t], np.stack(hist[-(c - 1):]))
            t += 1


@pytest.mark.parametrize("heads_first", [False, True])
@pytest.mark.parametrize("window", [None, 16, 40])
def test_kernel_under_a_window_and_a_ring_matches_its_jnp_twin(window,
                                                               heads_first):
    """The Mosaic kernel (interpret mode) with a lower limit, over a table
    whose columns alias a ring of 8 blocks, against the gathering twin: a
    chunk whose window edge falls inside it, decode rows of other slots,
    pad rows."""
    rng = np.random.default_rng(5)
    bs, RB, K, D, N, MB = 4, 8, 2, 32, 8, 24
    pool = [jnp.asarray(rng.normal(
        size=(4 * RB, K, bs, D) if heads_first else (4 * RB, bs, K, D)),
        jnp.float32) for _ in range(2)]
    slot = np.array([1] * 20 + [2, 3] + [0] * 10, np.int32)
    pos = np.concatenate([np.arange(50, 70), [33, 5], np.zeros(10)]
                         ).astype(np.int32)
    tables = slot[:, None] * RB + (np.arange(MB) % RB)[None, :]
    if window is None:          # no ring without a window: plain tables
        pos = np.minimum(pos, RB * bs - 1)
    q = jnp.asarray(rng.normal(size=(32, N, D)), jnp.float32)
    args = (q, *pool, jnp.asarray(tables), jnp.asarray(pos + 1))
    want = PG.paged_attention_reference(*args, scale=0.2, window=window,
                                        heads_first=heads_first)
    got = paged_attention(*args, scale=0.2, window=window, name="twin",
                          heads_first=heads_first)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_differential_attention_is_one_grouped_attention():
    """The paired-head layout against the four products written out."""
    rng = np.random.default_rng(7)
    S, N, K, D = 9, 8, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(S, n, D)), jnp.float32)
               for n in (N, K, K))
    lp = {"lambda_q1": jnp.full((D,), 0.1), "lambda_k1": jnp.full((D,), 0.2),
          "lambda_q2": jnp.full((D,), -0.1), "lambda_k2": jnp.full((D,), 0.3),
          "sub_norm": jnp.asarray(rng.normal(size=(2 * D,)), jnp.float32)}
    o = HY.windowed_attention(
        HY.paired_queries(q)[None], HY.paired_cache(k)[None],
        HY.paired_cache(v)[None], D ** -0.5, 4)[0]
    got = HY.differential_merge(o, lp, 3, 1e-5)
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    mask = jnp.asarray((j <= i) & (j > i - 4))
    want = R._differential(q, k, v, lp, jnp.float32(3), mask,
                           {"head_dim": D, "eps": 1e-5})
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_importer_places_the_five_kinds_and_counts_the_published_size():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    cfg = config_from_hf(types.SimpleNamespace(**row["config"]))
    kinds = cfg.layer_kinds
    assert len(kinds) == 32 and kinds == tuple(R.kinds(32))
    assert [l for l, k in enumerate(kinds) if k == "mamba"] == list(
        range(0, 17, 2))
    assert [l for l, k in enumerate(kinds) if k == "window"] == list(
        range(1, 16, 2))
    assert kinds[17] == "full"
    assert all(kinds[l] == ("gmu", "cross")[l % 2] for l in range(18, 32))
    assert [(key, seg.period, seg.num_layers) for key, seg in cfg.segments] \
        == [("mamba_window_blocks", ("mamba", "window"), 8),
            ("mamba_full_blocks", ("mamba", "full"), 1),
            ("gmu_cross_blocks", ("gmu", "cross"), 7)]
    assert (cfg.attn_window, cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv,
            cfg.ssm_dt_rank, cfg.head_dim) == (512, 5120, 16, 4, 160, 64)
    assert cfg.tie_embeddings and cfg.pos_emb == "none"
    assert round(cfg.num_params() / 1e9, 2) == 3.85
    # the count is the tree's: shapes alone, nothing of this size is made
    shapes = jax.eval_shape(lambda k: T.init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == cfg.num_params()


def test_axes_tree_matches_the_parameters(model):
    cfg, params, *_ = model
    H.assert_axes_name_every_leaf(cfg, params)
    assert cfg.num_params() == sum(x.size for x in jax.tree.leaves(params))


test_entry_points_that_refuse_a_stack_of_kinds = H.entry_points_refuse_test(
    FAMILY, ["model"])

