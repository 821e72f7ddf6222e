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

from benchmarks.reference import sambay_lm as R
from deepspeed_tpu.inference.fastgen import BlockAllocator, FastGenEngine
from deepspeed_tpu.models import hybrid as HY
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import config_from_hf
from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

TOL = 2e-5
HF = dict(model_type="phi4flash", hidden_size=64, intermediate_size=96,
          layer_norm_eps=1e-5, max_position_embeddings=4096, mb_per_layer=2,
          num_attention_heads=8, num_hidden_layers=8, num_key_value_heads=4,
          sliding_window=16, tie_word_embeddings=True, vocab_size=128,
          hidden_act="silu")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture(scope="module")
def model():
    cfg = config_from_hf(types.SimpleNamespace(**HF))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    # biases and norm offsets off zero, so a dropped one shows
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = tree.unflatten([x + 0.05 * jax.random.normal(k, x.shape)
                             for x, k in zip(leaves, keys)])
    toks = np.random.default_rng(0).integers(0, 128, (2, 60)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        whole = T.forward(params, jnp.asarray(toks), cfg)
    return cfg, params, toks, whole, R.arch_from_config(HF, HF)


def _engine(cfg, params, **kw):
    kw = {"n_blocks": 64, "block_size": 4, "max_blocks_per_seq": 16,
          "token_budget": 16, "state_slots": 3, "use_pallas_kernel": False,
          **kw}
    return FastGenEngine(cfg, params, **kw)


def _drive(eng, cfg, toks, attn, chunk, n_prompt):
    """The runner's check (``benchmarks/runners/serve.py::check_logits``) in
    small: every sequence ``allocate``d once, ticks of the flat prompt rows
    ``chunk`` at a time, then decode ticks; logits of every position."""
    Tn, mb, bs = eng.token_budget, eng.max_blocks_per_seq, eng.block_size
    S = toks.shape[1]
    tabs, blocks = [], []
    for _ in toks:
        b = eng.allocator.allocate(S // bs + 1)
        t = np.zeros(mb, np.int32)
        t[:len(b)] = b
        tabs.append(t)
        blocks.append(b)
    fwd = jax.jit(lambda pr, pool, t, p, tb: PG.forward_paged(
        pr, t, p, tb, pool, cfg, attention_fn=attn))
    got = {}

    def tick(rows):
        t = np.zeros(Tn, np.int32)
        p = np.zeros(Tn, np.int32)
        tb = np.zeros((Tn, mb), np.int32)
        for r, (i, pos) in enumerate(rows):
            t[r], p[r], tb[r] = toks[i, pos], pos, tabs[i]
        with jax.default_matmul_precision("highest"):
            lg, eng.pool = fwd(eng.params, eng.pool, jnp.asarray(t),
                               jnp.asarray(p), jnp.asarray(tb))
        for r, (i, pos) in enumerate(rows):
            got[(i, pos)] = lg[r]

    flat = [(i, p) for i in range(len(toks)) for p in range(n_prompt)]
    for lo in range(0, len(flat), chunk):
        tick(flat[lo:lo + chunk])
    for p in range(n_prompt, S):
        tick([(i, p) for i in range(len(toks))])
    for b in blocks:
        eng.allocator.free(b)
    return jnp.stack([jnp.stack([got[(i, p)] for p in range(S)])
                      for i in range(len(toks))])


def test_whole_forward_matches_the_reference(model):
    cfg, params, toks, whole, arch = model
    assert _rel(whole, R.forward_logits(params, toks, arch)) < TOL


@pytest.mark.parametrize("attn,chunk", [
    (None, 13),               # chunk and sequence boundaries fall mid-tick
    (paged_attention, 13),    # the kernels (interpret mode) under the tick
    (None, 16),               # a full tick: the ring holds window + run
])
def test_paged_ticks_match_whole_forward_and_reference(model, attn, chunk):
    """60 positions under a window of 16 and a ring of 32: the ring wraps,
    the window's edge falls inside chunks, the second sequence starts in
    the tick that ends the first, and every pool starts full of garbage
    (a slot's last tenant): state is zero at position 0 whatever is there."""
    cfg, params, toks, whole, arch = model
    eng = _engine(cfg, params)
    assert eng.pool["wk"].shape[1] == 4 * 8      # (3 slots + trash) x 8
    eng.pool = {k: v + 1.0 for k, v in eng.pool.items()}
    got = _drive(eng, cfg, toks, attn, chunk, n_prompt=57)
    assert _rel(got, whole) < TOL
    assert _rel(got, R.forward_logits(params, toks, arch)) < TOL
    assert eng.allocator.free_blocks == 63 and eng.allocator.free_slots == 3


def test_engine_serves_two_interleaved_and_reuses_a_slot(model):
    """Through ``FastGenEngine`` itself: token budget under the prompts'
    length, two sequences interleaved, a third admitted into the slot the
    first left (two slots only, so it waits for one). Greedy tokens against
    the reference's logits, teacher-forced on the engine's own output."""
    cfg, params, toks, _, arch = model
    eng = _engine(cfg, params, state_slots=2)
    # 47 positions each: the reference compiles one length
    prompts = {1: toks[0, :41].tolist(), 2: toks[1, :35].tolist(),
               3: toks[0, 5:47].tolist()}
    want = {1: 6, 2: 12, 3: 5}
    eng.put([1, 2, 3], [prompts[u] for u in (1, 2, 3)])
    waits = eng._tm_slot_waits.total()
    slots_seen = {}
    with jax.default_matmul_precision("highest"):
        for _ in range(200):
            eng.step()
            for u, s in eng.seqs.items():
                if s.blocks:
                    slots_seen[u] = s.blocks[0]
                if not s.done and len(s.generated) >= want[u]:
                    eng._finish(s)
            if all(s.done for s in eng.seqs.values()):
                break
    assert eng._tm_slot_waits.total() - waits == 1
    assert all(b in (1, 2) for b in slots_seen.values())
    assert slots_seen[3] == slots_seen[1]     # handed on by the first to end
    for u in (1, 2, 3):
        out = eng.query(u)[1][:want[u]]
        seq = np.asarray(prompts[u] + out, np.int32)[None]
        ref = R.forward_logits(params, seq, arch)[0]
        n = len(prompts[u])
        assert out == [int(t) for t in jnp.argmax(
            ref[n - 1:n - 1 + want[u]], axis=-1)]
    eng.flush([1, 2, 3])
    assert eng.allocator.free_slots == 2 and eng.allocator.free_blocks == 63


def test_state_outside_the_block_pool_does_not_grow(model):
    """Per sequence the rings and the state are a slot's, whatever the
    length: after 3 x the ring's positions the sequence holds one slot and
    only the one ``full`` layer's blocks; the other layers wrote no block."""
    cfg, params, toks, _, _ = model
    eng = _engine(cfg, params, n_blocks=40, max_blocks_per_seq=32)
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
    eng = _engine(cfg, params)
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
    axes = T.param_logical_axes(cfg)
    flat_p = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    flat_a = dict(jax.tree_util.tree_flatten_with_path(
        axes, is_leaf=lambda x: isinstance(x, tuple))[0])
    assert flat_p.keys() == flat_a.keys()
    assert all(len(flat_a[k]) == flat_p[k].ndim for k in flat_p)
    assert cfg.num_params() == sum(x.size for x in jax.tree.leaves(params))


@pytest.mark.parametrize("entry", ["forward_decode", "pipeline", "tp", "pld"])
def test_entry_points_that_refuse_a_stack_of_kinds(model, entry):
    cfg, params, toks, *_ = model
    with pytest.raises(NotImplementedError, match="layer kinds|layer_kinds"):
        if entry == "forward_decode":
            T.forward_decode(params, jnp.asarray(toks[:, :4]), {},
                             jnp.zeros((2,), jnp.int32), cfg)
        elif entry == "pipeline":
            T.pipelined_lm_loss(params, jnp.asarray(toks), cfg, 2)
        elif entry == "pld":
            T.forward_hidden(params, jnp.asarray(toks), cfg,
                             pld_keep=jnp.ones((8,)))
        else:
            from deepspeed_tpu.comm.mesh import (MeshConfig, initialize_mesh,
                                                 reset_mesh)

            reset_mesh()
            initialize_mesh(MeshConfig(data=4, tensor=2))
            try:
                _engine(cfg, params, tp=True)
            finally:
                reset_mesh()
