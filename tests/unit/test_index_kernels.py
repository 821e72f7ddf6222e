"""The sparse layers' kernels, interpreted, against plain jnp: the choice
(``ops/pallas/sparse_choice.py`` and ``paged.sparse_choice``: a bisection
over the scores' bits) against ``lax.top_k``, the indexer's scores
(``ops/pallas/index_scores.py``), the attention under a choice, and the
walks of ``tools/index_kernel_alone.py`` and ``tools/choice_kernel_alone.py``.
The stack these layers stand in is ``test_keye_sparse_stack.py``'s.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.ops.pallas import index_scores as IX
from deepspeed_tpu.ops.pallas import sparse_choice as SC
from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

from family_harness import TOL, load_tool, rel


# ------------------------------------------------------------------ #
# the choice
# ------------------------------------------------------------------ #
def _choice_by_top_k(scores, lengths, topk):
    """``lax.top_k``'s set a row, in the order of the scores' BITS: it
    holds ``-0.0`` equal to ``+0.0``, the choice ranks it under."""
    S = scores.shape[1]
    valid = np.arange(S)[None] < lengths[:, None]
    scores = np.where((scores == 0) & np.signbit(scores),
                      np.float32(-1e-30), scores)
    _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), topk)
    picked = np.zeros(scores.shape, bool)
    np.put_along_axis(picked, np.asarray(idx), True, axis=1)
    return picked & valid


def _choice_case(case):
    """(scores [T, S], lengths [T], topk) of a case of the choice."""
    rng = np.random.default_rng(3)
    Tn, S, topk = 12, 256, 40
    if case in ("a-tile-counts-nothing", "three-tiles"):
        Tn, S = (64, 384) if case == "a-tile-counts-nothing" else (70, 384)
    elif case == "lengths-inside-a-lane-tile":
        S = 384
    scores = rng.normal(size=(Tn, S)).astype(np.float32) * 1e3
    lengths = rng.integers(topk + 1, S + 1, Tn).astype(np.int32)
    if case == "ties":
        scores = rng.integers(-2, 3, (Tn, S)).astype(np.float32)
    elif case == "zeros":
        scores[:] = 0.0
    elif case == "short":
        lengths = rng.integers(1, topk + 1, Tn).astype(np.int32)
        lengths[0] = topk + 5           # one row chooses, the others cannot
    elif case == "pad-rows":
        # rows of no position at all among rows that choose, and what lies
        # past a row's length is never looked at
        lengths[::3] = 0
        scores = np.where(np.arange(S)[None] < lengths[:, None], scores,
                          np.float32(np.inf))
    elif case == "a-tile-counts-nothing":
        # the kernel's first tile of 32 rows has no row over ``topk``
        lengths[:32] = rng.integers(1, topk + 1, 32)
    elif case == "lengths-inside-a-lane-tile":
        lengths = np.asarray([41, 127, 128, 129, 130, 200, 255, 256, 257,
                              258, 300, 383], np.int32)
    elif case == "three-tiles":
        # the second tile's longest row ends in the second of three planes
        lengths[32:64] = rng.integers(topk + 1, 201, 32)
    elif case == "signed-zeros":
        # ten scores over zero, then zeros of both signs: the cut is
        # ``+0.0`` where a row has thirty of them and ``-0.0`` where not
        plus = rng.random((Tn, S)) < np.linspace(0.02, 0.9, Tn)[:, None]
        scores = np.where(plus, np.float32(0.0), np.float32(-0.0))
        scores[:, 3:33:3] = rng.integers(1, 9, (Tn, 10))
    elif case == "ties-in-one-row":
        scores[5] = rng.integers(-2, 3, S)
    return scores, lengths, topk


CHOICE_CASES = ["random", "ties", "zeros", "short", "pad-rows",
                "a-tile-counts-nothing", "lengths-inside-a-lane-tile",
                "three-tiles", "signed-zeros", "ties-in-one-row"]


@pytest.mark.parametrize("layout", ["rows", "lane-tiles", "kernel"])
@pytest.mark.parametrize("case", CHOICE_CASES)
def test_the_choice_is_the_exact_top_k(case, layout):
    """``sparse_choice`` (a bisection over the scores' bits, then over
    positions among equals) against ``lax.top_k`` a row, in the plain
    path's layout, the kernels' and by the kernel itself (interpreted; its
    mask is the plain form's element for element too): random scores of
    both signs, scores drawn from five values (equals straddle the cut: the
    lower position first) in every row or in one, all zeros, zeros of both
    signs astride the cut, rows no longer than ``topk`` alone or a whole
    tile of them, rows of no position, lengths on either side of a lane
    tile's end, more rows than a tile."""
    scores, lengths, topk = _choice_case(case)
    Tn, S = scores.shape
    want = _choice_by_top_k(scores, lengths, topk)
    pos = jnp.arange(S, dtype=jnp.int32)
    tiles = jnp.asarray(scores).reshape(Tn, S // 128, 128).transpose(1, 0, 2)

    def plain_tiles():
        return PG.sparse_choice(
            tiles, pos.reshape(S // 128, 1, 128),
            jnp.asarray(lengths)[None, :, None], topk, (0, 2), S)

    if layout == "rows":
        got = PG.sparse_choice(jnp.asarray(scores), pos[None],
                               jnp.asarray(lengths)[:, None], topk, (1,), S)
    elif layout == "lane-tiles":
        got = plain_tiles().transpose(1, 0, 2).reshape(Tn, S)
    else:
        # whole tiles of rows, as ``index_scores`` hands them over: the
        # rows past the tick's choose nothing whatever their scores hold
        got = SC.sparse_choice(
            jnp.pad(tiles, ((0, 0), (0, -Tn % IX.TILE_ROWS), (0, 0)),
                    constant_values=np.nan),
            jnp.asarray(lengths), topk, interpret=True)
        assert got.dtype == jnp.float32 and not bool(got[:, Tn:].any())
        np.testing.assert_array_equal(np.asarray(got[:, :Tn]),
                                      np.asarray(plain_tiles()))
        got = got[:, :Tn].transpose(1, 0, 2).reshape(Tn, S)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(want.sum(1), np.minimum(lengths, topk))
    if case == "zeros":     # equal scores: the lowest positions
        assert want[:, :topk].all() and not want[:, topk:].any()

# ------------------------------------------------------------------ #
# the kernels, interpreted, against plain jnp
# ------------------------------------------------------------------ #
def _tick_rows(rng, S1, MB, bs, runs):
    """Rows of a tick: ``runs`` of (slot, first position, rows)."""
    slot = np.concatenate([np.full(n, s) for s, _, n in runs])
    pos = np.concatenate([np.arange(a, a + n) for _, a, n in runs])
    tables = np.zeros((S1, MB), np.int32)
    blocks = 1 + rng.permutation((S1 - 1) * MB)      # every block once
    for s in range(1, S1):
        tables[s] = blocks[(s - 1) * MB:s * MB]
    return slot.astype(np.int32), pos.astype(np.int32), tables


# (slot, first position, rows) of a tick's runs; tiles are of 32 rows
INDEX_RUNS = {
    "whole-tile": [(1, 100, 32)],
    "share-a-tile": [(1, 200, 5), (2, 31, 12), (3, 100, 15)],
    "across-tiles": [(3, 100, 40)],
    "rows-alone": [(1, 200, 1), (2, 31, 1), (3, 250, 1)],
    "pad-rows": [(1, 200, 1), (2, 31, 1), (3, 100, 40), (1, 201, 7),
                 (0, 0, 3)],
}
INDEX_CASES = [(runs, heads, store, "random") for runs in INDEX_RUNS
               for heads in (4, 16) for store in ("float32", "bfloat16")] \
    + [("pad-rows", 16, "float32", weights)
       for weights in ("negative-rows", "zero-head")]


def _index_case(runs, heads, store, weights, MB=32):
    """(q, w, store, tables, lengths, slot) of a tick of ``runs`` (a name
    of ``INDEX_RUNS`` or a list), tables of ``MB`` blocks of 8."""
    rng = np.random.default_rng(5)
    S1, bs, W = 4, 8, 128
    slot, pos, tables = _tick_rows(
        rng, S1, MB, bs, INDEX_RUNS[runs] if isinstance(runs, str) else runs)
    Tn = len(slot)
    dtype = jnp.dtype(store)
    keys = jnp.asarray(rng.normal(size=(1 + (S1 - 1) * MB, bs, W)),
                       dtype).at[..., 8:].set(0.0)
    q = jnp.asarray(rng.normal(size=(Tn, heads, W)), dtype).at[
        ..., 8:].set(0.0)
    w = rng.normal(size=(Tn, heads)).astype(np.float32)
    if weights == "negative-rows":      # the first row alone, one of a run
        w[[0, 5]] = -np.abs(w[[0, 5]])
    elif weights == "zero-head":
        w[:, 2] = 0.0
    return (q, jnp.asarray(w), keys, jnp.asarray(tables),
            jnp.asarray(pos + 1), jnp.asarray(slot))


def _index_rows(got, Tn, S):
    """``index_scores``' planes as ``[Tn, S]``."""
    nC, Tp, C = got.shape
    assert Tp % IX.TILE_ROWS == 0 and nC * C >= S
    return got.transpose(1, 0, 2).reshape(Tp, nC * C)[:Tn, :S]


@pytest.mark.parametrize("runs,heads,store,weights", INDEX_CASES)
def test_index_scores_kernel_matches_plain_jnp(runs, heads, store, weights):
    """A run that is its whole tile, runs that share a tile, a run that
    crosses tiles, rows alone, pad rows: the kernel's scores under each
    row's length are the plain path's, for few heads and the cell's 16, a
    float32 store and the served bfloat16 (whose products are exact in
    float32: the same tolerance). A row whose weights are all negative
    scores nothing above zero (the ``relu`` comes BEFORE the weights), and
    a head of weight zero leaves no trace of its queries."""
    q, w, keys, tables, lengths, slot = _index_case(runs, heads, store,
                                                    weights)
    Tn, MB, bs = len(slot), tables.shape[1], keys.shape[1]

    def rows_of(q):
        with jax.default_matmul_precision("highest"):
            return _index_rows(IX.index_scores(
                q, w, keys, tables, lengths, slot, interpret=True),
                Tn, MB * bs)

    with jax.default_matmul_precision("highest"):
        want = IX.index_scores_reference(q, w, keys, tables[slot])
    rows = rows_of(q)
    live = np.arange(MB * bs)[None] < np.asarray(lengths)[:, None]
    np.testing.assert_allclose(np.where(live, rows, 0),
                               np.where(live, want, 0), rtol=1e-5, atol=1e-4)
    if weights == "negative-rows":
        assert (np.where(live, rows, 0)[[0, 5]] <= 0).all()
        assert (np.where(live, rows, 0)[[0, 5]] < 0).any()
    elif weights == "zero-head":
        np.testing.assert_array_equal(
            np.where(live, rows_of(q.at[:, 2].multiply(-3.0)), 0),
            np.where(live, rows, 0))


def test_index_scores_walks_longer_than_its_ring(monkeypatch):
    """Walks of one step, of fewer steps than the ring has slots and of
    twice as many (a step cut to 128 positions: tables of 128 blocks of 8
    are this test's alone, so no other trace of the call is met): every
    fetch lands in the slot its step reads, rows alone and runs alike."""
    monkeypatch.setattr(IX, "_STEP_POSITIONS", 128)
    q, w, keys, tables, lengths, slot = _index_case(
        [(1, 99, 1), (2, 299, 1), (3, 1000, 1), (1, 100, 3), (2, 990, 30),
         (3, 600, 12)], 4, "float32", "random", MB=128)
    assert IX.step_positions(8, 1024) == 128 and IX._SLOTS == 4
    assert sorted(set(-(-np.asarray(lengths) // 128)))[:3] == [1, 3, 5]
    with jax.default_matmul_precision("highest"):
        got = IX.index_scores(q, w, keys, tables, lengths, slot,
                              interpret=True)
        want = IX.index_scores_reference(q, w, keys, tables[slot])
    live = np.arange(1024)[None] < np.asarray(lengths)[:, None]
    np.testing.assert_allclose(
        np.where(live, _index_rows(got, len(slot), 1024), 0),
        np.where(live, want, 0), rtol=1e-5, atol=1e-4)


def _sub_jaxprs(jaxpr):
    """``jaxpr`` and every jaxpr under its equations' parameters."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _sub_jaxprs(sub)


def test_the_index_kernel_sums_its_heads_off_the_mxu():
    """The mechanism of PR 56, pinned where a CPU can see it: the kernel's
    body holds ONE product a form (a row alone, a tile: two in all; the
    heads' weighted sum was a second product in each, over the tile's
    weights laid block-diagonally), and no operand ``[.., R * heads]``
    reaches the call."""
    q, w, keys, tables, lengths, slot = _index_case("pad-rows", 16,
                                                    "bfloat16", "random")
    H = q.shape[1]
    traced = jax.make_jaxpr(lambda *a: IX.index_scores(*a, interpret=False))(
        q, w, keys, tables, lengths, slot)
    calls = [e for j in _sub_jaxprs(traced.jaxpr) for e in j.eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    call, = calls
    assert call.params["name"] == "index_scores"
    products = [e for j in _sub_jaxprs(call.params["jaxpr"]) for e in j.eqns
                if e.primitive.name == "dot_general"]
    assert len(products) == 2
    for e in products:                  # the keys' type in, float32 out
        assert {v.aval.dtype for v in e.invars} == {jnp.dtype(jnp.bfloat16)}
        assert e.outvars[0].aval.dtype == jnp.float32
    wide = IX.TILE_ROWS * H
    assert not [v.aval.shape for v in call.invars
                if v.aval.shape and v.aval.shape[-1] == wide]


def test_the_index_alone_tool_still_walks():
    """``tools/index_kernel_alone.py`` on its tiny cases, interpreted: the
    call runs chained at two trip counts, the steps it reckons are the
    runs', and the tree's kernel beside itself differs nowhere (its times
    are a chip's to give: none is read here)."""
    tool = load_tool("index_kernel_alone")
    # the cell's six tick programs, by (rows, table tier)
    assert {case[:2] for case in tool.CASES.values()} == {
        (rows, tier) for rows in (256, 2048) for tier in (36, 72, 144)}
    H, _, W, bs, NB = tool.TINY_DIMS
    for case in tool.TINY.values():
        ops = tool.operands(np.random.default_rng(0), case, tool.TINY_DIMS,
                            jnp.float32)
        q, w, store, tables, lengths, slot = ops
        assert q.shape == (case[0], H, W) and store.shape == (NB, bs, W)
        assert tables.shape == (case[2] + 2, case[1])
        positions = IX.step_positions(bs, case[1] * bs)
        tile, alone = tool.count_steps(lengths, slot, positions)
        # the decode rows walk alone, the chunk's rows a tile together (a
        # decode tick's pads: one run of one step)
        assert alone == sum(-(-int(n) // positions)
                            for n in np.asarray(lengths)[:case[2]])
        assert tile == (case[0] // 32 if case[3] else 1)
        one = float(IX.index_scores(*ops, interpret=True)[0, 0, 0])
        totals = [float(tool.chained(IX, n, True)(*ops)) for n in (1, 3)]
        np.testing.assert_allclose(totals, [one, 3 * one], rtol=1e-6)
        same = tool.compare(IX, IX, ops, True)
        assert same["differ"] == 0 and same["live"] == int(
            np.asarray(lengths).sum())

def test_the_choice_alone_tool_still_walks():
    """``tools/choice_kernel_alone.py`` on its tiny cases, interpreted:
    both forms run chained and give one mask (its times are a chip's to
    give: none is read here)."""
    tool = load_tool("choice_kernel_alone")
    # the cell's six tick programs, by (rows, table tier)
    assert {case[:2] for case in tool.CASES.values()} == {
        (rows, tier) for rows in (256, 2048) for tier in (36, 72, 144)}
    forms = tool.forms_of(None, tool.TINY_TOPK, True)
    for case in tool.TINY.values():
        scores, lengths = tool.operands(np.random.default_rng(0), case)
        assert scores.shape == (case[1], case[0], 128)
        tiles, counting, planes = SC.count_tiles(lengths, tool.TINY_TOPK)
        assert tiles == case[0] // 32 and counting == (2 if case[3] else 1)
        assert planes == (4 if case[3] else 2)
        totals = [float(tool.chained(forms[f], 2)(scores, lengths))
                  for f in ("kernel", "plain")]
        assert totals[0] == totals[1]
        np.testing.assert_array_equal(
            np.asarray(forms["kernel"](scores, lengths)),
            np.asarray(forms["plain"](scores, lengths)))


def test_attention_under_a_choice_matches_plain_jnp():
    """``paged_attention(chosen=)``: every step takes the choice as a mask,
    rows alone and rows of a run alike; with every position chosen it is
    the kernel without a choice, to the bit."""
    rng = np.random.default_rng(6)
    S1, MB, bs, N, K, D = 4, 32, 8, 4, 2, 16
    slot, pos, tables = _tick_rows(rng, S1, MB, bs, [
        (1, 200, 1), (2, 31, 1), (3, 100, 40), (1, 201, 7), (0, 0, 3)])
    Tn = len(slot)
    NB = 1 + (S1 - 1) * MB
    kp = jnp.asarray(rng.normal(size=(NB, bs, K, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, bs, K, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(Tn, N, D)), jnp.float32)
    lengths = jnp.asarray(pos + 1)
    chosen = rng.random((Tn, MB * bs)) < 0.3
    chosen[np.arange(Tn), pos] = True            # a row sees itself
    Tp = -(-Tn // 32) * 32
    planes = jnp.asarray(np.pad(chosen, ((0, Tp - Tn), (0, 0))),
                         jnp.float32).reshape(Tp, -1, 128).transpose(1, 0, 2)
    kw = dict(interpret=True, name="sparse_attention",
              row_table=jnp.asarray(slot))
    with jax.default_matmul_precision("highest"):
        got = paged_attention(q, kp, vp, jnp.asarray(tables), lengths,
                              chosen=planes, **kw)
        want = PG.paged_attention_reference(
            q, kp, vp, jnp.asarray(tables)[slot], lengths,
            chosen=jnp.asarray(chosen))
        every = paged_attention(q, kp, vp, jnp.asarray(tables), lengths,
                                chosen=jnp.ones_like(planes), **kw)
        plain = paged_attention(q, kp, vp, jnp.asarray(tables), lengths,
                                **kw)
    assert rel(got, want) < TOL
    assert bool(jnp.all(every == plain))
