"""Grouped-query layers that attend to the positions a learned indexer
chooses (``TransformerConfig.layer_kinds`` of ``sparse``; the ``KeyeVL2``
family's language model, DeepSeek sparse attention on the Qwen3-MoE block),
over expert layers that hold a share of their experts.

Toy widths, float32, matmul precision "highest", ``topk`` SHORTER than the
prompts: the paged tick (``models/paged.forward_paged`` over the engine's
block ranges: keys, values and index keys), the whole-sequence forward
(``T.forward``) and the plain reference
(``benchmarks/reference/keye_sparse_lm.py``, which imports nothing of the
program) are three implementations of the same equations and agree to
rounding, ~1e-6 relative. The choice is made three ways (``lax.top_k`` and a
scatter in ``T.forward``, ``lax.top_k`` a block of rows in the reference, a
bisection over the scores' bits in the tick) and every one is exact, so the
sets agree unless two scores differ by rounding alone: the tests that hold
the choice FIXED hand the reference's sets to both sides, so that a fault
in the attention cannot hide behind an exchanged position and an exchanged
position cannot pass for a fault.
"""
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import keye_sparse_lm as R
from deepspeed_tpu.inference.fastgen import FastGenEngine
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import (config_from_hf, import_hf_model,
                                            params_from_keye_vl2)
from deepspeed_tpu.moe import layer as MOE
from deepspeed_tpu.ops.pallas import index_scores as IX
from deepspeed_tpu.ops.pallas import sparse_choice as SC
from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

TOL = 2e-5
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOPK = 16


def _hf(**kw):
    hf = dict(model_type="KeyeVL2", hidden_size=64, intermediate_size=96,
              moe_intermediate_size=32, head_dim=16, num_attention_heads=4,
              num_key_value_heads=2, num_hidden_layers=3, num_experts=4,
              num_local_experts=4, router_experts=8, num_experts_per_tok=2,
              norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=10000000,
              rope_scaling={"mrope_section": [2, 3, 3],
                            "rope_type": "default", "type": "default"},
              sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
                         "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                         "q_chunk_size": 512, "topk": TOPK},
              decoder_sparse_step=1, mlp_only_layers=[], attention_bias=False,
              use_sliding_window=False, sliding_window=None,
              tie_word_embeddings=False, vocab_size=128,
              max_position_embeddings=4096)
    hf.update(kw)
    return hf


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _noisy(params, seed=1):
    """Every leaf off its start, the indexer's and the router's widely (so
    that scores are spread and a dropped term shows)."""
    def one(path, x):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(jax.random.PRNGKey(seed),
                               hash(name) % (2 ** 31))
        noise = jax.random.normal(k, x.shape)
        if "norm" in name or "ln" in name:
            return x + 0.1 * noise
        if "gate_w" in name:
            # ``init_params`` draws a share's router as one of EQUAL shares
            # (the held experts' columns repeated): a column of its own
            # again, so that another share's columns are another result
            return 10.0 * (x + 0.02 * noise)
        return x * (10.0 if "idx" in name else 3.0)

    return jax.tree_util.tree_map_with_path(one, params)


@pytest.fixture(scope="module")
def model():
    hf = _hf()
    cfg = config_from_hf(types.SimpleNamespace(**hf))
    params = _noisy(T.init_params(cfg, jax.random.PRNGKey(0)))
    toks = np.random.default_rng(0).integers(0, 128, (2, 60)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        whole = T.forward(params, jnp.asarray(toks), cfg)
    return cfg, params, toks, whole, R.arch_from_config(hf, hf)


def _engine(cfg, params, **kw):
    kw = {"n_blocks": 64, "block_size": 8, "max_blocks_per_seq": 16,
          "token_budget": 32, "state_slots": 3, "use_pallas_kernel": False,
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


# ------------------------------------------------------------------ #
# three implementations of the same equations
# ------------------------------------------------------------------ #
def test_whole_forward_matches_the_reference(model):
    cfg, params, toks, whole, arch = model
    assert toks.shape[1] > 3 * TOPK
    assert _rel(whole, R.forward_logits(params, toks, arch)) < TOL


@pytest.mark.parametrize("attn,chunk,budget,blocks", [
    (None, 27, 32, 16),         # chunks that cross ``topk`` and a sequence
    (None, 32, 32, 16),
    # the kernels, interpreted: every row walks its sequence under the
    # choice as a mask. Tables of 16 blocks reach 8 x ``topk``, of 32
    # blocks 16 x; a tick of 64 rows is two of the kernels' row tiles
    (paged_attention, 27, 32, 16),
    (paged_attention, 27, 32, 32),
    (paged_attention, 50, 64, 32),
])
def test_paged_ticks_match_whole_forward_and_reference(model, attn, chunk,
                                                       budget, blocks):
    """Chunked prefill (the first chunk of 27 rows crosses ``topk`` 16: its
    first rows attend to all they have, its last choose) and decode through
    the engine's pool."""
    cfg, params, toks, whole, arch = model
    eng = _engine(cfg, params, use_pallas_kernel=attn is not None,
                  token_budget=budget, max_blocks_per_seq=blocks)
    got = _drive(eng, cfg, toks, attn, chunk, 48)
    assert _rel(got, whole) < TOL
    assert _rel(got, R.forward_logits(params, toks, arch)) < TOL


@pytest.mark.parametrize("kernels", [False, True])
def test_with_the_choice_held_fixed(monkeypatch, kernels):
    """The reference's own sets handed to all three: what is left to
    compare is the attention over a given set. One layer: a stack's layers
    are one traced step of a scan, which a set a layer cannot be handed
    to."""
    hf = _hf(num_hidden_layers=1)
    cfg = config_from_hf(types.SimpleNamespace(**hf))
    params = _noisy(T.init_params(cfg, jax.random.PRNGKey(0)))
    arch = R.arch_from_config(hf, hf)
    one = np.random.default_rng(0).integers(0, 128, (1, 60)).astype(np.int32)
    sets = []
    want = R.forward_logits(params, one, arch, chosen=sets)
    assert _rel(R.forward_logits(params, one, arch, given=sets), want) < 1e-6
    (given,) = sets
    # a set of the reference has exactly ``min(t + 1, topk)`` positions
    np.testing.assert_array_equal(
        np.asarray(given).sum(1), np.minimum(np.arange(60) + 1, TOPK))
    # ... and moving it moves the logits: the sets are what is compared
    moved = [jnp.roll(given, 1, axis=1) | jnp.eye(60, dtype=bool)]
    assert _rel(R.forward_logits(params, one, arch, given=moved), want) > 0.01
    monkeypatch.setattr(T, "chosen_positions",
                        lambda scores, topk: given[None])
    with jax.default_matmul_precision("highest"):
        whole = T.forward(params, jnp.asarray(one), cfg)
    assert _rel(whole, want) < TOL

    def fixed(scores, pos, lengths, topk, axes, reach):
        rows = jnp.pad(given, ((0, 0), (0, reach - 60)))[
            jnp.clip(lengths.reshape(-1) - 1, 0, 59)]            # [T, reach]
        if len(axes) == 2:                  # the kernels' [S / 128, T, 128]
            rows = rows.reshape(rows.shape[0], -1, 128).transpose(1, 0, 2)
        return rows & (pos < lengths)

    monkeypatch.setattr(PG, "sparse_choice", fixed)
    eng = _engine(cfg, params, use_pallas_kernel=kernels)
    got = _drive(eng, cfg, one, paged_attention if kernels else None, 27, 48)
    assert _rel(got, want) < TOL


def test_contexts_no_longer_than_topk_equal_the_dense_kind(model):
    """While a row has no more than ``topk`` positions it attends to all of
    them: the ``sparse`` kind is then the ``full`` kind to the bit, in the
    whole forward and through the pool."""
    cfg, params, toks, _, _ = model
    short = toks[:, :TOPK]
    dense = dataclasses.replace(cfg, layer_kinds=("full",) * cfg.num_layers)
    a = T.forward(params, jnp.asarray(short), cfg)
    b = T.forward(params, jnp.asarray(short), dense)
    assert bool(jnp.all(a == b))
    got = _drive(_engine(cfg, params), cfg, short, None, 11, 12)
    want = _drive(_engine(dense, params), dense, short, None, 11, 12)
    assert bool(jnp.all(got == want))


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


def test_equal_scores_choose_the_lower_position_in_the_whole_forward():
    scores = jnp.zeros((1, 40, 40), jnp.float32)
    chosen = np.asarray(T.chosen_positions(scores, 8))[0]
    for t in range(40):
        np.testing.assert_array_equal(
            np.flatnonzero(chosen[t]), np.arange(min(t + 1, 8)))


def test_the_span_counts_the_tiles_that_count():
    """``sparse_choice_tiles`` / ``_counting``: tiles of the choice's
    kernel that hold a real row, and those of them with a row over
    ``topk``: a prompt's first chunk behind three decode rows (the first
    tile counts for them, the second has no row over 64, the third has),
    and a decode tick's one tile of a bucket of eight."""
    lengths = np.concatenate([[380, 370, 375], np.arange(1, 94)])
    mixed = PG._sparse_span(6, 64, 3, [3], 96, 2048, lengths)
    assert mixed["sparse_choice_tiles"] == 6 * 3
    assert mixed["sparse_choice_tiles_counting"] == 6 * 2
    assert mixed["sparse_rows_choosing"] == 3 + 93 - 64
    decode = PG._sparse_span(6, 64, 3, [], 3, 256, lengths[:3])
    assert decode["sparse_choice_tiles"] == 6 \
        == decode["sparse_choice_tiles_counting"]
    short = PG._sparse_span(6, 64, 0, [0], 40, 256, np.arange(1, 41))
    assert short["sparse_choice_tiles"] == 12
    assert short["sparse_choice_tiles_counting"] == 0


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
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "..", "tools",
                        "index_kernel_alone.py")
    spec = importlib.util.spec_from_file_location("index_kernel_alone", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
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
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "..", "tools",
                        "choice_kernel_alone.py")
    spec = importlib.util.spec_from_file_location("choice_kernel_alone",
                                                  path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
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
    assert _rel(got, want) < TOL
    assert bool(jnp.all(every == plain))


# ------------------------------------------------------------------ #
# the pool: three stores of a layer's block range
# ------------------------------------------------------------------ #
def test_every_position_writes_its_index_key(model):
    """Rows too short to choose write their index keys all the same: a
    sequence served in one-row ticks from position 0 chooses from keys
    written long before it could choose."""
    cfg, params, toks, whole, _ = model
    eng = _engine(cfg, params)
    got = _drive(eng, cfg, toks[:1, :40], None, 32, 0)
    assert _rel(got, whole[:1, :40]) < TOL
    # blocks alone: a sparse layer keeps nothing a sequence slot
    assert set(eng.pool) == {"k", "v", "idx"}
    assert eng.pool["idx"].shape == (3, 64, 8, PG.index_row_width(cfg))
    # a position's key lies in its 8 columns of a padded row
    idx = np.asarray(eng.pool["idx"])
    assert np.abs(idx[..., :8]).sum() > 0 and not idx[..., 8:].any()


@pytest.mark.parametrize("kernels", [False, True])
def test_a_tick_counts_its_own_sequences(model, kernels):
    """A pool of blocks alone says nothing of slots: a tick numbers the
    sequences it holds, whatever their first blocks' ids, and a table has
    a row a sequence of the tick (``PG.tick_tables``), not a block of the
    pool. Decode rows of two sequences whose first blocks lie past any
    table's rows, the second sequence's first."""
    cfg, params, toks, whole, _ = model
    eng = _engine(cfg, params, use_pallas_kernel=kernels, state_slots=30,
                  token_budget=4)
    for _ in range(20):                 # first blocks 21 and 22
        eng.allocator.allocate(1)
    attn = paged_attention if kernels else None
    got = _drive(eng, cfg, toks[::-1, :20], attn, 4, 16)
    assert _rel(got, whole[::-1, :20]) < TOL
    # (a table's row is padded to whole lane tiles: 576 entries take 640)
    assert PG.tick_tables(4, 16) == 4 and PG.tick_tables(2048, 576) == 51


def test_the_engine_holds_its_slots_under_a_ticks_tables(model, monkeypatch):
    """Scalar memory's room for tables bounds a tick's sequences only
    where it is under a table a row."""
    cfg, params = model[:2]
    monkeypatch.setattr(PG, "TABLE_WORDS", 128 * 8)
    with pytest.raises(ValueError, match="holds 8 sequences"):
        _engine(cfg, params, state_slots=8)
    _engine(cfg, params, state_slots=7)
    _engine(cfg, params, state_slots=8, token_budget=8)


def test_a_slots_next_sequence_sees_none_of_the_last_ones_index_keys(model):
    """Two sequences one after the other through the same blocks of the
    same slot: the second's logits are those of a fresh pool."""
    cfg, params, toks, whole, _ = model
    eng = _engine(cfg, params)
    _drive(eng, cfg, toks[:1], None, 27, 48)
    kept = np.asarray(eng.pool["idx"]).copy()
    got = _drive(eng, cfg, toks[1:, :50], None, 27, 40)
    assert np.abs(kept).sum() > 0                   # the blocks were dirty
    assert _rel(got, whole[1:, :50]) < TOL


def test_engine_serves_greedy_tokens_of_the_whole_forward(model):
    """Through ``FastGenEngine.step``: chunked prefill and decode ticks in
    one queue, every greedy token the reference's, and the span's account
    of what the sparse layers did."""
    import deepspeed_tpu.inference.fastgen as FG
    from deepspeed_tpu import telemetry

    cfg, params, toks, _, arch = model
    eng = _engine(cfg, params)
    prompts = {1: toks[0, :40].tolist(), 2: toks[1, :21].tolist()}
    new = 6
    spans, real = [], telemetry.span

    def spy(name, attrs=None, **kw):
        if name == "decode_tick":
            spans.append(attrs)
        return real(name, attrs=attrs, **kw)

    scored = telemetry.counter("fastgen_index_positions_total")
    read = telemetry.counter("fastgen_sparse_positions_read_total")
    before = (scored.value(), read.value())
    eng.put(list(prompts), list(prompts.values()))
    orig, FG.telemetry.span = FG.telemetry.span, spy
    try:
        with jax.default_matmul_precision("highest"):
            for _ in range(50):
                eng.step()
                for s in eng.seqs.values():
                    if not s.done and len(s.generated) >= new:
                        eng._finish(s)
                if all(s.done for s in eng.seqs.values()):
                    break
    finally:
        FG.telemetry.span = orig
    for u, prompt in prompts.items():
        out = eng.query(u)[1][:new]
        ref = R.forward_logits(
            params, np.asarray(prompt + out, np.int32)[None], arch)[0]
        n = len(prompt)
        assert out == [int(t) for t in jnp.argmax(
            ref[n - 1:n - 1 + new], axis=-1)]
    first = spans[0]
    # the first tick: 32 rows of the 40-token prompt, positions 0 .. 31
    assert first["sparse_layers"] == 3 and first["rows"] == 32
    assert first["index_positions"] == 3 * sum(range(1, 33))
    assert first["index_walk_positions"] == 3 * 32
    assert first["sparse_selected"] == 3 * sum(
        min(n, TOPK) for n in range(1, 33))
    assert first["sparse_rows_choosing"] == 32 - TOPK
    # one tile of rows a layer, and a row of it is longer than ``topk``
    assert first["sparse_choice_tiles"] == 3 \
        == first["sparse_choice_tiles_counting"]
    assert first["sparse_selected_decode"] == 0
    # every row walks its sequence under the choice as a mask
    assert first["sparse_positions_read"] == first["index_positions"]
    # a decode tick of both sequences: a walk a row
    last = spans[-1]
    assert last["decode_rows"] == 2 == last["rows"]
    assert last["index_walk_positions"] == last["index_positions"]
    assert last["sparse_selected_decode"] == last["sparse_selected"] \
        == 3 * 2 * TOPK
    assert scored.value() - before[0] == sum(
        s["index_positions"] for s in spans)
    assert read.value() - before[1] == sum(
        s["sparse_positions_read"] for s in spans)
    eng.flush(list(prompts))
    assert eng.allocator.free_blocks == 63


# ------------------------------------------------------------------ #
# the experts' share
# ------------------------------------------------------------------ #
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's section 4 under a softmax router with ``norm_topk_prob``:
    the routed parts that all eight shares give are the uncut reference's
    expert layer; the reference given a share leaves the same experts
    out."""
    rng = np.random.default_rng(0)
    H, F, E, k, rows = 32, 16, 16, 4, 24

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)

    lp = {"gate_w": draw(H, E)}
    experts = {"w_gate": draw(E, H, F), "w_up": draw(E, H, F),
               "w_down": draw(E, F, H)}
    x = draw(rows, H)
    arch = dict(top_k=k, route_norm=True, first_expert=0, faults=())

    def share(first, held):
        return MOE.dropless_moe_ffn(
            x, lp["gate_w"], {n: w[first:first + held]
                              for n, w in experts.items()},
            "swiglu", k, score_func="softmax", route_norm=True,
            first_expert=first)

    with jax.default_matmul_precision("highest"):
        want = R._moe(x, lp, jax.tree.map(lambda w: w[None], experts), 0,
                      arch)
        parts = [share(2 * i, 2) for i in range(8)]
        whole, counts = share(0, 16)
        ref_share = R._moe(x, lp, {n: w[None, 6:8]
                                   for n, w in experts.items()}, 0,
                           {**arch, "first_expert": 6})
    assert _rel(sum(p for p, _ in parts), want) < TOL
    assert _rel(whole, want) < TOL
    assert _rel(parts[3][0], ref_share) < TOL
    for _, r in parts:
        np.testing.assert_array_equal(r, counts)
    assert counts.shape == (16,) and int(counts.sum()) == rows * k


# ------------------------------------------------------------------ #
# the importer
# ------------------------------------------------------------------ #
def test_importer_reads_the_published_config():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    cfg = config_from_hf(types.SimpleNamespace(**row["config"]))
    assert cfg.layer_kinds == ("sparse",) * 48 and cfg.standard_blocks
    assert (cfg.sparse_topk, cfg.index_heads, cfg.index_head_dim) == (
        2048, 16, 64)
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_dim, cfg.moe_ffn,
            cfg.n_experts, cfg.router_experts, cfg.moe_top_k) == (
                32, 4, 128, 768, 128, 128, 8)
    assert cfg.qk_norm and cfg.moe_route_norm and not cfg.moe_shared_size
    assert cfg.rope_theta == 1e7 and cfg.rope_scaling is None
    assert cfg.moe_score_func == "softmax" and not cfg.moe_router_experts
    # 30.6 B in all (published: 30B)
    assert round(cfg.num_params() / 1e9, 1) == 30.6
    cut = dict(row["config"], num_hidden_layers=6, num_experts=16,
               num_local_experts=16, router_experts=128, vocab_size=18992)
    c = config_from_hf(types.SimpleNamespace(**cut))
    assert (c.n_experts, c.router_experts, c.moe_first_expert) == (16, 128, 0)
    assert round(c.num_params() / 1e6) == 659
    # the indexer a layer: 2.26 M
    per_layer = (c.num_params() - dataclasses.replace(
        c, layer_kinds=(), index_heads=0).num_params()) // 6
    assert per_layer == 2048 * 16 * 64 + 2048 * 64 + 2 * 64 + 2048 * 16
    for bad in (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}),
                dict(use_sliding_window=True),
                dict(sa_config=dict(row["config"]["sa_config"],
                                    indexer_num_kv_heads=2))):
        with pytest.raises(NotImplementedError):
            config_from_hf(types.SimpleNamespace(**{**row["config"], **bad}))


def test_state_dict_under_the_family_s_names_imports(model):
    """A fabricated ``KeyeVL2`` state dict (the Qwen3-MoE names under the
    language model's prefix, torch layout ``[out, in]``, beside a vision
    tower's tensors) gives back the tree it was made from; a share takes
    its own experts."""
    cfg, params, toks, whole, _ = model
    hf = _hf(num_experts=8, num_local_experts=8, router_experts=8)
    full = config_from_hf(types.SimpleNamespace(**hf))
    tree = _noisy(T.init_params(full, jax.random.PRNGKey(2)), seed=4)
    b = jax.tree.map(np.asarray, tree["blocks"])
    pre = "model.language_model."
    sd = {pre + "embed_tokens.weight": np.asarray(tree["tok_emb"]),
          pre + "norm.weight": np.asarray(tree["final_norm"]["scale"]),
          "lm_head.weight": np.asarray(tree["lm_head"]).T,
          "model.visual.patch_embed.proj.weight": np.zeros((4, 4))}
    names = {"wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
             "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
             "gate_w": "mlp.gate", "idx_wq": "self_attn.indexer.wq",
             "idx_wk": "self_attn.indexer.wk",
             "idx_ww": "self_attn.indexer.weights_proj"}
    for i in range(3):
        lyr = f"{pre}layers.{i}."
        for ours, theirs in names.items():
            sd[lyr + theirs + ".weight"] = b[ours][i].T
        sd[lyr + "input_layernorm.weight"] = b["ln1"]["scale"][i]
        sd[lyr + "post_attention_layernorm.weight"] = b["ln2"]["scale"][i]
        sd[lyr + "self_attn.q_norm.weight"] = b["q_norm"][i]
        sd[lyr + "self_attn.k_norm.weight"] = b["k_norm"][i]
        sd[lyr + "self_attn.indexer.k_norm.weight"] = \
            b["idx_k_norm"]["scale"][i]
        sd[lyr + "self_attn.indexer.k_norm.bias"] = b["idx_k_norm"]["bias"][i]
        for e in range(8):
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                 ("w_down", "down_proj")):
                sd[f"{lyr}mlp.experts.{e}.{theirs}.weight"] = b[ours][i, e].T
    got_cfg, got = import_hf_model((sd, types.SimpleNamespace(**hf)))
    assert got_cfg == full
    flat_a, flat_b = (dict(jax.tree_util.tree_leaves_with_path(t))
                      for t in (got, tree))
    assert flat_a.keys() == flat_b.keys()
    for key in flat_a:
        np.testing.assert_array_equal(flat_a[key], np.asarray(flat_b[key]))
    share_hf = _hf(num_experts=2, num_local_experts=2, router_experts=8,
                   first_expert=4)
    share_cfg = config_from_hf(types.SimpleNamespace(**share_hf))
    share = params_from_keye_vl2(sd, share_cfg)
    np.testing.assert_array_equal(share["blocks"]["w_up"], b["w_up"][:, 4:6])
    assert share["blocks"]["gate_w"].shape == (3, 64, 8)


@pytest.mark.parametrize("entry", ["forward_decode", "pipeline"])
def test_entry_points_that_refuse_sparse_layers(model, entry):
    cfg, params, toks, _, _ = model
    with pytest.raises(NotImplementedError, match="layer kinds"):
        T._require_one_stack(cfg, entry)


def test_sparse_layers_stand_alone_in_their_stack():
    hf = _hf()
    cfg = config_from_hf(types.SimpleNamespace(**hf))
    for bad in (dict(layer_kinds=("sparse", "full", "sparse")),
                dict(sparse_topk=0), dict(pos_emb="none")):
        with pytest.raises(NotImplementedError, match="sparse layers"):
            T.init_params(dataclasses.replace(cfg, **bad),
                          jax.random.PRNGKey(0))


# ------------------------------------------------------------------ #
# mistakes made on purpose
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("mistake", R.FAULTS)
def test_a_mistake_made_on_purpose_is_seen(model, mistake):
    """Each mistake of the cell's ``chip_readings``, in float32 where
    rounding hides nothing: the system stands ~1e-6 from the reference and
    far from the reference that makes the mistake (the smallest, the index
    key's LayerNorm dropped, moves the chosen sets of a toy model by a few
    positions a row)."""
    cfg, params, toks, whole, arch = model
    wrong = R.forward_logits(params, toks, {**arch, "faults": (mistake,)})
    assert _rel(whole, wrong) > 0.02
    assert _rel(whole, R.forward_logits(params, toks, arch)) < TOL
