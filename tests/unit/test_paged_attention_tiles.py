"""The row-tiled paged-attention kernel: a tile's rows that carry one block
table walk it once, any other layout walks per row — the result is the
reference's for every layout, and the engine's compile keys do not know.

Row layouts are DATA to one compiled program per head layout: that is the
property under test, so every case of a head layout reuses one jitted call.
"""
import functools
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.fastgen import FastGenEngine
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.ops.pallas.paged_attention import (_geometry,
                                                      count_steps,
                                                      count_walks,
                                                      latent_paged_attention,
                                                      paged_attention,
                                                      step_ranges, tile_rows)

def _shape(N, K, D, BS, MB, NB, T, dtype, span=False, heads_first=False,
           latent=None):
    """A head layout with its pool and tick sizes; ``C``: the cache
    positions of one fetch step there, by the kernel's own rule. ``span``:
    the calls of window and full layers over experts: bfloat16 products,
    one table a sequence beside each row's (``row_table``), and a window
    of two and a half steps, so that a walk has an edge at either end.
    ``heads_first``: pool blocks ``[K, BS, D]``; ``latent``: ONE pool of
    rows ``[BS, D]`` whose first ``latent`` columns are the value (K is 1)."""
    dims = (NB, BS, D) if latent else (NB, K, BS, D) if heads_first \
        else (NB, BS, K, D)
    pool = jax.ShapeDtypeStruct(dims, jnp.dtype(dtype))
    C = BS * _geometry(jax.ShapeDtypeStruct((T, N, D), pool.dtype),
                       (pool,) if latent else (pool, pool), latent or D,
                       heads_first)[3]
    return types.SimpleNamespace(N=N, K=K, D=D, BS=BS, MB=MB, NB=NB, T=T,
                                 dtype=dtype, C=C, span=span, pool=dims,
                                 heads_first=heads_first, latent=latent,
                                 window=2 * C + C // 2 if span else None)


# toy widths: T = 80 is 2.5 tiles of 32 rows (the wrapper pads); a walk is
# one fetch step there. "mistral": the chat cell's heads, blocks and bf16
# pool, where a step is C positions (eight blocks) and the walks below take
# up to three;
# "trinity": the published head layout of the window and full layers over
# experts (48 query heads on 8 of 128: a group of 6)
SHAPES = {
    "rep4": _shape(8, 2, 64, 8, 8, 96, 80, "float32"),
    "rep1": _shape(2, 2, 64, 8, 8, 96, 80, "float32"),
    "mistral": _shape(32, 8, 128, 32, 24, 72, 64, "bfloat16"),
    "trinity": _shape(48, 8, 128, 32, 24, 72, 64, "bfloat16", span=True),
    # the sparse layers' head layout over blocks of 128: a step carries two
    # lane widths, 256 positions (the walks of STEP_LAYOUTS reach 5 blocks)
    "keye": _shape(32, 4, 128, 128, 5, 24, 64, "bfloat16"),
    # the chain of a call's fetches (CHAIN_LAYOUTS): one tile, where a chunk
    # of 32 rows is a call of ONE walk; blocks that lie heads first, walks
    # of up to two and a half steps of 128 positions; a latent pool at 16
    # heads on one row, two and a half steps of 512
    "rep4x32": _shape(8, 2, 64, 8, 8, 96, 32, "float32"),
    "paired": _shape(8, 2, 64, 8, 40, 96, 64, "float32", heads_first=True),
    "latent": _shape(16, 1, 256, 32, 40, 96, 64, "bfloat16", latent=128),
}


def _tick(g, rows):
    """(table, length) rows, then pads (zero table, length 1), as device
    arrays."""
    tables = np.zeros((g.T, g.MB), np.int32)
    lengths = np.ones((g.T,), np.int32)
    for r, (tab, n) in enumerate(rows):
        tables[r], lengths[r] = tab, n
    return jnp.asarray(tables), jnp.asarray(lengths)


def _seq_table(g, rng, n_blocks):
    tab = np.zeros((g.MB,), np.int32)
    tab[:n_blocks] = rng.permutation(np.arange(1, g.NB))[:n_blocks]
    return tab


def _chunk(g, rng, start, rows):
    """``rows`` prompt rows of one sequence from position ``start``."""
    tab = _seq_table(g, rng, (start + rows - 1) // g.BS + 1)
    return [(tab, p + 1) for p in range(start, start + rows)]


def _decode(g, rng, n, lengths=None):
    rows = []
    for i in range(n):
        length = lengths[i] if lengths else int(
            rng.integers(1, g.MB * g.BS + 1))
        rows.append((_seq_table(g, rng, (length - 1) // g.BS + 1), length))
    return rows


def _shared_first_block(g, rng, n):
    """Rows whose tables agree on the first block only (a shared prefix
    block): equal first ids must not read as one table."""
    first = int(rng.integers(1, g.NB))
    rows = []
    for _ in range(n):
        tab = _seq_table(g, rng, 4)
        tab[0] = first
        rows.append((tab, int(rng.integers(g.BS + 1, 4 * g.BS + 1))))
    return rows


def _two_prompts(g, rng, steps=0):
    """``Session.check_logits``'s packing: two prompts back to back with no
    alignment in a full-width tick; with ``steps``, the decode tick that
    follows (two rows, then pads)."""
    a, b = _chunk(g, rng, 0, 37 + steps), _chunk(g, rng, 0, 30 + steps)
    if steps:
        return [a[-1], b[-1]]
    return a + b


LAYOUTS = {
    "all_decode": lambda g, rng: _decode(g, rng, g.T),
    "chunk_on_tile_boundary": lambda g, rng: (
        _decode(g, rng, 32) + _chunk(g, rng, 10, 32)),
    "decode_then_chunk_mid_tile": lambda g, rng: (
        _decode(g, rng, 5) + _chunk(g, rng, 7, 50)),
    "two_chunks_meet_mid_tile": lambda g, rng: (
        _chunk(g, rng, 0, 45) + _chunk(g, rng, 3, 35)),
    "trailing_pads": lambda g, rng: _chunk(g, rng, 0, 20),
    "chunk_across_blocks_ragged_end": lambda g, rng: _chunk(g, rng, 5, 37),
    "shared_first_block_only": lambda g, rng: _shared_first_block(g, rng, 40),
    "check_logits_prefill": lambda g, rng: _two_prompts(g, rng),
    "check_logits_decode": lambda g, rng: _two_prompts(g, rng, steps=3),
}
# walks of more than one fetch step: decode rows whose contexts end a
# position before, on and after a step's edge and in a third step; a chunk
# that crosses a tile boundary and a step's edge behind three such rows in
# its first tile, pads after it
STEP_LAYOUTS = {
    "decode_rows_at_the_steps_edges": lambda g, rng: _decode(
        g, rng, 7, [1, g.C - 1, g.C, g.C + 1, 2 * g.C + 17, g.BS, g.C + g.BS]),
    "chunk_across_a_tile_and_a_steps_edge": lambda g, rng: (
        _decode(g, rng, 3, [g.C + 1, 1, 2 * g.C + 17])
        + _chunk(g, rng, g.C - 20, 45)),
    # the open / edge rule: a window's lower edge a position before, on and
    # after a step's boundary (without a window: contexts of whole steps);
    # a chunk none of whose steps is open (its tile's rows end either side
    # of a step's edge); a tile that is one run, with open steps, beside a
    # tile of two runs, which has none
    "windows_lower_edge_at_a_steps_boundary": lambda g, rng: _decode(
        g, rng, 6, [(g.window or g.C) + g.C + d for d in (-1, 0, 1)]
        + [(g.window or g.C) + d for d in (-1, 0, 1)]),
    "chunk_whose_every_step_is_an_edge": lambda g, rng: _chunk(
        g, rng, g.C - 20, 40),
    "tile_of_one_run_beside_a_tile_of_two": lambda g, rng: (
        _chunk(g, rng, 2 * g.C + 5, 45) + _chunk(g, rng, 2 * g.C - 3, 19)),
}


def _ring(g, rng, length):
    """A decode row whose table's columns alias a ring of blocks, two more
    than a window (or a step) holds: what a window layer's slot is."""
    ring = -(-(g.window or g.C) // g.BS) + 2
    ids = rng.permutation(np.arange(1, g.NB))[:ring]
    tab = np.zeros((g.MB,), np.int32)
    n = (length - 1) // g.BS + 1
    tab[:n] = ids[np.arange(n) % ring]
    return tab, length


def _begins(g, rng, ring):
    """Decode rows whose walks begin at steps 1, 0, 2, 0, 3, 1, 0 under the
    window (without one: walks of 2, 1, 3, 1 .. steps), so that a walk's
    first step lies in either slot whatever its own first step's number
    is, then a chunk whose first step is step 1; under a ``ring`` the
    decode rows' tables alias one."""
    w, C, top = g.window or 0, g.C, g.MB * g.BS
    lengths = [min(n, top) for n in (
        w + C + 5, 3, w + 2 * C + 1, w + 7, w + 3 * C + 1, w + 2 * C, 1)]
    decode = [_ring(g, rng, n) for n in lengths] if ring \
        else _decode(g, rng, len(lengths), lengths)
    return decode + _chunk(g, rng, min(w + C + 10, top - 40), 40)


PAD = (0, 1)                           # a pad row: the zero table, length 1
# what breaks a chain of fetches that is wrong (a walk finds its first fetch
# started by the walk before it, in this tile or the one before): walks of
# one step, so that both slots are in flight; a run of one row between two
# of many; a run that ends with its tile before a tile of pads; first steps
# of either parity; contexts of one position; pad rows between sequences
CHAIN_LAYOUTS = {
    "a_tile_of_one_step_decode_rows": lambda g, rng: _decode(
        g, rng, 32, [int(n) for n in rng.integers(
            1, min(g.C, g.MB * g.BS) + 1, 32)]),
    "one_row_between_two_runs": lambda g, rng: (
        _chunk(g, rng, 5, 20) + _decode(g, rng, 1) + _chunk(g, rng, 9, 30)),
    "run_ends_with_its_tile_before_a_tile_of_pads": lambda g, rng: (
        _decode(g, rng, 4) + _chunk(g, rng, 11, 28)),
    "first_steps_of_either_parity": lambda g, rng: _begins(g, rng, False),
    "first_steps_of_either_parity_on_a_ring": lambda g, rng: _begins(
        g, rng, True),
    "rows_of_length_one": lambda g, rng: (
        _decode(g, rng, 20, [1] * 20) + _chunk(g, rng, 0, 5)
        + _decode(g, rng, 9, [1, 2, 1, g.MB * g.BS, 1, 1,
                              min(g.C, g.MB * g.BS), 1, 1])),
    "pad_rows_between_two_sequences": lambda g, rng: (
        _chunk(g, rng, min(g.C, g.MB * g.BS - 6) - 4, 10) + [PAD] * 5
        + _chunk(g, rng, 2, 10)
        + [PAD] + _decode(g, rng, 3)),
}
# a tile alone: a chunk that fills it is a call of one walk, which starts
# cold and hands nothing on; then a tile of 32 walks
ONE_TILE_LAYOUTS = {
    "a_call_of_one_walk": lambda g, rng: _chunk(g, rng, 3, g.T),
    "a_tile_of_one_step_decode_rows": CHAIN_LAYOUTS[
        "a_tile_of_one_step_decode_rows"],
}
ALL_LAYOUTS = {**LAYOUTS, **STEP_LAYOUTS, **CHAIN_LAYOUTS, **ONE_TILE_LAYOUTS}
CASES = [(h, l) for h in ("rep1", "rep4") for l in sorted(LAYOUTS)] + [
    (h, l) for h in ("mistral", "trinity") for l in sorted(STEP_LAYOUTS)] + [
    (h, l) for h in ("rep4", "mistral", "trinity", "paired", "latent")
    for l in sorted(CHAIN_LAYOUTS)] + [
    ("rep4x32", l) for l in sorted(ONE_TILE_LAYOUTS)]


def _by_run(g, tables):
    """One table a run of rows (row 0 the pad rows') and each row's: what
    a call with ``row_table`` takes, of a tick's table a row."""
    new = jnp.concatenate([jnp.ones((1,), bool), jnp.any(
        tables[1:] != tables[:-1], axis=1)])
    which = jnp.where(jnp.any(tables != 0, axis=1),
                      jnp.cumsum(new), 0).astype(jnp.int32)
    return jnp.zeros((g.T + 1, g.MB), jnp.int32).at[which].set(
        tables), which


@functools.lru_cache(maxsize=None)
def _case(heads, dtype):
    """One compiled kernel, one compiled reference, one pool per head
    layout and dtype."""
    g = SHAPES[heads]
    rng = np.random.default_rng(3)
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rng.normal(size=(g.T, g.N, g.D)), dt)
    kpool = jnp.asarray(rng.normal(size=g.pool), dt)
    vpool = jnp.asarray(rng.normal(size=g.pool), dt)
    if g.latent:
        def kernel(q, pool, _, tables, lengths):
            return latent_paged_attention(q, pool, tables, lengths, g.latent,
                                          g.D ** -0.5, interpret=True)

        def reference(q, pool, _, tables, lengths):
            # one KV head: a position's row is its key, the row's leading
            # columns its value
            rows = pool[tables].astype(jnp.float32).reshape(
                g.T, g.MB * g.BS, g.D)
            with jax.default_matmul_precision("highest"):
                s = jnp.einsum("tnd,tcd->tnc", q.astype(jnp.float32),
                               rows) * g.D ** -0.5
                live = jnp.arange(g.MB * g.BS)[None, None] \
                    < lengths[:, None, None]
                p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
                return jnp.einsum("tnc,tcd->tnd", p, rows[..., :g.latent])

        return q, kpool, None, jax.jit(kernel), jax.jit(reference)
    if g.span:
        def kernel(q, kpool, vpool, tables, lengths):
            by_run, which = _by_run(g, tables)
            return paged_attention(q, kpool, vpool, by_run, lengths,
                                   interpret=True, window=g.window,
                                   mxu_dtype=dt, row_table=which)
        kernel = jax.jit(kernel)
    else:
        kernel = jax.jit(functools.partial(paged_attention, interpret=True,
                                           heads_first=g.heads_first))

    def reference(q, kpool, vpool, tables, lengths):
        with jax.default_matmul_precision("highest"):
            return PG.paged_attention_reference(
                q.astype(jnp.float32), kpool.astype(jnp.float32),
                vpool.astype(jnp.float32), tables, lengths,
                window=g.window, heads_first=g.heads_first)

    return q, kpool, vpool, kernel, jax.jit(reference)


def _tol(g):
    # a bf16 pool's output is rounded to bf16: half a unit in the last place
    return dict(rtol=2e-4, atol=2e-5) if g.dtype == "float32" else dict(
        rtol=2 ** -8, atol=2 ** -8)


@pytest.mark.parametrize("heads,layout", CASES,
                         ids=[f"{h}-{l}" for h, l in CASES])
def test_kernel_matches_reference_for_any_row_layout(heads, layout):
    g = SHAPES[heads]
    q, kpool, vpool, kernel, reference = _case(heads, g.dtype)
    rng = np.random.default_rng(sorted(ALL_LAYOUTS).index(layout))
    tables, lengths = _tick(g, ALL_LAYOUTS[layout](g, rng))
    got = np.asarray(kernel(q, kpool, vpool, tables, lengths), np.float32)
    want = np.asarray(reference(q, kpool, vpool, tables, lengths))
    np.testing.assert_allclose(got, want, **_tol(g))


@pytest.mark.parametrize("heads", ["rep4", "mistral"])
def test_a_walk_of_no_step_hands_the_chain_on(heads):
    """Rows of length 0 (no caller sends them: a tick's are ``pos + 1``)
    make walks of no step, which wait for nothing and still start the
    fetch of the run after them: a row alone, a run of five, the last rows
    of a tile and the first of the next. Their output is zero and every
    other row's is the reference's."""
    g = SHAPES[heads]
    q, kpool, vpool, kernel, reference = _case(heads, g.dtype)
    rng = np.random.default_rng(17)
    rows = _decode(g, rng, 3) + _chunk(g, rng, 4, 5) + _decode(g, rng, 20) \
        + _chunk(g, rng, 0, 8) + _decode(g, rng, 2)
    tables, lengths = _tick(g, rows)
    none = np.zeros((g.T,), bool)
    none[[1, 3, 4, 5, 6, 7, 30, 31, 32, 33]] = True
    lengths = jnp.where(jnp.asarray(none), 0, lengths)
    got = np.asarray(kernel(q, kpool, vpool, tables, lengths), np.float32)
    want = np.asarray(reference(q, kpool, vpool, tables, lengths))
    np.testing.assert_allclose(got[~none], want[~none], **_tol(g))
    assert not got[none].any()


@pytest.mark.parametrize("heads", ["rep1", "rep4"])
def test_kernel_bf16_pool_float32_statistics(heads):
    """The configuration's arithmetic: bf16 values, float32 scores,
    statistics and accumulator. Against the float32 reference on the same
    bf16 values the error is the rounding of the bf16 output alone."""
    g = SHAPES[heads]
    q, kpool, vpool, kernel, reference = _case(heads, "bfloat16")
    rng = np.random.default_rng(11)
    tables, lengths = _tick(
        g, LAYOUTS["decode_then_chunk_mid_tile"](g, rng))
    got = np.asarray(kernel(q, kpool, vpool, tables, lengths), np.float32)
    want = np.asarray(reference(q, kpool, vpool, tables, lengths))
    assert got.dtype == np.float32 and np.isfinite(got).all()
    # bf16 keeps 8 bits: half a unit in the last place is 2**-9 relative
    np.testing.assert_allclose(got, want, rtol=2 ** -8, atol=2 ** -8)


def _operands(q, *pools):
    return (jax.ShapeDtypeStruct(q, jnp.bfloat16),
            tuple(jax.ShapeDtypeStruct(p, jnp.bfloat16) for p in pools))


# the serving configurations' operands (bf16; blocks of 32 but for Keye's,
# of 128) -> (rows a tile, blocks a fetch step): a change to one
# configuration's geometry changes its tick programs, and shows here
TRINITY = _operands((2048, 48, 128), *[(4 * 29 * 192, 32, 8, 128)] * 2), \
    _operands((2048, 48, 128), *[(12288, 32, 8, 128)] * 2)
GEOMETRY = {
    "trinity-large-preview-swa": (TRINITY[0], 128, False, (32, 4)),
    "trinity-large-preview-global": (TRINITY[1], 128, False, (32, 4)),
    "mistral-7b": (_operands((512, 32, 128), *[(2400, 32, 8, 128)] * 2),
                   128, False, (32, 8)),
    "pythia-6.9b": (_operands((512, 32, 128), *[(640, 32, 32, 128)] * 2),
                    128, False, (32, 2)),
    "phi-4-mini-flash-window": (
        _operands((512, 40, 128), *[(8 * 73 * 32, 10, 32, 128)] * 2),
        128, True, (32, 4)),
    "phi-4-mini-flash-shared": (
        _operands((512, 40, 128), *[(10900, 10, 32, 128)] * 2),
        128, True, (32, 4)),
    "moonlight-16b-a3b": (_operands((512, 16, 640), (9 * 4352, 32, 640)),
                          512, False, (32, 16)),
    # 8 KV heads of 64 lie two to a pool row: the kernel sees 4 of 128
    "lfm2-24b-a2b": (
        _operands((2048, 32, 128), *[(2 * 20480, 32, 4, 128)] * 2),
        128, False, (32, 8)),
    # the one pool of 128-position blocks: two of them a step
    "keye-vl-2.0-30b-a3b": (
        _operands((2048, 32, 128), *[(6 * 4353, 128, 4, 128)] * 2),
        128, False, (32, 2)),
}


@pytest.mark.parametrize("config", sorted(GEOMETRY))
def test_geometry_of_the_serving_configurations(config):
    (q, pools), value_dim, heads_first, want = GEOMETRY[config]
    assert _geometry(q, pools, value_dim, heads_first)[2:] == want


def test_tile_rows_follow_the_accumulator():
    assert tile_rows(32, 128) == 32      # Mistral, Pythia: 0.5 MB
    assert tile_rows(16, 512) == 32      # Moonlight's latents: 1 MB
    assert tile_rows(128, 512) == 16     # never under 16


def _brute_steps(lengths, starts, tile, C, window):
    """(steps, open steps) of a call, column by column: a run's walk takes
    every step that holds a column some row of it sees; a step is open if
    the run is one row or its whole tile and every row sees every column
    of it."""
    lengths = list(lengths) + [1] * (-len(lengths) % tile)
    starts = list(starts) + [False] * (len(lengths) - len(starts))
    steps = n_open = 0
    r0 = 0
    while r0 < len(lengths):
        r1 = r0 + 1
        while r1 % tile and not starts[r1]:
            r1 += 1
        sees = [set(range(max(n - window, 0) if window else 0, n))
                for n in lengths[r0:r1]]
        for i in range(max(lengths[r0:r1]) // C + 1):
            cols = set(range(i * C, (i + 1) * C))
            # the walk is one stretch of steps: from the first that holds
            # a live column to the last
            steps += any(cols & s for s in sees)
            n_open += r1 - r0 in (1, tile) and all(cols <= s for s in sees)
        r0 = r1
    return steps, n_open


RULE_CASES = [(C, window) for C in (8, 64, 128, 256)
              for window in (None, 1, C - 1, C, C + 31, 3 * C, 5 * C + 7)]


@pytest.mark.parametrize("C,window", RULE_CASES)
def test_step_ranges_against_every_column(C, window):
    """The rule the kernel's walk and the host's count share, against a
    check of every column of every step for every row."""
    rng = np.random.default_rng(C + (window or 0))
    edges = [1, C - 1, C, C + 1, 2 * C, (window or C) + C,
             (window or C) + C + 1, 4 * C - 1]
    for trial in range(120):
        lo = int(edges[trial % len(edges)] if trial < 40
                 else rng.integers(1, 6 * C))
        hi = lo + int(rng.integers(0, 32))
        for whole in (True, False):
            begin, first, last, end = (int(x) for x in step_ranges(
                np.int64(lo), np.int64(hi), C, window, whole))
            assert 0 <= begin <= first <= last <= end
            sees = [set(range(max(n - window, 0) if window else 0, n))
                    for n in range(lo, hi + 1)]
            for i in range(hi // C + 2):
                cols = set(range(i * C, (i + 1) * C))
                live = any(cols & s for s in sees)
                # outside the walk no row sees a column (inside it a step
                # may hold none: the stretch between a window's end and the
                # next row's start is never longer than the rows' spread)
                assert begin <= i < end or not live, (lo, hi, i)
                assert (first <= i < last) == (
                    whole and all(cols <= s for s in sees)), (lo, hi, i)


@pytest.mark.parametrize("heads,layout",
                         [(h, l) for h in ("mistral", "trinity", "keye")
                          for l in sorted(STEP_LAYOUTS)],
                         ids=lambda x: x)
def test_count_steps_against_every_column(heads, layout):
    g = SHAPES[heads]
    # Trinity's scores hold a step to one lane width; the others' to two
    assert g.C == (128 if heads == "trinity" else 256)
    rng = np.random.default_rng(sorted(STEP_LAYOUTS).index(layout))
    rows = STEP_LAYOUTS[layout](g, rng)
    lengths = np.array([n for _, n in rows] + [1] * (g.T - len(rows)))
    tabs = [tuple(t) for t, _ in rows] + [(0,) * g.MB] * (g.T - len(rows))
    starts = [True] + [a != b for a, b in zip(tabs[1:], tabs[:-1])]
    want = _brute_steps(lengths, starts, 32, g.C, g.window)
    assert count_steps(lengths, starts, 32, g.C, g.window) == want
    assert want[0] > 0


def _brute_walks(starts, tile):
    """A call's walks, a row at a time: a new one wherever the table
    changes or a tile begins."""
    return sum(new or r % tile == 0 for r, new in enumerate(starts))


def _starts(g, rows):
    """Whole tiles' rows of a layout: which carry another table than the
    row before (the pads the zero table)."""
    tabs = [tuple(t) if np.ndim(t) else (0,) * g.MB for t, _ in rows] \
        + [(0,) * g.MB] * (g.T + -g.T % 32 - len(rows))
    return [True] + [a != b for a, b in zip(tabs[1:], tabs[:-1])]


@pytest.mark.parametrize("heads,layout", [
    (h, l) for h, ls in (("rep4", {**LAYOUTS, **CHAIN_LAYOUTS}),
                         ("mistral", {**STEP_LAYOUTS, **CHAIN_LAYOUTS}),
                         ("rep4x32", ONE_TILE_LAYOUTS)) for l in sorted(ls)],
    ids=lambda x: x)
def test_count_walks_against_the_rows(heads, layout):
    """The host's count of a call's walks against the runs counted a row
    at a time."""
    g = SHAPES[heads]
    starts = _starts(g, ALL_LAYOUTS[layout](g, np.random.default_rng(
        sorted(ALL_LAYOUTS).index(layout))))
    want = _brute_walks(starts, 32)
    assert count_walks(starts, 32) == want >= len(starts) // 32
    if layout == "a_call_of_one_walk":
        assert want == 1
    with pytest.raises(ValueError):
        count_walks(starts[:-1], 32)          # not whole tiles


@pytest.mark.parametrize("layout", [
    "chunk_across_a_tile_and_a_steps_edge",      # the tile form, and rows
    "decode_rows_at_the_steps_edges",            # rows alone
    # the chain of fetches under a choice (every step masked by it)
    "a_tile_of_one_step_decode_rows", "one_row_between_two_runs",
    "pad_rows_between_two_sequences",
    "run_ends_with_its_tile_before_a_tile_of_pads"])
def test_a_choice_in_lane_planes_at_two_planes_a_step(layout):
    """``paged_attention(chosen=)`` where a step carries 256 positions and
    the choice lies in planes of 128, as ``sparse_choice`` writes them: a
    step reads two consecutive planes (five planes here: the wrapper adds
    the sixth), the tile form's spread product over both and a row alone
    its own row of each. Against the jnp reference under the same choice,
    at the tolerance of the file's bfloat16 cases."""
    g = SHAPES["keye"]
    assert g.C == 256 and g.MB * g.BS // 128 == 5
    q, kpool, vpool, _, _ = _case("keye", g.dtype)
    rng = np.random.default_rng(sorted(ALL_LAYOUTS).index(layout))
    tables, lengths = _tick(g, ALL_LAYOUTS[layout](g, rng))
    chosen = rng.random((g.T, g.MB * g.BS)) < 0.3
    chosen[np.arange(g.T), np.asarray(lengths) - 1] = True   # a row, itself
    planes = jnp.asarray(chosen, jnp.float32).reshape(
        g.T, -1, 128).transpose(1, 0, 2)
    by_run, which = _by_run(g, tables)
    kernel = functools.partial(
        paged_attention, q, kpool, vpool, by_run, lengths, interpret=True,
        mxu_dtype=jnp.bfloat16, row_table=which)
    got = np.asarray(kernel(chosen=planes), np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(PG.paged_attention_reference(
            q.astype(jnp.float32), kpool.astype(jnp.float32),
            vpool.astype(jnp.float32), tables, lengths,
            chosen=jnp.asarray(chosen)))
    np.testing.assert_allclose(got, want, rtol=2 ** -8, atol=2 ** -8)
    # and the choice bites: without it the rows attend to more
    assert np.abs(got - np.asarray(kernel(), np.float32)).max() > 2 ** -4


# --------------------------------------------------------------------- #
CFG = dict(hidden_size=64, num_layers=2, num_heads=4, max_seq_len=256,
           vocab_size=512, dtype="float32")
SHARED = "fastgen_paged_shared_rows_total"
PREFILL = "fastgen_prefill_tokens_total"


def _counter(name):
    return telemetry.snapshot()["counters"].get(name, 0.0)


def _drive(engine, prompts, new_tokens):
    """A SplitFuse schedule: two prompts, a third arrives while they
    decode, a fourth later; greedy. -> {uid: tokens}."""
    out = {u: [] for u in range(len(prompts))}

    def tick(n):
        for _ in range(n):
            for uid, tok in engine.step().items():
                if len(out[uid]) < new_tokens:
                    out[uid].append(tok)

    engine.put([0, 1], prompts[:2])
    tick(3)
    engine.put([2], prompts[2:3])
    tick(2)
    engine.put([3], prompts[3:])
    tick(new_tokens + 6)
    return out


def test_engine_tokens_keys_and_hit_share_with_and_without_the_kernel():
    rng = np.random.default_rng(5)
    lens = [70, 9, 100, 41]
    prompts = [rng.integers(0, 512, n).tolist() for n in lens]
    runs = {}
    for use_kernel in (True, False):
        eng = FastGenEngine("tiny", n_blocks=64, block_size=16,
                            max_blocks_per_seq=16, token_budget=64,
                            temperature=0.0, seed=0,
                            use_pallas_kernel=use_kernel, **CFG)
        before = _counter(SHARED), _counter(PREFILL)
        tokens = _drive(eng, prompts, new_tokens=8)
        runs[use_kernel] = (tokens, set(eng._ticks),
                            _counter(SHARED) - before[0],
                            _counter(PREFILL) - before[1])
    (tok_k, keys_k, shared_k, prefill_k) = runs[True]
    (tok_r, keys_r, shared_r, prefill_r) = runs[False]
    assert tok_k == tok_r
    assert all(len(t) == 8 for t in tok_k.values())
    assert keys_k == keys_r and all(len(k) == 2 for k in keys_k)
    assert prefill_k == prefill_r == sum(lens)
    # hand count, tiles of R = 32 rows in a 64-row budget. Tick 1: rows
    # 0-63 are prompt 0's first 64 tokens: both tiles inside the chunk.
    # Tick 2: prompt 0's last 6 rows, then prompt 1's 9: no tile inside
    # one chunk. Tick 4 (prompt 2 arrives; two decode rows first): rows
    # 2-63, the one tile 32-63 inside. Tick 5: its other 38 rows behind
    # two decode rows, tile 0 is mixed and 32-39 is partly pads: none.
    # Tick 6 (prompt 3 behind three decode rows): rows 3-43, none.
    assert tile_rows(4, 16) == 32
    assert shared_k == 64 + 32
    assert shared_r == 0                 # the reference path shares nothing


def test_decode_only_run_shares_no_rows():
    rng = np.random.default_rng(6)
    eng = FastGenEngine("tiny", n_blocks=64, block_size=16,
                        max_blocks_per_seq=16, token_budget=64,
                        temperature=0.0, seed=0, use_pallas_kernel=True,
                        **CFG)
    before = _counter(SHARED)
    # prompts shorter than a tile: no tile lies inside a chunk, and the
    # ticks that follow hold decode rows alone
    eng.put([0, 1, 2], [rng.integers(0, 512, n).tolist() for n in (5, 7, 3)])
    for _ in range(6):
        assert eng.step()
    assert _counter(PREFILL) > 0
    assert _counter(SHARED) == before


def test_tick_span_counts_fetch_steps_and_open_ones():
    """``attn_steps`` / ``attn_open_steps`` on ``decode_tick`` and
    ``fastgen_attention_steps_total{form}``: a 150-token prompt in chunks
    of 64 rows beside a short one, then decode rows, one of them past a
    step's 128 positions (its first step is open); every tick against the
    column-by-column count of its rows, two layers a tick."""
    from deepspeed_tpu.telemetry import tracing

    eng = FastGenEngine("tiny", n_blocks=64, block_size=16,
                        max_blocks_per_seq=16, token_budget=64,
                        temperature=0.0, seed=0, use_pallas_kernel=True,
                        **CFG)
    (layers, window, C), = eng._walks
    assert (layers, window, C) == (2, None, 128)
    rng = np.random.default_rng(8)
    eng.put([0, 1], [rng.integers(0, 512, n).tolist() for n in (150, 20)])

    def totals():
        return [_counter(f'fastgen_attention_steps_total{{form="{f}"}}')
                for f in ("open", "masked")]

    before = totals()
    tracer = tracing.get_tracer()
    was, tracer.enabled = tracer.enabled, True
    want, walks = [], []
    try:
        for _ in range(7):
            # the tick's rows as the scheduler will lay them: decode rows
            # (a sequence each), then the chunks in admission order, pads
            seqs = [eng.seqs[u] for u in eng._admit_order]
            runs = [[s.pos + 1] for s in seqs
                    if not s.prefill_remaining and not s.done]
            room = 64 - len(runs)
            for s in seqs:
                n = min(s.prefill_remaining, room)
                if n:
                    runs.append(range(s.pos + 1, s.pos + n + 1))
                    room -= n
            lengths = [n for run in runs for n in run]
            starts = [i == 0 for run in runs for i, _ in enumerate(run)]
            pads = -len(lengths) % 32      # the bucket's and the wrapper's
            starts = starts + [True] + [False] * (pads - 1) if pads \
                else starts
            want.append(_brute_steps(lengths + [1] * pads, starts, 32, C,
                                     window))
            walks.append(_brute_walks(starts, 32))
            eng.step()
        events = tracer.export_chrome()["traceEvents"]
    finally:
        tracer.enabled = was
    ticks = [e["args"] for e in events if e.get("name") == "decode_tick"][-7:]
    assert [(a["attn_steps"], a["attn_open_steps"]) for a in ticks] == [
        (layers * n, layers * n_open) for n, n_open in want]
    # a walk a run of rows under one table, in each layer's call
    assert [a["attn_walks"] for a in ticks] == [layers * n for n in walks]
    # which geometry ran: the positions a step carries, the kernel's rule
    assert {a["attn_step_positions"] for a in ticks} == {C}
    got = [b - a for a, b in zip(before, totals())]
    assert got == [layers * sum(o for _, o in want),
                   layers * sum(n - o for n, o in want)]
    assert got[0] > 0             # the long sequence's decode rows
