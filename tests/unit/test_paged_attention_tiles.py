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
                                                      paged_attention,
                                                      tile_rows)

def _shape(N, K, D, BS, MB, NB, T, dtype):
    """A head layout with its pool and tick sizes; ``C``: the cache
    positions of one fetch step there, by the kernel's own rule."""
    pool = jax.ShapeDtypeStruct((NB, BS, K, D), jnp.dtype(dtype))
    C = BS * _geometry(jax.ShapeDtypeStruct((T, N, D), pool.dtype),
                       (pool, pool), D, False)[3]
    return types.SimpleNamespace(N=N, K=K, D=D, BS=BS, MB=MB, NB=NB, T=T,
                                 dtype=dtype, C=C)


# toy widths: T = 80 is 2.5 tiles of 32 rows (the wrapper pads); a walk is
# one fetch step there. "mistral": the chat cell's heads, blocks and bf16
# pool, where a step is C positions and the walks below take up to three
SHAPES = {
    "rep4": _shape(8, 2, 64, 8, 8, 96, 80, "float32"),
    "rep1": _shape(2, 2, 64, 8, 8, 96, 80, "float32"),
    "mistral": _shape(32, 8, 128, 32, 24, 72, 64, "bfloat16"),
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
}
CASES = [(h, l) for h in ("rep1", "rep4") for l in sorted(LAYOUTS)] + [
    ("mistral", l) for l in sorted(STEP_LAYOUTS)]


@functools.lru_cache(maxsize=None)
def _case(heads, dtype):
    """One compiled kernel, one compiled reference, one pool per head
    layout and dtype."""
    g = SHAPES[heads]
    rng = np.random.default_rng(3)
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rng.normal(size=(g.T, g.N, g.D)), dt)
    kpool = jnp.asarray(rng.normal(size=(g.NB, g.BS, g.K, g.D)), dt)
    vpool = jnp.asarray(rng.normal(size=(g.NB, g.BS, g.K, g.D)), dt)
    kernel = jax.jit(functools.partial(paged_attention, interpret=True))

    def reference(q, kpool, vpool, tables, lengths):
        with jax.default_matmul_precision("highest"):
            return PG.paged_attention_reference(
                q.astype(jnp.float32), kpool.astype(jnp.float32),
                vpool.astype(jnp.float32), tables, lengths)

    return q, kpool, vpool, kernel, jax.jit(reference)


@pytest.mark.parametrize("heads,layout", CASES,
                         ids=[f"{h}-{l}" for h, l in CASES])
def test_kernel_matches_reference_for_any_row_layout(heads, layout):
    g = SHAPES[heads]
    q, kpool, vpool, kernel, reference = _case(heads, g.dtype)
    layouts = {**LAYOUTS, **STEP_LAYOUTS}
    rng = np.random.default_rng(sorted(layouts).index(layout))
    tables, lengths = _tick(g, layouts[layout](g, rng))
    got = np.asarray(kernel(q, kpool, vpool, tables, lengths), np.float32)
    want = np.asarray(reference(q, kpool, vpool, tables, lengths))
    # a bf16 pool's output is rounded to bf16: half a unit in the last place
    tol = dict(rtol=2e-4, atol=2e-5) if g.dtype == "float32" else dict(
        rtol=2 ** -8, atol=2 ** -8)
    np.testing.assert_allclose(got, want, **tol)


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


# the four serving configurations' operands (bf16, blocks of 32) -> (rows a
# tile, blocks a fetch step): a change to one configuration's geometry
# changes its tick programs, and shows here
GEOMETRY = {
    "mistral-7b": (_operands((512, 32, 128), *[(2400, 32, 8, 128)] * 2),
                   128, False, (32, 4)),
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
}


@pytest.mark.parametrize("config", sorted(GEOMETRY))
def test_geometry_of_the_serving_configurations(config):
    (q, pools), value_dim, heads_first, want = GEOMETRY[config]
    assert _geometry(q, pools, value_dim, heads_first)[2:] == want


def test_tile_rows_follow_the_accumulator():
    assert tile_rows(32, 128) == 32      # Mistral, Pythia: 0.5 MB
    assert tile_rows(16, 512) == 32      # Moonlight's latents: 1 MB
    assert tile_rows(128, 512) == 16     # never under 16


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
