"""The Mamba-2 recurrence's two forms (``hybrid.ssd``: runs of one through
``ssd_step``, the others through ``ssd_chunk``; ``ops/pallas/ssd.py``), each
as its Mosaic kernel (interpreted) and as its plain reference, against one
row after another, and the pieces the host counts. The toy
widths are ``test_nemotron_h_stack.py``'s mixer: 16 heads of 8 over 8 groups
of a state 128 wide; and ONE group for every head (the ``granitemoehybrid``
family's: 4 heads of 64, two to a tile of the store as published, the
kernel's loop over groups run once).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from deepspeed_tpu.models import hybrid as HY
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.ops.pallas import ssd as SD

from family_harness import TOL, rel


def _ssd_case(slots, positions, fast=False, nh=16, P=8, G=8, N=128, seed=0):
    rng = np.random.default_rng(seed)
    Tn = len(slots)
    slot = jnp.asarray(slots, jnp.int32)
    runs = HY.runs_of(slot, jnp.asarray(positions, jnp.int32))
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, B, C = f(Tn, nh, P), f(Tn, G, N) / 11, f(Tn, G, N) / 11
    delta = jnp.asarray(rng.uniform(1e-3, 1.0, (Tn, nh)), jnp.float32)
    g = -jnp.asarray(rng.uniform(1e-3, 2.0, (Tn, nh)), jnp.float32)
    if fast:
        # a head that decays by e^-30 a row: 1 / G overflows float32
        # within three rows of a chunk
        g = g.at[:, 3].set(-30.0)
    plain = f(max(slots) + 1, nh, P, N)
    s0 = jnp.where(runs.fresh[:, None, None, None], 0.0, plain[slot])
    y, after = HY.ssd_recurrence(x, delta, g, B, C, runs, s0)
    want = np.array(plain)
    for t in range(Tn):
        if bool(runs.last[t]) and slots[t] > 0:
            want[slots[t]] = after[t]
    # the store's layout: the state values down a tile's rows
    state, want = SD.to_store(plain, G), SD.to_store(jnp.asarray(want), G)
    assert rel(SD.from_store(state, nh), plain) == 0
    return (x, delta, g, B, C, runs, state, slot), \
        jnp.where((slot > 0)[:, None, None], y, 0.0), want


SSD_CASES = {
    # two decode rows, a run that goes on from stored state, a fresh run,
    # two pad rows
    "a-tick-of-16": ([1, 2] + [3] * 5 + [4] * 7 + [0, 0],
                     [9, 4] + list(range(7, 12)) + list(range(7)) + [0, 0],
                     False),
    # runs of 100 and 70 rows (several chunks, cut mid-chunk), decode rows
    # before and after them, a fast head
    "chunks-and-a-fast-head": (
        [1] + [3] * 100 + [4] * 70 + [5] + [0] * 3,
        [9] + list(range(7, 107)) + list(range(70)) + [3] + [0] * 3, True),
    "every-row-a-run-of-one": (list(range(1, 9)), [5] * 8, False),
    # a run over several chunks that ends mid-chunk, and a second run that
    # starts in that chunk (two pieces of one chunk), then a decode row
    "two-runs-in-one-chunk": (
        [3] * 150 + [4] * 30 + [5] + [0] * 11,
        list(range(20, 170)) + list(range(30)) + [8] + [0] * 11, False),
    # prompt rows that start off the chunks' grid after decode rows
    "a-run-after-decode-rows": (
        list(range(1, 38)) + [40] * 90 + [0],
        [6] * 37 + list(range(11, 101)) + [0], True),
    # a run that closes mid-chunk and another that opens behind its last
    # row and goes on through two more chunks, a fast head across each edge
    "a-piece-opens-behind-another-run-s-last-row": (
        [3] * 21 + [4] * 40 + [0] * 3,
        list(range(5, 26)) + list(range(2, 42)) + [0] * 3, True),
}

# cases at the published chunk of 256 rows, where the kernel walks a chunk's
# triangle in sub-blocks and leaves out those above the diagonal: a run of
# three pieces that goes on from stored state beside decode rows and a
# second run in its last chunk, a fast head across the pieces' edges
SSD_CASES_256 = {
    "three-pieces-of-256": (
        [1, 2] + [3] * 600 + [4] * 100 + [0] * 66,
        [5, 6] + list(range(7, 607)) + list(range(100)) + [0] * 66, True),
}


#: (heads, channels, groups) of the mixer, a sequence's row of the store
GEOMETRIES = {"8-groups": ((16, 8, 8), (8, 128, 16)),
              "one-group": ((4, 64, 1), (2, 128, 128))}


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("case", sorted(SSD_CASES) + sorted(SSD_CASES_256))
def test_both_forms_of_the_recurrence_match_one_row_after_another(
        case, kernel, geometry):
    """``hybrid.ssd`` (runs of one through ``ssd_step``, the others through
    ``ssd_chunk``: the Mosaic kernels interpreted where ``kernel``, else
    their plain references) against ``ssd_recurrence``: outputs and the
    state each run leaves in its slot."""
    slots, positions, fast = {**SSD_CASES, **SSD_CASES_256}[case]
    (nh, P, G), store = GEOMETRIES[geometry]
    assert SD.store_shape(nh, G, P, 128) == store
    args, y_want, state_want = _ssd_case(slots, positions, fast, nh=nh, P=P,
                                         G=G)
    with jax.default_matmul_precision("highest"):
        y, state = jax.jit(lambda *a: HY.ssd(
            *a, chunk=256 if case in SSD_CASES_256 else 16,
            use_kernel=kernel))(*args)
    assert bool(jnp.isfinite(y).all())
    assert rel(y, y_want) < TOL
    assert rel(jnp.asarray(state), jnp.asarray(state_want)) < TOL


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_a_tick_with_no_piece_runs_no_step_of_the_chunk_kernel(geometry):
    """Decode rows and pads alone: the chunked form's grid is the tick's
    pieces, none here (a step of it reports itself when it RUNS), its
    output is zero and the store comes back bit for bit; a tick with a run
    of two rows runs the one step."""
    (nh, P, G), _ = GEOMETRIES[geometry]
    (x, delta, g, B, C, runs, state, slot), _, _ = _ssd_case(
        [1, 2, 3, 4] + [5] * 2 + [0] * 10, [9, 4, 7, 1, 3, 4] + [0] * 10,
        nh=nh, P=P, G=G)
    steps = []
    kernel = SD._chunk_kernel

    def reporting(*refs, **kwargs):
        jax.debug.callback(lambda p: steps.append(int(p)), pl.program_id(0))
        return kernel(*refs, **kwargs)

    alone = jnp.asarray(runs.start & runs.last)
    for rows, pieces in ((jnp.zeros((16,), bool), []),
                         ((slot > 0) & ~alone, [0])):
        steps.clear()
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(SD, "_chunk_kernel", reporting)
            y, after = jax.block_until_ready(SD.ssd_chunk(
                x, delta, g, B, C, runs, rows, state, slot, 16))
        assert steps == pieces
        if not pieces:
            assert float(jnp.abs(y).max()) == 0.0
            np.testing.assert_array_equal(np.asarray(after),
                                          np.asarray(state))
        else:
            assert float(jnp.abs(y[4:6]).min()) > 0.0
            assert float(jnp.abs(after[5] - state[5]).max()) > 0.0


@pytest.mark.parametrize("kernel", [False, True])
def test_the_chunked_form_takes_runs_of_one_past_the_step_form_s_count(
        monkeypatch, kernel):
    monkeypatch.setattr(HY, "SSD_STEP_ROWS", 3)
    args, y_want, state_want = _ssd_case(list(range(1, 9)), [5] * 8)
    with jax.default_matmul_precision("highest"):
        y, state = HY.ssd(*args, chunk=16, use_kernel=kernel)
    assert rel(y, y_want) < TOL
    assert rel(jnp.asarray(state), jnp.asarray(state_want)) < TOL


@pytest.mark.parametrize("kernel", [False, True])
def test_pad_rows_touch_no_state(kernel):
    args, _, _ = _ssd_case([0] * 8 + [2] + [0] * 7, [0] * 8 + [3] + [0] * 7)
    y, state = HY.ssd(*args, chunk=16, use_kernel=kernel)
    before = args[6]
    np.testing.assert_array_equal(np.asarray(state[0]), np.asarray(before[0]))
    np.testing.assert_array_equal(np.asarray(state[1]), np.asarray(before[1]))
    assert float(jnp.abs(state[2] - before[2]).max()) > 0
    assert float(jnp.abs(y[:8]).max()) == 0.0


def test_the_step_kernel_skips_rows_of_slot_zero_wherever_they_lie():
    """Called alone with a skipped row BETWEEN live ones (``hybrid.ssd``
    never does): the kernel's grid is the count of live rows, which lie
    first by contract, so the caller sorts; a live row behind a skipped
    one is the contract broken, and the reference says what was meant."""
    args, _, _ = _ssd_case([1, 2, 0, 0], [5, 6, 0, 0])
    x, delta, g, B, C, runs, state, slot = args
    a = jnp.exp(g)
    y_k, s_k = SD.ssd_step(x, delta, a, B, C, state, slot, runs.fresh)
    y_r, s_r = SD.ssd_step_reference(x, delta, a, B, C, state, slot,
                                     runs.fresh)
    assert rel(y_k[:2], y_r[:2]) < TOL and float(jnp.abs(y_k[2:]).max()) == 0
    assert rel(s_k, s_r) < TOL


@pytest.mark.parametrize("case", sorted(SSD_CASES))
def test_the_host_counts_the_pieces_the_loop_runs(case):
    """``paged._ssd_span`` (what the engine writes on a tick's span) against
    the tick's own pieces."""
    slots, positions, _ = SSD_CASES[case]
    real = [s for s in slots if s > 0]
    slot = np.asarray(slots)
    runs = HY.runs_of(jnp.asarray(slot), jnp.asarray(positions, jnp.int32))
    start = np.asarray(runs.start) & (slot > 0)
    alone = start & np.asarray(runs.last)
    decode = 0
    while decode < len(real) and alone[decode]:
        decode += 1
    starts = [int(t) for t in np.nonzero(start)[0] if t >= decode]
    span = PG._ssd_span(16, decode, starts, len(real), len(slots))
    step = min(int(alone.sum()), HY.SSD_STEP_ROWS)
    assert span["ssd_step_rows"] == step
    assert span["ssd_chunk_rows"] == len(real) - step
    assert span["ssd_state_rows"] == int(start.sum())
    chunk_rows = (slot > 0) & ~alone
    t = np.arange(len(slots))
    opens = chunk_rows & (np.asarray(runs.start) | (t % 16 == 0))
    assert span["ssd_chunk_pieces"] == int(opens.sum())


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("mistake", ["decay-dropped", "wrong-group"])
def test_a_mistake_in_the_recurrence_is_seen_in_both_forms(mistake, kernel):
    slots, positions, _ = SSD_CASES["a-tick-of-16"]
    (x, delta, g, B, C, runs, state, slot), y_want, _ = _ssd_case(
        slots, positions)
    if mistake == "decay-dropped":
        g = jnp.zeros_like(g)
    else:
        B, C = jnp.roll(B, 1, axis=1), jnp.roll(C, 1, axis=1)
    y, _ = HY.ssd(x, delta, g, B, C, runs, state, slot, chunk=16,
                  use_kernel=kernel)
    assert rel(y, y_want) > 100 * TOL
