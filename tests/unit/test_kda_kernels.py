"""The delta rule's two forms (``hybrid.delta_rule``: runs of one through
``kda_step``, the others through ``kda_chunk``; ``ops/pallas/kda.py``)
against the recurrence one row after another, the pieces the host counts,
and ``tools/kda_kernel_alone.py``'s walk. Heads of 128: the rule's tiles
are the real ones. The stack these layers stand in is
``test_kimi_linear_stack.py``'s.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import hybrid as HY
from deepspeed_tpu.ops.pallas import kda as KD

from family_harness import TOL, load_tool, rel


def _rule_case(slots, positions, fast=False, heads=2, seed=0):
    rng = np.random.default_rng(seed)
    Tn, N, D = len(slots), heads, 128
    slot = jnp.asarray(slots, jnp.int32)
    runs = HY.runs_of(slot, jnp.asarray(positions, jnp.int32))
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    q, k, v = f(Tn, N, D) / 11, f(Tn, N, D), f(Tn, N, D)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.asarray(rng.uniform(1e-3, 2.0, (Tn, N, D)), jnp.float32)
    if fast:
        # a channel that decays by e^-30 a row: 1 / G overflows float32
        # within three rows of a chunk
        g = g.at[:, 0, 5].set(-30.0)
    b = jnp.asarray(rng.uniform(0, 1, (Tn, N)), jnp.float32)
    state = f(max(slots) + 1, N, D, D)
    s0 = jnp.where(runs.fresh[:, None, None, None], 0.0, state[slot])
    o, after = HY.kda_recurrence(q, k, v, g, b, runs, s0)
    want = np.array(state)
    for t in range(Tn):
        if bool(runs.last[t]) and slots[t] > 0:
            want[slots[t]] = after[t]
    return (q, k, v, g, b, runs, state, slot), \
        jnp.where((slot > 0)[:, None, None], o, 0.0), want


RULE_CASES = {
    # two decode rows, a run that goes on from stored state, a fresh run,
    # two pad rows
    "a-tick-of-16": ([1, 2] + [3] * 5 + [4] * 7 + [0, 0],
                     [9, 4] + list(range(7, 12)) + list(range(7)) + [0, 0],
                     False),
    # runs of 100 and 70 rows (several chunks, cut mid-chunk), decode rows
    # before and after them, a fast channel
    "chunks-and-a-fast-channel": (
        [1] + [3] * 100 + [4] * 70 + [5] + [0] * 3,
        [9] + list(range(7, 107)) + list(range(70)) + [3] + [0] * 3, True),
    "every-row-a-run-of-one": (list(range(1, 9)), [5] * 8, False),
    # a run over three chunks that ends mid-chunk, and a second run that
    # starts in that chunk (two pieces of one chunk), then a decode row
    "two-runs-in-one-chunk": (
        [3] * 150 + [4] * 30 + [5] + [0] * 11,
        list(range(20, 170)) + list(range(30)) + [8] + [0] * 11, False),
    # prompt rows that start off the 64-grid after fewer than 64 decode
    # rows: the run's first piece is the tail of the decode rows' chunk
    "a-run-after-decode-rows": (
        list(range(1, 38)) + [40] * 90 + [0],
        [6] * 37 + list(range(11, 101)) + [0], True),
}


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_both_forms_of_the_rule_match_the_recurrence(case, kernel):
    """``delta_rule`` (runs of one through ``kda_step``, the others through
    ``kda_chunk``: the two Mosaic kernels interpreted where ``kernel``,
    else their plain references) against one row after another: outputs
    and the state each run leaves in its slot."""
    slots, positions, fast = RULE_CASES[case]
    args, o_want, state_want = _rule_case(slots, positions, fast)
    with jax.default_matmul_precision("highest"):
        o, state = jax.jit(lambda *a: HY.delta_rule(*a, use_kernel=kernel))(
            *args)
    assert bool(jnp.isfinite(o).all())
    assert rel(o, o_want) < TOL
    assert rel(jnp.asarray(state), jnp.asarray(state_want)) < TOL


@pytest.mark.parametrize("kernel", [False, True])
def test_the_chunk_form_alone_takes_runs_of_one_past_the_step_form_s_count(
        monkeypatch, kernel):
    """More runs of one than the one-row form takes: the rest go through
    the chunk form, a piece a row."""
    monkeypatch.setattr(HY, "KDA_STEP_ROWS", 3)
    args, o_want, state_want = _rule_case(list(range(1, 9)), [5] * 8)
    with jax.default_matmul_precision("highest"):
        o, state = HY.delta_rule(*args, use_kernel=kernel)
    assert rel(o, o_want) < TOL
    assert rel(jnp.asarray(state), jnp.asarray(state_want)) < TOL


@pytest.mark.parametrize("kernel", [False, True])
def test_pad_rows_touch_no_state(kernel):
    args, _, _ = _rule_case([0] * 8 + [2] + [0] * 7, [0] * 8 + [3] + [0] * 7)
    o, state = HY.delta_rule(*args, use_kernel=kernel)
    before = args[6]
    np.testing.assert_array_equal(np.asarray(state[0]), np.asarray(before[0]))
    np.testing.assert_array_equal(np.asarray(state[1]), np.asarray(before[1]))
    assert float(jnp.abs(state[2] - before[2]).max()) > 0
    assert float(jnp.abs(o[:8]).max()) == 0.0


@pytest.mark.parametrize("kernel", [False, True])
def test_a_bucket_whose_chunks_hold_no_row_starts_nothing(kernel):
    """Rows of the one-row form and pads alone: the chunk form has no
    piece, the store is as it was bit for bit and its output zero."""
    slots = list(range(1, 6)) + [0] * 123
    args, _, _ = _rule_case(slots, [4] * 5 + [0] * 123)
    q, k, v, g, b, runs, state, slot = args
    chunk = jax.jit(functools.partial(KD.kda_chunk, interpret=True)) \
        if kernel else KD.kda_chunk_reference
    o, after = chunk(q, k, v, g, b, runs, jnp.zeros((128,), bool), state,
                     slot)
    np.testing.assert_array_equal(np.asarray(after), np.asarray(state))
    assert o.shape == q.shape and float(jnp.abs(o).max()) == 0.0
    n, *_ = KD._pieces(jnp.zeros((128,), bool), runs.start, runs.last,
                       runs.fresh, slot, KD.CHUNK)
    assert int(n[0]) == 0 == KD.count_pieces([])


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("mistake", ["decay-dropped", "b-is-one"])
def test_a_mistake_in_the_rule_is_seen_in_both_forms(mistake, kernel):
    """What the comparison above can see: either form against the
    recurrence that makes a mistake reads far over the tolerance."""
    slots, positions, _ = RULE_CASES["two-runs-in-one-chunk"]
    args, _, _ = _rule_case(slots, positions)
    q, k, v, g, b, runs, state, slot = args
    if mistake == "decay-dropped":
        g = jnp.zeros_like(g)
    else:
        b = jnp.ones_like(b)
    s0 = jnp.where(runs.fresh[:, None, None, None], 0.0, state[slot])
    o_wrong, _ = HY.kda_recurrence(q, k, v, g, b, runs, s0)
    with jax.default_matmul_precision("highest"):
        o, _ = HY.delta_rule(*args, use_kernel=kernel)
    assert rel(o, jnp.where((slot > 0)[:, None, None], o_wrong, 0.0)) \
        > 100 * TOL


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_the_host_counts_the_pieces_the_kernel_runs(case):
    """``count_pieces`` (the span's ``kda_chunk_pieces``) is the grid the
    kernel is given, for the runs ``delta_rule`` hands the chunk form."""
    slots, positions, _ = RULE_CASES[case]
    slot = jnp.asarray(slots, jnp.int32)
    runs = HY.runs_of(slot, jnp.asarray(positions, jnp.int32))
    start, last = np.asarray(runs.start), np.asarray(runs.last)
    firsts, ends = np.nonzero(start)[0], np.nonzero(last)[0]
    taken = [(int(a), int(e - a + 1)) for a, e in zip(firsts, ends)
             if slots[a] > 0 and e > a]       # runs of one: the step form
    rows = np.zeros((len(slots),), bool)
    for a, n in taken:
        rows[a:a + n] = True
    T = -(-len(slots) // KD.CHUNK) * KD.CHUNK
    pad = lambda x, fill: jnp.pad(  # noqa: E731
        jnp.asarray(x), (0, T - len(slots)), constant_values=fill)
    n, chunk, lo, hi, _, flag = KD._pieces(
        pad(rows, False), pad(runs.start, True), pad(runs.last, True),
        pad(runs.fresh, True), pad(slot, 0), KD.CHUNK)
    n = int(n[0])
    assert n == KD.count_pieces(taken)
    # a piece lies inside one chunk and one run; a run's first opens it
    # and its last closes it
    assert bool((lo[:n] <= hi[:n]).all()) and bool((hi[:n] < KD.CHUNK).all())
    assert int(jnp.sum(flag[:n] & 1)) == len(taken) \
        == int(jnp.sum((flag[:n] & 4) > 0))
    assert int(jnp.sum(hi[:n] - lo[:n] + 1)) == int(rows.sum())



def test_the_kernel_alone_tool_still_walks():
    """``tools/kda_kernel_alone.py`` on its tiny cases, interpreted: the
    three forms run chained and the kernel agrees with the plain form (its
    times are a chip's to give: none is read here)."""
    tool = load_tool("kda_kernel_alone")
    assert set(tool.CASES) >= {"mixed-one-run", "mixed-two-runs", "decode"}
    forms = tool.forms_of(KD, True)
    assert set(forms) == {"kernel", "plain", "solve"}
    ops, pieces = tool.operands(np.random.default_rng(0),
                                tool.TINY["mixed"], (2, 128))
    assert pieces == 3
    found = tool.compare(KD, ops, True)
    assert found["finite"] and found["o_rel"] < TOL \
        and found["state_rel"] < TOL
    ops, pieces = tool.operands(np.random.default_rng(0),
                                tool.TINY["decode"], (2, 128))
    assert pieces == 0
    for name in ("plain", "solve"):     # the kernel's trace is above
        total, state = tool.chained(forms[name], 2)(*ops)
        assert float(total) == 0.0 or name == "solve"
        np.testing.assert_array_equal(np.asarray(state), np.asarray(ops[7]))
