"""The benchmark's gap chain (``benchmarks/gap_chain.py``): the join of a
program run to its enqueue by ``run_id`` and the split of the device's gap
among the host spans, on the recorded v5e traces and on hand-made events;
the phase of a device operation from the program's own scope names."""
import os
import statistics
import types

import jax
import jax.numpy as jnp
import pytest

from benchmarks import gap_chain as gc
from benchmarks import trace_reduce as tr

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "testdata")
PROBE = os.path.join(TESTDATA, "v5e_4chip_probe.xplane.pb")
EVERYTHING = (0.0, float("inf"))

#: the probe as a chain: two runs of ``jit_flash_step`` (run ids 25 and
#: 29), each enqueued inside a ``probe.flash`` span that also waits for
#: it; between them the probe's other programs and a 2 ms sleep
PROBE_SPEC = {
    "module": "jit_flash_step", "dispatch": "probe.flash",
    "fence": "probe.flash", "fence_encloses_dispatch": False,
    "owners": {"probe.flash": "dispatch", "probe.paged": "paged",
               "probe.scan": "scan", "probe.collective": "collective",
               "probe.sleep": "sleep"},
}


def recorded(name):
    path = os.path.join(TESTDATA, name)
    hd = gc.HostDevice(path, set().union(
        *(set(s["owners"]) | {s["fence"]} for s in gc.CHAINS.values())))
    return path, hd, tr.ReducedTrace.from_file(path)


@pytest.fixture(scope="module")
def probe():
    return gc.HostDevice(PROBE, set(PROBE_SPEC["owners"]))


# --------------------------------------------------------------------- #
# the recorded 4-chip probe
# --------------------------------------------------------------------- #
def test_probe_runs_join_their_enqueue_by_run_id(probe):
    assert [r.run_id for r in probe.runs] == list(range(25, 33))
    # ordinal 0 only: chips 1-3 ran the collective under run ids 2 and 3
    assert sorted(probe.enqueue) == list(range(25, 33))
    assert sorted(probe.complete) == list(range(25, 33))
    flash = [r for r in probe.runs if r.name.startswith("jit_flash_step")]
    for r in flash:
        # the first enqueue ran on a runtime thread, the second on the
        # main one: both lie inside a probe.flash span by time
        at = probe.enqueue[r.run_id].start
        lo, hi = probe.spans["probe.flash"][
            probe.last_started("probe.flash", at)]
        assert lo <= at <= hi


def test_probe_gap_is_split_and_owners_sum_to_it(probe):
    report, rows, joined = gc.chain(probe, PROBE_SPEC, EVERYTHING)
    assert report["unmatched"] == {} and report["matched"] == 2
    assert [r.run_id for r in joined] == [25, 29]
    (row,) = rows
    assert row["gap"] == pytest.approx(
        joined[1].start - joined[0].end) and 6e-3 < row["gap"] < 7e-3
    owners = sum(v for k, v in row.items() if k not in ("gap", "by_span"))
    assert owners == pytest.approx(row["gap"], abs=1e-12)
    # the sleep is 2.5 ms of the gap, under its own span
    assert row["sleep"] == pytest.approx(2.5477e-3, rel=1e-3)
    assert row["dispatch"] > 0          # probe.flash(k+1) up to the enqueue
    assert row["runtime"] > 0 and report["negative_runtime"] == 0
    # the device clock runs 1.6-2.0 ms behind the host's in this trace
    off = report["host_minus_device_ms"]
    assert 1.0 < off["at_least"] <= off["at_most"] < 3.0


def test_probe_metadata_scopes_decoded_from_the_wire(probe):
    scopes = gc.op_scopes(PROBE, 0)
    by_name = {tr.parse_instruction(text)[0]: s
               for (_, text), s in scopes.items()}
    assert by_name["jvp__.1"] == "jit(flash_step)/jvp()/pallas_call:"
    assert by_name["transpose_jvp___.3"] == \
        "jit(flash_step)/transpose(jvp())/pallas_call:"
    assert by_name["paged_attention.1"] == \
        "jit(paged_attention)/pallas_call:"
    # every key carries the program's id, as the module's name does
    programs = {p for p, _ in scopes}
    assert all(any(str(p) in r.name for r in probe.runs) for p in programs)
    assert gc.op_scopes(PROBE, 7) == {}      # no such chip


def test_a_program_without_the_spans_gives_no_metric_and_no_error(probe):
    """The parent commit has no ``tick_dispatch`` span: every reader must
    leave its metric out, not raise."""
    report, rows, _ = gc.chain(
        gc.HostDevice(PROBE, set(gc.CHAINS["serve"]["owners"])),
        gc.CHAINS["serve"], EVERYTHING)
    assert rows == [] and "reason" in report["unmatched"]
    run = types.SimpleNamespace(trace=None, extras={})
    assert gc.metric(run, "gap_schedule_ms") is None


# --------------------------------------------------------------------- #
# the recorded real chain (``tools/capture_chain.py`` on one v5e chip: the
# decode cell's runner at two layers, the one-chip training cell as it is)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("kind", ["serve", "train"])
def test_recorded_chain_joins_one_to_one_and_owners_sum_to_the_gap(kind):
    _, hd, reduced = recorded(f"v5e_chain_{kind}.xplane.pb")
    spec = gc.CHAINS[kind]
    report, rows, joined = gc.chain(hd, spec, reduced.window)
    assert report["unmatched"] == {}
    assert report["matched"] == report["runs"] == len(joined) >= 4
    assert report["negative_runtime"] == 0
    off = report["host_minus_device_ms"]
    assert off["at_least"] <= off["at_most"]
    owners = {*spec["owners"].values(), "client", "runtime"}
    for row in rows:
        assert sum(row[o] for o in owners) == pytest.approx(row["gap"],
                                                            abs=1e-9)
        assert all(row[o] >= 0 for o in owners)
        assert 1e-3 < row["gap"] < 8e-3       # 3 ms a tick, 3.4 ms a step
    # between two runs of the program the device ran nothing else: gaps
    # plus the idle time inside the runs is the reducer's idle time
    check = gc.idle_check(rows, joined, reduced, 0)
    assert check["rel_diff"] < 0.02
    assert check["gaps_s"] == pytest.approx(sum(r["gap"] for r in rows))


def test_recorded_tick_is_owned_by_dispatch_and_the_runtime():
    _, hd, reduced = recorded("v5e_chain_serve.xplane.pb")
    report, rows, _ = gc.chain(hd, gc.CHAINS["serve"], reduced.window)
    med = lambda o: statistics.median(r[o] for r in rows)   # noqa: E731
    # my chip run, PR 23 (two layers): 1.5 ms dispatch and 1.0 ms runtime
    # of a 3.0 ms gap; schedule, commit, frontend and client 0.1-0.2 each
    assert 1.0e-3 < med("dispatch") < 2.2e-3
    assert 0.5e-3 < med("runtime") < 1.6e-3
    assert med("dispatch") > 3 * max(med("schedule"), med("commit"),
                                     med("frontend"), med("client"))
    # the runtime enqueues after the jitted call has returned, mostly
    assert report["enqueued_after_dispatch_returned"] >= report["runs"] // 2
    # the tick's kernel and scopes carry the program's names
    names = {o.name.split(".")[0] for o in reduced.ops[0]}
    assert "paged_attention" in names
    scopes = set(gc.op_scopes(os.path.join(
        TESTDATA, "v5e_chain_serve.xplane.pb")).values())
    assert all(s.startswith("jit(tick)/") for s in scopes)
    leaves = {gc.leaf_scope(s) for s in scopes}
    # (no ``sample``: the greedy argmax is fused into the head's matmul,
    # ``convolution_reduce_fusion``, which keeps the matmul's name stack)
    assert {"attn", "mlp", "lm_head", "embed"} <= leaves
    assert any("/attn/paged_attention" in s for s in scopes)


def test_recorded_step_splits_into_four_phases():
    path, hd, reduced = recorded("v5e_chain_train.xplane.pb")
    _, _, joined = gc.chain(hd, gc.CHAINS["train"], reduced.window)
    phases = gc.device_phases(joined, reduced, gc.op_scopes(path), 0)
    # outside the four phases (collectives apart): compiler-made copies
    assert phases["unscoped_share"] < 0.05
    med = lambda p: statistics.median(   # noqa: E731
        r[p] for r in phases["per_run"])
    assert med("collective") == 0.0                       # one chip
    # full remat at two layers under a 50k-wide head: the recompute is the
    # blocks' forward only, the backward twice the whole forward
    assert 0 < med("recompute") < med("fwd") < med("bwd")
    assert 0.02 < med("optimizer") < 0.08     # Adam + the accumulator
    step = statistics.median(r.end - r.start for r in joined)
    assert sum(med(p) for p in ("fwd", "recompute", "bwd", "optimizer")) \
        == pytest.approx(step, rel=0.05)
    assert {"attn", "mlp", "lm_head_loss", "optimizer", "grad_accumulate"} \
        <= set(phases["seconds_by_scope"])
    names = {o.name.split(".")[0] for o in reduced.ops[0]}
    assert {"flash_fwd", "flash_dq", "flash_dkv"} <= names


# --------------------------------------------------------------------- #
# hand-made events: what the split and the join must refuse
# --------------------------------------------------------------------- #
def test_split_gives_each_instant_to_the_innermost_span():
    spans = {"outer": [(0.0, 10.0)], "inner": [(2.0, 4.0), (6.0, 7.0)],
             "leaf": [(3.0, 3.5)], "later": [(12.0, 13.0)]}
    got = gc.split_interval(1.0, 11.0, spans)
    assert got == {"outer": pytest.approx(6.0), "inner": pytest.approx(2.5),
                   "leaf": pytest.approx(0.5), None: pytest.approx(1.0)}
    assert sum(got.values()) == pytest.approx(10.0)
    assert gc.split_interval(20.0, 21.0, spans) == {None: 1.0}


def hand_made(runs, enqueue, spans, complete=None):
    hd = gc.HostDevice.__new__(gc.HostDevice)
    hd.runs = [gc.Run(*r) for r in runs]
    hd.enqueue = {k: gc.Enq(*v) for k, v in enqueue.items()}
    hd.complete = {k: gc.Enq(*v) for k, v in (complete or {}).items()}
    hd.spans = {n: sorted(v) for n, v in spans.items()}
    return hd


def serving_events(**change):
    """Two ticks, device clock 1.5 s behind the host's: tick k runs on
    the device [k, k + 0.6]; the host reads back until k + 0.7 (+1.5)."""
    ev = {
        "runs": [("jit_tick(1)", 0.0, 0.6, 7), ("jit_tick(1)", 1.0, 1.6, 8)],
        "enqueue": {7: (1.45, 1.46), 8: (2.45, 2.46)},
        "complete": {7: (2.15, 2.16), 8: (3.15, 3.16)},
        "spans": {
            "serving_tick": [(1.30, 2.22), (2.30, 3.22)],
            "schedule_tick": [(1.31, 1.40), (2.31, 2.40)],
            "decode_tick": [(1.40, 2.20), (2.40, 3.20)],
            "tick_dispatch": [(1.40, 1.47), (2.40, 2.47)],
            "tick_readback": [(1.47, 2.20), (2.47, 3.20)],
            "tick_commit": [(2.20, 2.22), (3.20, 3.22)],
            "serving_harvest": [(2.22, 2.25), (3.22, 3.25)],
            "serving_submit": [(2.26, 2.28)],
        }}
    ev.update(change)
    return hand_made(**ev)


def test_serving_chain_on_hand_made_ticks():
    report, rows, joined = gc.chain(serving_events(), gc.CHAINS["serve"],
                                    EVERYTHING)
    assert report["unmatched"] == {} and len(joined) == 2
    (row,) = rows
    assert row["gap"] == pytest.approx(0.4)
    # host interval: read-back end 2.20 to enqueue 2.45
    assert row["commit"] == pytest.approx(0.02)
    assert row["frontend"] == pytest.approx(0.03 + 0.02 + 0.01)
    assert row["schedule"] == pytest.approx(0.09)
    assert row["dispatch"] == pytest.approx(0.05)
    assert row["client"] == pytest.approx(0.25 - 0.02 - 0.06 - 0.09 - 0.05)
    assert row["runtime"] == pytest.approx(0.4 - 0.25)
    assert sum(v for k, v in row.items() if k not in ("gap", "by_span")) \
        == pytest.approx(row["gap"])
    off = report["host_minus_device_ms"]
    assert off["at_least"] == pytest.approx(1450.0)
    assert off["at_most"] == pytest.approx(1550.0)


@pytest.mark.parametrize("change, key", [
    ({"enqueue": {7: (1.45, 1.46)}}, "runs_without_enqueue"),
    ({"enqueue": {7: (1.25, 1.26), 8: (2.45, 2.46)}},
     "enqueues_before_any_span"),
    ({"enqueue": {7: (1.45, 1.46), 8: (1.46, 1.465)}},
     "spans_with_several_runs"),
])
def test_a_join_that_is_not_one_to_one_yields_nothing(change, key):
    report, rows, joined = gc.chain(serving_events(**change),
                                    gc.CHAINS["serve"], EVERYTHING)
    assert rows == [] and joined == [] and report["unmatched"][key]


def test_training_chain_on_hand_made_steps():
    hd = hand_made(
        runs=[("jit_train_step(5)", 0.0, 0.9, 3),
              ("jit_train_step(5)", 1.0, 1.9, 4)],
        enqueue={3: (1.56, 1.57), 4: (2.56, 2.57)},
        spans={"bench.step": [(1.50, 2.48), (2.49, 3.48)],
               "train_batch_fetch": [(1.50, 1.52), (2.49, 2.51)],
               "train_batch_input": [(1.52, 1.53), (1.53, 1.55),
                                     (2.51, 2.52), (2.52, 2.54)],
               "train_step": [(1.55, 1.60), (2.55, 2.60)]})
    report, rows, _ = gc.chain(hd, gc.CHAINS["train"], EVERYTHING)
    assert report["unmatched"] == {}
    (row,) = rows
    # host interval: end of bench.step(k) 2.48 to the enqueue 2.56
    assert row["input"] == pytest.approx(0.03)
    assert row["dispatch"] == pytest.approx(0.01)
    assert row["client"] == pytest.approx(0.01 + 0.02 + 0.01)
    assert row["runtime"] == pytest.approx(0.1 - 0.08)


# --------------------------------------------------------------------- #
# phases from the program's own names
# --------------------------------------------------------------------- #
def test_phase_of_real_name_stacks():
    """Lower a toy step the way the engine builds one and sort every
    operation's name stack: forward, recompute, backward and optimizer
    are told apart by the program's scopes and JAX's transform marks."""
    def block(w, x):
        with jax.named_scope("attn"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("mlp"):
            return jnp.sin(h @ w)

    def step(w, x):
        with jax.named_scope("loss_and_grads"):
            loss, g = jax.value_and_grad(
                lambda w: jnp.sum(jax.checkpoint(block)(w, x)))(w)
        with jax.named_scope("optimizer"):
            return w - 0.1 * g, loss

    text = jax.jit(step).lower(jnp.ones((8, 8)), jnp.ones((4, 8))).as_text(
        debug_info=True)
    import re

    stacks = set(re.findall(r'loc\("(jit\(step\)[^"]*)"', text))
    by_phase = {}
    for s in stacks:
        by_phase.setdefault(gc.phase_of(s), set()).add(s)
    assert set(by_phase) == {"fwd", "recompute", "bwd", "optimizer"}
    assert any(s.endswith("jvp(attn)/tanh") for s in by_phase["fwd"])
    assert all("rematted_computation" in s for s in by_phase["recompute"])
    assert any("/attn/" in s for s in by_phase["recompute"])
    assert all("transpose(jvp" in s for s in by_phase["bwd"])
    assert {gc.leaf_scope(s) for s in by_phase["fwd"]} >= {"attn", "mlp"}
    assert gc.phase_of("jit(train_step)/optimizer/zero_param_update/x") \
        == "optimizer"
    assert gc.phase_of("jit(train_step)/grad_reduce/sharding") == "optimizer"
    assert gc.phase_of("") is None and gc.phase_of("tables:") is None


def test_an_enqueue_after_the_call_returned_is_the_runtimes():
    """The runtime enqueues on its own thread, often after the jitted
    call has returned (145 of 190 ticks in the decode cell, my chip run,
    PR 23): the host's part of the gap then ends where ``tick_dispatch``
    did, and the wait for the launch goes to the runtime."""
    late = serving_events(enqueue={7: (1.45, 1.46), 8: (2.52, 2.53)})
    report, rows, joined = gc.chain(late, gc.CHAINS["serve"], EVERYTHING)
    assert report["unmatched"] == {} and len(joined) == 2
    assert report["enqueued_after_dispatch_returned"] == 1
    (row,) = rows
    assert row["dispatch"] == pytest.approx(0.07)     # the whole span
    # host interval 2.20 - 2.47; the gap's other 0.13 s is the runtime's
    assert row["runtime"] == pytest.approx(0.4 - 0.27)
    assert sum(v for k, v in row.items() if k not in ("gap", "by_span")) \
        == pytest.approx(row["gap"])
