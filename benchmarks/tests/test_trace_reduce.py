"""The reducer against the recorded v5e trace (``testdata/``, made by
``tools/capture_testdata.py`` on four chips) and against hand-made
intervals."""
import os

import pytest

from benchmarks import trace_reduce as tr
from benchmarks.roofline import flash_attention, paged_attention

PROBE = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                     "testdata", "v5e_4chip_probe.xplane.pb")
SPANS = ("probe.flash", "probe.paged", "probe.scan", "probe.collective",
         "probe.sleep", "probe.outer")


@pytest.fixture(scope="module")
def probe():
    return tr.ReducedTrace.from_file(PROBE, window_span="probe.outer")


def test_interval_arithmetic():
    merged = tr.union([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert merged == [(0, 3), (5, 6)]
    assert tr.total(merged) == 4
    assert tr.intersect(merged, tr.union([(2, 5.5)])) == 1.5
    assert tr.complement(merged, -1, 7) == [(-1, 0), (3, 5), (6, 7)]
    assert tr.clip([(0, 10)], 2, 3) == [(2, 3)]


def test_parse_instruction():
    text = ("%all-gather.6 = bf16[1024,1024]{1,0:T(8,128)(2,1)S(1)} "
            "all-gather(bf16[256,1024]{1,0:T(8,128)(2,1)} %p), channel_id=1")
    assert tr.parse_instruction(text) == ("all-gather.6", "all-gather")
    text = ("%jvp__.1 = (bf16[4,512,128]{2,1,0:T(8,128)(2,1)S(1)}, "
            "f32[4,512,1]{2,1,0:T(8,128)S(1)}) custom-call(bf16[4,512,128]"
            "{2,1,0} %a), custom_call_target=\"tpu_custom_call\"")
    assert tr.parse_instruction(text) == ("jvp__.1", "custom-call")


def test_planes_and_window(probe):
    assert probe.chips == [0, 1, 2, 3]
    # probe.outer: two passes of ~6 ms
    assert 0.010 < probe.window_s < 0.015


def test_leaves_drop_containers(probe):
    kinds = {o.kind for o in probe.ops[0]}
    assert "while" not in kinds            # the scan's container
    assert "fusion" in kinds and "custom-call" in kinds


def test_busy_union_and_idle_share(probe):
    busy = probe.busy_s()
    # chip 0 ran tens of microseconds of kernels, chips 1-3 one collective
    assert 1e-5 < busy < 2e-4
    assert probe.idle_share() == pytest.approx(1 - busy / probe.window_s)
    assert probe.idle_share() > 0.98
    for c in probe.chips:
        merged = probe.busy_intervals(c)
        assert all(a < b for a, b in merged)
        assert all(merged[i][1] <= merged[i + 1][0]
                   for i in range(len(merged) - 1))


def test_exposed_collective_time(probe):
    # the probe's all-gather and all-reduce are synchronous: nothing else
    # runs on a chip meanwhile, so all of their time is exposed
    coll = probe.op_seconds(lambda o: o.is_collective)
    assert coll > 0
    assert probe.collective_exposed_s() == pytest.approx(coll, rel=0.05)


def test_exposed_excludes_overlap():
    ops = {0: [tr.Op("fusion.1", "fusion", "", 0.0, 1.0),
               tr.Op("all-reduce.1", "all-reduce", "", 2.0, 3.0)]}
    asyn = {0: [tr.Op("all-gather-start.1", "all-gather-start", "", 0.5, 1.5)]}
    t = tr.ReducedTrace(ops, asyn, {}, [tr.Span(tr.WINDOW_SPAN, 0.0, 4.0)])
    # async span 0.5-1.5 is hidden behind the fusion until 1.0; the
    # synchronous all-reduce is all exposed
    assert t.collective_exposed_s() == pytest.approx(0.5 + 1.0)
    assert t.busy_s() == pytest.approx(2.0)
    assert t.idle_share() == pytest.approx(0.5)


def test_kernels_found_by_shape(probe):
    kinds = [flash_attention.classify(o) for o in probe.ops_in_window(0)
             if o.is_mosaic]
    assert {"fwd", "dq", "dkv"} <= set(kinds)
    paged = [o for o in probe.ops_in_window(0)
             if o.is_mosaic and flash_attention.classify(o) is None]
    assert paged and paged_attention.classify(paged[0]) == "paged"


def test_gap_attribution(probe):
    gaps = probe.longest_gaps(SPANS, 10)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    names = [n for n, _ in gaps]
    # the 2 ms sleep under probe.sleep is among the longest idle gaps
    assert "probe.sleep" in names
    assert all(s > 0 for _, s in gaps)


def test_module_runs_and_spans(probe):
    mods = probe.module_runs("jit_paged_attention")
    assert 1 <= len(mods) <= 2
    assert len(probe.spans("probe.paged")) == 2
    top = probe.top_ops(10)
    assert len(top) <= 10 and top[0][1] >= top[-1][1]


# --------------------------------------------------------------------- #
# the ticks of a traced stretch (``readers.traced_ticks``) on the recorded
# serving chain: 13 decode ticks, runs 0.0458-0.1161 on the device's clock
# inside ``serving_tick`` spans 0.0450-0.1184 on the host's
# --------------------------------------------------------------------- #
CHAIN_SERVE = os.path.join(os.path.dirname(PROBE), "v5e_chain_serve.xplane.pb")
TICK_READERS = ("tick_host_p50_ms", "tick_dev_decode_p50_ms",
                "tick_dev_mixed_p50_ms")


def _traced_serving_run(monkeypatch, window=None):
    import types

    from benchmarks import gap_chain

    monkeypatch.setattr(gap_chain, "trace_file", lambda run: CHAIN_SERVE)
    trace = tr.ReducedTrace.from_file(CHAIN_SERVE)
    if window is not None:
        trace.window = window
    return types.SimpleNamespace(
        trace=trace, extras={}, cache={}, telemetry=None, peaks=None,
        cell=types.SimpleNamespace(runner="serve", name="recorded"))


def test_traced_ticks_join_run_span_and_mark(monkeypatch):
    from benchmarks import readers

    run = _traced_serving_run(monkeypatch)
    rows = readers.traced_ticks(run)
    mods = run.trace.module_runs("jit_tick")
    spans = run.trace.spans("serving_tick")
    assert len(rows) == len(mods) == len(spans) == 13
    assert [r["device"] for r in rows] == pytest.approx(
        [m.end - m.start for m in mods])
    assert [r["wall"] for r in rows] == pytest.approx(
        [s.end - s.start for s in spans])
    assert not any(r["mixed"] for r in rows)      # 13 ``bench.tick.decode``
    assert run.extras["gap_chain"]["ticks_whole"] == 13
    run.trace = None                              # an untraced run
    assert readers.traced_ticks(run) is None


@pytest.mark.parametrize("cut,lost", [
    # the stretch begins inside the first tick: after its span began on the
    # host's clock, before its run began on the device's (12 spans, 13 runs)
    ("start", 0),
    # ... ends inside the last tick's span, after its run (12 spans, 13 runs)
    ("end_host", 12),
    # ... ends inside the last run (PR 24's case: more spans than runs)
    ("end_device", 12)])
def test_traced_ticks_on_a_stretch_that_cuts_a_tick(monkeypatch, cut, lost):
    """Counting spans against runs matched nothing here and every tick
    reader returned None; the join by ``run_id`` loses the cut tick only."""
    from benchmarks import readers
    from benchmarks.manifest import load_plugin

    whole = readers.traced_ticks(_traced_serving_run(monkeypatch))
    lo, hi = tr.ReducedTrace.from_file(CHAIN_SERVE).window
    window = {"start": (0.0455, hi), "end_host": (lo, 0.1183),
              "end_device": (lo, 0.1150)}[cut]
    run = _traced_serving_run(monkeypatch, window)
    n_spans = len(run.trace.spans("serving_tick"))
    n_runs = len(run.trace.module_runs("jit_tick"))
    assert (n_spans, n_runs) == {"start": (12, 13), "end_host": (12, 13),
                                 "end_device": (12, 12)}[cut]
    rows = readers.traced_ticks(run)
    assert rows == whole[:lost] + whole[lost + 1:]
    host = load_plugin("layer_metrics", "tick_host_p50_ms").read(run)
    dev = load_plugin("layer_metrics", "tick_dev_decode_p50_ms").read(run)
    assert 2.0 < host < 4.0 and 2.5 < dev < 3.0          # ms, as recorded
    # no tick of the recording held prompt rows: nothing to read, no error
    assert load_plugin("layer_metrics", "tick_dev_mixed_p50_ms").read(run) \
        is None
