"""Structured tracing + flight recorder (``telemetry/tracing.py``) — the
ISSUE-5 acceptance surface:

* ring-buffer semantics: bounded, oldest-evicted-first, evictions counted;
* lossless Chrome trace-event export: sorted ``ts``, complete ``X`` (or
  matched ``B``/``E``) events, ``pid``/``tid`` everywhere — the schema
  Perfetto / ``chrome://tracing`` loads;
* request-scoped traces: every serving uid's timeline carries its
  admission verdict and exactly one terminal state across the
  completed / shed / expired / poisoned / rejected paths (chaos fault
  points force the failure-shaped ones);
* flight dumps fire on the four triggers — stall-watchdog escalation,
  circuit-breaker open, preemption exit, unhandled engine-step
  exception — and each dump validates as Chrome trace JSON containing
  the request/step spans leading up to the trigger;
* a DISABLED tracer stays near-free (overhead guard), and ``/trace`` +
  ``/flight`` scrape live over the exposition server.
"""
import json
import time
import urllib.request

import numpy as np
import pytest

import deepspeed_tpu as dst
from deepspeed_tpu import telemetry
from deepspeed_tpu.runtime.config import load_config
from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from deepspeed_tpu.telemetry.tracing import Tracer, main as trace_dump_main
from deepspeed_tpu.testing import chaos


@pytest.fixture(autouse=True)
def _clean():
    telemetry.reset()
    chaos.disarm()
    yield
    chaos.disarm()
    telemetry.reset()


# --------------------------------------------------------------------- #
# Chrome trace-event schema validator (what "validates as Chrome trace
# JSON" means everywhere below)
# --------------------------------------------------------------------- #
def validate_chrome(doc):
    """Assert ``doc`` is a loadable Chrome trace-event document: JSON-
    serializable, ``ts``-sorted, every event carrying pid/tid/name/ph,
    ``X`` events complete (dur >= 0) and ``B``/``E`` events matched per
    track. Returns the event list."""
    assert isinstance(doc, dict) and isinstance(doc.get("traceEvents"), list)
    json.dumps(doc)   # round-trippable
    events = doc["traceEvents"]
    last_ts = float("-inf")
    begin_stacks = {}
    for ev in events:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(ev), ev
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert ev["ts"] >= last_ts, "events not sorted by ts"
        last_ts = ev["ts"]
        ph = ev["ph"]
        if ph == "X":
            assert ev.get("dur", -1) >= 0
        elif ph == "B":
            begin_stacks.setdefault((ev["pid"], ev["tid"]), []).append(
                ev["name"])
        elif ph == "E":
            stack = begin_stacks.get((ev["pid"], ev["tid"]), [])
            assert stack and stack.pop() == ev["name"], "unmatched E event"
        else:
            assert ph in ("i", "I", "M"), f"unexpected phase {ph!r}"
    assert all(not s for s in begin_stacks.values()), "unmatched B events"
    return events


def _load(path):
    with open(path) as f:
        return json.load(f)


def _request_span(doc, uid):
    spans = [e for e in doc["traceEvents"]
             if e["ph"] == "X" and e["name"] == f"request/{uid}"]
    assert spans, f"no request/{uid} span in trace"
    return spans[-1]


# --------------------------------------------------------------------- #
# ring buffer / core recording
# --------------------------------------------------------------------- #
class TestRingBuffer:
    def test_eviction_order_and_drop_counter(self):
        tr = telemetry.configure_tracing(enabled=True, capacity=4)
        for i in range(6):
            with tr.span(f"s{i}"):
                pass
        names = [e["name"] for e in tr.export_chrome()["traceEvents"]]
        assert names == ["s2", "s3", "s4", "s5"]   # oldest evicted first
        assert telemetry.counter("trace_events_dropped_total").value() == 2

    def test_nested_spans_share_trace_and_link_parent(self):
        tr = telemetry.configure_tracing(enabled=True)
        with tr.span("outer"):
            with tr.span("inner"):
                tr.event("marker", k=1)
        events = validate_chrome(tr.export_chrome())
        by_name = {e["name"]: e for e in events}
        outer, inner = by_name["outer"], by_name["inner"]
        assert inner["args"]["trace_id"] == outer["args"]["trace_id"]
        assert inner["args"]["parent_span_id"] \
            and "parent_span_id" not in outer["args"]
        assert by_name["marker"]["ph"] == "i"

    def test_open_request_span_exports_in_flight(self):
        tr = telemetry.configure_tracing(enabled=True)
        tr.request_begin(9, prompt_len=3)
        span = _request_span(tr.export_chrome(), 9)
        assert span["args"]["in_flight"] is True
        tr.request_end(9, "completed")
        span = _request_span(tr.export_chrome(), 9)
        assert "in_flight" not in span["args"]
        assert span["args"]["state"] == "completed"

    def test_sample_rate_zero_records_nothing(self):
        tr = telemetry.configure_tracing(enabled=True, sample_rate=0.0)
        with tr.span("root"):
            with tr.span("child"):    # child of unsampled root: silent too
                tr.event("pt")
        tr.request_begin(1)
        tr.request_end(1, "completed")
        assert tr.export_chrome()["traceEvents"] == []

    def test_wall_clock_anchor_makes_real_timestamps(self):
        tr = telemetry.configure_tracing(enabled=True)
        with tr.span("s"):
            pass
        ev = tr.export_chrome()["traceEvents"][0]
        # dslint: disable-next-line or direct compare: ts is wall-clock µs
        assert abs(ev["ts"] / 1e6
                   - tr._anchor_wall) < 60.0   # within a minute of anchor

    def test_phase_stats_quantiles(self):
        tr = telemetry.configure_tracing(enabled=True)
        for dur in (0.001, 0.002, 0.003):
            tr.record_span("phase_a", dur)
        stats = tr.phase_stats()
        a = stats["phase_a"]
        assert a["count"] == 3
        assert a["p50_s"] <= a["p95_s"] <= a["p99_s"]
        assert abs(a["total_s"] - 0.006) < 1e-6

    def test_disabled_tracer_overhead_guard(self):
        tr = Tracer(enabled=False)
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("hot"):
                pass
            tr.event("e")
            tr.request_event(1, "x")
        dt = time.perf_counter() - t0
        # generous CI bound: a disabled site must stay an attribute check
        # (measured ~0.1 µs/iteration; the guard trips at 25 µs)
        assert dt < n * 25e-6, f"disabled tracer cost {dt / n * 1e6:.1f}us/call"
        assert tr.flight_status()["buffered_events"] == 0

    def test_telemetry_span_feeds_tracer_when_enabled(self):
        telemetry.configure_tracing(enabled=True)
        with telemetry.span("piggyback"):
            pass
        names = [e["name"] for e in
                 telemetry.get_tracer().export_chrome()["traceEvents"]]
        assert "piggyback" in names
        # and the histogram side is unchanged
        assert telemetry.get_registry().get("span_seconds") is not None


# --------------------------------------------------------------------- #
# config plumbing
# --------------------------------------------------------------------- #
class TestConfig:
    def test_telemetry_section_keys_parse(self):
        cfg = load_config({"telemetry": {
            "tracing": True, "trace_buffer_events": 128,
            "trace_sample_rate": 0.5, "flight_dump_dir": "/tmp/x"}})
        assert cfg.telemetry.tracing is True
        assert cfg.telemetry.trace_buffer_events == 128

    def test_telemetry_section_validates(self):
        with pytest.raises(DeepSpeedConfigError):
            load_config({"telemetry": {"trace_sample_rate": 1.5}})
        with pytest.raises(DeepSpeedConfigError):
            load_config({"telemetry": {"trace_buffer_events": 0}})

    def test_on_stall_accepts_dump_trace(self):
        cfg = load_config({"fault_tolerance": {"on_stall": "dump_trace"}})
        assert cfg.fault_tolerance.on_stall == "dump_trace"
        with pytest.raises(DeepSpeedConfigError):
            load_config({"fault_tolerance": {"on_stall": "page_oncall"}})


# --------------------------------------------------------------------- #
# serving request traces (completed / shed / expired / poisoned /
# rejected — chaos forces the failure-shaped paths)
# --------------------------------------------------------------------- #
FG_CFG = dict(hidden_size=64, num_layers=2, num_heads=4, max_seq_len=128,
              vocab_size=512, dtype="float32")


def _engine(**kw):
    from deepspeed_tpu.inference.fastgen import FastGenEngine

    base = dict(n_blocks=16, block_size=16, max_blocks_per_seq=8,
                token_budget=32, temperature=0.0, seed=0)
    base.update(kw)
    return FastGenEngine("tiny", **base, **FG_CFG)


def _front(engine=None, **over):
    from deepspeed_tpu.serving import ServingFrontend

    cfg = dict(max_queue=4, default_max_new_tokens=4,
               circuit_failure_threshold=2, circuit_backoff_s=0.05,
               circuit_backoff_max_s=1.0)
    cfg.update(over)
    return ServingFrontend(engine if engine is not None else _engine(),
                           config=cfg)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


class TestRequestTraces:
    def test_completed_request_has_full_timeline(self):
        tr = telemetry.configure_tracing(enabled=True)
        fe = _front()
        assert fe.submit(1, _prompt(5)).__class__.__name__ == "Admitted"
        fe.run_until_drained()
        fe.close()
        doc = tr.export_chrome()
        validate_chrome(doc)
        span = _request_span(doc, 1)
        assert span["args"]["state"] == "completed"
        assert span["args"]["tokens"] == 4
        insts = [e for e in doc["traceEvents"]
                 if e["ph"] == "i" and e["tid"] == span["tid"]]
        assert any(e["name"] == "admission"
                   and e["args"]["verdict"] == "admitted" for e in insts)
        assert any(e["name"] == "first_service"
                   and e["args"]["queue_wait_s"] >= 0 for e in insts)
        # the ticks that served it are on the timeline too
        assert any(e["name"] == "serving_tick"
                   for e in doc["traceEvents"] if e["ph"] == "X")

    def test_shed_and_overloaded_verdicts_traced(self):
        tr = telemetry.configure_tracing(enabled=True)
        fe = _front(max_queue=2, shed_policy="reject_oldest")
        fe.submit(1, _prompt(5))
        fe.submit(2, _prompt(5, seed=1))
        fe.submit(3, _prompt(5, seed=2))   # sheds uid 1 (oldest)
        doc = tr.export_chrome()
        validate_chrome(doc)
        shed = _request_span(doc, 1)
        assert shed["args"]["state"] == "shed"
        assert shed["args"]["reason"] == "queue_full"
        # reject_newest policy: the incoming uid itself is turned away
        fe2 = _front(max_queue=1, shed_policy="reject_newest")
        fe2.submit(10, _prompt(5))
        fe2.submit(11, _prompt(5, seed=3))
        doc = tr.export_chrome()
        rej = _request_span(doc, 11)
        assert rej["args"]["state"] == "rejected"
        assert rej["args"]["reason"] == "queue_full"
        insts = [e for e in doc["traceEvents"] if e["ph"] == "i"
                 and e["tid"] == rej["tid"] and e["name"] == "admission"]
        assert insts and insts[-1]["args"]["verdict"] == "overloaded"
        assert insts[-1]["args"]["retry_after_s"] >= 0
        fe.close()
        fe2.close()

    def test_invalid_request_traced_as_rejected(self):
        tr = telemetry.configure_tracing(enabled=True)
        fe = _front()
        fe.submit(5, [])    # empty prompt
        span = _request_span(tr.export_chrome(), 5)
        assert span["args"]["state"] == "rejected"
        assert span["args"]["reason"] == "invalid"
        fe.close()

    def test_expired_request_traced(self):
        tr = telemetry.configure_tracing(enabled=True)
        fe = _front()
        fe.submit(7, _prompt(5), deadline_s=0.01)
        time.sleep(0.05)
        fe.run_tick()
        span = _request_span(tr.export_chrome(), 7)
        assert span["args"]["state"] == "expired"
        assert span["args"]["reason"] == "deadline"
        fe.close()

    def test_poisoned_request_traced_via_chaos(self):
        tr = telemetry.configure_tracing(enabled=True)
        fe = _front()
        fe.submit(8, _prompt(5))
        chaos.arm("serving/tick=fail:1")
        fe.run_tick()    # fails; newest suspect evicted as poisoned
        span = _request_span(tr.export_chrome(), 8)
        assert span["args"]["state"] == "failed"
        assert span["args"]["reason"] == "poisoned"
        # the tick failure itself is on the timeline
        fails = [e for e in tr.export_chrome()["traceEvents"]
                 if e["name"] == "tick_failure"]
        assert fails and fails[0]["args"]["error"] == "ChaosError"
        fe.close()

    def test_duplicate_submit_does_not_clobber_live_trace(self):
        tr = telemetry.configure_tracing(enabled=True)
        fe = _front()
        fe.submit(3, _prompt(5))
        fe.submit(3, _prompt(5))    # duplicate: rejected, uid still live
        doc = tr.export_chrome()
        span = _request_span(doc, 3)
        assert span["args"]["in_flight"] is True   # live trace survived
        insts = [e for e in doc["traceEvents"] if e["ph"] == "i"
                 and e["tid"] == span["tid"] and e["name"] == "admission"]
        verdicts = [e["args"]["verdict"] for e in insts]
        assert verdicts.count("admitted") == 1
        assert "rejected" in verdicts   # the duplicate's verdict, as event
        fe.close()


# --------------------------------------------------------------------- #
# flight dumps: circuit open (chaos-forced) + serving endpoints
# --------------------------------------------------------------------- #
class TestFlightRecorder:
    def test_chaos_forced_circuit_open_dumps_request_context(self, tmp_path):
        tr = telemetry.configure_tracing(enabled=True,
                                         dump_dir=str(tmp_path))
        fe = _front()   # failure_threshold=2
        fe.submit(1, _prompt(5))
        fe.run_tick()              # healthy tick: span history to dump
        chaos.arm("serving/tick=fail:4")
        fe.run_tick()
        fe.run_tick()              # second consecutive failure → OPEN
        from deepspeed_tpu.serving import OPEN
        assert fe.breaker.state == OPEN
        dumps = [p for p in tmp_path.iterdir()
                 if p.name.startswith("flight_circuit_open")]
        assert len(dumps) == 1
        doc = _load(dumps[0])
        validate_chrome(doc)
        assert doc["otherData"]["reason"] == "circuit_open"
        assert "failure_streak=2" in doc["otherData"]["note"]
        # the dump contains the request + tick spans leading up to it
        names = [e["name"] for e in doc["traceEvents"]]
        assert "request/1" in names
        assert "serving_tick" in names and "schedule_tick" in names
        assert telemetry.counter("flight_recorder_dumps_total").value(
            reason="circuit_open") == 1
        fe.close()

    def test_dump_retention_prunes_oldest(self, tmp_path):
        tr = telemetry.configure_tracing(enabled=True,
                                         dump_dir=str(tmp_path),
                                         keep_dumps=3)
        with tr.span("s"):
            pass
        paths = [tr.dump_flight("manual") for _ in range(5)]
        assert all(p is not None for p in paths)
        import os

        left = sorted(p.name for p in tmp_path.iterdir())
        # the newest three survive (a sick replica dumping once per
        # backoff window forever must not fill the disk)
        assert left == [f"flight_manual_{os.getpid()}_{i}.json"
                        for i in (3, 4, 5)]

    def test_dump_never_raises_from_failure_handlers(self, tmp_path):
        tr = telemetry.configure_tracing(enabled=True,
                                         dump_dir=str(tmp_path))
        # non-JSON-serializable span attr: the dump degrades it to str()
        # instead of raising into the circuit/SIGTERM handler calling it
        with tr.span("odd", blob=object()):
            pass
        path = tr.dump_flight("manual")
        assert path is not None
        validate_chrome(_load(path))
        # unwritable dump dir: logged, swallowed, None returned
        tr.dump_dir = str(tmp_path / "nope" / "\0bad")
        assert tr.dump_flight("manual") is None

    def test_dump_disabled_tracer_is_noop(self, tmp_path):
        tr = telemetry.get_tracer()    # reset() left it disabled
        assert tr.dump_flight("whatever") is None
        assert list(tmp_path.iterdir()) == []

    def test_trace_and_flight_endpoints_scrape(self):
        tr = telemetry.configure_tracing(enabled=True)
        with tr.span("visible"):
            pass
        srv = telemetry.start_metrics_server(0)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            with urllib.request.urlopen(base + "/trace", timeout=5) as r:
                doc = json.loads(r.read())
            events = validate_chrome(doc)
            assert any(e["name"] == "visible" for e in events)
            with urllib.request.urlopen(base + "/flight", timeout=5) as r:
                status = json.loads(r.read())
            assert status["enabled"] is True
            assert status["buffered_events"] >= 1
            assert status["dumps_written"] == 0
            assert {"capacity", "dump_dir", "sample_rate",
                    "open_requests"} <= set(status)
        finally:
            telemetry.stop_metrics_server()

    def test_trace_dump_cli_summary(self, tmp_path, capsys):
        tr = telemetry.configure_tracing(enabled=True,
                                         dump_dir=str(tmp_path))
        with tr.span("slow_phase"):
            time.sleep(0.01)
        tr.request_begin(4)
        tr.request_end(4, "completed")
        path = tr.dump_flight("manual", note="cli-test")
        assert trace_dump_main([path]) == 0
        out = capsys.readouterr().out
        assert "slow_phase" in out and "request/4" in out
        assert "dump reason: manual" in out
        assert trace_dump_main([str(tmp_path / "missing.json")]) == 2
        assert trace_dump_main([path, "--top"]) == 2        # value missing
        assert trace_dump_main([path, "--top", "ten"]) == 2  # not an int
        assert trace_dump_main([path, "--top", "2"]) == 0

    def test_compile_account_records_trace_events(self):
        """What ``flops_profiler``'s own compile log held (PR 51 took it
        out) is in the account of every compile of the process: the
        profiled function under its own label in
        ``xla_program_seconds_total`` and, a compile, as an ``xla_compile``
        span in the flight recorder."""
        import jax.numpy as jnp

        from deepspeed_tpu.profiling import flops_profiler as fp
        from deepspeed_tpu.telemetry import host

        tr = telemetry.configure_tracing(enabled=True)
        telemetry.install_compile_account()
        monkey = pytest.MonkeyPatch()
        monkey.setattr(host, "SMALL_PROGRAM_S", 0.0)   # 'double' is small
        try:
            def double(x):
                return x * 2.0

            out = fp.profile_fn(double, jnp.ones((8,)))
        finally:
            monkey.undo()
        assert out["flops"] >= 0
        seconds = telemetry.get_registry().get("xla_program_seconds_total")
        assert seconds.value(program="double", phase="compile") > 0
        assert seconds.value(program="double", phase="lower") > 0
        telemetry.refresh_host_counters()
        spans = [e for e in tr.export_chrome()["traceEvents"]
                 if e["name"] == "xla_compile"]
        assert any(e["args"].get("program") == "double" and
                   e["args"].get("phase") == "compile" for e in spans)


# --------------------------------------------------------------------- #
# training engine: chaos-forced step exception, forced stall escalation,
# preemption exit — each leaves a validating dump with step spans
# --------------------------------------------------------------------- #
class TestEngineFlightDumps:
    def test_stall_step_exception_and_preemption_dumps(self, tmp_path):
        from deepspeed_tpu.runtime.dataloader import synthetic_lm_data
        import itertools

        spec = dst.causal_lm_spec("tiny", dtype="float32", num_layers=2,
                                  max_seq_len=64)
        config = {"train_batch_size": 8,
                  "train_micro_batch_size_per_gpu": 1,
                  "gradient_accumulation_steps": 1,
                  "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                  "telemetry": {"stall_deadline_s": 300.0, "tracing": True,
                                "flight_dump_dir": str(tmp_path)},
                  "fault_tolerance": {"on_stall": "dump_trace"}}
        engine, *_ = dst.initialize(model=spec, config=config)
        try:
            data = itertools.cycle(synthetic_lm_data(8, 64, 512, seed=0))
            for _ in range(2):
                engine.train_batch(data)

            # 1) chaos-forced unhandled step exception → crash-context dump
            chaos.arm("train/step=fail:1")
            with pytest.raises(chaos.ChaosError):
                engine.train_batch(data)
            chaos.disarm()
            dumps = [p for p in tmp_path.iterdir()
                     if p.name.startswith("flight_engine_step_exception")]
            assert len(dumps) == 1
            doc = _load(dumps[0])
            validate_chrome(doc)
            # the step spans leading up to the crash are in the dump
            steps = [e for e in doc["traceEvents"]
                     if e["name"] == "train_step"]
            assert len(steps) >= 2
            assert doc["otherData"]["note"] == "step=2"

            # 2) forced stall → on_stall="dump_trace" escalation dumps,
            # naming the last completed span
            assert engine._watchdog.check(
                now=time.monotonic() + 400.0) is True
            dumps = [p for p in tmp_path.iterdir()
                     if p.name.startswith("flight_stall")]
            assert len(dumps) == 1
            doc = _load(dumps[0])
            validate_chrome(doc)
            assert doc["otherData"]["reason"] == "stall"
            assert doc["otherData"]["note"] == "train_step"

            # 3) preemption exit → dump rides along with the emergency path
            with pytest.raises(SystemExit):
                engine._preemption_exit()
            dumps = [p for p in tmp_path.iterdir()
                     if p.name.startswith("flight_preemption")]
            assert len(dumps) == 1
            validate_chrome(_load(dumps[0]))
            assert telemetry.counter("flight_recorder_dumps_total").value(
                reason="stall") == 1
        finally:
            engine.shutdown_telemetry()
