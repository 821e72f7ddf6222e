"""Serving resilience layer: admission control, load shedding, circuit
breaking, health surfaces (``deepspeed_tpu/serving``).

The headline properties proven here:

* a 10× queue-capacity burst sheds cleanly — zero crashes, zero leaked
  KV blocks, every request terminally resolved with a structured reason,
  ``/readyz`` flipping unready → ready within the test;
* an armed ``serving/tick`` fault point opens the circuit after the
  configured threshold, ``/readyz`` reports unready while open, and
  half-open probing restores service once the fault drains.

All on the CPU backend with a tiny model — tier-1 eligible; the burst
tests carry the ``overload`` marker's SIGALRM per-test timeout so a hung
tick fails fast.
"""
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.fastgen import FastGenEngine
from deepspeed_tpu.runtime.config import load_config
from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from deepspeed_tpu.serving import (
    CLOSED,
    OPEN,
    Admitted,
    Overloaded,
    Rejected,
    ServingFrontend,
)
from deepspeed_tpu.testing import chaos

CFG = dict(hidden_size=64, num_layers=2, num_heads=4, max_seq_len=128,
           vocab_size=512, dtype="float32")

#: fast-drain serving defaults for a tiny CPU engine
SCFG = dict(max_queue=4, default_max_new_tokens=4,
            circuit_failure_threshold=2, circuit_backoff_s=0.05,
            circuit_backoff_max_s=1.0)


@pytest.fixture(autouse=True)
def _clean():
    telemetry.reset()
    chaos.disarm()
    yield
    chaos.disarm()
    telemetry.reset()


def _engine(**kw):
    base = dict(n_blocks=16, block_size=16, max_blocks_per_seq=8,
                token_budget=32, temperature=0.0, seed=0)
    base.update(kw)
    return FastGenEngine("tiny", **base, **CFG)


def _front(engine=None, **over):
    cfg = dict(SCFG)
    cfg.update(over)
    return ServingFrontend(engine if engine is not None else _engine(),
                           config=cfg)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


# --------------------------------------------------------------------- #
# bounded admission + shedding policies
# --------------------------------------------------------------------- #
class TestAdmission:
    def test_queue_cap_overloaded_with_retry_hint(self):
        fe = _front(max_queue=2)
        assert isinstance(fe.submit(1, _prompt(8)), Admitted)
        assert isinstance(fe.submit(2, _prompt(8)), Admitted)
        res = fe.submit(3, _prompt(8))
        assert isinstance(res, Overloaded)
        assert res.reason == "queue_full"
        assert res.retry_after_s > 0
        # structured terminal record, queryable like any other outcome
        assert fe.result(3).state == "rejected"
        assert fe.result(3).reason == "queue_full"
        assert telemetry.counter("serving_rejected_total").value(
            reason="queue_full") >= 1
        fe.close()

    def test_invalid_requests_rejected_not_raised(self):
        fe = _front()
        assert isinstance(fe.submit(1, _prompt(8)), Admitted)
        dup = fe.submit(1, _prompt(8))
        assert isinstance(dup, Rejected) and dup.reason == "invalid"
        # the duplicate must NOT clobber the live request's tracking
        assert fe.active_uids() == [1]
        assert fe.result(1).state == "active"
        long = fe.submit(2, _prompt(500))
        assert isinstance(long, Rejected) and "max_len" in long.detail
        empty = fe.submit(3, [])
        assert isinstance(empty, Rejected)
        # the engine never partially admitted any of them
        assert set(fe.engine.seqs) == {1}
        # ... and the original request still completes normally
        fe.run_until_drained(100)
        assert fe.result(1).state == "completed"
        fe.close()

    def test_reject_oldest_sheds_oldest(self):
        fe = _front(max_queue=2, shed_policy="reject_oldest")
        fe.submit(1, _prompt(8))
        fe.submit(2, _prompt(8))
        res = fe.submit(3, _prompt(8))
        assert isinstance(res, Admitted)
        assert fe.result(1).state == "shed"
        assert fe.result(1).reason == "queue_full"
        assert sorted(fe.active_uids()) == [2, 3]
        assert 1 not in fe.engine.seqs   # blocks/bookkeeping released
        assert telemetry.counter("serving_shed_total").value(
            policy="reject_oldest") == 1
        fe.close()

    def test_deadline_aware_sheds_least_likely(self):
        fe = _front(max_queue=2, shed_policy="deadline_aware")
        fe.submit(1, _prompt(8), deadline_s=100.0)   # comfortable
        fe.submit(2, _prompt(8), deadline_s=0.01)    # hopeless
        res = fe.submit(3, _prompt(8), deadline_s=50.0)
        assert isinstance(res, Admitted)
        assert fe.result(2).state == "shed"
        assert sorted(fe.active_uids()) == [1, 3]
        fe.close()

    def test_deadline_aware_rejects_incoming_when_it_is_most_doomed(self):
        fe = _front(max_queue=2, shed_policy="deadline_aware")
        fe.submit(1, _prompt(8), deadline_s=100.0)
        fe.submit(2, _prompt(8), deadline_s=100.0)
        res = fe.submit(3, _prompt(8), deadline_s=0.001)
        assert isinstance(res, Overloaded)
        assert sorted(fe.active_uids()) == [1, 2]
        fe.close()

    def test_deadline_aware_without_deadlines_rejects_newest(self):
        fe = _front(max_queue=2, shed_policy="deadline_aware")
        fe.submit(1, _prompt(8))
        fe.submit(2, _prompt(8))
        res = fe.submit(3, _prompt(8))
        assert isinstance(res, Overloaded) and res.reason == "queue_full"
        assert sorted(fe.active_uids()) == [1, 2]
        fe.close()


class TestDegradation:
    def test_kv_pressure_clamps_grant_then_sheds(self):
        # cap = 15 usable blocks; degrade past ~4.5 blocks PROJECTED,
        # overload past ~9
        fe = _front(engine=_engine(n_blocks=16),
                    kv_degrade_watermark=0.3, kv_high_watermark=0.6,
                    degraded_max_new_tokens=2, max_queue=8)
        a = fe.submit(1, _prompt(48), max_new_tokens=64)   # projects 4/15
        assert isinstance(a, Admitted) and not a.degraded
        for _ in range(3):
            fe.run_tick()          # prefill allocates the blocks
        assert fe._kv_util() >= 0.25
        b = fe.submit(2, _prompt(8), max_new_tokens=64)   # projects 5/15
        assert isinstance(b, Admitted)
        assert b.degraded and b.max_new_tokens == 2
        assert telemetry.counter("serving_degraded_total").value() == 1
        # projected past the high watermark: overloaded, not admitted
        c = fe.submit(3, _prompt(100), max_new_tokens=4)   # 7 more blocks
        assert isinstance(c, Overloaded) and c.reason == "kv_pressure"
        fe.run_until_drained(200)
        # the degraded request really was clamped
        assert fe.result(2).state == "completed"
        assert len(fe.result(2).tokens) == 2
        fe.close()


# --------------------------------------------------------------------- #
# circuit breaker + poison isolation
# --------------------------------------------------------------------- #
class TestCircuitBreaker:
    def test_opens_rejects_and_recovers_via_half_open_probe(self):
        fe = _front()
        fe.submit(1, _prompt(8), max_new_tokens=2)
        assert fe.run_tick()                    # healthy tick (suspects clear)
        chaos.arm("serving/tick=fail:3")
        assert not fe.run_tick()                # failure 1
        assert fe.breaker.state == CLOSED
        assert not fe.run_tick()                # failure 2 -> threshold
        assert fe.breaker.state == OPEN
        assert not fe.health.readiness()[0]
        assert telemetry.gauge("serving_circuit_state").value() == 2
        # open circuit: admissions reject fast with the probe window hint
        res = fe.submit(9, _prompt(8))
        assert isinstance(res, Overloaded) and res.reason == "circuit_open"
        assert res.retry_after_s >= 0
        # inside the backoff window ticks don't even reach the engine
        assert not fe.run_tick()
        assert chaos._armed.hits("serving/tick") == 2
        time.sleep(0.06)
        assert not fe.run_tick()                # half-open probe fails (hit 3)
        assert fe.breaker.state == OPEN         # re-opened, doubled backoff
        time.sleep(0.12)
        assert fe.run_tick()                    # probe passes (fault drained)
        assert fe.breaker.state == CLOSED
        assert fe.health.readiness()[0]
        # service resumed: the queued request still completes
        fe.run_until_drained(100)
        assert fe.result(1).state == "completed"
        assert telemetry.counter(
            "serving_circuit_transitions_total").value(to="open") == 2
        fe.close()

    def test_open_circuit_recovers_via_submit_with_empty_queue(self):
        """With no active requests nothing calls run_tick (the documented
        drive loops stop at zero), so once the backoff window expires a
        submit must be ADMITTED as the probe vehicle — otherwise the
        replica is bricked until restart. The probe's failure must not
        scapegoat that request either."""
        fe = _front()                           # threshold 2, backoff 0.05
        chaos.arm("serving/tick=fail:3")
        fe.submit(1, _prompt(8), max_new_tokens=2)
        fe.run_tick()                           # fail 1 -> evicts suspect 1
        assert fe.result(1).state == "failed"
        fe.submit(2, _prompt(8), max_new_tokens=2)
        fe.run_tick()                           # fail 2 -> evict + OPEN
        assert fe.breaker.state == OPEN and fe.active_count() == 0
        # inside the window: still rejected fast
        res = fe.submit(3, _prompt(8))
        assert isinstance(res, Overloaded) and res.reason == "circuit_open"
        time.sleep(0.06)                        # window expires, queue empty
        adm = fe.submit(4, _prompt(8), max_new_tokens=2)
        assert isinstance(adm, Admitted)        # probe vehicle admitted
        fe.run_tick()                           # half-open probe fails (hit 3)
        assert fe.breaker.state == OPEN
        assert 4 in fe._reqs, "probe vehicle must not be scapegoated"
        time.sleep(0.12)                        # doubled window expires
        fe.run_tick()                           # probe passes -> CLOSED
        assert fe.breaker.state == CLOSED
        fe.run_until_drained(100)
        assert fe.result(4).state == "completed"
        fe.close()

    def test_poisoned_request_evicted_loop_survives(self):
        fe = _front(circuit_failure_threshold=5)
        fe.submit(1, _prompt(8), max_new_tokens=3)
        assert fe.run_tick()                    # uid 1 is a cleared suspect
        fe.submit(2, _prompt(8))                # the "poisoned" arrival
        chaos.arm("serving/tick=fail:1")
        assert not fe.run_tick()                # fails once -> evict suspect 2
        assert fe.result(2).state == "failed"
        assert fe.result(2).reason == "poisoned"
        assert 2 not in fe.engine.seqs
        assert telemetry.counter(
            "serving_poison_evictions_total").value() == 1
        # loop recovers without the circuit ever opening
        assert fe.breaker.state == CLOSED
        fe.run_until_drained(100)
        assert fe.result(1).state == "completed"
        fe.close()

    def test_tick_failure_rolls_back_engine_state(self):
        """A failing tick must leave engine host bookkeeping exactly as it
        was — retrying after the fault drains produces the same stream a
        never-faulted engine produces."""
        ref = _engine()
        ref.put([1], [_prompt(12)])
        want = []
        for _ in range(6):
            want.append(dict(ref.step()))

        eng = _engine()
        eng.put([1], [_prompt(12)])
        got = []
        chaos.arm("serving/tick=fail:2")
        for _ in range(10):
            try:
                chaos.chaos_point("serving/tick")
            except chaos.ChaosError:
                continue
            got.append(dict(eng.step()))
            if len(got) == 6:
                break
        assert got == want
        # retry AFTER scheduling state was built: inject inside step()
        eng2 = _engine()
        eng2.put([2], [_prompt(20)])
        free0 = eng2.allocator.free_blocks
        orig = eng2._step_impl

        def boom():
            raise RuntimeError("device fell over")

        eng2._step_impl = boom
        pre = (eng2.seqs[2].prefilled, eng2.seqs[2].pos)
        with pytest.raises(RuntimeError):
            eng2.step()
        assert (eng2.seqs[2].prefilled, eng2.seqs[2].pos) == pre
        assert eng2.allocator.free_blocks == free0
        eng2._step_impl = orig
        out = eng2.step()             # clean retry proceeds normally
        assert eng2.seqs[2].prefilled > 0 or out

    @staticmethod
    def _host_state(eng):
        """Everything a tick may change on the host: the rows' arrays (the
        table matrix among them), the allocator's lists in order, the
        rotation, and what lives on the descriptors."""
        rows = eng._rows
        return ({name: getattr(rows, name)[:rows.hi].copy()
                 for name in rows.TICK_FIELDS},
                eng.allocator.snapshot(), eng._decode_rr,
                {u: (s.done, list(s.generated), s.pos, s.prefilled,
                     s.last_tok, s.blocks, s.table.copy().tolist())
                 for u, s in eng.seqs.items()})

    @staticmethod
    def _assert_same_state(got, want):
        for name, arr in want[0].items():
            np.testing.assert_array_equal(got[0][name], arr, err_msg=name)
        assert got[1:] == want[1:]

    def _mid_history(self, **kw):
        """An engine whose next tick holds a decode row that has to grow
        a block and a chunk that ends in mid-prompt."""
        eng = _engine(**kw)
        eng.put([2], [_prompt(15)])
        eng.step()
        eng.step()                    # position 16: the second block next
        eng.put([3], [_prompt(40, seed=1)])
        assert eng.seqs[2].pos == 16 and eng.seqs[2].held == 1
        return eng

    def test_a_failed_tick_leaves_every_row_as_it_was(self):
        """The schedule has grown a decode row's table and advanced a
        chunk when the program raises: every array row, the table matrix
        and the allocator are what they were, and the retry is the tick a
        never-faulted engine runs."""
        ref = self._mid_history()
        want = dict(ref.step())
        assert ref.seqs[2].held == 2 and 0 < ref.seqs[3].prefilled < 40

        eng = self._mid_history()
        before = self._host_state(eng)
        good = eng._ticks

        class Boom(dict):
            def __getitem__(self, key):
                raise RuntimeError("injected")

        eng._ticks = Boom(good)
        with pytest.raises(RuntimeError, match="injected"):
            eng.step()
        eng._ticks = good
        self._assert_same_state(self._host_state(eng), before)
        assert eng.allocator._journal is None
        assert dict(eng.step()) == want
        self._assert_same_state(self._host_state(eng),
                                self._host_state(ref))

    def test_a_tick_that_fails_in_its_commit_is_undone(self, monkeypatch):
        """The last statement of ``tick_commit`` raises: by then the tick
        has kept its tokens, a sequence has ended on its end-of-sequence
        token and its blocks are back on the free list. All of it is
        undone, the free list's order too."""
        from deepspeed_tpu.inference import fastgen

        eos = self._mid_history().step()[2]
        ref, eng = self._mid_history(), self._mid_history()
        ref.eos_token_id = eng.eos_token_id = eos
        want = dict(ref.step())
        assert ref.seqs[2].done and not ref.seqs[2].blocks
        before = self._host_state(eng)
        calls = []

        def refresh(**kw):
            calls.append(kw)
            if len(calls) == 1:
                raise KeyboardInterrupt

        monkeypatch.setattr(fastgen.telemetry, "refresh_host_counters",
                            refresh)
        with pytest.raises(KeyboardInterrupt):
            eng.step()
        self._assert_same_state(self._host_state(eng), before)
        assert dict(eng.step()) == want
        self._assert_same_state(self._host_state(eng),
                                self._host_state(ref))


# --------------------------------------------------------------------- #
# health surfaces
# --------------------------------------------------------------------- #
def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


class TestHealthSurfaces:
    def test_healthz_readyz_over_http(self):
        srv = telemetry.start_metrics_server(0)
        base = f"http://127.0.0.1:{srv.port}"
        fe = _front()
        code, body = _get(base + "/healthz")
        assert code == 200 and body["status"] == "ok"
        assert body["checks"]["serving"]["ok"]
        code, body = _get(base + "/readyz")
        assert code == 200

        # open the circuit -> /readyz drains, /healthz stays alive
        for _ in range(fe.cfg.circuit_failure_threshold):
            fe.breaker.record_failure()
        code, body = _get(base + "/readyz")
        assert code == 503 and body["status"] == "unavailable"
        assert body["checks"]["serving"]["circuit"] == "open"
        code, _ = _get(base + "/healthz")
        assert code == 200

        # stale tick heartbeat WITH work pending -> liveness fails (the
        # restart-me signal); circuit-open submits are rejected, so plant
        # the pending work directly
        fe.breaker.record_success()
        fe.submit(1, _prompt(8))
        fe.last_tick_t = fe.clock() - 10 * fe.cfg.heartbeat_timeout_s
        code, body = _get(base + "/healthz")
        assert code == 503
        assert body["checks"]["serving"]["last_tick_age_s"] > \
            fe.cfg.heartbeat_timeout_s
        # ...but the SAME stale heartbeat with an empty queue is just an
        # idle replica: a traffic pause must not restart healthy pods
        fe.run_until_drained(100)
        fe.last_tick_t = fe.clock() - 10 * fe.cfg.heartbeat_timeout_s
        code, body = _get(base + "/healthz")
        assert code == 200 and "idle" in body["checks"]["serving"]["note"]

        # closing the frontend unregisters its probes: endpoints are 200
        # again (a bare metrics process claims nothing)
        fe.close()
        assert _get(base + "/healthz")[0] == 200
        assert _get(base + "/readyz")[0] == 200

    def test_full_queue_flips_readiness(self):
        fe = _front(max_queue=2)
        assert fe.health.readiness()[0]
        fe.submit(1, _prompt(8))
        fe.submit(2, _prompt(8))
        ok, detail = fe.health.readiness()
        assert not ok and detail["queue"] == 2
        fe.run_until_drained(100)
        assert fe.health.readiness()[0]
        fe.close()


# --------------------------------------------------------------------- #
# overload bursts (the acceptance-criteria chaos tests)
# --------------------------------------------------------------------- #
TERMINAL = {"completed", "shed", "expired", "failed", "rejected"}


@pytest.mark.overload
def test_overload_burst_sheds_cleanly_no_kv_leak():
    """10x queue-capacity burst: no crash, every request terminally
    resolved with a structured reason, zero leaked KV blocks, readiness
    unready -> ready within the test."""
    eng = _engine(n_blocks=32)
    free0 = eng.allocator.free_blocks
    fe = _front(engine=eng, max_queue=4, shed_policy="reject_oldest",
                default_max_new_tokens=3)
    gen = chaos.OverloadGenerator(vocab_size=512, prompt_len=(4, 20), seed=0)
    reqs = gen.burst(40)                       # 10x max_queue
    unready_seen = False
    for i, (uid, prompt) in enumerate(reqs):
        res = fe.submit(uid, prompt)
        assert isinstance(res, (Admitted, Overloaded))
        if not fe.health.readiness()[0]:
            unready_seen = True
        if i % 8 == 7:
            fe.run_tick()                      # some service amid the storm
    assert unready_seen, "a 10x burst must flip readiness at some point"
    fe.run_until_drained(2000)
    assert fe.health.readiness()[0], "drained replica must be ready again"
    outcomes = {}
    for uid, _ in reqs:
        r = fe.result(uid)
        assert r.state in TERMINAL, (uid, r)
        assert r.state == "completed" or r.reason, r
        outcomes[r.state] = outcomes.get(r.state, 0) + 1
    assert outcomes.get("completed", 0) >= 4   # the survivors were served
    assert outcomes.get("shed", 0) >= 20       # reject_oldest shed the rest
    assert not eng.seqs and not fe.active_count()
    assert eng.allocator.free_blocks == free0, "leaked KV blocks"
    fe.close()


@pytest.mark.overload
def test_overload_burst_reject_newest_and_repeated_waves():
    """reject_newest: overflow is turned away with retry hints; repeated
    burst waves (burst -> partial drain -> burst) never leak blocks."""
    eng = _engine(n_blocks=32)
    free0 = eng.allocator.free_blocks
    fe = _front(engine=eng, max_queue=4, shed_policy="reject_newest",
                default_max_new_tokens=3)
    gen = chaos.OverloadGenerator(seed=1)
    all_uids = []
    for _wave in range(4):
        for uid, prompt in gen.burst(12):
            all_uids.append(uid)
            res = fe.submit(uid, prompt)
            if isinstance(res, Overloaded):
                assert res.reason in ("queue_full", "kv_pressure")
                assert res.retry_after_s > 0
        for _ in range(6):                     # partial drain between waves
            fe.run_tick()
    fe.run_until_drained(2000)
    for uid in all_uids:
        assert fe.result(uid).state in TERMINAL
    assert eng.allocator.free_blocks == free0
    fe.close()


@pytest.mark.overload
def test_kv_leak_guard_across_shed_evict_expire_paths():
    """Satellite leak guard: a mix of shedding, deadline expiry, poison
    eviction and normal completion drains back to the initial free-block
    count."""
    eng = _engine(n_blocks=32)
    free0 = eng.allocator.free_blocks
    fe = _front(engine=eng, max_queue=6, shed_policy="reject_oldest",
                default_max_new_tokens=4, circuit_failure_threshold=10)
    gen = chaos.OverloadGenerator(seed=2)
    uids = []
    for i, (uid, prompt) in enumerate(gen.burst(18)):
        uids.append(uid)
        # every third request gets a deadline it cannot meet -> expiry path
        fe.submit(uid, prompt, deadline_s=0.02 if i % 3 == 0 else None)
        if i % 5 == 4:
            fe.run_tick()
    # poison-eviction path: one failing tick right after an admission
    uid, prompt = gen.request()
    uids.append(uid)
    fe.submit(uid, prompt)
    chaos.arm("serving/tick=fail:1")
    fe.run_tick()
    chaos.disarm()
    assert fe.result(uid).state == "failed"
    time.sleep(0.03)                           # let the short deadlines pass
    fe.run_until_drained(2000)
    states = {u: fe.result(u).state for u in uids}
    assert set(states.values()) <= TERMINAL
    assert "expired" in states.values()
    assert not eng.seqs
    assert eng.allocator.free_blocks == free0, states
    fe.close()


# --------------------------------------------------------------------- #
# config + misc
# --------------------------------------------------------------------- #
class TestServingConfig:
    def test_section_parses_and_wires(self):
        cfg = load_config({
            "train_micro_batch_size_per_gpu": 1,
            "serving": {"max_queue": 7, "shed_policy": "deadline_aware",
                        "kv_high_watermark": 0.9},
        })
        assert cfg.serving.max_queue == 7
        fe = ServingFrontend.from_ds_config(
            _engine(), {"train_micro_batch_size_per_gpu": 1,
                        "serving": {"max_queue": 7}},
            register_health=False)
        assert fe.cfg.max_queue == 7 and fe.ctrl.max_queue == 7
        fe.close()

    def test_section_validates(self):
        for bad in ({"shed_policy": "drop_table"},
                    {"kv_high_watermark": 1.5},
                    {"kv_degrade_watermark": 0.99, "kv_high_watermark": 0.5},
                    {"max_queue": 0},
                    {"circuit_backoff_s": 0},          # full-rate probing
                    {"circuit_backoff_max_s": 0.1},    # < backoff_s
                    {"heartbeat_timeout_s": 0},
                    {"degraded_max_new_tokens": 0}):
            with pytest.raises(DeepSpeedConfigError):
                load_config({"train_micro_batch_size_per_gpu": 1,
                             "serving": bad})

    def test_object_config_validated_too(self):
        from deepspeed_tpu.runtime.config import ServingSectionConfig

        with pytest.raises(DeepSpeedConfigError, match="max_queue"):
            ServingFrontend(_engine(),
                            config=ServingSectionConfig(max_queue=0),
                            register_health=False)

    def test_queue_wait_histogram_recorded(self):
        fe = _front()
        fe.submit(1, _prompt(8), max_new_tokens=2)
        fe.run_until_drained(50)
        assert fe.result(1).state == "completed"
        hist = telemetry.histogram("serving_queue_wait_seconds")
        assert hist.child() is not None and hist.child().count >= 1
        fe.close()

    def test_submit_harvests_engine_side_completions(self):
        """Work that finished outside a frontend tick (caller driving the
        engine directly) must not occupy queue slots at the next submit."""
        fe = _front(max_queue=1, default_max_new_tokens=2)
        fe.submit(1, _prompt(8))
        while len(fe.engine.seqs[1].generated) < 2:
            fe.engine.step()                   # engine driven directly
        res = fe.submit(2, _prompt(8))
        assert isinstance(res, Admitted), res  # stale entry harvested
        assert fe.result(1).state == "completed"
        fe.run_until_drained(100)
        fe.close()

    def test_result_answers_after_external_flush(self):
        """result() must answer (not KeyError) for an active uid whose
        engine sequence was flushed behind the frontend's back."""
        fe = _front()
        fe.submit(1, _prompt(8))
        fe.engine.flush([1])
        r = fe.result(1)
        assert r.state == "active" and r.tokens == []
        fe.run_tick()                          # harvest resolves it
        assert fe.result(1).state == "failed"
        assert fe.result(1).reason == "evicted"
        fe.close()

    def test_result_history_bounded(self):
        """Sustained overload with fresh uids must not grow the terminal-
        record map without limit (oldest records evicted past the cap)."""
        fe = _front(max_queue=1, max_result_history=5)
        fe.submit(1, _prompt(8))
        for uid in range(100, 120):
            res = fe.submit(uid, _prompt(8))
            assert isinstance(res, Overloaded)
        assert len(fe._results) == 5
        assert fe.result(119).state == "rejected"   # newest kept
        with pytest.raises(KeyError):
            fe.result(100)                          # oldest evicted
        fe.close()

    def test_rejection_storm_does_not_evict_completed_records(self):
        """Bounded history evicts REJECTED records first: a completed
        request's result must survive an overload storm bigger than the
        cap (its caller polls result(); the rejected callers already got
        their answer synchronously)."""
        fe = _front(max_queue=1, max_result_history=4,
                    default_max_new_tokens=2)
        fe.submit(1, _prompt(8))
        fe.run_until_drained(50)
        assert fe.result(1).state == "completed"
        fe.submit(2, _prompt(8))                    # occupy the queue
        for uid in range(200, 220):                 # 20 > cap rejections
            assert isinstance(fe.submit(uid, _prompt(8)), Overloaded)
        assert fe.result(1).state == "completed"    # survived the storm
        assert len(fe._results) == 4
        fe.run_until_drained(50)
        fe.close()

    def test_repeated_rejection_of_one_uid_stays_bounded(self):
        """One client hammering one uid through an overload window must
        not grow any frontend structure per retry."""
        fe = _front(max_queue=1)
        fe.submit(1, _prompt(8))
        for _ in range(50):
            assert isinstance(fe.submit(2, _prompt(8)), Overloaded)
        assert len(fe._rejected_fifo) <= 1
        assert len(fe._results) == 1
        fe.run_until_drained(100)
        fe.close()

    def test_kv_shed_only_when_it_clears_the_bound(self):
        """kv_pressure must not kill a small live request to make room
        for a prompt the freed blocks still can't fit — that loses the
        victim AND rejects the incoming request."""
        fe = _front(engine=_engine(n_blocks=16), max_queue=8,
                    shed_policy="reject_oldest",
                    kv_high_watermark=0.5, kv_degrade_watermark=0.3)
        fe.submit(1, _prompt(20))              # 2 blocks once prefilled
        for _ in range(2):
            fe.run_tick()
        res = fe.submit(2, _prompt(120))       # needs 8 of 15 blocks
        assert isinstance(res, Overloaded) and res.reason == "kv_pressure"
        assert fe.active_uids() == [1], "innocent victim was shed for naught"
        fe.run_until_drained(200)
        fe.close()

    def test_deadline_aware_uses_engine_default_deadline(self):
        """A request admitted without an explicit deadline still expires
        by the engine's request_deadline_s — the shed policy must rank it
        by that same deadline, not treat it as unsheddable."""
        fe = _front(engine=_engine(request_deadline_s=0.01),
                    max_queue=2, shed_policy="deadline_aware")
        fe.submit(1, _prompt(8))                    # inherits 0.01s — doomed
        fe.submit(2, _prompt(8), deadline_s=100.0)
        res = fe.submit(3, _prompt(8), deadline_s=50.0)
        assert isinstance(res, Admitted)
        assert fe.result(1).state == "shed"         # not the fresh traffic
        fe.run_until_drained(200)
        fe.close()

    def test_run_until_drained_waits_out_open_circuit(self):
        """The drain helper must sleep toward the probe window while the
        circuit is open, not burn its tick budget spinning."""
        fe = _front(circuit_failure_threshold=2, circuit_backoff_s=0.1)
        fe.submit(1, _prompt(8), max_new_tokens=2)
        fe.run_tick()
        chaos.arm("serving/tick=fail:2")
        fe.run_tick(), fe.run_tick()
        assert fe.breaker.state == OPEN
        chaos.disarm()
        ticks = fe.run_until_drained(400)
        assert fe.result(1).state == "completed"    # drained THROUGH the
        assert ticks < 400                          # backoff window
        fe.close()

    def test_run_until_drained_deadline_escape(self):
        """``max_ticks`` bounds iterations, not TIME — with open-circuit
        sleeps in the loop, only ``deadline_s`` bounds how long a drain
        against a persistently sick replica can block."""
        fe = _front(circuit_failure_threshold=2, circuit_backoff_s=0.2,
                    circuit_backoff_max_s=5.0)
        fe.submit(1, _prompt(8), max_new_tokens=2)
        fe.run_tick()
        chaos.arm("serving/tick=fail:1000")
        fe.run_tick(), fe.run_tick()
        assert fe.breaker.state == OPEN
        t0 = time.monotonic()
        fe.run_until_drained(10_000, deadline_s=0.3)
        assert time.monotonic() - t0 < 2.0
        assert fe.active_count() == 1       # gave up with work pending
        chaos.disarm()
        time.sleep(0.21)                    # wait out the open window
        fe.run_until_drained(400)
        assert fe.result(1).state == "completed"
        fe.close()

    def test_two_frontends_get_distinct_health_probes(self):
        fe1 = _front()
        fe2 = _front()
        assert fe1.health.name == "serving"
        assert fe2.health.name == "serving-2"
        # closing one must not blind the other's readiness surface
        for _ in range(fe2.cfg.circuit_failure_threshold):
            fe2.breaker.record_failure()
        fe1.close()
        ok, report = telemetry.health_report("ready")
        assert not ok and report["checks"]["serving-2"]["circuit"] == "open"
        fe2.close()

    def test_close_resolves_active_requests(self):
        eng = _engine()
        free0 = eng.allocator.free_blocks
        fe = _front(engine=eng)
        fe.submit(1, _prompt(8))
        fe.run_tick()
        fe.close()
        assert fe.result(1).state == "failed"
        assert fe.result(1).reason == "shutdown"
        assert eng.allocator.free_blocks == free0


# --------------------------------------------------------------------- #
# what a tick says about itself: spans, attributes, kind, first token
# --------------------------------------------------------------------- #
class TestTickTelemetry:
    def _run_one(self, prompt_len=26, new=3):
        """One request whose prompt takes four ticks of an 8-token budget
        (8 + 8 + 8 + 2): first service after tick 1, first token after
        tick 4. The injected clock moves one second a tick."""
        now = [0.0]
        fe = ServingFrontend(_engine(token_budget=8),
                             config=dict(SCFG), clock=lambda: now[0])
        fe.submit(1, _prompt(prompt_len), max_new_tokens=new)
        ticks = 0
        while fe.active_count():
            now[0] += 1.0
            fe.run_tick()
            ticks += 1
        return fe, ticks

    def test_ttft_is_the_first_token_and_queue_wait_the_first_service(self):
        telemetry.configure_tracing(enabled=True)
        fe, ticks = self._run_one()
        assert ticks == 4 + 2         # 4 prompt ticks, then 2 more tokens
        wait = telemetry.histogram("serving_queue_wait_seconds").summary()
        ttft = telemetry.histogram("serving_tenant_ttft_seconds").summary(
            tenant="default")
        assert wait["count"] == ttft["count"] == 1
        assert wait["sum"] == pytest.approx(1.0)    # after the first chunk
        assert ttft["sum"] == pytest.approx(4.0)    # three ticks later
        events = {e["name"]: e for e in
                  telemetry.get_tracer().export_chrome()["traceEvents"]
                  if e["ph"] == "i"}
        assert events["first_service"]["ts"] < events["first_token"]["ts"]
        assert events["first_token"]["args"]["ttft_s"] == pytest.approx(4.0)
        fe.close()

    def test_tick_kind_is_on_the_counter_and_on_the_span(self):
        telemetry.configure_tracing(enabled=True)
        fe, _ = self._run_one()
        by_kind = {}
        for key, n in telemetry.counter("fastgen_ticks_total").labels_items():
            by_kind[dict(key)["kind"]] = by_kind.get(dict(key)["kind"], 0) + n
        # four ticks held prompt rows, the two after them none
        assert by_kind == {"mixed": 4, "decode": 2}
        spans = [e for e in
                 telemetry.get_tracer().export_chrome()["traceEvents"]
                 if e["ph"] == "X"]
        ticks = [e["args"] for e in spans if e["name"] == "decode_tick"]
        assert [a["kind"] for a in ticks] == ["mixed"] * 4 + ["decode"] * 2
        assert [a["tick"] for a in ticks] == [1, 2, 3, 4, 5, 6]
        assert [a["prefill_tokens"] for a in ticks] == [8, 8, 8, 2, 0, 0]
        assert [a["decode_rows"] for a in ticks] == [0, 0, 0, 0, 1, 1]
        assert all(a["rows"] == a["decode_rows"] + a["prefill_tokens"]
                   and a["bucket"] == 8 and a["mb_tier"] for a in ticks)
        # every boundary of a tick has its span, nested as PERF.md says
        names = [e["name"] for e in spans]
        for name in ("serving_submit", "serving_tick", "schedule_tick",
                     "tick_dispatch", "tick_readback", "tick_commit",
                     "serving_harvest"):
            assert names.count(name) >= 1, name
        by_id = {e["args"]["trace_id"]: e for e in spans
                 if "parent_span_id" not in e["args"]}
        inner = {e["name"] for e in spans if e["name"] in
                 ("tick_dispatch", "tick_readback")}
        assert inner == {"tick_dispatch", "tick_readback"}
        for e in spans:
            if e["name"] in ("schedule_tick", "decode_tick", "tick_commit"):
                assert by_id[e["args"]["trace_id"]]["name"] == "serving_tick"
        submit = next(e for e in spans if e["name"] == "serving_submit")
        assert submit["args"]["uid"] == 1
        # attributes are per occurrence: none of them keys a histogram
        # (a collection's generation is a label: three values)
        keys = [dict(k) for k, _ in
                telemetry.get_registry().get("span_seconds").labels_items()]
        assert keys and all(set(k) == {"span"} for k in keys
                            if k["span"] != "gc_pause")
        fe.close()
