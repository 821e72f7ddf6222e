"""A scripted history of serving ticks against a record made with the tree
before the scheduler's state moved into arrays (PR 54).

``FastGenEngine.step`` may change how it keeps its books, not what it
sends: for the same history of calls every tick's packed array (tables,
tokens, positions, the sampled rows, the key words: ``_pack_tick``) and
every returned ``{uid: token}`` are the record's, bit for bit, so the tick
programs, the cache entries and every sampled token are too.

The histories run through ``ServingFrontend`` on tiny models and hold what
a scheduler has to get right: staggered admissions, prompts longer than the
budget, a pool that runs out under decode rows and under a prompt, more
decode rows than the budget (the rotation), an end-of-sequence token, a
sequence reaching ``max_len``, a deadline expiring, a wait for a sequence
slot (``slots``: a model with ``state_slots``) and one tick that fails
after its schedule was made, is rolled back and is retried.

The record is ``tick_history/parent.json``; ``python
tests/unit/test_tick_history.py <file>`` writes one from the tree it is run
in (the committed one came from commit 32171c9's archive).
"""
import base64
import json
import pathlib
import sys
import types
import zlib

import numpy as np
import pytest

RECORD = pathlib.Path(__file__).parent / "tick_history" / "parent.json"

DENSE = dict(hidden_size=64, num_layers=2, num_heads=4, max_seq_len=128,
             vocab_size=512, dtype="float32")
#: tick -> the submissions made before it: (uid, prompt length, tokens
#: granted, deadline in seconds or None)
HISTORIES = {
    # 47 usable blocks of 4 positions, ticks of 16 rows (8 in the small
    # bucket), sequences of at most 64 positions
    "dense": dict(
        engine=dict(n_blocks=30, block_size=4, max_blocks_per_seq=16,
                    token_budget=16, eos_token_id=None),
        submits={
            0: [(1, 5, 12, None), (2, 40, 6, None)],
            1: [(3, 7, 9, None)],
            3: [(4, 3, 70, None), (5, 9, 7, 50.0)],
            6: [(100 + i, 2 + i % 3, 3 + i % 4, None) for i in range(30)],
            30: [(50 + i, 6, 10, None) for i in range(6)],
            44: [(60, 50, 3, None), (61, 20, 3, None), (62, 20, 3, None),
                 (63, 20, 3, None)],
        },
        clock_jumps={9: 100.0}, fail_at=(12, 33, 47), vocab=512),
    # twelve decode rows, then a budget of eight: the rotation's cut
    "cut": dict(
        engine=dict(n_blocks=64, block_size=4, max_blocks_per_seq=16,
                    token_budget=16, eos_token_id=None),
        submits={0: [(1 + i, 2, 9 + i % 3, None) for i in range(12)],
                 5: [(20, 3, 4, None)]},
        budget_at={3: 8}, fail_at=(6,), vocab=512),
    # three sequence slots: the fourth sequence waits for one
    "slots": dict(
        engine=dict(n_blocks=40, block_size=4, max_blocks_per_seq=16,
                    token_budget=16, state_slots=3, eos_token_id=None,
                    use_pallas_kernel=False),
        submits={
            0: [(1, 6, 8, None), (2, 21, 5, None)],
            1: [(3, 4, 10, None), (4, 5, 4, None), (5, 9, 6, 50.0)],
            9: [(6, 3, 12, None), (7, 7, 3, None)],
        },
        clock_jumps={4: 100.0}, fail_at=(6,), vocab=128),
}
#: the token that ends a sequence, read off a first pass without one (the
#: writer below prints candidates): a decode row samples it in mid-life
EOS = {"dense": 348, "cut": 499, "slots": 71}
#: a stack with per-sequence state beside its blocks (short convolutions,
#: ``lfm2_moe``), small enough to compile in seconds
_KINDS = ["conv", "full_attention", "conv"]
SLOTS_MODEL = dict(
    model_type="lfm2_moe", hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_attention_heads=4, num_key_value_heads=2,
    num_hidden_layers=len(_KINDS), layer_types=_KINDS, num_dense_layers=2,
    num_experts=8, num_experts_per_tok=4, norm_eps=1e-5, norm_topk_prob=True,
    routed_scaling_factor=1, use_expert_bias=True, conv_L_cache=3,
    conv_bias=False, rope_parameters={"rope_theta": 1000000,
                                      "rope_type": "default"},
    vocab_size=128, max_position_embeddings=4096)


class _Clock:
    """The engine's ``time``: a microsecond a reading, and the jumps the
    history makes (a deadline is real time to the engine)."""

    def __init__(self):
        self.now = 1000.0

    def perf_counter(self) -> float:
        self.now += 1e-6
        return self.now

    def __getattr__(self, name):
        import time

        return getattr(time, name)


def _engine(name: str):
    import jax

    from deepspeed_tpu.inference.fastgen import FastGenEngine
    from deepspeed_tpu.models import transformer as T

    kw = dict(HISTORIES[name]["engine"], eos_token_id=EOS[name])
    if name != "slots":
        return FastGenEngine("tiny", temperature=0.0, seed=0, **kw, **DENSE)
    from deepspeed_tpu.models.hf_import import config_from_hf

    cfg = config_from_hf(types.SimpleNamespace(**SLOTS_MODEL))
    # every leaf off its start, so that the sequences' tokens differ
    leaves, tree = jax.tree_util.tree_flatten(
        T.init_params(cfg, jax.random.PRNGKey(0)))
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    return FastGenEngine(cfg, tree.unflatten(
        [x + 0.05 * jax.random.normal(k, x.shape)
         for x, k in zip(leaves, keys)]), **kw)


def play(name: str, eos="script"):
    """Run the history; one entry a call of ``step()``: the packed array
    it sent (None: it sent none), what it returned (None: it raised)."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference import fastgen
    from deepspeed_tpu.serving import ServingFrontend

    hist = HISTORIES[name]
    telemetry.reset()
    clock = _Clock()
    real_time, fastgen.time = fastgen.time, clock
    try:
        eng = _engine(name)
        if eos != "script":
            eng.eos_token_id = eos
        fe = ServingFrontend(
            eng, config=dict(max_queue=64, kv_high_watermark=1.0,
                             kv_degrade_watermark=1.0,
                             degraded_max_new_tokens=64,
                             circuit_failure_threshold=4),
            register_health=False, health_name=f"history-{name}")
        rng = np.random.default_rng(7)
        ticks, now = [], {"packed": None, "fail": False}
        pack, step = eng._pack_tick, eng.step

        def pack_and_maybe_fail(*a, **kw):
            now["packed"] = pack(*a, **kw)
            if now["fail"]:
                now["fail"] = False
                raise RuntimeError("the tick fails after its schedule")
            return now["packed"]

        def recorded_step():
            now["packed"], out = None, None
            try:
                out = step()
                return out
            finally:
                ticks.append((now["packed"], out))

        eng._pack_tick, eng.step = pack_and_maybe_fail, recorded_step
        last, peak = max(hist["submits"]), 0
        for t in range(400):
            for uid, n, grant, deadline in hist["submits"].get(t, ()):
                prompt = rng.integers(1, hist["vocab"], n).tolist()
                fe.submit(uid, prompt, max_new_tokens=grant,
                          deadline_s=deadline)
            clock.now += hist.get("clock_jumps", {}).get(t, 0.0)
            eng.token_budget = hist.get("budget_at", {}).get(
                t, eng.token_budget)
            now["fail"] = t in hist["fail_at"]
            if not fe.active_count():
                if t > last:
                    break
                continue
            peak = max(peak, sum(
                1 for s in eng.seqs.values() if not s.done
                and s.prefill_remaining == 0 and s.last_tok is not None))
            fe.run_tick()
        assert not fe.active_count(), "the history never drained"
        facts = {
            "peak_decode_rows": peak,
            "preempt_decode": telemetry.counter(
                "fastgen_preemptions_total").value(phase="decode"),
            "preempt_prefill": telemetry.counter(
                "fastgen_preemptions_total").value(phase="prefill"),
            "slot_waits": telemetry.counter(
                "fastgen_state_slot_waits_total").total(),
            "expired": telemetry.counter(
                "fastgen_deadline_expired_total").total(),
            "tick_failures": telemetry.counter(
                "serving_tick_failures_total").total(),
            "finished": telemetry.counter(
                "fastgen_sequences_finished_total").total(),
            "states": {str(u): [fe.result(u).state, fe.result(u).reason,
                                len(fe.result(u).tokens)]
                       for s in hist["submits"].values() for u, *_ in s},
            "free_blocks": eng.allocator.free_blocks,
        }
        fe.close()
        return ticks, facts
    finally:
        fastgen.time = real_time
        telemetry.reset()


def _encode(packed):
    if packed is None:
        return None
    return base64.b64encode(zlib.compress(
        np.ascontiguousarray(packed, np.int32).tobytes())).decode()


def _decode(text):
    if text is None:
        return None
    return np.frombuffer(zlib.decompress(base64.b64decode(text)), np.int32)


def write(path: str) -> None:
    record = {}
    for name in HISTORIES:
        ticks, _ = play(name, eos=None)
        mid = {}
        for _, out in ticks[len(ticks) // 4:len(ticks) // 2]:
            for tok in (out or {}).values():
                mid[tok] = mid.get(tok, 0) + 1
        print(name, "tokens sampled in the second quarter:",
              sorted(mid.items(), key=lambda kv: -kv[1])[:12])
        ticks, facts = play(name)
        record[name] = {
            "eos": EOS[name], "facts": facts,
            "ticks": [{"packed": _encode(p),
                       "out": None if out is None else
                       {str(u): int(t) for u, t in out.items()}}
                      for p, out in ticks]}
        print(name, len(ticks), "ticks", facts)
    pathlib.Path(path).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(path).write_text(json.dumps(record, indent=0) + "\n")


@pytest.fixture(scope="module")
def record():
    return json.loads(RECORD.read_text())


@pytest.fixture(scope="module", params=sorted(HISTORIES))
def played(request, record):
    name = request.param
    assert record[name]["eos"] == EOS[name]
    return name, record[name], play(name)


def test_every_tick_sends_and_returns_what_the_parent_did(played):
    _, want, (ticks, _) = played
    assert len(ticks) == len(want["ticks"])
    for i, ((packed, out), rec) in enumerate(zip(ticks, want["ticks"])):
        sent = _decode(rec["packed"])
        assert (packed is None) == (sent is None), f"tick {i}"
        if sent is not None:
            assert packed.dtype == np.int32
            np.testing.assert_array_equal(packed, sent, err_msg=f"tick {i}")
        got = None if out is None else {str(u): int(t)
                                        for u, t in out.items()}
        assert got == rec["out"], f"tick {i}"
        # the order of a tick's answers too (heads in row order)
        assert out is None or list(got) == list(rec["out"]), f"tick {i}"


def test_the_history_ends_where_the_parents_did(played):
    _, want, (_, facts) = played
    assert json.loads(json.dumps(facts)) == want["facts"]


def test_the_history_holds_what_it_is_for(played):
    """The record is of a history in which each thing happened; a script
    that no longer reaches one of them proves nothing about it."""
    name, want, _ = played
    facts, ticks = want["facts"], want["ticks"]
    states = facts["states"]
    failed = [i for i, t in enumerate(ticks) if t["out"] is None]
    assert len(failed) == facts["tick_failures"] >= 1
    # the failed tick had made its schedule, and the retry sent the same
    # rows (all but the key words, drawn anew)
    for i in failed:
        sent, again = _decode(ticks[i]["packed"]), \
            _decode(ticks[i + 1]["packed"])
        np.testing.assert_array_equal(sent[:-2], again[:-2])
    ended = [u for t in ticks for u, tok in (t["out"] or {}).items()
             if tok == want["eos"]]
    assert ended and all(states[u][2] >= 2 for u in ended), \
        "no decode row sampled the end-of-sequence token"
    if name == "cut":
        # more decode rows than the tick holds
        assert facts["peak_decode_rows"] == 12
        assert max(len(t["out"] or ()) for t in ticks[4:]) == 8
        return
    assert facts["expired"] == 1
    assert states["5"][:2] == ["expired", "deadline"]
    if name == "slots":
        assert facts["slot_waits"] >= 1
        return
    assert facts["preempt_decode"] >= 1 and facts["preempt_prefill"] >= 1
    # a prompt longer than the budget; a sequence that ran to max_len
    # (prompt + kept tokens fill its 64 positions)
    assert states["2"] == ["completed", "", 6]
    assert states["4"] == ["completed", "", 64 - 3]
    # more live sequences than a tick has rows
    assert len(HISTORIES[name]["submits"][6]) > 16


if __name__ == "__main__":
    write(sys.argv[1])
