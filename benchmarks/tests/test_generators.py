import numpy as np

from benchmarks.generators import closed_loop, common, open_poisson, zipf_batches
from benchmarks.manifest import BENCH_DIR, load_json
import os

CHAT = load_json(os.path.join(BENCH_DIR, "traffic", "chat-steady-v2.json"))["params"]
PER_WINDOW = round(CHAT["rate_per_s"] * 50)     # arrivals in a 50 s window
CLOSED = load_json(os.path.join(BENCH_DIR, "traffic", "decode-closed.json"))["params"]


def _open(seed, params=CHAT, start=-10.0, end=50.0):
    return open_poisson.build(params, seed, 32000, 2048, start, end)


def _schedule(src):
    return [(r.due, len(r.prompt), r.max_new) for r in src.requests]


def test_open_same_seed_same_requests():
    a, b = _open(3), _open(3)
    assert [(r.due, r.prompt, r.max_new) for r in a.requests] == \
        [(r.due, r.prompt, r.max_new) for r in b.requests]
    assert len({r.uid for r in a.requests}) == len(a.requests)


def test_open_schedule_is_recorded_and_the_seed_draws_the_tokens():
    assert CHAT["schedule_seed"] is not None
    a, c = _open(3), _open(4)
    assert _schedule(a) == _schedule(c)
    assert all(x.prompt != y.prompt for x, y in zip(a.requests, c.requests))
    # whatever stretch is asked for, a stratum's draw is the same
    tail = _open(3, start=20.0, end=64.0)
    assert _schedule(tail)[:20] == [r for r in _schedule(a) if r[0] >= 20.0][:20]
    # without a recorded schedule the run's seed draws it
    fresh = {k: v for k, v in CHAT.items() if k != "schedule_seed"}
    assert _schedule(_open(3, fresh)) != _schedule(_open(4, fresh))
    assert _schedule(_open(3, fresh)) == _schedule(_open(3, fresh))


def test_open_fixed_work_in_every_stratum():
    fresh = {k: v for k, v in CHAT.items() if k != "schedule_seed"}
    per = round(CHAT["rate_per_s"] * CHAT["stratum_s"])
    for seed in (1, 2):
        src = _open(seed, fresh)
        assert len(src.requests) == 6 * per
        for k in range(-1, 5):
            rows = [r for r in src.requests if 10 * k <= r.due < 10 * (k + 1)]
            assert len(rows) == per
    # the same multiset of lengths whatever the draw
    assert sorted(len(r.prompt) for r in _open(1, fresh).requests) == \
        sorted(len(r.prompt) for r in _open(2, fresh).requests)
    assert sorted(r.max_new for r in _open(1, fresh).requests) == \
        sorted(r.max_new for r in _open(2, fresh).requests)


def test_open_arrivals_are_poisson_bursts_and_lulls():
    """Given the counts, instants are independent and uniform: gaps are
    exponential (coefficient of variation 1), slots of one mean gap are
    left empty as often as e^-1, and some hold three or more."""
    fresh = {k: v for k, v in CHAT.items() if k != "schedule_seed"}
    slot = 1.0 / CHAT["rate_per_s"]
    cvs, empty, crowded = [], [], []
    for seed in range(20):
        dues = np.array([r.due for r in _open(seed, fresh, 0.0, 50.0).requests])
        gaps = np.diff(dues)
        cvs.append(gaps.std() / gaps.mean())
        counts = np.bincount((dues // slot).astype(int), minlength=PER_WINDOW)
        empty.append((counts == 0).mean())
        crowded.append((counts >= 3).mean())
    assert 0.9 < np.mean(cvs) < 1.1
    assert 0.32 < np.mean(empty) < 0.42          # e^-1 = 0.37
    assert 0.05 < np.mean(crowded) < 0.11        # 1 - 5/(2e) = 0.08
    # and the recorded draw is one of them, not a smoothed stream
    dues = np.array([r.due for r in _open(0, CHAT, 0.0, 50.0).requests])
    counts = np.bincount((dues // slot).astype(int), minlength=PER_WINDOW)
    assert (counts == 0).mean() > 0.3 and counts.max() >= 3
    assert np.diff(dues).max() > 3 * slot


def test_open_lengths_inside_clips():
    src = _open(5)
    p, o = CHAT["prompt_tokens"], CHAT["output_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in src.requests)
    assert all(o["min"] <= r.max_new <= o["max"] for r in src.requests)
    med = np.median([len(r.prompt) for r in src.requests])
    assert 0.8 * p["median"] < med < 1.25 * p["median"]
    assert all(0 <= t < 32000 for r in src.requests for t in r.prompt)


def test_open_hands_out_by_due_time_whatever_the_server_does():
    src = _open(6)
    first = src.due(-5.0)
    assert first and all(r.due <= -5.0 for r in first)
    assert src.next_due() > -5.0
    src.on_complete(first[0], 100.0)       # completions change nothing
    later = src.due(0.0)
    assert all(-5.0 < r.due <= 0.0 for r in later)
    src.stop()
    assert src.due(1e9) == [] and src.next_due() is None


def test_closed_keeps_every_client_in_flight():
    src = closed_loop.build(CLOSED, 7, 50432, 768, -10.0, 30.0)
    start = src.due(-10.0)
    assert len(start) == CLOSED["clients"] == 20
    assert src.due(0.0) == [] and src.next_due() is None
    in_flight = {r.client: r for r in start}
    for t in np.linspace(-9, 29, 200):
        c = int(t * 7) % 20
        src.on_complete(in_flight[c], float(t))
        nxt = src.due(float(t))
        assert len(nxt) == 1 and nxt[0].client == c and nxt[0].due == float(t)
        in_flight[c] = nxt[0]
        assert len(in_flight) == 20
    p, o = CLOSED["prompt_tokens"], CLOSED["output_tokens"]
    for r in in_flight.values():
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert o["min"] <= r.max_new <= o["max"]
        assert len(r.prompt) + r.max_new < 768
    src.stop()
    src.on_complete(in_flight[0], 30.0)
    assert src.due(31.0) == []


def test_closed_same_seed_same_requests_per_client():
    a = closed_loop.build(CLOSED, 7, 50432, 768, -10.0, 30.0)
    b = closed_loop.build(CLOSED, 7, 50432, 768, -10.0, 30.0)
    ra = sorted(a.due(-10.0), key=lambda r: r.client)
    rb = sorted(b.due(-10.0), key=lambda r: r.client)
    assert [(r.prompt, r.max_new) for r in ra] == [(r.prompt, r.max_new) for r in rb]


def test_stratified_lengths_and_fit():
    rng = np.random.default_rng(0)
    u = common.stratified_lengths({"dist": "uniform", "min": 64, "max": 128}, 16, rng)
    assert sorted(u.tolist()) == [66, 70, 74, 78, 82, 86, 90, 94, 98, 102,
                                  106, 110, 114, 118, 122, 126]
    assert common.fit_prompt(1000, 100, 768) == 667
    assert common.fit_prompt(10, 100, 768) == 10


def test_zipf_batches():
    params = {"micro_batch_per_chip": 2, "seq_len": 128, "zipf_a": 1.1}
    a = zipf_batches.build(params, 1, 1000)
    b = zipf_batches.build(params, 1, 1000)
    assert a.batch(0, 4).shape == (8, 128) and a.batch(0, 4).dtype == np.int32
    assert (a.batch(3, 4) == b.batch(3, 4)).all()
    assert (a.batch(3, 4) != a.batch(4, 4)).any()
    assert a.tokens_per_step(4) == 8 * 128
    toks = np.concatenate([a.batch(i, 4).ravel() for i in range(20)])
    assert toks.min() >= 0 and toks.max() < 1000
    # a Zipf law: the commonest token is far commoner than the median one
    counts = np.sort(np.bincount(toks, minlength=1000))[::-1]
    assert counts[0] > 20 * max(1, counts[500])
