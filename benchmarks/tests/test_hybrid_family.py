"""The ``phi4flash`` family's files (configuration
``phi-4-mini-flash-reasoning``, cell ``serve-phi4flash-reason-closed``, mix
``reason-closed``): loaded by name, held to the numbers of the issue that
asked for them (ISSUE 31), the reference against the program at the
rehearsal size, the flops counter and the two roofline files by hand on call
texts recorded from the tick program compiled for a v5e, and the five readers
on a made-up run record.

``test_reference_and_rehearsal.py`` compares every configuration with
``reference/dense_lm.py`` by name, so its two cases for this family cannot
pass (as the ``deepseek_v3`` family's cannot); the comparison with the
reference the configuration names is made here.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from benchmarks import harness, manifest, model_config, weights
from benchmarks.flops import hybrid as hybrid_flops
from benchmarks.layer_metrics import (gmu_share_pct,
                                      shared_attention_roofline,
                                      ssm_share_pct, state_slots_peak_pct,
                                      window_attention_roofline)
from benchmarks.roofline import (shared_paged_attention, tick_attrs,
                                 window_paged_attention)

M = manifest.load_manifest()
CELL = "serve-phi4flash-reason-closed"
CONFIG = "phi-4-mini-flash-reasoning"

# the Mosaic calls of the (512, 136) tick as compiled for a v5e at the
# cell's sizes (names and operand shapes as the trace's event names give
# them; layouts cut): 72 + 1 slots x 32 ring blocks x 8 window layers
WINDOW = ('%window_paged_attention.11 = bf16[512,40,128]{2,1,0} custom-call('
          's32[512,192]{1,0} %t, s32[1024]{0} %m, bf16[512,40,128]{2,1,0} %q, '
          'bf16[18688,10,32,128]{3,2,1,0} %k, bf16[18688,10,32,128]{3,2,1,0} '
          '%v), custom_call_target="tpu_custom_call"')
SHARED = ('%shared_paged_attention.14 = bf16[512,40,128]{2,1,0} custom-call('
          's32[512,192]{1,0} %t, s32[1024]{0} %m, bf16[512,40,128]{2,1,0} %q, '
          'bf16[10900,10,32,128]{3,2,1,0} %k, bf16[10900,10,32,128]{3,2,1,0} '
          '%v), custom_call_target="tpu_custom_call"')
DENSE = ('%paged_attention.11 = bf16[64,32,128]{2,1,0} custom-call('
         's32[64,24]{1,0} %t, s32[128]{0} %m, bf16[64,32,128]{2,1,0} %q, '
         'bf16[10240,32,32,128]{3,2,1,0} %k, bf16[10240,32,32,128]{3,2,1,0} '
         '%v), custom_call_target="tpu_custom_call"')
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


class _Op:
    is_mosaic = True

    def __init__(self, text, seconds=1e-3, at=0.0):
        self.text, self.seconds = text, seconds
        self.start, self.end = at, at + seconds
        self.name = text.split(" ", 1)[0].lstrip("%")


class _Trace:
    chips = [0]

    def __init__(self, ops):
        self._ops = ops

    def busy_s(self):
        return sum(o.seconds for o in self._ops)

    def ops_in_window(self, chip):
        return self._ops

    def op_seconds(self, pred):
        return sum(o.seconds for o in self._ops if pred(o))


def _served_config():
    return model_config.build(manifest.load_cell(CELL).config, "serve")


def _tick(start, end, blocks, attended, positions=None, w_attended=None):
    """A row of ``tick_attrs.per_tick``."""
    row = {"start": start, "end": end, "blocks": blocks,
           "prompt_attended": attended}
    if positions is not None:
        row.update(window_positions=positions, window_attended=w_attended)
    return row


# ------------------------------------------------------------------ #
def test_cell_config_and_mix_load_by_name_with_the_issues_numbers():
    cell = manifest.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.runner) \
        == (CONFIG, "reason-closed", 1, "serve")
    eng = cell.deploy["engine"]
    assert eng == {"n_blocks": 10900, "block_size": 32,
                   "max_blocks_per_seq": 136, "token_budget": 512,
                   "state_slots": 72}
    p = cell.traffic["params"]
    assert cell.traffic["generator"] == "closed_loop"
    assert (p["clients"], p["preroll_s"]) == (64, 10)
    assert p["prompt_tokens"] == {"dist": "uniform", "min": 768, "max": 1280}
    assert p["output_tokens"] == {"dist": "uniform", "min": 1024, "max": 3072}
    # the longest sequence fits a table; every client has a slot; all 64 at
    # their longest hold 80 % of the blocks
    assert 1280 + 3072 == 136 * 32 and eng["state_slots"] >= p["clients"]
    assert 64 * 136 / (eng["n_blocks"] - 1) < 0.80
    assert cell.deploy["serving"]["max_queue"] > p["clients"]
    assert cell.config["as_run"]["serve"] == cell.config["published"] \
        == {"num_hidden_layers": 32}
    row = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert row["reduced"] == []
    assert {m.name for m in cell.end_to_end} == {"serve_out_tokens_per_s",
                                                 "setup_s"}
    names = {m.name for m in cell.per_layer}
    assert {"ssm_share_pct", "gmu_share_pct", "window_attention_roofline",
            "shared_attention_roofline", "state_slots_peak_pct"} <= names
    assert not names & {"closed.paged_share_pct",
                        "closed.paged_attention_roofline"}
    assert len(names) == 22
    spec = cell.deploy["logits_check"]
    assert spec["prompt_lens"] == [1600, 300] and spec["decode_steps"] == 8
    # the limit lies over every reading of the system and under every
    # mistake the cell's file says it fails
    got = spec["chip_readings"]
    assert got["system_max"] < spec["rel_tol"] \
        < min(got["reference_in_float8_e4m3_min"],
              *got["mistakes_failed"].values())
    assert all(v < spec["rel_tol"] for v in got["mistakes_unseen"].values())


def test_the_file_holds_every_number_of_the_catalog():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Phi-4-mini-flash-reasoning")
    conf = manifest.load_cell(CELL).config
    row = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert row["source"] == entry["source_url"] == conf["source"]
    assert [k for k, v in entry["config"].items() if conf.get(k) != v] == []


def test_served_model_is_the_published_one_uncut():
    cfg = _served_config()
    assert [(k, c.period, c.num_layers) for k, c in cfg.segments] == [
        ("mamba_window_blocks", ("mamba", "window"), 8),
        ("mamba_full_blocks", ("mamba", "full"), 1),
        ("gmu_cross_blocks", ("gmu", "cross"), 7)]
    assert 3.84e9 < cfg.num_params() < 3.86e9 and cfg.dtype == "bfloat16"
    assert (cfg.vocab_size, cfg.tie_embeddings) == (200064, True)


def test_warmup_reaches_every_bucket_and_tier_of_the_cell():
    """One request at a time: a prompt of n tokens runs chunks of 512 rows,
    a last chunk in the 64-row bucket if it fits, then decode ticks."""
    cell = manifest.load_cell(CELL)
    eng = cell.deploy["engine"]
    bs, budget = eng["block_size"], eng["token_budget"]
    tiers = [eng["max_blocks_per_seq"] // 4, eng["max_blocks_per_seq"] // 2,
             eng["max_blocks_per_seq"]]

    def tier(pos):
        return next(t for t in tiers if pos // bs + 1 <= t)

    seen = set()
    for n in cell.deploy["warmup"]["prompt_lens"]:
        at = 0
        while at < n:
            rows = min(budget, n - at)
            seen.add((64 if rows <= 64 else budget, tier(at + rows - 1)))
            at += rows
        seen.add((64, tier(n)))
    assert seen == {(b, t) for b in (64, budget) for t in tiers}


# ------------------------------------------------------------------ #
def _toy():
    conf = dict(manifest.load_cell(CELL).config)
    cfg = dataclasses.replace(
        model_config.build(conf, "serve", rehearse=True), dtype="float32")
    hf = {**model_config.hf_kwargs(conf, "serve"), **conf["rehearse"]}
    reference = manifest.load_plugin("reference", conf["reference"])
    return cfg, reference, reference.arch_from_config(conf, hf)


def test_the_named_reference_agrees_with_the_program_forward():
    """The rehearsal size keeps one of each of the five kinds of layer and a
    window shorter than the tokens; the weights are the benchmark's own
    (biases and norm offsets off zero)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import transformer as T

    cfg, reference, arch = _toy()
    assert set(cfg.layer_kinds) == {"mamba", "window", "full", "gmu", "cross"}
    params = weights.init_on_device(cfg, 3)
    assert all(float(jnp.abs(x).max()) > 0 for x in jax.tree.leaves(params))
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 48)).astype(np.int32)
    assert arch["window"] < 48
    with jax.default_matmul_precision("highest"):
        got = T.forward(params, jnp.asarray(toks), cfg)
    want = reference.forward_logits(params, toks, arch)
    # float32 both sides, the same equations: rounding alone
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 2e-5
    some = reference.forward_logits(params, toks, arch, at=[47, 4, 5])
    np.testing.assert_allclose(some, want[:, np.asarray([47, 4, 5])],
                               rtol=1e-6, atol=1e-6)


def test_the_reference_imports_nothing_of_the_program():
    _, reference, _ = _toy()
    with open(reference.__file__) as f:
        source = f.read()
    assert "deepspeed_tpu" not in source.split('"""', 2)[2]
    assert reference.kinds(32)[16:20] == ["mamba", "full", "gmu", "cross"]


def test_hybrid_flops_by_hand():
    cfg = _served_config()
    per = hybrid_flops.mixer_matmul_params(cfg)
    h, di = 2560, 5120
    assert per["mamba"] == h * 2 * di + di * 192 + 160 * di + di * h
    assert per["gmu"] == 2 * h * di
    # q and o are h x h; k and v h x h/2 each (20 of 40 heads)
    assert per["window"] == per["full"] == 3 * h * h
    assert per["cross"] == 2 * h * h
    mlp = 3 * h * 10240
    active = 200064 * h + 9 * per["mamba"] + 9 * per["window"] \
        + 7 * per["gmu"] + 7 * per["cross"] + 32 * mlp
    assert hybrid_flops.active_matmul_params(cfg) == active
    # the tied table once, the vectors not at all: just under num_params
    assert 0 < cfg.num_params() - active < 2e6
    # at 4,096 tokens: a window layer sees 512 positions, the full layer
    # and the seven cross layers 2,048 on average; nine recurrences
    attn = 4 * 40 * 64 * (8 * 512 + 8 * 2048)
    scan = 9 * 2 * 3 * di * 16
    assert hybrid_flops.train_flops_per_token(cfg, 0, 4096) \
        == 6.0 * active + 3.0 * (attn + scan)


def test_shared_and_window_rooflines_by_hand(monkeypatch):
    s, w = shared_paged_attention, window_paged_attention
    assert s.classify(_Op(SHARED)) == "shared" and w.classify(
        _Op(WINDOW)) == "window"
    assert s.classify(_Op(WINDOW)) is None and w.classify(_Op(SHARED)) is None
    assert s.classify(_Op(DENSE)) is None and w.classify(_Op(DENSE)) is None
    # a position: 10 paired heads x 128 keys and as many values in bf16
    assert s.position_bytes(SHARED) == s.position_bytes(WINDOW) == (32, 5120)
    assert s.needed_ops(1000, 40, 64) == 2 * 40 * 3 * 64 * 1000

    class Run:
        peaks, model = PEAKS, _served_config()

    # a decode tick of 64 rows at ~2.2k (4,500 blocks; each window 512
    # positions) and a tick that also holds a 448-row chunk from 0
    ticks = [_tick(1.0, 1.1, 4500, 0, 64 * 512, 0),
             _tick(2.0, 2.2, 4400, 448 * 449 // 2, 63 * 512 + 448,
                   448 * 449 // 2)]
    monkeypatch.setattr(tick_attrs, "per_tick", lambda run: ticks)
    # 8 calls of each tick (the window layers; the full and cross layers),
    # and 8 of a tick the stretch cut
    for k, text, need in (
            (s, SHARED, [4500 * 32 * 5120, 4400 * 32 * 5120]),
            (w, WINDOW, [64 * 512 * 5120, (63 * 512 + 448) * 5120])):
        calls = [_Op(text, at=t + i * 1e-3) for t in (0.5, 1.0, 2.0)
                 for i in range(8)]
        seconds, bound = k.least_seconds(Run, calls)
        ops = s.needed_ops(448 * 449 // 2, 40, 64) * 8 / 197e12
        assert ops < need[1] * 8 / 819e9                  # memory-bound
        assert seconds == pytest.approx(8 * sum(need) / 819e9)
        assert bound == "memory"
        assert k.least_seconds(Run, []) is None
    # a long chunk deep in a context is compute-bound
    ticks[1] = _tick(2.0, 2.2, 100, 512 * 4000)
    seconds, bound = s.least_seconds(Run, [_Op(SHARED, at=2.0)])
    assert seconds == pytest.approx(
        s.needed_ops(512 * 4000, 40, 64) / 197e12) and bound == "compute"
    # a program whose spans lack the window's attributes (every other
    # model), or that wrote no spans at all: nothing to read
    assert w.least_seconds(Run, [_Op(WINDOW, at=2.0)]) is None
    monkeypatch.setattr(tick_attrs, "per_tick", lambda run: [])
    assert s.least_seconds(Run, [_Op(SHARED, at=2.0)]) is None


def test_the_five_readers_on_a_made_up_run(monkeypatch):
    from benchmarks import gap_chain

    def fusion(n, seconds):
        op = _Op(f'%fusion.{n} = bf16[64,5120]{{1,0}} fusion(bf16[64,5120]'
                 f'{{1,0}} %x), kind=kLoop', seconds)
        op.is_mosaic = False
        return op

    scan, gate, mlp = fusion(1, 4e-3), fusion(2, 1e-3), fusion(3, 5e-3)
    ops = [_Op(WINDOW, 1e-3, at=1.0 + i * 2e-3) for i in range(8)] \
        + [_Op(SHARED, 1e-3, at=1.001 + i * 2e-3) for i in range(8)] \
        + [scan, gate, mlp]
    monkeypatch.setattr(tick_attrs, "per_tick", lambda run: [
        _tick(1.0, 1.2, 4500, 0, 64 * 512, 0)])
    monkeypatch.setattr(gap_chain, "trace_file", lambda run: "a.xplane.pb")
    monkeypatch.setattr(gap_chain, "op_scopes", lambda path: {
        (1, scan.text): "jit(tick)/while/body/closed_call/ssm/ssm_scan/mul",
        (1, gate.text): "jit(tick)/while/body/closed_call/gmu/mul",
        (1, mlp.text): "jit(tick)/while/body/closed_call/mlp/dot_general",
        (1, WINDOW): "jit(tick)/while/body/closed_call/attn/"
                     "window_paged_attention/pallas_call"})
    gauges = {"fastgen_state_slots_in_use_peak": {(): 64.0}}
    snap = {"counters": {}, "gauges": gauges, "histograms": {}}

    class Run:
        peaks, model = PEAKS, _served_config()
        trace = _Trace(ops)
        telemetry = harness.Telemetry(snap, snap)
        extras = {"engine": dict(manifest.load_cell(CELL).deploy["engine"])}
        cache = {}
        cell = manifest.load_cell(CELL)

    busy = 16e-3 + 10e-3
    assert ssm_share_pct.read(Run) == pytest.approx(100 * 4e-3 / busy)
    assert gmu_share_pct.read(Run) == pytest.approx(100 * 1e-3 / busy)
    assert window_attention_roofline.read(Run) == pytest.approx(
        100 * 8 * 64 * 512 * 5120 / 819e9 / 8e-3)
    assert shared_attention_roofline.read(Run) == pytest.approx(
        100 * 8 * 4500 * 32 * 5120 / 819e9 / 8e-3)
    assert state_slots_peak_pct.read(Run) == pytest.approx(100 * 64 / 72)

    # the parent's program, or any other model's: no such kernel, scope,
    # gauge or slots -> nothing
    other = fusion(4, 1e-3)

    class Parent(Run):
        trace = _Trace([_Op(DENSE, 1e-3), other])
        telemetry = harness.Telemetry(
            {"counters": {}, "gauges": {}, "histograms": {}},
            {"counters": {}, "gauges": {}, "histograms": {}})
        extras = {"engine": {"n_blocks": 640}}

    monkeypatch.setattr(gap_chain, "op_scopes", lambda path: {
        (1, other.text): "jit(tick)/while/body/closed_call/mlp/dot_general"})
    monkeypatch.setattr(tick_attrs, "per_tick", lambda run: [])
    readers = (ssm_share_pct, gmu_share_pct, window_attention_roofline,
               shared_attention_roofline, state_slots_peak_pct)
    for reader in readers:
        assert reader.read(Parent) is None, reader.__name__
    Parent.trace = None
    for reader in readers:
        assert reader.read(Parent) is None, reader.__name__
