"""The ``kimi_linear`` family's files (configuration ``kimi-linear-48b-a3b``,
cell ``serve-kimi-linear-48b-rollout-closed``, mix ``rollout-closed``):
loaded by name, held to the numbers of the issue that asked for them
(ISSUE 41) and to the catalog's row, the reference against the program at
the rehearsal size, the warm-up against every program a window can meet,
the flops counter and the two rooflines' needs by hand, and the four new
readers on a made-up run record.

What a family needs beside its configuration, by name: a ``reference``
(``arch_from_config``, ``forward_logits``, ``next_token_loss``), a ``flops``
counter (``train_flops_per_token``), a cell file, a mix, and a reader a
per-layer metric it brings. How many families, cells or metrics there are
is nobody's to assert here.
"""
import dataclasses
import json

import numpy as np
import pytest

from benchmarks import harness, manifest, model_config, weights
from benchmarks.flops import kimi_linear as kimi_flops
from benchmarks.layer_metrics import (kda_chunk_roofline, kda_share_pct,
                                      kda_state_rows_per_tick,
                                      kda_step_roofline)
from benchmarks.roofline import kda_chunk, kda_step, tick_attrs

M = manifest.load_manifest()
CELL = "serve-kimi-linear-48b-rollout-closed"
CONFIG = "kimi-linear-48b-a3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}

# the one-row form's call of the (256, 128) tick as compiled for a v5e at
# the cell's sizes: 6 kda layers x (272 + 1) slots' matrices
STEP = ('%kda_step.3 = (f32[256,32,128]{2,1,0}, f32[1638,32,128,128]'
        '{3,2,1,0}) custom-call(s32[256]{0} %s, s32[256]{0} %f, '
        'f32[256,128,128]{2,1,0} %x, f32[256,32,128]{2,1,0} %v, '
        'f32[1638,32,128,128]{3,2,1,0} %state), '
        'custom_call_target="tpu_custom_call"')


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


# ------------------------------------------------------------------ #
def test_cell_config_and_mix_load_by_name_with_the_issues_numbers():
    cell = manifest.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.runner) \
        == (CONFIG, "rollout-closed", 1, "serve")
    eng = cell.deploy["engine"]
    assert (eng["block_size"], eng["token_budget"], eng["state_slots"]) \
        == (32, 2048, 272)
    assert cell.deploy["serving"]["max_queue"] >= 256
    p = cell.traffic["params"]
    assert cell.traffic["generator"] == "closed_loop"
    assert (p["clients"], p["preroll_s"]) == (256, 10)
    assert p["prompt_tokens"]["dist"] == p["output_tokens"]["dist"] \
        == "uniform"
    assert (p["prompt_tokens"]["min"], p["prompt_tokens"]["max"]) \
        == (512, 1024)
    # ISSUE 41's answers, or its one pre-stated fallback (which the mix's
    # notes then carry the numbers for)
    answers = (p["output_tokens"]["min"], p["output_tokens"]["max"])
    assert (answers, eng["max_blocks_per_seq"]) in (
        ((1024, 3072), 128), ((512, 1536), 80), ((768, 1280), 80))
    if answers != (1024, 3072):
        assert "fewer than 100" in cell.traffic["notes"]
    # the longest sequence fits a table; every client has a slot; all 256
    # at their longest hold 80 % of the blocks, the degrade watermark, and
    # a decode tick of every client is the small bucket, unpadded
    longest = p["prompt_tokens"]["max"] + answers[1]
    assert longest <= eng["max_blocks_per_seq"] * eng["block_size"]
    assert eng["state_slots"] >= p["clients"]
    assert 256 * (longest // 32) / (eng["n_blocks"] - 1) <= 0.8001
    assert eng["token_budget"] // 8 == p["clients"]
    row = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert row["reduced"] == ["num_hidden_layers", "linear_attn_config",
                              "num_experts", "vocab_size"]
    conf = cell.config
    assert conf["as_run"]["serve"] == {"num_hidden_layers": 8}
    assert set(conf["published"]) == set(row["reduced"])
    assert conf["deployment"]["chips_that_share_a_layer"] == 8
    assert {m.name for m in cell.end_to_end} == {"serve_out_tokens_per_s",
                                                 "setup_s"}
    names = {m.name for m in cell.per_layer}
    new = {"kda_share_pct", "kda_step_roofline", "kda_chunk_roofline",
           "kda_state_rows_per_tick"}
    assert new | {"latent_share_pct", "latent_attention_roofline",
                  "experts_share_pct", "held_expert_gmm_roofline",
                  "expert_held_pair_share_pct", "expert_rows_per_held_expert",
                  "expert_load_imbalance", "state_slots_peak_pct",
                  "closed.decode_rows_per_tick", "closed.device_idle_pct",
                  "closed.hbm_peak_gb", "closed.win_ticks_per_s"} <= names
    # readers of another family's kernels and shares do not hold here
    assert not names & {"closed.paged_share_pct", "swa_attention_roofline",
                        "global_attention_roofline", "ssm_share_pct",
                        "conv_share_pct", "expert_gmm_roofline"}
    for m in M["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_out_tokens_per_s"
    spec = cell.deploy["logits_check"]
    # two prompts in one stream of 2,048-row ticks: the second starts
    # inside the first tick and is cut by its end; then decode ticks
    a, b = spec["prompt_lens"]
    assert a < eng["token_budget"] < a + b
    assert spec["decode_steps"] >= 127 and a + spec["decode_steps"] < longest


def test_the_limit_lies_between_its_readings():
    """Over every reading of the system, under the reference computed in
    float8_e4m3, with room on both sides; each mistake of the issue's list
    either fails the limit or is named as held by a CPU test."""
    spec = manifest.load_cell(CELL).deploy["logits_check"]
    got = spec["chip_readings"]
    system = list(got["system"].values())
    assert len(system) >= 12 and max(system) == got["system_max"]
    lower = min(got["reference_computed_in_float8_e4m3"].values())
    tol = spec["rel_tol"]
    assert 1.2 * max(system) < tol < lower / 1.2
    seen = {k for k, v in got["system_against_a_mistaken_reference"].items()
            if v > tol}
    seen |= {k for k, v in
             got["system_with_a_fault_against_the_reference"].items()
             if v > tol}
    unseen = set(got["the_check_cannot_see"])
    assert seen | unseen >= {
        "decay-dropped", "b-is-one", "taps-reversed", "no-l2norm",
        "top-7-for-top-8", "rotary-on-latent",
        "state-dropped-at-tick-boundaries",
        "state-carried-into-the-next-sequence"}
    for name, test in got["the_check_cannot_see"].items():
        assert test.startswith("tests/unit/test_kimi_linear_stack.py::")


def test_the_file_holds_every_number_of_the_catalog():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    conf = manifest.load_cell(CELL).config
    entry = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert conf["published"][key] == value, key
        else:
            assert conf[key] == value, key
    la, theirs = conf["linear_attn_config"], \
        row["config"]["linear_attn_config"]
    assert {k for k in theirs if la[k] != theirs[k]} \
        == {"kda_layers", "full_attn_layers"}
    # layers 1-8 of the published lists
    assert la["kda_layers"] == [i for i in theirs["kda_layers"] if i <= 8]
    assert la["full_attn_layers"] == [4, 8]


def test_served_model_is_the_share_the_file_describes():
    import jax

    from deepspeed_tpu.models import paged as PG

    cfg = _served_config()
    conf = manifest.load_cell(CELL).config
    eng = manifest.load_cell(CELL).deploy["engine"]
    assert cfg.layer_kinds == ("kda", "kda", "kda", "latent") * 2
    assert (cfg.n_experts, cfg.router_experts, cfg.moe_top_k,
            cfg.moe_first_expert) == (32, 256, 8, 0)
    assert (cfg.vocab_size, cfg.hidden_size, cfg.first_dense_layers) \
        == (20480, 2304, 1)
    assert cfg.pos_emb == "none" and cfg.mla and not cfg.tie_embeddings
    assert cfg.num_params() == conf["bytes"]["num_params_as_run"]
    pool = jax.eval_shape(lambda: PG.init_paged_kv(
        cfg, eng["n_blocks"], eng["block_size"],
        state_slots=eng["state_slots"], max_run=eng["token_budget"]))
    size = {k: int(np.prod(v.shape)) * v.dtype.itemsize
            for k, v in pool.items()}
    assert size["kda"] // 273 == 6 * 2_097_152
    assert size["kda_conv"] // 273 == 6 * 73_728
    assert (size["kda"] + size["kda_conv"]) // 273 \
        == conf["bytes"]["state_bytes_a_sequence"]
    assert size["latent"] == 2 * eng["n_blocks"] * 32 * 1280
    # weights 4.19 + state 3.56 + latent pool: the cell's arguments
    total = 2 * conf["bytes"]["parameters_as_run"] + sum(size.values())
    assert total / 16e9 > 0.25


def test_warmup_reaches_every_bucket_and_tier_of_the_cell():
    """One request at a time: a prompt of n tokens runs chunks of 2,048
    rows (a chunk that fits the 256-row bucket runs there), then decode
    ticks; the window's ticks are those programs and no other."""
    cell = manifest.load_cell(CELL)
    eng = cell.deploy["engine"]
    bs, budget = eng["block_size"], eng["token_budget"]
    small = budget // 8
    tiers = [eng["max_blocks_per_seq"] // 4, eng["max_blocks_per_seq"] // 2,
             eng["max_blocks_per_seq"]]

    def tier(pos):
        return next(t for t in tiers if pos // bs + 1 <= t)

    seen = set()
    for n in cell.deploy["warmup"]["prompt_lens"]:
        at = 0
        while at < n:
            rows = min(budget, n - at)
            seen.add((small if rows <= small else budget,
                      tier(at + rows - 1)))
            at += rows
        seen.add((small, tier(n)))
    assert seen == {(b, t) for b in (small, budget) for t in tiers}
    p = cell.traffic["params"]
    longest = p["prompt_tokens"]["max"] + p["output_tokens"]["max"]
    assert tier(longest - 1) == tiers[-1]


# ------------------------------------------------------------------ #
def _toy():
    conf = dict(manifest.load_cell(CELL).config)
    cfg = dataclasses.replace(
        model_config.build(conf, "serve", rehearse=True), dtype="float32")
    hf = {**model_config.hf_kwargs(conf, "serve"), **conf["rehearse"]}
    reference = manifest.load_plugin("reference", conf["reference"])
    return cfg, reference, reference.arch_from_config(conf, hf)


def test_the_named_reference_agrees_with_the_program_forward():
    """The rehearsal size keeps the cut's pattern and heads of 128; the
    weights are the benchmark's own (norm gains off one, the router's bias
    off zero)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import transformer as T

    cfg, reference, arch = _toy()
    assert cfg.layer_kinds == ("kda", "kda", "kda", "latent") * 2
    assert (cfg.n_experts, cfg.router_experts, cfg.kda_head_dim) \
        == (4, 16, 128)
    params = weights.init_on_device(cfg, 3)
    assert all(float(jnp.abs(x).max()) > 0 for x in jax.tree.leaves(params))
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = T.forward(params, jnp.asarray(toks), cfg)
    want = reference.forward_logits(params, toks, arch)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 2e-5
    some = reference.forward_logits(params, toks, arch, at=[47, 4, 5])
    np.testing.assert_allclose(some, want[:, np.asarray([47, 4, 5])],
                               rtol=1e-6, atol=1e-6)
    routes = []
    reference.forward_logits(params, toks[:1], arch, at=[3], routes=routes)
    assert len(routes) == 7 and routes[0].shape == (1, 4)
    loss = reference.next_token_loss(params, toks, arch)
    assert abs(loss - np.log(cfg.vocab_size)) < 1.0


def test_the_reference_imports_nothing_of_the_program():
    _, reference, arch = _toy()
    with open(reference.__file__) as f:
        source = f.read()
    assert "deepspeed_tpu" not in source.split('"""', 2)[2]
    assert arch["kinds"] == ("kda", "kda", "kda", "latent") * 2
    for name in ("arch_from_config", "forward_logits", "next_token_loss"):
        assert callable(getattr(reference, name))
    with pytest.raises(ValueError, match="model_type"):
        reference.arch_from_config({"model_type": "llama"}, {})


def test_kimi_linear_flops_by_hand():
    cfg = _served_config()
    per = kimi_flops.mixer_matmul_params(cfg)
    # the mixers' counts less their elementwise leaves (taps, A_log,
    # dt_bias and the gain; the latent's norm)
    assert per["kda"] == 39_514_272 - 3 * 4 * 4096 - 32 - 4096 - 128
    assert per["latent"] == 29_114_880 - 512
    h = 2304
    expert_layer = 3 * h * (8 * 1024 + 1024) + h * 256
    want = 20480 * h + 6 * per["kda"] + 2 * per["latent"] \
        + 3 * h * 9216 + 7 * expert_layer
    assert kimi_flops.active_matmul_params(cfg) == want
    rule = 8 * 32 * 128 ** 2 * 6
    attn = 2 * 32 * (128 + 64 + 128) * 512 * 2
    assert kimi_flops.train_flops_per_token(cfg, 0, 1024) \
        == pytest.approx(6.0 * want + 3.0 * (rule + attn))


def test_the_rooflines_needs_by_hand():
    cfg = _served_config()
    assert kda_step.state_bytes(cfg) == 2_097_152
    # a decode tick of 256 rows: every row's matrix once in and once out
    assert kda_step.needed_bytes(256, cfg) == 256 * 2 * 2_097_152
    # a chunk of 64 rows a head of 128: 6 C^2 D + 6 C D^2 operations
    assert kda_chunk.needed_ops(64, 1, 128) \
        == 6 * 64 * 64 * 128 + 6 * 64 * 128 * 128
    assert kda_chunk.needed_ops(2048, 32, 128) \
        == 2048 * 32 * (6 * 64 * 128 + 6 * 128 * 128)
    assert kda_chunk.needed_bytes(3, 32, 128) == 3 * 2 * 2_097_152

    class Run:
        peaks, model, trace, cache = PEAKS, cfg, None, {}

    import benchmarks.roofline.tick_attrs as TA
    ticks = [{"start": 1.0, "end": 1.1, "kda_step_rows": 256,
              "kda_chunk_rows": 0, "kda_state_rows": 256},
             {"start": 1.2, "end": 1.4, "kda_step_rows": 250,
              "kda_chunk_rows": 1798, "kda_state_rows": 253}]
    real = TA.per_tick
    TA.per_tick = lambda run: ticks
    try:
        calls = [_Op(STEP, at=1.0 + i * 1e-3) for i in range(6)] \
            + [_Op(STEP, at=1.2 + i * 1e-3) for i in range(6)]
        seconds, bound = kda_step.least_seconds(Run, calls)
        assert seconds == pytest.approx(
            6 * (256 + 250) * 2 * 2_097_152 / 819e9)
        assert bound == "memory"
        seconds, bound = kda_chunk.least_seconds(Run, calls)
        ops = 1798 * 32 * (6 * 64 * 128 + 6 * 128 * 128) / 197e12
        mem = 3 * 2 * 2_097_152 / 819e9
        assert ops > mem and bound == "compute"
        assert seconds == pytest.approx(6 * ops)
    finally:
        TA.per_tick = real
    assert kda_step.classify(_Op(STEP)) == "kda_step"
    assert kda_step.classify(_Op(STEP.replace("kda_step", "gmm"))) is None


def test_the_new_readers_on_a_made_up_run(monkeypatch):
    proj = ('%fusion.7 = bf16[256,12288]{1,0} fusion(bf16[256,2304]{1,0} '
            '%a), kind=kOutput')
    chunk = ('%fusion.8 = f32[32,32,64,64]{3,2,1,0} fusion(f32[2048,32,128]'
             '{2,1,0} %k), kind=kLoop')
    other = '%fusion.9 = bf16[256,2304]{1,0} fusion(bf16[256,2304]{1,0} %b)'

    class Op(_Op):
        is_mosaic = False

    ops = [Op(proj, 1e-3, at=1.0), _Op(STEP, 3e-3, at=1.01),
           Op(other, 5e-3, at=1.02), Op(chunk, 1e-3, at=1.21)]
    from benchmarks import gap_chain

    monkeypatch.setattr(gap_chain, "trace_file", lambda run: "x.pb")
    monkeypatch.setattr(gap_chain, "op_scopes", lambda path: {
        (0, proj): "jit(tick)/while/body/kda/dot_general",
        (0, STEP): "jit(tick)/while/body/kda/kda_step",
        (0, chunk): "jit(tick)/while/body/kda/kda_chunk/while/body/dot",
        (0, other): "jit(tick)/while/body/experts/gmm"})
    monkeypatch.setattr(tick_attrs, "per_tick", lambda run: [
        {"start": 1.0, "end": 1.1, "blocks": 8192, "prompt_attended": 0,
         "kda_step_rows": 256, "kda_chunk_rows": 0, "kda_state_rows": 256},
        {"start": 1.2, "end": 1.3, "blocks": 8192, "prompt_attended": 900,
         "kda_step_rows": 250, "kda_chunk_rows": 1798,
         "kda_state_rows": 253},
        {"start": 1.4, "end": 1.5, "blocks": 8192, "prompt_attended": 0,
         "kda_step_rows": 256, "kda_chunk_rows": 0, "kda_state_rows": 256}])
    class Run:
        peaks, model = PEAKS, _served_config()
        trace = _Trace(ops)
        telemetry = harness.Telemetry(*[{"counters": {}, "gauges": {},
                                         "histograms": {}}] * 2)
        extras, cache = {}, {}

    assert kda_share_pct.read(Run) == pytest.approx(100 * 5e-3 / 10e-3)
    assert kda_state_rows_per_tick.read(Run) == 256.0
    # one call in the first tick's run: its need over its time
    assert kda_step_roofline.read(Run) == pytest.approx(
        100 * 256 * 2 * 2_097_152 / 819e9 / 3e-3)
    need = 6 * 1798 * 32 * (6 * 64 * 128 + 6 * 128 * 128) / 197e12
    assert kda_chunk_roofline.read(Run) == pytest.approx(100 * need / 1e-3)
    assert Run.extras["roofline_bound"] == {"kda_step": "memory",
                                            "kda_chunk": "compute"}

    # the parent's program, or any other model's: no such scope, call or
    # attribute -> nothing, and nothing raises
    class Parent(Run):
        trace = _Trace([Op(other, 1e-3)])
        extras, cache = {}, {}

    monkeypatch.setattr(gap_chain, "op_scopes", lambda path: {
        (0, other): "jit(tick)/while/body/experts/gmm"})
    monkeypatch.setattr(tick_attrs, "per_tick", lambda run: [
        {"start": 0.0, "end": 1.0, "blocks": 10, "prompt_attended": 3}])
    readers = (kda_share_pct, kda_state_rows_per_tick, kda_step_roofline,
               kda_chunk_roofline)
    for reader in readers:
        assert reader.read(Parent) is None, reader.__name__
    Parent.trace, Parent.cache = None, {}
    monkeypatch.setattr(gap_chain, "trace_file", lambda run: None)
    monkeypatch.setattr(tick_attrs, "per_tick", lambda run: [])
    for reader in readers:
        assert reader.read(Parent) is None, reader.__name__
