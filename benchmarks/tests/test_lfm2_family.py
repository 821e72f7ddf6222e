"""The ``lfm2_moe`` family's files (configuration ``lfm2-24b-a2b``, cell
``serve-lfm2-24b-concurrent-closed``, mix ``concurrent-closed``): loaded by
name, held to the numbers of the issue that asked for them (ISSUE 37) and
to the catalog's row, the reference against the program at the rehearsal
size, the warm-up against every program a window can meet, the flops
counter by hand, the existing attention roofline on the call text of the
tick compiled for a v5e (heads of 64 lie two to a pool row), and the two
new readers on a made-up run record.

What a family needs beside its configuration, by name: a ``reference``
(``arch_from_config``, ``forward_logits``, ``next_token_loss``), a ``flops``
counter (``train_flops_per_token``), a cell file, a mix, and a reader a
per-layer metric it brings. How many families there are is nobody's to
assert here.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from benchmarks import harness, manifest, model_config, weights
from benchmarks.flops import lfm2 as lfm2_flops
from benchmarks.layer_metrics import (conv_share_pct,
                                      conv_state_rows_per_tick,
                                      global_attention_roofline,
                                      state_slots_peak_pct)
from benchmarks.roofline import global_attention, swa_attention, tick_attrs

M = manifest.load_manifest()
CELL = "serve-lfm2-24b-concurrent-closed"
CONFIG = "lfm2-24b-a2b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# the attention call of the (256, 64) tick as compiled for a v5e at the
# cell's sizes: 272 + 1 slots, two attention layers x 20,480 blocks of 32
# positions, 8 key-value heads of 64 stored two to a row
GLOBAL = ('%global_attention.14 = bf16[256,32,128]{2,1,0} custom-call('
          's32[273,64]{1,0} %t, s32[768]{0} %m, bf16[256,32,128]{2,1,0} %q, '
          'bf16[40960,32,4,128]{3,2,1,0} %k, bf16[40960,32,4,128]{3,2,1,0} '
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


# ------------------------------------------------------------------ #
def test_cell_config_and_mix_load_by_name_with_the_issues_numbers():
    cell = manifest.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.runner) \
        == (CONFIG, "concurrent-closed", 1, "serve")
    eng = cell.deploy["engine"]
    assert eng == {"n_blocks": 20480, "block_size": 32,
                   "max_blocks_per_seq": 64, "token_budget": 2048,
                   "state_slots": 272}
    assert cell.deploy["serving"]["max_queue"] >= 256
    p = cell.traffic["params"]
    assert cell.traffic["generator"] == "closed_loop"
    assert (p["clients"], p["preroll_s"]) == (256, 10)
    assert p["prompt_tokens"] == {"dist": "uniform", "min": 256, "max": 768}
    assert p["output_tokens"] == {"dist": "uniform", "min": 768, "max": 1280}
    # the longest sequence fits a table; every client has a slot; all 256
    # at their longest hold 80 % of the blocks, the degrade watermark, and
    # a decode tick of every client is the small bucket, unpadded
    longest = 768 + 1280
    assert longest <= eng["max_blocks_per_seq"] * eng["block_size"]
    assert eng["state_slots"] >= p["clients"]
    assert 256 * (longest // 32) / (eng["n_blocks"] - 1) <= 0.8001
    assert eng["token_budget"] // 8 == p["clients"]
    row = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert row["reduced"] == ["num_hidden_layers", "layer_types"]
    conf = cell.config
    assert conf["as_run"]["serve"] == {"num_hidden_layers": 10}
    assert set(conf["published"]) == set(row["reduced"])
    assert conf["layer_types"] == conf["published"]["layer_types"][:10]
    assert {m.name for m in cell.end_to_end} == {"serve_out_tokens_per_s",
                                                 "setup_s"}
    names = {m.name for m in cell.per_layer}
    new = {"conv_share_pct", "conv_state_rows_per_tick"}
    assert new | {"experts_share_pct", "expert_gmm_roofline",
                  "expert_load_imbalance", "state_slots_peak_pct",
                  "global_attention_roofline", "closed.decode_rows_per_tick",
                  "closed.device_idle_pct", "closed.hbm_peak_gb",
                  "closed.win_ticks_per_s"} <= names
    # readers of another family's kernels and shares do not hold here
    assert not names & {"closed.paged_share_pct", "swa_attention_roofline",
                        "latent_attention_roofline", "ssm_share_pct",
                        "held_expert_gmm_roofline"}
    for m in M["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_out_tokens_per_s"
    spec = cell.deploy["logits_check"]
    # two prompts in one stream of 2,048-row ticks: the second starts
    # inside the first tick and is cut by its end
    a, b = spec["prompt_lens"]
    assert a < eng["token_budget"] < a + b
    assert a + spec["decode_steps"] < longest


def test_the_limit_lies_between_its_readings():
    """Over every reading of the system, under the reference computed in
    the nearest lower precision and under every mistake and fault the
    check can see; what it cannot see is named with the test that holds
    it."""
    spec = manifest.load_cell(CELL).deploy["logits_check"]
    got = spec["chip_readings"]
    assert got["decode_steps"] == spec["decode_steps"]
    assert len(got["system"]) >= 8
    assert max(got["system"].values()) == got["system_max"] \
        < spec["rel_tol"]
    assert spec["rel_tol"] < got["reference_computed_in_float8_e4m3_min"]
    seen = got["system_against_a_mistaken_reference"]
    faults = got["system_with_a_fault_against_the_reference"]
    unseen = got["the_check_cannot_see"]
    assert set(seen) | set(faults) >= {
        "no-qk-norm", "no-rope", "no-expert-bias-in-selection",
        "taps-reversed", "state-dropped-at-tick-boundaries",
        "state-carried-into-the-next-sequence"}
    for name, value in {**seen, **faults}.items():
        if name in unseen:
            assert "tests/unit/test_lfm2_stack.py" in unseen[name]
        else:
            assert spec["rel_tol"] < value, name


def test_the_file_holds_every_number_of_the_catalog():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "LFM2-24B-A2B")
    conf = manifest.load_cell(CELL).config
    row = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert row["source"] == entry["source_url"] == conf["source"]
    assert row["file"] == f"benchmarks/configs/{CONFIG}.json"
    differ = [k for k, v in entry["config"].items() if conf.get(k) != v]
    assert sorted(differ) == sorted(row["reduced"])
    assert all(conf["published"][k] == entry["config"][k] for k in differ)
    # no width among them, and the nested group copied whole
    assert not {"hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "num_experts", "vocab_size",
                "num_attention_heads", "num_key_value_heads",
                "conv_L_cache"} & set(differ)
    assert conf["rope_parameters"] == entry["config"]["rope_parameters"]
    # what the catalog's config does not carry is written down
    for key in ("order of a layer", "tie_word_embeddings", "dense layers",
                "conv mixer", "attention", "rotary", "router", "the + 1e-6"):
        assert key in conf["assumed"], key
    assert "pipeline of four chips" in conf["deployment"]["chips"]


def test_served_model_is_the_stage_the_file_describes():
    cfg = _served_config()
    assert [(k, c.num_layers, c.n_experts, c.layer_kinds)
            for k, c in cfg.segments] == [
        ("dense_blocks", 2, 0, ("conv", "conv")),
        ("blocks", 8, 64, ("full", "conv", "conv", "conv") * 2)]
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            cfg.ffn_size, cfg.moe_ffn, cfg.moe_top_k, cfg.conv_taps,
            cfg.vocab_size, cfg.max_seq_len) \
        == (2048, 32, 8, 64, 11776, 1536, 4, 3, 65536, 128000)
    assert cfg.tie_embeddings and cfg.moe_route_norm_eps == 1e-6
    # 5.27 B parameters, 10.53 GB in bfloat16 (the count has a final-norm
    # bias too many: tests/unit/test_latent_moe_serving.py)
    assert cfg.num_params() - cfg.hidden_size == 5_267_090_176
    assert cfg.dtype == "bfloat16"
    # the memory the cell's notes promise: 4,096 B a token, 64 KB a slot
    from deepspeed_tpu.models import paged as PG
    import jax

    eng = manifest.load_cell(CELL).deploy["engine"]
    pool = jax.eval_shape(lambda: PG.init_paged_kv(
        cfg, eng["n_blocks"], eng["block_size"],
        state_slots=eng["state_slots"], max_run=eng["token_budget"]))
    assert pool["k"].shape == (2, 20480, 32, 4, 128)      # two heads a row
    per_token = sum(np.prod(pool[n].shape[3:]) * 2 * 2 for n in ("k", "v"))
    assert per_token == 4096
    assert np.prod(pool["conv"].shape) * 2 // 273 == 65536
    assert sum(np.prod(x.shape) * 2 for x in pool.values()) \
        == 2_702_245_888


def test_warmup_reaches_every_bucket_and_tier_of_the_cell():
    """One request at a time: a prompt of n tokens runs chunks of 2,048
    rows (a chunk that fits the 256-row bucket runs there), then decode
    ticks; the window's ticks are those programs and no other: a tick is
    256 rows or 2,048, its tables a quarter, a half or the whole of 64
    blocks by its longest sequence."""
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
    # the traffic's lengths reach no further than the warm-up's programs
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
    """The rehearsal size keeps the cut's pattern and heads of 64; the
    weights are the benchmark's own (norm gains off one, the router's bias
    off zero)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import transformer as T

    cfg, reference, arch = _toy()
    assert cfg.layer_kinds == ("conv", "conv") + (
        "full", "conv", "conv", "conv") * 2
    assert (cfg.n_experts, cfg.head_dim) == (8, 64)
    params = weights.init_on_device(cfg, 3)
    assert all(float(jnp.abs(x).max()) > 0 for x in jax.tree.leaves(params))
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = T.forward(params, jnp.asarray(toks), cfg)
    want = reference.forward_logits(params, toks, arch)
    # float32 both sides, the same equations: rounding alone
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 2e-5
    some = reference.forward_logits(params, toks, arch, at=[47, 4, 5])
    np.testing.assert_allclose(some, want[:, np.asarray([47, 4, 5])],
                               rtol=1e-6, atol=1e-6)
    routes = []
    reference.forward_logits(params, toks[:1], arch, at=[3], routes=routes)
    assert len(routes) == 8 and routes[0].shape == (1, 4)
    loss = reference.next_token_loss(params, toks, arch)
    assert abs(loss - np.log(cfg.vocab_size)) < 1.0


def test_the_reference_imports_nothing_of_the_program():
    _, reference, arch = _toy()
    with open(reference.__file__) as f:
        source = f.read()
    assert "deepspeed_tpu" not in source.split('"""', 2)[2]
    assert arch["kinds"] == ("conv", "conv") + (
        "full", "conv", "conv", "conv") * 2
    for name in ("arch_from_config", "forward_logits", "next_token_loss"):
        assert callable(getattr(reference, name))
    with pytest.raises(ValueError, match="model_type"):
        reference.arch_from_config({"model_type": "llama"}, {})


def test_lfm2_flops_by_hand():
    cfg = _served_config()
    h = 2048
    conv = 3 * h * h + h * h
    attn = h * 2048 + 2 * h * 512 + 2048 * h
    assert lfm2_flops.mixer_matmul_params(cfg) == {"conv": conv,
                                                   "full": attn}
    dense = 3 * h * 11776
    expert = 3 * h * 4 * 1536 + h * 64
    active = 65536 * h + 2 * (conv + dense) + 6 * (conv + expert) \
        + 2 * (attn + expert)
    assert lfm2_flops.active_matmul_params(cfg) == active
    assert lfm2_flops.train_flops_per_token(cfg, 0, 4096) == \
        6.0 * active + 3.0 * (4.0 * 32 * 64 * 2048 * 2
                              + 2.0 * 5 * 2048 * 8)
    # the published depth: 2.3 B met by a token (A2B)
    full = dataclasses.replace(
        cfg, num_layers=40, layer_kinds=("conv", "conv", "full", "conv") * 10)
    assert round(lfm2_flops.active_matmul_params(full) / 1e9, 1) == 2.3
    assert callable(manifest.load_plugin(
        "flops", manifest.load_cell(CELL).config["flops"]
    ).train_flops_per_token)


def test_the_attention_roofline_reads_the_packed_pool(monkeypatch):
    g = global_attention
    assert g.classify(_Op(GLOBAL)) == "global"
    assert swa_attention.classify(_Op(GLOBAL)) is None
    # a position: 8 heads x 64 keys and as many values in bfloat16, as
    # four rows of 128
    assert swa_attention.position_bytes(GLOBAL) == (32, 2048)

    class Run:
        peaks, model = PEAKS, _served_config()

    # a decode tick of 256 rows at ~1,000 positions (256 x 32 blocks), two
    # attention layers: bytes alone
    ticks = [{"start": 1.0, "end": 1.1, "blocks": 256 * 32,
              "prompt_attended": 0, "window_positions": 0,
              "window_attended": 0}]
    monkeypatch.setattr(tick_attrs, "per_tick", lambda run: ticks)
    calls = [_Op(GLOBAL, at=1.0 + i * 1e-3) for i in range(2)]
    seconds, bound = g.least_seconds(Run, calls)
    assert seconds == pytest.approx(2 * 256 * 32 * 32 * 2048 / 819e9)
    assert bound == "memory"


def test_the_new_readers_on_a_made_up_run(monkeypatch):
    conv_op = ('%fusion.7 = bf16[256,2048]{1,0} fusion(bf16[256,6144]{1,0} '
               '%a), kind=kLoop')
    other = '%fusion.9 = bf16[256,2048]{1,0} fusion(bf16[256,2048]{1,0} %b)'

    class Op(_Op):
        is_mosaic = False

    ops = [Op(conv_op, 2e-3, at=1.0), Op(other, 6e-3, at=1.01),
           _Op(GLOBAL, 2e-3, at=1.02)]
    from benchmarks import gap_chain

    monkeypatch.setattr(gap_chain, "trace_file", lambda run: "x.pb")
    monkeypatch.setattr(gap_chain, "op_scopes", lambda path: {
        (0, conv_op): "jit(tick)/while/body/conv/dot_general",
        (0, other): "jit(tick)/while/body/experts/gmm"})
    monkeypatch.setattr(tick_attrs, "per_tick", lambda run: [
        {"start": 1.0, "end": 1.1, "blocks": 8192, "prompt_attended": 0,
         "conv_state_rows": 256, "state_slots": 256},
        {"start": 1.2, "end": 1.3, "blocks": 8192, "prompt_attended": 900,
         "conv_state_rows": 257, "state_slots": 256},
        {"start": 1.4, "end": 1.5, "blocks": 8192, "prompt_attended": 0,
         "conv_state_rows": 256, "state_slots": 256}])
    zero = {"counters": {}, "gauges": {}, "histograms": {}}
    end = {"counters": {}, "histograms": {},
           "gauges": {"fastgen_state_slots_in_use_peak": {(): 256.0}}}

    class Run:
        peaks, model = PEAKS, _served_config()
        trace = _Trace(ops)
        telemetry = harness.Telemetry(zero, end)
        extras = {"engine": dict(manifest.load_cell(CELL).deploy["engine"])}
        cache = {}

    assert conv_share_pct.read(Run) == pytest.approx(100 * 2e-3 / 10e-3)
    assert conv_state_rows_per_tick.read(Run) == 256.0
    assert state_slots_peak_pct.read(Run) == pytest.approx(100 * 256 / 272)
    assert global_attention_roofline.read(Run) is not None

    # the parent's program, or any other model's: no such scope and no
    # such attribute -> nothing, and nothing raises
    class Parent(Run):
        trace = _Trace([Op(other, 1e-3)])

    monkeypatch.setattr(tick_attrs, "per_tick", lambda run: [
        {"start": 0.0, "end": 1.0, "blocks": 10, "prompt_attended": 3}])
    for reader in (conv_share_pct, conv_state_rows_per_tick):
        assert reader.read(Parent) is None, reader.__name__
    Parent.trace = None
    monkeypatch.setattr(gap_chain, "trace_file", lambda run: None)
    monkeypatch.setattr(tick_attrs, "per_tick", lambda run: [])
    for reader in (conv_share_pct, conv_state_rows_per_tick):
        assert reader.read(Parent) is None, reader.__name__
