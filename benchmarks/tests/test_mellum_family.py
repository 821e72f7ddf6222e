"""The ``mellum`` family's files (configuration ``mellum2-12b-a2.5b``, cell
``train-mellum2-12b-moe8k-1chip``, mix ``zipf-8k-mb2``): loaded by name,
held to the numbers of the issue that asked for them (ISSUE 49) and to the
catalog's row, the flops counter and the rooflines' needs by hand, and
the nine new readers on a made-up run record. (The reference against the
program, and its mistakes, are ``tests/unit/test_mellum_stack.py``'s; the
float8 reading is the chip's: at a toy size rounding the weights moves the
loss by less than any limit.)
"""
import json

import pytest

from benchmarks import harness, manifest, model_config
from benchmarks.flops import mellum as mellum_flops
from benchmarks.layer_metrics import (full_flash_attention_roofline,
                                      full_flash_share_pct,
                                      train_expert_gmm_roofline,
                                      train_experts_share_pct,
                                      train_moe_held_expert_rows,
                                      train_moe_load_imbalance,
                                      train_router_share_pct,
                                      window_flash_attention_roofline,
                                      window_flash_share_pct)
from benchmarks.roofline import (full_flash_attention, train_expert_gmm,
                                 window_flash_attention)

M = manifest.load_manifest()
CELL = "train-mellum2-12b-moe8k-1chip"
CONFIG = "mellum2-12b-a2.5b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
NEW = {"window_flash_attention_roofline", "window_flash_share_pct",
       "full_flash_attention_roofline", "full_flash_share_pct",
       "train_expert_gmm_roofline", "train_experts_share_pct",
       "train_router_share_pct", "train_moe_held_expert_rows",
       "train_moe_load_imbalance"}

# Mosaic calls of the step as compiled for a v5e at the cell's sizes
FWD = ('%window_flash_fwd.2 = (bf16[64,8192,128]{2,1,0}, f32[64,8192,1]'
       '{2,1,0}) custom-call(bf16[64,8192,128]{2,1,0} %q, bf16[8,8192,128]'
       '{2,1,0} %k, bf16[8,8192,128]{2,1,0} %v), '
       'custom_call_target="tpu_custom_call"')
DKV = ('%window_flash_dkv.1 = (bf16[8,8192,128]{2,1,0}, bf16[8,8192,128]'
       '{2,1,0}) custom-call(bf16[64,8192,128]{2,1,0} %q, bf16[8,8192,128]'
       '{2,1,0} %k, bf16[8,8192,128]{2,1,0} %v, bf16[64,8192,128]{2,1,0} '
       '%do, f32[64,8,1,1024]{3,2,1,0} %lse, f32[64,8,1,1024]{3,2,1,0} %d), '
       'custom_call_target="tpu_custom_call"')
GMM = ('%gmm.70 = bf16[131072,896]{1,0} custom-call(s32[] %a, s32[17]{0} %b, '
       's32[95]{0} %c, s32[95]{0} %d, s32[1]{0} %e, bf16[131072,2304]{1,0} '
       '%lhs, bf16[16,2304,896]{2,1,0} %rhs), '
       'custom_call_target="tpu_custom_call"')
TGMM = ('%tgmm.3 = bf16[16,2304,896]{2,1,0} custom-call(s32[] %a, s32[17]{0} '
        '%b, s32[95]{0} %c, s32[95]{0} %d, s32[1]{0} %e, bf16[131072,2304]'
        '{1,0} %lhs, bf16[131072,896]{1,0} %rhs), '
        'custom_call_target="tpu_custom_call"')
FULL = ('%flash_fwd.1 = (bf16[64,8192,128]{2,1,0}, f32[64,8192,1]{2,1,0}) '
        'custom-call(bf16[64,8192,128]{2,1,0} %q, bf16[8,8192,128]{2,1,0} '
        '%k, bf16[8,8192,128]{2,1,0} %v), '
        'custom_call_target="tpu_custom_call"')


class _Op:
    is_mosaic = True

    def __init__(self, text, seconds=1e-3):
        self.text, self.seconds = text, seconds
        self.name = text.split(" ", 1)[0].lstrip("%")


class _Trace:
    chips = [0]

    def __init__(self, ops):
        self._ops = ops

    def busy_s(self):
        return 2 * sum(o.seconds for o in self._ops)

    def ops_in_window(self, chip):
        return self._ops

    def op_seconds(self, pred):
        return sum(o.seconds for o in self._ops if pred(o))


def _trained_config():
    return model_config.build(manifest.load_cell(CELL).config, "train",
                              remat="full")


def _histogram_snapshot(name, count, total):
    return {"counters": {}, "gauges": {}, "histograms": {name: {
        "buckets": [1.0], "children": {(): ([count, 0], count, total)}}}}


def _run(ops, histograms=()):
    end = {"counters": {}, "gauges": {}, "histograms": {}}
    for name, count, total in histograms:
        end["histograms"].update(
            _histogram_snapshot(name, count, total)["histograms"])
    start = {"counters": {}, "gauges": {}, "histograms": {}}
    return harness.RunRecord(
        cell=manifest.load_cell(CELL), seconds=50.0, chips=1, device={},
        peaks=PEAKS, model=_trained_config(), setup_s=1.0, client={},
        telemetry=harness.Telemetry(start, end), trace=_Trace(ops))


# ------------------------------------------------------------------ #
def test_the_files_are_what_the_issue_asked_for():
    cell = manifest.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.runner) \
        == (CONFIG, "zipf-8k-mb2", 1, "train")
    assert cell.traffic["generator"] == "zipf_batches"
    assert cell.traffic["params"] == {
        "micro_batch_per_chip": 2, "seq_len": 8192, "zipf_a": 1.1}
    deploy = cell.deploy
    assert (deploy["remat"], deploy["attention"], deploy["warmup_steps"],
            deploy["trace_seconds"]) == ("full", "flash", 2, 4)
    assert deploy["engine"]["zero_optimization"] == {"stage": 3}
    assert deploy["engine"]["optimizer"]["params"]["lr"] == 1e-5
    reads = {m.name for m in cell.end_to_end}
    assert reads == {"train_tokens_per_s_per_chip", "setup_s"}
    layer = {m.name for m in cell.per_layer}
    assert NEW <= layer
    assert {"train_step_p50_ms", "train_mfu_pct", "train.hbm_peak_gb",
            "train_dev_fwd_ms", "train_dev_bwd_ms"} <= layer
    # the full kernels' roofline counts a causal square for every call it
    # finds by operand counts: not this cell's to report
    assert not {"flash_attention_roofline", "flash_share_pct"} & layer
    for m in M["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] \
                and m["moves"] == "train_tokens_per_s_per_chip"


def test_the_configuration_keeps_every_published_width():
    with open(CATALOG) as f:
        published = next(json.loads(line) for line in f
                         if "Mellum2-12B" in line)["config"]
    config = manifest.load_cell(CELL).config
    row = next(c for c in M["configs"] if c["name"] == CONFIG)
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(row["reduced"]) == set(config["published"])
    assert all(config["published"][k] == published[k] for k in differs)
    for width in ("hidden_size", "head_dim", "num_attention_heads",
                  "num_key_value_heads", "moe_intermediate_size",
                  "num_experts_per_tok", "sliding_window",
                  "rope_parameters", "intermediate_size"):
        assert config[width] == published[width], width
    assert (config["num_experts"], config["router_experts"],
            config["first_expert"]) == (16, 64, 0)
    assert config["layer_types"] == published["layer_types"][:4]
    assert config["deployment"]["chips_that_share_a_layer"] == 4
    assert {"qk_norm", "router_aux_loss_coef",
            "router_at_initialisation"} <= set(config["assumed"])
    cfg = _trained_config()
    assert cfg.num_params() == 595_156_480
    assert cfg.remat == "full" and cfg.dtype == "bfloat16"
    # the draw the share trains from: stated in the file, and no width
    assert cfg.moe_router_init_std == 0.16 == config["as_run"]["train"][
        "router_init_std"]
    assert cfg.init_std == 0.02


def test_flops_count_this_chips_share_of_a_tokens_experts():
    cfg = _trained_config()
    assert mellum_flops.experts_met(cfg) == 2.0
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512
    layer = attention + 2304 * 64 + 2 * 3 * 2304 * 896
    by_hand = 4 * layer + 24576 * 2304
    assert mellum_flops.active_matmul_params(cfg) == by_hand == 191_692_800
    positions = 3 * 1024 + 4096
    assert mellum_flops.attended_positions(cfg, 8192) == positions
    assert mellum_flops.train_flops_per_token(cfg, 0, 8192) == \
        6.0 * by_hand + 3.0 * 4 * 32 * 128 * positions
    # the model's eight experts a token would read four times the expert
    # part: what the counter must not do for a share
    whole = 4 * (attention + 2304 * 64 + 8 * 3 * 2304 * 896) + 24576 * 2304
    assert whole / by_hand > 1.7
    # short sequences: a window layer attends to what a full one does
    assert mellum_flops.attended_positions(cfg, 1024) == 4 * 512


def test_window_flash_need_is_the_live_area():
    need = window_flash_attention
    assert need.classify(_Op(FWD)) == "fwd" and need.classify(_Op(DKV)) == \
        "dkv"
    assert need.classify(_Op(FULL)) is None and need.classify(_Op(GMM)) \
        is None
    area = 8192 * 1024 - 1024 * 1024 / 2
    assert need.live_area(8192, 1024) == area
    assert need.live_area(8192, 0) == need.live_area(8192, 9000) \
        == 8192 * 8192 / 2
    ops, moved = need.ops_and_bytes("fwd", FWD, 1024)
    assert ops == 2 * 2 * 64 * area * 128
    assert moved == 2 * (2 * 64 + 2 * 8) * 8192 * 128 + 4 * 64 * 8192
    assert need.ops_and_bytes("dkv", DKV, 1024)[0] == 3 * 2 * 64 * area * 128
    # about a quarter of the causal triangle
    assert 0.23 < area / (8192 * 8192 / 2) < 0.24
    run = _run([_Op(FWD, 1e-3), _Op(DKV, 2e-3), _Op(FULL, 5e-3)])
    least = (2 + 3) * 2 * 64 * area * 128 / PEAKS["bf16_flops_per_s"]
    assert window_flash_attention_roofline.read(run) == pytest.approx(
        100 * least / 3e-3)
    assert window_flash_share_pct.read(run) == pytest.approx(100 * 3 / 16)
    # the full layer's calls, by name: the causal square, and nothing of
    # the window calls or the grouped matmuls
    full = full_flash_attention
    assert full.classify(_Op(FULL)) == "fwd"
    assert full.classify(_Op(FWD)) is None and full.classify(_Op(GMM)) is None
    square = 2 * 2 * 64 * (8192 * 8192 / 2) * 128
    assert full_flash_attention_roofline.read(run) == pytest.approx(
        100 * square / PEAKS["bf16_flops_per_s"] / 5e-3)
    assert full_flash_share_pct.read(run) == pytest.approx(100 * 5 / 16)


def test_expert_gmm_need_is_the_held_pairs():
    need = train_expert_gmm
    assert need.classify(_Op(GMM)) == "gmm" and need.classify(_Op(TGMM)) \
        == "tgmm"
    assert need.classify(_Op(FWD)) is None
    pairs = 32768.0
    ops, moved = need.ops_and_bytes("gmm", GMM, pairs)
    assert ops == 2 * pairs * 2304 * 896
    assert moved == 2 * (pairs * (2304 + 896) + 16 * 2304 * 896)
    ops, moved = need.ops_and_bytes("tgmm", TGMM, pairs)
    assert ops == 2 * pairs * 2304 * 896
    assert moved == 2 * (pairs * (2304 + 896) + 16 * 2304 * 896)
    # the shape's rows are every pair's, never the need
    assert need.ops_and_bytes("gmm", GMM, 140000.0)[0] == \
        2 * 131072 * 2304 * 896
    run = _run([_Op(GMM, 1e-3), _Op(TGMM, 1e-3)],
               [("train_moe_held_expert_rows", 8, 8 * 2048.0),
                ("train_moe_load_imbalance", 8, 8 * 1.25)])
    assert need.held_pairs(run) == 16 * 2048.0
    least = 2 * 2 * pairs * 2304 * 896 / PEAKS["bf16_flops_per_s"]
    assert train_expert_gmm_roofline.read(run) == pytest.approx(
        100 * least / 2e-3)
    assert train_moe_held_expert_rows.read(run) == 2048.0
    assert train_moe_load_imbalance.read(run) == 1.25
    # a program without the histogram (the parent) gives nothing to read
    bare = _run([_Op(GMM, 1e-3)])
    assert train_expert_gmm_roofline.read(bare) is None
    assert train_moe_held_expert_rows.read(bare) is None
    assert train_moe_load_imbalance.read(bare) is None


def test_scope_readers_return_nothing_without_a_trace():
    run = _run([])
    run.trace = None
    for reader in (train_experts_share_pct, train_router_share_pct,
                   window_flash_share_pct, window_flash_attention_roofline,
                   full_flash_share_pct, full_flash_attention_roofline,
                   train_expert_gmm_roofline):
        assert reader.read(run) is None
