"""The ``ouro`` family's files (configuration ``ouro-2.6b``, cell
``serve-ouro-2.6b-cot-closed``, mix ``cot-closed``): loaded by name, held to
the numbers of the issue that asked for them (ISSUE 55) and to the
catalog's row, the reference against the program at the rehearsal size, the
warm-up against every program a window can meet, the flops counter by hand.

What a family needs beside its configuration, by name: a ``reference``
(``arch_from_config``, ``forward_logits``, ``next_token_loss``), a ``flops``
counter (``train_flops_per_token``), a cell file and a mix. This family
brings no per-layer metric and no reader (the manifest holds the 128 the
driver admits): its cell is on the lists of accepted metrics that read what
it runs.
"""
import dataclasses
import json

import numpy as np
import pytest

from benchmarks import manifest, model_config, weights
from benchmarks.flops import dense as dense_flops
from benchmarks.flops import ouro as ouro_flops

M = manifest.load_manifest()
CELL = "serve-ouro-2.6b-cot-closed"
CONFIG = "ouro-2.6b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _served_config():
    return model_config.build(manifest.load_cell(CELL).config, "serve")


# ------------------------------------------------------------------ #
def test_cell_config_and_mix_load_by_name_with_the_issues_numbers():
    cell = manifest.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.runner) \
        == (CONFIG, "cot-closed", 1, "serve")
    eng = cell.deploy["engine"]
    assert (eng["block_size"], eng["max_blocks_per_seq"],
            eng["token_budget"]) == (32, 16, 512)
    p = cell.traffic["params"]
    assert cell.traffic["generator"] == "closed_loop"
    assert p["preroll_s"] == 10
    assert p["prompt_tokens"] == {"dist": "uniform", "min": 64, "max": 160}
    # ISSUE 55's sizes, or one of its two pre-stated fallbacks (which the
    # mix's notes then carry the numbers for)
    assert (eng["n_blocks"], p["clients"]) in ((193, 10), (161, 8))
    answers = (p["output_tokens"]["min"], p["output_tokens"]["max"])
    assert answers in ((256, 320), (192, 256))
    if (eng["n_blocks"], answers) != (193, (256, 320)):
        assert "fallback" in cell.traffic["notes"]
    assert cell.deploy["serving"] == {}         # the frontend's defaults
    longest = p["prompt_tokens"]["max"] + answers[1]
    assert longest <= eng["max_blocks_per_seq"] * eng["block_size"]
    # every client at its longest at once stays under the frontend's 0.80
    # watermark: nothing sheds, degrades or is preempted
    assert p["clients"] * -(-longest // 32) <= 0.80 * (eng["n_blocks"] - 1)
    row = next(c for c in M["configs"] if c["name"] == CONFIG)
    conf = cell.config
    assert row["reduced"] == [] == conf["reduced"]
    assert conf["published"] == conf["as_run"]["serve"] \
        == {"num_hidden_layers": 48, "total_ut_steps": 4}
    assert conf["deployment"]["chips_that_share_a_layer"] == 1
    for key in ("assumed", "bytes", "reference", "flops", "rehearse"):
        assert key in conf
    for name in ("four norms a layer", "the norm between passes",
                 "the exit gate", "the exit rule", "a cache a (pass, layer)"):
        assert name in conf["assumed"]
    assert {m.name for m in cell.end_to_end} == {"serve_out_tokens_per_s",
                                                 "setup_s"}
    names = {m.name for m in cell.per_layer}
    assert {"closed.paged_share_pct", "closed.paged_attention_roofline",
            "closed.decode_rows_per_tick", "closed.device_idle_pct",
            "closed.hbm_peak_gb", "closed.kv_pool_peak_pct",
            "closed.tick_dev_decode_p50_ms", "closed.win_ticks_per_s",
            "serve.setup_compile_s"} <= names
    assert len(names) == 35 and len(M["per_layer"]) <= 128
    assert len(cell.why) <= 200


def test_the_limit_lies_between_its_readings():
    """Over every reading of the system, under the reference computed in
    float8_e4m3, with room on both sides; each mistake of the issue's list
    either fails the limit or is named as held by a CPU test."""
    spec = manifest.load_cell(CELL).deploy["logits_check"]
    got = spec["chip_readings"]
    system = list(got["system"].values())
    assert len(system) >= 20 and max(system) == got["system_max"]
    lower = min(got["system_against_the_reference_in_float8_e4m3"].values())
    tol = spec["rel_tol"]
    assert 1.4 * max(system) <= tol < lower / 1.2
    seen = {k for k, v in got["system_against_a_mistaken_reference"].items()
            if v > tol}
    unseen = set(got["the_check_cannot_see"])
    reference = manifest.load_plugin("reference", "ouro_lm")
    assert seen | unseen >= set(reference.FAULTS)
    assert not seen & unseen
    for name, test in got["the_check_cannot_see"].items():
        assert test.startswith("tests/unit/test_ouro_loop.py::")


def test_the_file_holds_every_number_of_the_catalog():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    conf = manifest.load_cell(CELL).config
    entry = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] == row["source_url"]
    assert {k for k, v in row["config"].items() if conf.get(k) != v} == set()


def test_served_model_is_the_model_whole():
    cfg = _served_config()
    conf = manifest.load_cell(CELL).config
    assert (cfg.num_layers, cfg.loop_passes, cfg.exit_threshold) \
        == (48, 4, 1.0)
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            cfg.ffn_size, cfg.vocab_size, cfg.max_seq_len) \
        == (2048, 16, 16, 128, 5632, 49152, 65536)
    assert cfg.post_norms and cfg.activation == "swiglu" \
        and cfg.norm == "rmsnorm" and cfg.dtype == "bfloat16"
    b = conf["bytes"]
    assert cfg.num_params() == b["num_params_as_run"] \
        == b["num_params_published"] == 2_667_974_657
    assert b["layers"] + b["embedding_and_head"] + b["final_norm"] \
        + b["exit_gate"] == b["num_params_as_run"]
    assert b["cache_layers"] * b["kv_bytes_a_token_a_cache_layer"] \
        == b["kv_bytes_a_token"] == 1_572_864
    # weights + pool: well over a quarter of one chip's 16 GB
    eng = manifest.load_cell(CELL).deploy["engine"]
    held = 2 * cfg.num_params() \
        + eng["n_blocks"] * 32 * b["kv_bytes_a_token"]
    assert held / 16e9 > 0.8


def test_warmup_reaches_every_bucket_and_tier_of_the_cell():
    """One request at a time: a prompt of n tokens runs one chunk (in the
    64-row bucket where it fits), then decode ticks; the window's ticks
    are those programs and no other."""
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
    """The rehearsal size keeps four passes; the weights are the
    benchmark's own (norm gains off one, the gate's bias off zero)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import transformer as T

    cfg, reference, arch = _toy()
    assert (cfg.num_layers, cfg.loop_passes) == (3, 4) and arch["passes"] == 4
    params = weights.init_on_device(cfg, 3)
    assert all(float(jnp.abs(x).max()) > 0 for x in jax.tree.leaves(params))
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 48)).astype(np.int32)
    for threshold in (1.0, 0.5):
        c = dataclasses.replace(cfg, exit_threshold=threshold)
        a = {**arch, "threshold": threshold}
        with jax.default_matmul_precision("highest"):
            got = T.forward(params, jnp.asarray(toks), c)
        want = reference.forward(params, toks, a)
        assert float(jnp.linalg.norm(got - want["logits"])
                     / jnp.linalg.norm(want["logits"])) < 2e-5
        chosen = set(np.asarray(want["chosen"]).ravel().tolist())
        assert chosen == {3} if threshold == 1.0 else len(chosen) > 1
    # (the last threshold's reference is the one of 0.5)
    some = reference.forward_logits(params, toks, a, at=[47, 4, 5])
    np.testing.assert_allclose(
        some, want["logits"][:, np.asarray([47, 4, 5])], rtol=1e-6, atol=1e-6)
    loss = reference.next_token_loss(params, toks, arch)
    assert abs(loss - np.log(cfg.vocab_size)) < 1.0


def test_the_reference_imports_nothing_of_the_program():
    _, reference, arch = _toy()
    with open(reference.__file__) as f:
        source = f.read()
    assert "deepspeed_tpu" not in source.split('"""', 2)[2]
    assert len(reference.FAULTS) == 9
    for name in ("arch_from_config", "forward_logits", "next_token_loss"):
        assert callable(getattr(reference, name))
    with pytest.raises(ValueError, match="model_type"):
        reference.arch_from_config({"model_type": "llama"}, {})


def test_ouro_flops_by_hand():
    """``dense``'s count with every layer met four times."""
    cfg = _served_config()
    per = ouro_flops.layer_matmul_params(cfg)
    assert per == 51_388_416 - 4 * 2048          # a layer less its norms
    active = ouro_flops.active_matmul_params(cfg)
    assert active == 4 * 48 * per + 49_152 * 2048
    flops = ouro_flops.train_flops_per_token(cfg, 0, 4096)
    assert flops == 6.0 * active + 6 * 4 * 48 * 2048 * 4096
    # one pass is the dense counter's own count (less the norms and the
    # gate, which multiply no matrix)
    once = dataclasses.replace(cfg, loop_passes=1)
    n = once.num_params()
    assert ouro_flops.train_flops_per_token(once, n, 4096) \
        == dense_flops.train_flops_per_token(once, n, 4096) \
        - 6.0 * (48 * 4 * 2048 + 2 * 2048)


@pytest.mark.slow
def test_the_rehearsal_walks_the_cell():
    """``run.py --rehearse`` in a subprocess: the cell's own code at the toy
    size, every phase, a last line that can never say ``correct``."""
    import os
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", "5500000001", "--seconds", "4",
         "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=manifest.ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert "rehearsal.serve_out_tokens_per_s" in line["metrics"]
