"""The manifest against the contract's limits, and the promise that a
later PR adds a configuration, a traffic mix, a per-layer metric and a
cell as files plus one ``workloads`` entry."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks import manifest

ROOT = manifest.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
M = manifest.load_manifest()


def test_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmarks"] and M["command"][-1] == "benchmarks/run.py"
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert 2 <= len(M["workloads"]) <= 24 and len(M["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(M["workloads"]) // 4)


def test_names_units_and_keys():
    names = []
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert m["source"] in SOURCES
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for m in M["end_to_end"] + M["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert len(names) == len(set(names))
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {c["name"] for c in M["configs"]} == {w["config"] for w in M["workloads"]}
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in M["end_to_end"])


def test_every_cell_finds_its_files_and_readers():
    e2e = {m["name"] for m in M["end_to_end"]}
    for w in M["workloads"]:
        cell = manifest.load_cell(w["name"])
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, "runners", cell.runner + ".py"))
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, "generators", cell.traffic["generator"] + ".py"))
        assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
        for m in cell.end_to_end:
            assert callable(manifest.load_plugin("end_to_end", m.reader).read)
        mine = {m.name for m in cell.end_to_end}
        for m in cell.per_layer:
            assert callable(manifest.load_plugin("layer_metrics", m.reader).read)
            assert m.moves in e2e and m.moves in mine, (w["name"], m.name)
        for group in ("reference", "flops"):
            assert os.path.exists(os.path.join(
                manifest.BENCH_DIR, group, cell.config[group] + ".py"))
        # depth is the only key a cell's role changes
        for role in cell.config["as_run"].values():
            assert set(role) == {"num_hidden_layers"}


def _open_loop_cells():
    cells = [manifest.load_cell(w["name"]) for w in M["workloads"]]
    return [c for c in cells if c.traffic["generator"] == "open_poisson"]


def test_open_loop_strata_hold_a_whole_number_of_arrivals():
    """``rate_per_s * stratum_s`` is rounded by the generator: a rate that
    does not divide would be offered as another rate than the file says."""
    assert _open_loop_cells()
    for cell in _open_loop_cells():
        p = cell.traffic["params"]
        per = p["rate_per_s"] * p["stratum_s"]
        assert abs(per - round(per)) < 1e-9 and round(per) >= 1, cell.name
        assert M["run_seconds"] % p["stratum_s"] == 0, cell.name


def test_every_tail_has_ten_samples_beyond_it():
    """A percentile with fewer than ten samples beyond it is a maximum of
    a few (choosing-metrics, section 1). Counted from the mix as the
    generator lays it over one window of ``run_seconds``: requests due for
    a tail of the time to first token, gaps between their tokens for a
    tail of the gap."""
    from benchmarks.manifest import load_plugin

    seen = 0
    for cell in _open_loop_cells():
        src = load_plugin("generators", cell.traffic["generator"]).build(
            cell.traffic["params"], 0, 32000, 4096, 0.0,
            float(M["run_seconds"]))
        samples = {"ttft": len(src.requests),
                   "itl": sum(r.max_new - 1 for r in src.requests)}
        for m in cell.end_to_end:
            tail = re.search(r"_(ttft|itl)_p(\d+)_", m.name)
            if tail:
                beyond = samples[tail.group(1)] * (1 - int(tail.group(2)) / 100)
                assert beyond >= 10, (cell.name, m.name, beyond)
                seen += 1
    assert seen >= 2


def test_files_under_paths_are_named_from_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(manifest.BENCH_DIR):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            assert ok.match(os.path.relpath(os.path.join(base, f), ROOT))


def test_harness_names_no_cell_no_model():
    words = [w["name"] for w in M["workloads"]] \
        + [c["name"] for c in M["configs"]] \
        + [w["traffic"] for w in M["workloads"]] \
        + ["mistral", "pythia", "neox"] \
        + [manifest.load_cell(w["name"]).config["reference"]
           for w in M["workloads"]]
    for rel in ("run.py", "harness.py", "manifest.py", "readers.py",
                "runners/train.py", "runners/serve.py", "trace_reduce.py"):
        src = open(os.path.join(manifest.BENCH_DIR, rel)).read()
        assert "if workload ==" not in src
        for w in words:
            assert w not in src, (rel, w)


@pytest.mark.slow
def test_a_later_pr_adds_files_only(tmp_path):
    """A configuration, a mix, a per-layer metric and a cell, added to a
    copy as new files plus one ``workloads`` entry (and the new metric's
    entry); no file that was there is edited; the new cell rehearses."""
    root = tmp_path / "repo"
    shutil.copytree(manifest.BENCH_DIR, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    b = root / "benchmarks"
    conf = json.loads((b / "configs" / "pythia-6.9b.json").read_text())
    conf["as_run"]["serve"]["num_hidden_layers"] = 8
    (b / "configs" / "pythia-new.json").write_text(json.dumps(conf))
    mix = json.loads((b / "traffic" / "decode-closed.json").read_text())
    mix["params"]["clients"] = 8
    mix["rehearse"]["clients"] = 2
    (b / "traffic" / "decode-closed-8.json").write_text(json.dumps(mix))
    # a new family: its plain reference and its FLOP count are files of
    # its own, named by the configuration file
    conf["reference"], conf["flops"] = "stub_lm", "stub"
    (b / "configs" / "pythia-new.json").write_text(json.dumps(conf))
    (b / "reference" / "stub_lm.py").write_text(
        "import sys\nfrom benchmarks.reference.dense_lm import *  # noqa\n"
        "print('stub_lm is the reference', file=sys.stderr)\n")
    (b / "flops" / "stub.py").write_text(
        "def train_flops_per_token(model, n_params, seq_len):\n"
        "    return 42.0\n")
    shutil.copy(b / "cells" / "serve-pythia69b-decode-closed.json",
                b / "cells" / "serve-new-cell.json")
    (b / "layer_metrics" / "ticks_in_window.py").write_text(
        "from benchmarks import readers\n\n\n"
        "def read(run):\n    return float(len(readers.window_ticks(run)))\n")
    m = json.loads(json.dumps(M))
    m["configs"].append({"name": "pythia-new", "source": "x", "reduced":
                         ["num_hidden_layers"], "why": "y",
                         "file": "benchmarks/configs/pythia-new.json"})
    m["workloads"].append({"name": "serve-new-cell", "config": "pythia-new",
                           "traffic": "decode-closed-8", "chips": 1,
                           "why": "z"})
    for e in m["end_to_end"]:
        if e["name"] == "serve_out_tokens_per_s":
            e["workloads"].append("serve-new-cell")
    m["per_layer"].append({"name": "ticks_in_window", "unit": "ticks",
                           "better": "higher", "source": "program_counter",
                           "layer": "engine inference/fastgen.py step",
                           "moves": "serve_out_tokens_per_s",
                           "workloads": ["serve-new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    out = subprocess.run(
        [sys.executable, str(b / "run.py"), "--workload", "serve-new-cell",
         "--seed", "1", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["metrics"]["rehearsal.ticks_in_window"]["value"] > 0
    assert line["device"]["platform"] == "cpu" and line["correct"] is False
    assert "stub_lm is the reference" in out.stderr
    assert json.loads(out.stdout.strip().splitlines()[-2])["failures"] == []
    flops = subprocess.run(
        [sys.executable, "-c",
         "from benchmarks.manifest import load_cell, load_plugin\n"
         "c = load_cell('serve-new-cell')\n"
         "print(load_plugin('flops', c.config['flops'])"
         ".train_flops_per_token(None, 7, 1))"],
        cwd=root, capture_output=True, text=True, timeout=60,
        env={**os.environ, "PYTHONPATH": str(root)})
    assert flops.stdout.strip() == "42.0", flops.stderr[-2000:]
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
