"""The plain reference against the program's ``forward`` at a toy size on
the CPU, for both architectures; and ``--rehearse`` of all four cells."""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import manifest, model_config, weights
from benchmarks.reference import dense_lm

M = manifest.load_manifest()
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("name", [c["name"] for c in M["configs"]])
def test_reference_agrees_with_the_program_forward(name):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import transformer as T

    row = next(c for c in M["configs"] if c["name"] == name)
    conf = manifest.load_json(os.path.join(manifest.ROOT, row["file"]))
    cfg = dataclasses.replace(
        model_config.build(conf, "serve", rehearse=True), dtype="float32")
    params = weights.init_on_device(cfg, 3)
    # biases and norm offsets are not zero, or dropping them would pass
    assert all(float(jnp.abs(x).max()) > 0 for x in jax.tree.leaves(params))
    hf = {**model_config.hf_kwargs(conf, "serve"), **conf["rehearse"]}
    arch = dense_lm.arch_from_config(conf, hf)
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = T.forward(params, jnp.asarray(toks), cfg)
    want = dense_lm.forward_logits(params, toks, arch)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    # float32 both sides. 1e-4: the program's GELU is the tanh
    # approximation, the source's (and the reference's) is exact: ~1e-5;
    # a wrong rotary fraction, a dropped bias or the sequential instead of
    # the parallel residual moves the logits by 1e-2 and more
    assert rel < 1e-4, rel
    loss = dense_lm.next_token_loss(params, toks, arch)
    assert abs(loss - np.log(cfg.vocab_size)) < 0.5


@pytest.mark.parametrize("seed", [3, 3000000061, 2 ** 31 + 5])
def test_weights_come_from_the_seed_whatever_its_size(seed):
    """The seed is an argument of the one weight program (a driver's seeds
    pass 2**31): the same seed gives the same weights, another seed
    others."""
    import jax

    conf = manifest.load_json(os.path.join(manifest.ROOT,
                                           M["configs"][0]["file"]))
    cfg = model_config.build(conf, "serve", rehearse=True)
    a, b, c = (jax.tree.leaves(weights.init_on_device(cfg, s))
               for s in (seed, seed, seed + 1))
    assert all((x == y).all() for x, y in zip(a, b))
    assert all((x != y).any() for x, y in zip(a, c))


def test_sliding_window_is_applied_by_the_reference():
    import jax.numpy as jnp

    q = jnp.ones((1, 6, 1, 4))
    v = jnp.arange(6, dtype=jnp.float32)[None, :, None, None] * jnp.ones((1, 6, 1, 4))
    full = dense_lm._attention(q, q, v, None)
    win = dense_lm._attention(q, q, v, 2)
    assert float(full[0, 5, 0, 0]) == pytest.approx(2.5)    # mean of 0..5
    assert float(win[0, 5, 0, 0]) == pytest.approx(4.5)     # mean of 4, 5


SERVING = [w for w in M["workloads"]
           if manifest.load_cell(w["name"]).runner == "serve"]


@pytest.mark.parametrize("cell_name", [w["name"] for w in SERVING])
def test_what_the_logits_tolerance_fails_and_what_it_cannot_see(cell_name):
    """The cell file's ``logits_check.rel_tol`` against mistakes made on
    purpose in the reference's own forward (float32, toy width, the cell's
    depth): how far each moves the logits of the last nine positions. What
    moves them by less than the tolerance is a mistake the chip's check
    cannot see, and the cell file says so."""
    import jax
    import jax.numpy as jnp

    cell = manifest.load_cell(cell_name)
    tol = cell.deploy["logits_check"]["rel_tol"]
    conf = dict(cell.config)
    conf["rehearse"] = {**conf["rehearse"], "num_hidden_layers":
                        conf["as_run"]["serve"]["num_hidden_layers"]}
    cfg = dataclasses.replace(
        model_config.build(conf, "serve", rehearse=True), dtype="float32")
    params = weights.init_on_device(cfg, 3)
    hf = {**model_config.hf_kwargs(conf, "serve"), **conf["rehearse"]}
    arch = dense_lm.arch_from_config(conf, hf)
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 64)).astype(np.int32)
    want = dense_lm.forward_logits(params, toks, arch)[0, -9:]

    def moved(p, a):
        got = dense_lm.forward_logits(p, toks, a)[0, -9:]
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    def int8(w):      # per output channel, the kindest common scheme
        if w.ndim < 2:
            return w
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        return jnp.round(w / scale) * scale

    blocks = params["blocks"]
    seen = {"a dropped layer": moved(
                {**params, "blocks": jax.tree.map(lambda a: a[:-1], blocks)},
                arch),
            "a wrong attention mask": moved(params, {**arch, "window": 8})}
    if arch["family"] == "gpt_neox":
        seen["dropped biases"] = moved(
            {**params, "blocks": {k: jnp.zeros_like(v) if k.startswith("b")
                                  else v for k, v in blocks.items()}}, arch)
        seen["the sequential residual"] = moved(
            params, {**arch, "parallel": False})
    unseen = {"weights rounded to 8 bits": moved(
                  jax.tree.map(int8, params), arch),
              "half the rotary fraction": moved(
                  params, {**arch, "rotary_dim": arch["rotary_dim"] // 2})}
    for what, rel in seen.items():
        assert rel > 4 * tol, (what, rel, tol)
    for what, rel in unseen.items():
        assert rel < tol, (what, rel, tol)


@pytest.mark.slow
@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_rehearse(cell, trace):
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", cell, "--seed", "5", "--seconds", "3",
         "--trace", str(trace), "--rehearse"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    line, report = json.loads(lines[-1]), json.loads(lines[-2])
    assert set(line) - {"breakdown"} == KEYS
    assert line["correct"] is False            # a rehearsal never passes
    assert line["device"]["platform"] == "cpu"
    chips = next(w["chips"] for w in M["workloads"] if w["name"] == cell)
    assert line["device"]["count"] == chips
    assert report["rehearsal"] is True and report["failures"] == []
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"] and all(k.startswith("rehearsal.")
                                   for k in line["metrics"])
    if not trace:
        assert "rehearsal.setup_s" in line["metrics"]


def test_no_accelerator_exits_nonzero_and_prints_nothing():
    cell = M["workloads"][1]["name"]
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", cell, "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "not a TPU" in out.stderr


@pytest.mark.parametrize("name,cls", [("mistral-7b-v0.1", "MistralConfig"),
                                      ("pythia-6.9b", "GPTNeoXConfig")])
def test_namespace_config_equals_the_transformers_object(name, cls):
    """``model_config.build`` hands the importer a plain namespace of the
    file's keys (no ``transformers`` import in a run); the result is what
    the ``transformers`` config object of the same keys gives."""
    import transformers

    from deepspeed_tpu.models.hf_import import config_from_hf

    row = next(c for c in M["configs"] if c["name"] == name)
    conf = manifest.load_json(os.path.join(manifest.ROOT, row["file"]))
    for role in ("train", "serve"):
        kw = model_config.hf_kwargs(conf, role)
        kw.pop("model_type")
        want = dataclasses.replace(
            config_from_hf(getattr(transformers, cls)(**kw)),
            dtype="bfloat16", remat="none")
        assert model_config.build(conf, role) == want
