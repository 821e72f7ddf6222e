#!/usr/bin/env python3
"""What the ``mellum`` training cell's ``loss_check`` reads, and what it has
to fail, measured on the chip at the cell's own size.

    chiprun -- python benchmarks/tools/mellum_probe.py --workload <cell> \\
        --seeds a,b [--batches 2] [--do system,lower,mistakes] [--out file]

For each seed the engine is built as the runner builds it (``dst.initialize``
from the cell's files), warmed, and then, on batches the feed never
reaches, the ENGINE's loss (a training step, as ``correct`` takes it) is set
against the reference from the same weights (the float32 master rounded to
the compute type): ``system`` the reference as it is; ``lower`` the
reference COMPUTED in float8_e4m3 (every linear layer's operands); and
``mistakes`` the reference with one mistake made on purpose (window dropped
on the sliding layers, YaRN dropped on the full layer, weights not
renormalised, the held share off by one expert). Every reading is the
runner's statistic: ``|mean engine - mean reference| / mean reference``
over the batches, the system against the altered reference. ``--rehearse
1`` walks it at the toy size on the CPU. Lines go to ``chiprun_out/<out>``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

MISTAKES = ("no-window", "no-yarn", "no-renorm", "share-off-by-one")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--batches", type=int, default=2)
    ap.add_argument("--do", default="system,lower,mistakes")
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--out", default="mellum_probe.jsonl")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import numpy as np

    import deepspeed_tpu as dst
    from benchmarks import model_config
    from benchmarks.manifest import load_cell, load_plugin
    from benchmarks.runners.train import CHECKED_BATCH

    cell = load_cell(args.workload)
    deploy = cell.deploy
    reference = load_plugin("reference", cell.config["reference"])
    variants = []
    if "system" in args.do:
        variants.append(("system", ()))
    if "lower" in args.do:
        variants.append(("float8", ("float8",)))
    if "mistakes" in args.do:
        variants += [(m, (m,)) for m in MISTAKES]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", args.out), "a")

    for seed in (int(s) for s in args.seeds.split(",")):
        cfg = model_config.build(cell.config, "train", remat=deploy["remat"],
                                 rehearse=bool(args.rehearse))
        traffic = dict(cell.traffic["params"])
        if args.rehearse:
            traffic.update(cell.traffic.get("rehearse", {}))
        batches = load_plugin("generators", cell.traffic["generator"]).build(
            traffic, seed, cfg.vocab_size)
        engine_cfg = dict(deploy["engine"])
        engine_cfg.update({
            "train_micro_batch_size_per_gpu": batches.micro_batch,
            "gradient_accumulation_steps": 1,
            "train_batch_size": batches.micro_batch,
            "mesh": {"data": 1}, "seed": seed, "steps_per_print": 10 ** 9})
        engine, *_ = dst.initialize(
            model=dst.causal_lm_spec(cfg, attention=deploy["attention"]),
            config=engine_cfg)
        for step in range(1 + int(deploy.get("warmup_steps", 2))):
            float(engine.train_batch(iter(
                [{"tokens": batches.batch(step, 1)}])))
        hf = model_config.hf_kwargs(cell.config, "train")
        if args.rehearse:
            hf.update(cell.config["rehearse"])
        arch = reference.arch_from_config(cell.config, hf)
        ours, theirs = [], {name: [] for name, _ in variants}
        for b in range(args.batches):
            as_computed = jax.tree.map(
                lambda x: x.astype(cfg.compute_dtype), engine.state["master"])
            checked = batches.batch(CHECKED_BATCH + b, 1)
            for name, faults in variants:
                theirs[name].append(reference.next_token_loss(
                    as_computed, checked, dict(arch, faults=faults)))
            del as_computed
            ours.append(float(engine.train_batch(
                iter([{"tokens": checked}]))))
        line = {"seed": seed, "batches": args.batches, "engine": ours,
                "rehearsal": bool(args.rehearse)}
        for name, losses in theirs.items():
            line[name] = {"reference": losses, "rel_diff": abs(
                float(np.mean(ours)) - float(np.mean(losses)))
                / abs(float(np.mean(losses)))}
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()
        engine.shutdown_telemetry()
        del engine
    return 0


if __name__ == "__main__":
    sys.exit(main())
