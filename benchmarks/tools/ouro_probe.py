#!/usr/bin/env python3
"""The readings the ``ouro`` cell's ``logits_check.rel_tol`` is set from,
and what the check can and cannot see (``tools/nemotron_h_probe.py`` and the
probes before it are the same idea for the families before).

    chiprun -- python benchmarks/tools/ouro_probe.py --workload <cell> \
        --seeds 1,2 --do system,lower,mistakes [--mistakes name,name] \
        [--passes 1,4] [--depth 4 --dtype float32 --blocks 40] [--out file]

Every reading is ``||a - b|| / ||b||`` over the logits of the check's
compared positions (a prompt's last and the decoded ones), the worst of the
check's prompts, as ``runners/serve.py::check_logits`` reads it. The
SYSTEM's logits are made once a seed, here, by the check's own stream of
ticks (chunked prefill of the prompts row after row, then decode ticks,
through the engine's pool with its kernel; ONE engine for every seed, its
weights drawn anew), and set against:

* ``system``: the reference (the number ``correct`` reads). With ``--depth
  D --dtype float32`` the same at matmul precision "highest" on a stack cut
  to its first D layers (all four passes): a bug shows there (1e-6 is
  rounding), rounding does not;
* ``lower``: the reference COMPUTED in float8_e4m3, the nearest precision
  below the configuration's: every linear layer's input and weights rounded
  (``reference._linear``): the system against it, and it against the
  reference;
* ``mistakes``: the reference with one mistake made on purpose (its
  ``FAULTS``): the SYSTEM against the mistaken reference, as ``correct``
  would read it, and the mistaken reference against the right one.

``--passes 1,4``: beside them, the device time of a 64-row decode tick of
the engine's own program at each number of passes (ten ticks timed on the
host's clock, after one): what an application of a layer costs with the
leaves met once and four times a tick.

``--rehearse 1``: the cell's rehearsal size, a dry run on the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

LOWER = [("    return x @ w\n",
          "    f8 = jnp.float8_e4m3fn\n"
          "    return x.astype(f8).astype(x.dtype) "
          "@ w.astype(f8).astype(w.dtype)\n")]


def variant_of(reference, name: str, edits):
    """The reference's module with ``edits`` made in its source."""
    with open(reference.__file__) as f:
        source = f.read()
    for old, new in edits:
        assert source.count(old) == 1, (name, old)
        source = source.replace(old, new)
    mod = types.ModuleType("ouro_lm_" + name.replace("-", "_"))
    exec(compile(source, reference.__file__, "exec"), mod.__dict__)
    return mod


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--do", default="system")
    ap.add_argument("--mistakes", default="",
                    help="of the reference's FAULTS; default: all")
    ap.add_argument("--passes", default="",
                    help="time a decode tick at these numbers of passes")
    ap.add_argument("--depth", type=int, default=0)
    ap.add_argument("--dtype", default="")
    ap.add_argument("--prompt-lens", default="")
    ap.add_argument("--decode-steps", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--blocks", type=int, default=0,
                    help="a smaller pool (a float32 stack beside it)")
    ap.add_argument("--out", default="ouro_probe.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import harness, manifest, model_config, weights
    from benchmarks.runners import serve
    from deepspeed_tpu.models import paged as PG

    cell = manifest.load_cell(args.workload)
    reference = manifest.load_plugin("reference", cell.config["reference"])
    if args.depth:
        cell.config["as_run"]["serve"]["num_hidden_layers"] = args.depth
    if args.dtype:
        cell.config["compute_dtype"] = args.dtype
    if args.blocks:
        cell.deploy["engine"]["n_blocks"] = args.blocks
    hf = model_config.hf_kwargs(cell.config, "serve")
    if args.rehearse:
        hf.update(cell.config["rehearse"])
    arch = reference.arch_from_config(cell.config, hf)
    seeds = [int(s) for s in args.seeds.split(",")]
    todo = args.do.split(",")
    spec = dict(cell.deploy.get("rehearse", {}).get("logits_check", {})
                if args.rehearse else cell.deploy["logits_check"])
    if args.prompt_lens:
        spec["prompt_lens"] = [int(n) for n in args.prompt_lens.split(",")]
    if args.decode_steps:
        spec["decode_steps"] = args.decode_steps
    n_dec = int(spec["decode_steps"])
    highest = cell.config["compute_dtype"] == "float32"
    out = {"cell": cell.name, "depth": hf["num_hidden_layers"],
           "passes": hf["total_ut_steps"],
           "dtype": cell.config["compute_dtype"],
           "prompt_lens": spec["prompt_lens"], "decode_steps": n_dec,
           "readings": {}}

    def rel(got, want):
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    def say(key, seed, value):
        out["readings"].setdefault(key, {})[str(seed)] = value
        harness.log(f"probe: {key} seed {seed}: {value}")

    session = serve.Session(cell, types.SimpleNamespace(
        seed=seeds[0], rehearse=bool(args.rehearse)))
    eng, cfg = session.engine, session.cfg
    attn = PG.paged_attention_reference
    if eng._use_kernel:
        from deepspeed_tpu.ops.pallas.paged_attention import \
            paged_attention as attn
    Tn, mb, bs = eng.token_budget, eng.max_blocks_per_seq, eng.block_size
    fwd = jax.jit(lambda params, pool, t, p, tb: PG.forward_paged(
        params, t, p, tb, pool, cfg, attention_fn=attn), donate_argnums=(1,))

    def system_logits(seed):
        """``check_logits``'s stream of ticks: [(tokens, compared positions,
        logits [positions, V])] a prompt."""
        rng = np.random.default_rng([seed, 7])
        seqs = []
        for n in spec["prompt_lens"]:
            toks = rng.integers(0, cfg.vocab_size, n + n_dec).astype(np.int32)
            blocks = eng.allocator.allocate((n + n_dec) // bs + 1)
            table = np.zeros((mb,), np.int32)
            table[:len(blocks)] = blocks
            seqs.append({"toks": toks, "n": n, "blocks": blocks,
                         "table": table, "logits": {}})

        def tick(rows):
            tokens = np.zeros((Tn,), np.int32)
            positions = np.zeros((Tn,), np.int32)
            tables = np.zeros((Tn, mb), np.int32)
            for r, (s, p) in enumerate(rows):
                tokens[r], positions[r], tables[r] = \
                    s["toks"][p], p, s["table"]
            logits, eng.pool = fwd(eng.params, eng.pool, jnp.asarray(tokens),
                                   jnp.asarray(positions),
                                   jnp.asarray(tables))
            for r, (s, p) in enumerate(rows):
                if p >= s["n"] - 1:
                    s["logits"][p] = logits[r]

        prefill = [(s, p) for s in seqs for p in range(s["n"])]
        for lo in range(0, len(prefill), Tn):
            tick(prefill[lo:lo + Tn])
        for step in range(n_dec):
            tick([(s, s["n"] + step) for s in seqs])
        rows = []
        for s in seqs:
            at = list(range(s["n"] - 1, s["n"] + n_dec))
            rows.append((s["toks"], at, jnp.stack(
                [s["logits"][p] for p in at]).astype(jnp.float32)))
            eng.allocator.free(s["blocks"])
        return rows

    def of(mod, params, toks, at, arch=arch):
        return mod.forward_logits(params, toks[None], arch, at=at)[0]

    ctx = jax.default_matmul_precision("highest") if highest \
        else jax.default_matmul_precision("default")
    for i, seed in enumerate(seeds):
        if i:       # the same engine and programs, the weights drawn anew
            eng.params = None
            eng.params = weights.init_on_device(cfg, seed)
        params = eng.params
        with ctx:
            got = system_logits(seed)
            harness.log(f"probe: system's logits of seed {seed} made")
            want = [of(reference, params, toks, at) for toks, at, _ in got]
            if "system" in todo:
                say("system", seed, max(
                    rel(g, w) for (_, _, g), w in zip(got, want)))
                say("system_by_prompt", seed,
                    [rel(g, w) for (_, _, g), w in zip(got, want)])
            if "lower" in todo:
                mod = variant_of(reference, "float8", LOWER)
                low = [of(mod, params, toks, at) for toks, at, _ in got]
                say("system_against_the_reference_in_float8_e4m3", seed,
                    max(rel(g, t) for (_, _, g), t in zip(got, low)))
                say("reference_in_float8_e4m3:against_the_reference", seed,
                    max(rel(t, w) for t, w in zip(low, want)))
                del mod, low
            names = args.mistakes.split(",") if args.mistakes \
                else reference.FAULTS
            for name in names if "mistakes" in todo else ():
                wrong = {**arch, "faults": frozenset([name])}
                theirs = [of(reference, params, toks, at, wrong)
                          for toks, at, _ in got]
                say(name + ":against_the_reference", seed, max(
                    rel(t, w) for t, w in zip(theirs, want)))
                say(name + ":system_against_it", seed, max(
                    rel(g, t) for (_, _, g), t in zip(got, theirs)))
        del got, want, params
    out["peak_bytes"] = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")

    for passes in (int(p) for p in args.passes.split(",") if p):
        # the engine's own 64-row program at the widest table, ten decode
        # rows, each of a sequence of 300 positions (as many as fit a table
        # and the pool)
        c = dataclasses.replace(cfg, loop_passes=passes)
        eng.params = eng.pool = None
        params = weights.init_on_device(c, seeds[0])
        pool = PG.init_paged_kv(c, eng.allocator.n_blocks, bs)
        small = eng._bucket(0)
        tables = np.zeros((small, mb), np.int32)
        rows = min(10, small)
        per = min(300 // bs + 1, mb, (eng.allocator.n_blocks - 1) // rows)
        for r in range(rows):
            tables[r, :per] = 1 + r * per + np.arange(per)
        pos = np.zeros((small,), np.int32)
        pos[:rows] = min(299, per * bs - 1)
        tick = jax.jit(lambda params, pool, t, p, tb: PG.forward_paged(
            params, t, p, tb, pool, c, attention_fn=attn),
            donate_argnums=(1,))
        ints = (jnp.zeros((small,), jnp.int32), jnp.asarray(pos),
                jnp.asarray(tables))
        logits, pool = tick(params, pool, *ints)
        jax.block_until_ready(logits)
        t0 = time.perf_counter()
        for _ in range(10):
            logits, pool = tick(params, pool, *ints)
        jax.block_until_ready(logits)
        say("decode_tick_ms_by_passes", passes,
            (time.perf_counter() - t0) * 100.0)
        del params, pool, logits

    session.fe.close()
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    dest = os.path.join(ROOT, "chiprun_out")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, args.out), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
