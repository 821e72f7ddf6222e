#!/usr/bin/env python3
"""The readings the ``nemotron_h`` cell's ``logits_check.rel_tol`` is set
from, and what the check can and cannot see (``tools/kimi_linear_probe.py``
and the probes before it are the same idea for the families before).

    chiprun -- python benchmarks/tools/nemotron_h_probe.py --workload <cell> \
        --seeds 1,2 --do system,lower,mistakes,faults \
        [--depth 4 --dtype float32 --blocks 2000 --slots 8 \
         --gmm-tile 512,512] [--out file]

Every reading is ``||a - b|| / ||b||`` over the logits of the check's
compared positions (a prompt's last and the decoded ones), the worst of the
check's prompts, as ``runners/serve.py::check_logits`` reads it. The
SYSTEM's logits are made once a seed, here, by the check's own stream of
ticks (chunked prefill of the prompts row after row, then decode ticks,
through the engine's pool and slots with its kernels), and set against:

* ``system``: the reference (the number ``correct`` reads). With ``--depth
  D --dtype float32`` the same at matmul precision "highest" on a stack cut
  to its first D layers: a bug shows there (1e-6 is rounding), rounding
  does not;
* ``lower``: the reference COMPUTED in float8_e4m3, the nearest precision
  below the configuration's: every linear layer's input and weights rounded
  (``reference._linear``), set against the reference itself;
* ``mistakes``: the reference with one mistake made on purpose (the
  SYSTEM against the mistaken reference, as ``correct`` would read it, and
  the mistaken reference against the right one): the reference's
  ``FAULTS`` (the decay dropped, heads reading the wrong group's ``B`` and
  ``C``, the skip dropped, the gate after the norm, the norm over the whole
  inner width, the taps reversed, ``relu`` for ``relu2``, one expert a token
  fewer, scaling 1, rotary on the attention layer);
* ``faults``: the SYSTEM with a fault made on purpose in the tick against
  the reference: the slots' state dropped at every tick boundary, state
  carried into the next sequence of a slot (the slots' rows start full of
  another sequence's state).

``--rehearse 1``: the cell's rehearsal size, a dry run on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
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
    mod = types.ModuleType("nemotron_h_lm_" + name.replace("-", "_"))
    exec(compile(source, reference.__file__, "exec"), mod.__dict__)
    return mod


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--do", default="system")
    ap.add_argument("--mistakes", default="",
                    help="of the reference's FAULTS; default: all")
    ap.add_argument("--depth", type=int, default=0)
    ap.add_argument("--dtype", default="")
    ap.add_argument("--prompt-lens", default="")
    ap.add_argument("--decode-steps", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--blocks", type=int, default=0,
                    help="a smaller pool (a float32 stack beside it)")
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--gmm-tile", default="",
                    help="tk,tn of the grouped matmul's weight tile (float32 "
                    "at precision highest runs out of VMEM with K whole)")
    ap.add_argument("--out", default="nemotron_h_probe.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import harness, manifest, model_config
    from benchmarks.runners import serve
    from deepspeed_tpu.models import hybrid as HY
    from deepspeed_tpu.models import paged as PG

    cell = manifest.load_cell(args.workload)
    reference = manifest.load_plugin("reference", cell.config["reference"])
    if args.gmm_tile:
        from deepspeed_tpu.moe import layer as moe_layer

        tile = tuple(int(n) for n in args.gmm_tile.split(","))
        moe_layer._whole_k_tile = lambda K, N, itemsize=2: tile
    if args.depth:
        # the LAST layers of the cut: its attention layer is among them
        cell.config["as_run"]["serve"]["num_hidden_layers"] = args.depth
        cell.config["as_run"]["serve"]["hybrid_override_pattern"] = \
            cell.config["hybrid_override_pattern"][-args.depth:]
    if args.dtype:
        cell.config["compute_dtype"] = args.dtype
    if args.blocks:
        cell.deploy["engine"]["n_blocks"] = args.blocks
    if args.slots:
        cell.deploy["engine"]["state_slots"] = args.slots
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
           "dtype": cell.config["compute_dtype"],
           "prompt_lens": spec["prompt_lens"], "decode_steps": n_dec,
           "readings": {}}

    def rel(got, want):
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    def say(key, seed, value):
        out["readings"].setdefault(key, {})[str(seed)] = value
        harness.log(f"probe: {key} seed {seed}: {value}")

    def system_logits(session, seed, dirty: bool = False,
                      forget: bool = False):
        """``check_logits``'s stream of ticks: [(tokens, compared
        positions, logits [positions, V])] a prompt. ``dirty``: every
        state row starts full of another sequence's state; ``forget``: the
        slots' state is zeroed after every tick."""
        eng, cfg = session.engine, session.cfg
        attn = PG.paged_attention_reference
        if eng._use_kernel:
            from deepspeed_tpu.ops.pallas.paged_attention import \
                paged_attention as attn
        Tn, mb, bs = eng.token_budget, eng.max_blocks_per_seq, eng.block_size
        if dirty:
            eng.pool = {**eng.pool, "ssd": eng.pool["ssd"] + 0.5,
                        "ssd_conv": eng.pool["ssd_conv"] + 0.5}
        rng = np.random.default_rng([seed, 7])
        seqs = []
        for n in spec["prompt_lens"]:
            toks = rng.integers(0, cfg.vocab_size, n + n_dec).astype(np.int32)
            blocks = eng.allocator.allocate((n + n_dec) // bs + 1)
            table = np.zeros((mb,), np.int32)
            table[:len(blocks)] = blocks
            seqs.append({"toks": toks, "n": n, "blocks": blocks,
                         "table": table, "logits": {}})
        fwd = jax.jit(lambda params, pool, t, p, tb: PG.forward_paged(
            params, t, p, tb, pool, cfg, attention_fn=attn),
            donate_argnums=(1,))

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
            if forget:
                eng.pool = {**eng.pool,
                            "ssd": jnp.zeros_like(eng.pool["ssd"]),
                            "ssd_conv": jnp.zeros_like(eng.pool["ssd_conv"])}
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

    def of(mod, params, toks, at):
        return mod.forward_logits(params, toks[None], arch, at=at)[0]

    for seed in seeds:
        session = serve.Session(cell, types.SimpleNamespace(
            seed=seed, rehearse=bool(args.rehearse)))
        params = session.engine.params
        ctx = jax.default_matmul_precision("highest") if highest \
            else jax.default_matmul_precision("default")
        with ctx:
            got = system_logits(session, seed)
            harness.log(f"probe: system's logits of seed {seed} made")
            want = [of(reference, params, toks, at) for toks, at, _ in got]
            harness.log(f"probe: reference of seed {seed} done")
            if "system" in todo:
                say("system", seed, max(
                    rel(g, w) for (_, _, g), w in zip(got, want)))
                say("system_by_prompt", seed,
                    [rel(g, w) for (_, _, g), w in zip(got, want)])
            if "faults" in todo:
                bad = system_logits(session, seed, forget=True)
                say("state-dropped-at-tick-boundaries", seed, max(
                    rel(b, w) for (_, _, b), w in zip(bad, want)))
                real_runs = HY.runs_of

                def carries(owner, positions):
                    r = real_runs(owner, positions)
                    return r._replace(fresh=jnp.zeros_like(r.fresh))

                HY.runs_of = carries
                try:
                    bad = system_logits(session, seed, dirty=True)
                finally:
                    HY.runs_of = real_runs
                say("state-carried-into-the-next-sequence", seed, max(
                    rel(b, w) for (_, _, b), w in zip(bad, want)))
            if "lower" in todo:
                mod = variant_of(reference, "float8", LOWER)
                say("reference_computed_in_float8_e4m3:against_the_reference",
                    seed, max(rel(of(mod, params, toks, at), w)
                              for (toks, at, _), w in zip(got, want)))
            names = args.mistakes.split(",") if args.mistakes \
                else reference.FAULTS
            for name in names if "mistakes" in todo else ():
                wrong = {**arch, "faults": frozenset([name])}
                theirs = [reference.forward_logits(
                    params, toks[None], wrong, at=at)[0]
                    for toks, at, _ in got]
                say(name + ":against_the_reference", seed, max(
                    rel(t, w) for t, w in zip(theirs, want)))
                say(name + ":system_against_it", seed, max(
                    rel(g, t) for (_, _, g), t in zip(got, theirs)))
        session.engine.params = session.engine.pool = None
        session.fe.close()
        del session, params, got, want
        import gc
        gc.collect()

    os.makedirs(harness.OUT_DIR, exist_ok=True)
    dest = os.path.join(ROOT, "chiprun_out")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, args.out), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
