#!/usr/bin/env python3
"""The readings the ``KeyeVL2`` cell's ``logits_check.rel_tol`` is set from,
what the check can and cannot see, and what the parts of a sparse layer
cost alone (``tools/kimi_linear_probe.py``, ``tools/lfm2_probe.py``,
``tools/afmoe_probe.py`` and ``tools/hybrid_probe.py`` are the same idea for
the families before).

    chiprun -- python benchmarks/tools/keye_probe.py --workload <cell> \
        --seeds 1,2 [--lower 1] [--mistake dense,half-topk,...] \
        [--dtype float32 --depth 2 --blocks 2000 --slots 4 \
         --gmm-tile 512,512] [--parts 1] [--out file]

Every reading is ``||a - b|| / ||b||`` over the logits of the check's
compared positions (a prompt's last and the decoded ones), the worst of the
check's prompts, as ``runners/serve.py::check_logits`` reads it. The
SYSTEM's logits are made once a seed, here, by the check's own stream of
ticks (chunked prefill of the prompts row after row, then decode ticks,
through the engine's pool with its kernels), and set against:

* always: the reference (the number ``correct`` reads). With ``--dtype
  float32`` the same at matmul precision "highest" (``--depth D``: a stack
  cut to its first D layers, ``--blocks`` / ``--slots``: a pool that fits
  beside float32 weights): a bug shows there (1e-6 is rounding), rounding
  does not;
* ``--lower 1``: the reference COMPUTED in float8_e4m3, the nearest
  precision below the configuration's: every linear layer's input and
  weights rounded (``reference._linear``: the projections, the indexer's,
  the router, the experts), set against the reference itself;
* ``--mistake a,b``: the reference with one mistake made on purpose
  (``reference.FAULTS``; the SYSTEM against the mistaken reference, as
  ``correct`` would read it, and the mistaken reference against the right
  one).

``--parts 1``: no logits; the parts of a sparse layer alone at the cell's
shapes, each in a program of its own, wall time a call over ten calls (so
~0.1 ms of dispatch is in every number): ``lax.top_k`` and
``paged.sparse_choice`` on the scores of a decode and of a chunk tick, a
gather of 2,048 chosen positions a row from the key store, attention over
the gathered. ``--rehearse 1``: the cell's rehearsal size, a dry run on the
CPU.
"""
from __future__ import annotations

import argparse
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
    mod = types.ModuleType("keye_sparse_lm_" + name.replace("-", "_"))
    exec(compile(source, reference.__file__, "exec"), mod.__dict__)
    return mod


def parts(cell, cfg, log) -> dict:
    """Wall time a call of the parts of a sparse layer, at the cell's
    shapes: milliseconds, the best of three rounds of ten calls."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import paged as PG

    eng = cell["engine"]
    bs, MB, slots = eng["block_size"], eng["max_blocks_per_seq"], \
        eng["state_slots"]
    S, topk = bs * MB, cfg.sparse_topk
    small = max(8, eng["token_budget"] // 8)
    K, D = cfg.kv_heads, cfg.head_dim
    out = {}

    def timed(name, fn, *args):
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*args))
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(10):
                r = fn(*args)
            jax.block_until_ready(r)
            best = min(best, (time.perf_counter() - t0) / 10)
        out[name] = round(best * 1e3, 3)
        log(f"probe: {name}: {out[name]} ms a call")

    key = jax.random.key(0)
    for rows in (-(-slots // 32) * 32, small, eng["token_budget"]):
        scores = jax.random.normal(key, (rows, S), jnp.float32)
        lengths = jnp.full((rows,), S - 7, jnp.int32)
        pos = jnp.arange(S, dtype=jnp.int32)
        timed(f"top_k_{rows}x{S}", lambda s: jax.lax.top_k(s, topk)[1],
              scores)
        timed(f"sparse_choice_{rows}x{S}", lambda s, n: PG.sparse_choice(
            s, pos[None], n[:, None], topk, (1,), S), scores, lengths)
        tiles = scores.reshape(rows, S // 128, 128).transpose(1, 0, 2)
        timed(f"sparse_choice_tiles_{rows}x{S}",
              lambda s, n: PG.sparse_choice(
                  s, pos.reshape(S // 128, 1, 128), n[None, :, None], topk,
                  (0, 2), S), tiles, lengths)
    rows = -(-slots // 32) * 32
    store = jnp.zeros((cfg.num_layers * eng["n_blocks"] * bs, K, D),
                      cfg.compute_dtype)
    idx = jax.random.randint(key, (rows, topk), 0, store.shape[0])
    timed(f"gather_{rows}x{topk}_of_{store.shape[0]}",
          lambda st, i: st[i], store, idx)
    timed(f"gather_sorted_{rows}x{topk}",
          lambda st, i: st[jnp.sort(i, axis=1)], store, idx)
    q = jnp.zeros((rows, K, cfg.num_heads // K, D), cfg.compute_dtype)

    def gathered(st, i, q):
        k, v = st[i], st[i + 1]
        s = jnp.einsum("tkrd,tskd->tkrs", q, k,
                       preferred_element_type=jnp.float32) * D ** -0.5
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("tkrs,tskd->tkrd", p, v)

    timed(f"gathered_attention_{rows}x{topk}", gathered, store, idx, q)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--lower", type=int, default=0)
    ap.add_argument("--mistake", default="")
    ap.add_argument("--parts", type=int, default=0)
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
    ap.add_argument("--out", default="keye_probe.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import harness, manifest, model_config
    from benchmarks.runners import serve
    from deepspeed_tpu.models import paged as PG

    cell = manifest.load_cell(args.workload)
    reference = manifest.load_plugin("reference", cell.config["reference"])
    if args.gmm_tile:
        from deepspeed_tpu.moe import layer as moe_layer

        tile = tuple(int(n) for n in args.gmm_tile.split(","))
        moe_layer._whole_k_tile = lambda K, N, itemsize=2: tile
    if args.depth:
        cell.config["as_run"]["serve"]["num_hidden_layers"] = args.depth
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
    deploy = cell.deploy["rehearse"] if args.rehearse else cell.deploy
    spec = dict(deploy["logits_check"])
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

    def finish() -> int:
        dest = os.path.join(ROOT, "chiprun_out")
        os.makedirs(dest, exist_ok=True)
        with open(os.path.join(dest, args.out), "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps(out))
        return 0

    if args.parts:
        out["parts_ms"] = parts(
            deploy, model_config.build(cell.config, "serve",
                                       rehearse=bool(args.rehearse)),
            harness.log)
        return finish()

    def rel(got, want):
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    def say(key, seed, value):
        out["readings"].setdefault(key, {})[str(seed)] = value
        harness.log(f"probe: {key} seed {seed}: {value}")

    def system_logits(session, seed):
        """``check_logits``'s stream of ticks: [(tokens, compared
        positions, logits [positions, V])] a prompt."""
        eng, cfg = session.engine, session.cfg
        attn = PG.paged_attention_reference
        if eng._use_kernel:
            from deepspeed_tpu.ops.pallas.paged_attention import \
                paged_attention as attn
        Tn, mb, bs = eng.token_budget, eng.max_blocks_per_seq, eng.block_size
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

    for seed in (int(s) for s in args.seeds.split(",")):
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
            say("system", seed, max(
                rel(g, w) for (_, _, g), w in zip(got, want)))
            say("system_by_prompt", seed,
                [rel(g, w) for (_, _, g), w in zip(got, want)])
            if args.lower:
                mod = variant_of(reference, "float8", LOWER)
                say("reference_computed_in_float8_e4m3:against_the_reference",
                    seed, max(rel(of(mod, params, toks, at), w)
                              for (toks, at, _), w in zip(got, want)))
            for name in filter(None, args.mistake.split(",")):
                wrong = {**arch, "faults": (name,)}
                theirs = [of(reference, params, toks, at, wrong)
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
    return finish()


if __name__ == "__main__":
    sys.exit(main())
