#!/usr/bin/env python3
"""The readings a stack-of-kinds cell's ``logits_check.rel_tol`` is set
from, and what separates a fault from rounding (``tools/logits_probe.py``
is the same idea for the families before; its ``--lower`` rounds what a
layer returns, and this family's layers hand on more than the residual).

    chiprun -- python benchmarks/tools/hybrid_probe.py --workload <cell> \
        --seeds 1,2 --do system,lower,mistakes,faults,kernels \
        [--depth 8 --dtype float32] [--prompt-lens 1600,300]

* ``system``: the runner's own ``check_logits`` per seed (chunked prefill +
  decode steps through the engine's pools with its kernels, against the
  reference). With ``--depth D --dtype float32`` the same at matmul
  precision "highest" on a stack cut to D layers: a bug shows there
  (1e-6 is rounding), rounding does not.
* ``lower``: the reference with each layer's weights and its residual
  stream in float8_e4m3, the nearest precision below the configuration's,
  against itself in float32, on the check's tokens and positions.
* ``mistakes``: the reference with one mistake made on purpose against
  itself: a window of 1,024 for 512, lambda = 0, the sub-norm dropped, a
  gated unit reading layer 14's scan, a cross layer attending over a range
  nobody wrote (zeros).
* ``faults``: the SYSTEM with a fault made on purpose against the
  reference: state not carried across a tick boundary, state carried
  across a sequence boundary.
* ``kernels``: the two kernel instantiations alone against their jnp twin
  at the published head shapes.

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

#: mistakes in the reference's own source: name -> (old text, new text)
MISTAKES = {
    "lambda-zero": ("o = a1 - lam * a2", "o = a1 - 0.0 * lam * a2"),
    "no-sub-norm": (
        "    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, "
        "keepdims=True)\n                          + arch[\"eps\"]) "
        "* lp[\"sub_norm\"]\n", "    o = o * lp[\"sub_norm\"]\n"),
    "gmu-reads-layer-14": (
        "        out, memory = _mamba(u, lp, arch)",
        "        out, m2 = _mamba(u, lp, arch)\n"
        "        memory = jnp.where(layer == 16.0, memory, m2)"),
    "cross-over-unwritten-range": (
        "        kv = shared\n",
        "        kv = jax.tree.map(jnp.zeros_like, shared)\n"),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--do", default="system")
    ap.add_argument("--depth", type=int, default=0)
    ap.add_argument("--dtype", default="")
    ap.add_argument("--prompt-lens", default="")
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--blocks", type=int, default=0,
                    help="a smaller pool (a float32 stack beside it)")
    ap.add_argument("--slots", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import harness, manifest, model_config, weights
    from benchmarks.runners import serve
    from deepspeed_tpu.models import hybrid as HY
    from deepspeed_tpu.models import paged as PG

    cell = manifest.load_cell(args.workload)
    reference = manifest.load_plugin("reference", cell.config["reference"])
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
    seeds = [int(s) for s in args.seeds.split(",")]
    todo = args.do.split(",")
    spec = dict(cell.deploy.get("rehearse", {}).get("logits_check", {})
                if args.rehearse else cell.deploy["logits_check"])
    if args.prompt_lens:
        spec["prompt_lens"] = [int(n) for n in args.prompt_lens.split(",")]
    n_dec = int(spec["decode_steps"])
    out = {"cell": cell.name, "depth": hf["num_hidden_layers"],
           "dtype": cell.config["compute_dtype"], "readings": {}}

    def rel(got, want):
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    def say(key, seed, value):
        out["readings"].setdefault(key, {})[str(seed)] = value
        harness.log(f"probe: {key} seed {seed}: {value}")

    def session_for(seed):
        s = serve.Session(cell, types.SimpleNamespace(
            seed=seed, rehearse=bool(args.rehearse)))
        s.deploy["logits_check"] = spec
        return s

    def check(seed):
        import gc

        s = session_for(seed)
        try:
            if cell.config["compute_dtype"] == "float32":
                with jax.default_matmul_precision("highest"):
                    return s.check_logits()
            return s.check_logits()
        finally:        # the next session's weights and pools need the room
            s.engine.params = s.engine.pool = None
            s.fe.close()
            del s
            gc.collect()

    def the_checks_tokens(seed, cfg):
        """(tokens, compared positions) as ``check_logits`` draws them."""
        rng = np.random.default_rng([seed, 7])
        return [(rng.integers(0, cfg.vocab_size, n + n_dec).astype(np.int32),
                 list(range(n - 1, n + n_dec))) for n in spec["prompt_lens"]]

    wanted = {}

    def reference_pairs(seed, variant):
        """Worst over the check's prompts of ||variant - reference|| /
        ||reference|| at the compared positions."""
        if seed not in wanted:
            cfg = model_config.build(cell.config, "serve",
                                     rehearse=bool(args.rehearse))
            params = weights.init_on_device(cfg, seed)
            wanted.clear()              # one seed's weights at a time
            wanted[seed] = params, [
                (toks, at, reference.forward_logits(
                    params, toks[None], arch, at=at)[0])
                for toks, at in the_checks_tokens(seed, cfg)]
            harness.log(f"probe: reference of seed {seed} done")
        params, rows = wanted[seed]
        return max(rel(variant(params, toks[None], at), want)
                   for toks, at, want in rows)

    if "system" in todo:
        for seed in seeds:
            say("system", seed, check(seed))

    if "faults" in todo:
        real_mamba, real_runs = HY.mamba, HY.runs_of

        def forgets(h, lp, cfg, runs, conv0, ssm0):
            return real_mamba(h, lp, cfg, runs._replace(
                fresh=jnp.ones_like(runs.fresh)), conv0, ssm0)

        def one_run(owner, positions):
            """A sequence that starts inside a tick continues the rows
            before it."""
            r = real_runs(owner, positions)
            t = jnp.arange(owner.shape[0], dtype=jnp.int32)
            start = jnp.where((t > 0) & (owner > 0), False, r.start)
            offset = t - jax.lax.cummax(jnp.where(start, t, 0))
            return r._replace(start=start, offset=offset,
                              fresh=r.fresh & (offset == r.offset))

        for name, (attr, fn) in {
                "state-dropped-at-tick-boundary": ("mamba", forgets),
                "state-carried-across-sequences": ("runs_of", one_run)
        }.items():
            setattr(HY, attr, fn)
            try:
                say(name, seeds[0], check(seeds[0]))
            finally:
                HY.mamba, HY.runs_of = real_mamba, real_runs

    if "lower" in todo:
        f8 = jnp.float8_e4m3fn
        real = reference._layer_jit

        def to(a):
            return a.astype(f8).astype(a.dtype)

        def layer(x, lp, *a, **kw):
            x, memory, shared = real(x, jax.tree.map(to, lp), *a, **kw)
            return to(x), memory, shared

        def lowered(params, toks, at):
            reference._layer_jit = layer
            try:
                return reference.forward_logits(params, toks, arch, at=at)[0]
            finally:
                reference._layer_jit = real

        for seed in seeds:
            say("reference_in_float8_e4m3", seed,
                reference_pairs(seed, lowered))

    if "mistakes" in todo:
        with open(reference.__file__) as f:
            source = f.read()
        variants = {"window-1024-for-512": (reference,
                                            {**arch, "window": 1024})}
        if args.rehearse:
            variants = {"window-doubled": (
                reference, {**arch, "window": 2 * arch["window"]})}
        for name, (old, new) in MISTAKES.items():
            assert source.count(old) == 1, name
            mod = types.ModuleType("mistaken_" + name.replace("-", "_"))
            exec(compile(source.replace(old, new), reference.__file__,
                         "exec"), mod.__dict__)
            variants[name] = (mod, arch)
        for name, (mod, a) in variants.items():
            say(name, seeds[0], reference_pairs(
                seeds[0], lambda p, t, at, mod=mod, a=a:
                mod.forward_logits(p, t, a, at=at)[0]))

    if "kernels" in todo:
        from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

        cfg = model_config.build(cell.config, "serve",
                                 rehearse=bool(args.rehearse))
        K, D = cfg.kv_heads // 2, 2 * cfg.head_dim
        N, W, bs, RB, MB, Tn = cfg.num_heads, cfg.attn_window, 32, 8, 40, 64
        if not args.rehearse:
            RB = 32
        rng = np.random.default_rng(11)
        pool = [jnp.asarray(rng.normal(size=(4 * RB, K, bs, D)), jnp.bfloat16)
                for _ in range(2)]
        q = jnp.asarray(rng.normal(size=(Tn, N, D)), jnp.bfloat16)
        span = RB * bs
        for name, window in (("window_paged_attention", W),
                             ("shared_paged_attention", None)):
            slot = np.array([1] * 40 + [2, 3] + [0] * (Tn - 42), np.int32)
            top = (MB * bs if window else span) - 41
            pos = np.concatenate([np.arange(top, top + 40),
                                  [min(700, top), 5],
                                  np.zeros(Tn - 42)]).astype(np.int32)
            tables = slot[:, None] * RB + (np.arange(MB) % RB)[None, :]
            a = (q, *pool, jnp.asarray(tables), jnp.asarray(pos + 1))
            want = PG.paged_attention_reference(
                *a, scale=0.125, window=window, heads_first=True)
            got = paged_attention(*a, scale=0.125, window=window,
                                  heads_first=True, name=name)
            say(name, 11, float(jnp.max(jnp.abs(
                got.astype(jnp.float32) - want.astype(jnp.float32)))))

    os.makedirs(harness.OUT_DIR, exist_ok=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
