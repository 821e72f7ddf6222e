#!/usr/bin/env python3
"""How far a serving cell's system is from its plain reference, seed by
seed, and what the cell's tolerance has to tell apart: the readings a
``logits_check.rel_tol`` is set from.

    chiprun -- python benchmarks/tools/logits_probe.py --workload <cell> \
        --seeds 1,2,3 [--lower 1] [--prompt-lens 4400,300] [--diagnose 1]

Per seed: the runner's own ``check_logits`` (chunked prefill + decode steps
through the engine's pool with its kernel, against the reference's full
forward; the reference is asked for the logits of the compared positions
alone where it can give them, so ``--prompt-lens`` can reach contexts whose
whole logits would not fit beside the deployment). With ``--lower`` also,
at every seed, the lower limit of the tolerance: the reference computed in
the nearest precision below the configuration's (each layer's weights and
the residual stream in float8_e4m3) against itself in float32, on the
check's own tokens and positions, the worst of its prompts as ``correct``
takes it. The tolerance has to lie between the largest ``check_logits`` and
the smallest of these. With ``--diagnose`` also, at the first seed, on one
sequence of
``--length`` tokens and the logits of its last nine positions:

* the program's plain forward (``T.forward``) in the cell's compute type,
  and in float32 at matmul precision "highest" (a bug shows here; rounding
  does not);
* the reference with its residual stream rounded between layers to
  bfloat16 (what the compute type costs whatever the program does), and
  the reference computed in the nearest precision below it, float8_e4m3:
  the residual stream alone, then the weights as well (the tolerance must
  fail that);
* for a family whose reference has a router: the mistakes made on purpose
  of ``tests/test_latent_moe_family.py``, at the published widths.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--diagnose", type=int, default=0)
    ap.add_argument("--lower", type=int, default=0)
    ap.add_argument("--prompt-lens", default="")
    ap.add_argument("--rehearse", type=int, default=0,
                    help="the cell's rehearsal size: a dry run on the CPU")
    ap.add_argument("--length", type=int, default=308)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import harness, manifest, model_config
    from benchmarks.runners import serve
    from deepspeed_tpu.models import transformer as T

    cell = manifest.load_cell(args.workload)
    reference = manifest.load_plugin("reference", cell.config["reference"])
    hf = model_config.hf_kwargs(cell.config, "serve")
    if args.rehearse:
        hf.update(cell.config["rehearse"])
    arch = reference.arch_from_config(cell.config, hf)
    seeds = [int(s) for s in args.seeds.split(",")]

    def rel(got, want):
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    def rounded(dtype, weights, params, toks, at):
        """The reference's logits at positions ``at``, its residual stream
        (and each layer's weights) rounded to ``dtype``."""
        real = reference._layer_jit

        def to(a):
            return a.astype(dtype).astype(a.dtype)

        def layer(x, lp, *a, **kw):
            if weights:
                lp = jax.tree.map(to, lp)
            return to(real(x, lp, *a, **kw))

        reference._layer_jit = layer
        try:
            return reference.forward_logits(params, toks, arch, at=at)[0]
        finally:
            reference._layer_jit = real

    takes_at = "at" in reference.forward_logits.__code__.co_varnames
    compared = []          # (tokens, positions, the reference's logits)

    class Rows:
        """What ``check_logits`` indexes with ``[0, positions]``."""

        def __init__(self, params, toks):
            self.params, self.toks = params, toks

        def __getitem__(self, idx):
            at = [int(p) for p in idx[1]]
            want = full(self.params, self.toks, arch, at=at)[0]
            compared.append((self.toks, at, want))
            return want

    full = reference.forward_logits
    for i, seed in enumerate(seeds):
        session = serve.Session(cell, types.SimpleNamespace(
            seed=seed, rehearse=bool(args.rehearse)))
        if args.prompt_lens:
            session.deploy["logits_check"] = {
                **session.deploy["logits_check"],
                "prompt_lens": [int(n) for n in args.prompt_lens.split(",")]}
        del compared[:]
        if takes_at:
            reference.forward_logits = lambda p, t, a: Rows(p, t)
        try:
            worst = session.check_logits()
        finally:
            reference.forward_logits = full
        out = {"seed": seed, "check_logits": worst, "mosaic": session.mosaic,
               "prompt_lens": session.deploy["logits_check"]["prompt_lens"]}
        if args.lower and compared:
            out["reference_float8"] = max(
                rel(rounded(jnp.float8_e4m3fn, True, session.engine.params,
                            toks, at), want) for toks, at, want in compared)
        print(json.dumps(out), flush=True)
        if not (args.diagnose and i == 0):
            session.fe.close()
            del session
            continue
        params, cfg = session.engine.params, session.cfg
        toks = np.random.default_rng([seed, 7]).integers(
            0, cfg.vocab_size, (1, args.length)).astype(np.int32)
        want = reference.forward_logits(params, toks, arch)[0, -9:]
        out = {"seed": seed, "length": args.length}
        def note(what, value):
            out[what] = value
            print(json.dumps({what: value}), flush=True)

        note("T.forward, compute type", rel(
            T.forward(params, jnp.asarray(toks), cfg)[0, -9:], want))
        with jax.default_matmul_precision("highest"):
            note("T.forward, float32 highest", rel(T.forward(
                params, jnp.asarray(toks),
                dataclasses.replace(cfg, dtype="float32"))[0, -9:], want))

        def lowered(dtype, weights=False):
            return rel(rounded(dtype, weights, params, toks,
                               list(range(args.length - 9, args.length))),
                       want)

        note("reference, residual in bfloat16", lowered(jnp.bfloat16))
        note("reference, residual in float8_e4m3",
             lowered(jnp.float8_e4m3fn))
        note("reference, layer weights and residual in float8_e4m3",
             lowered(jnp.float8_e4m3fn, weights=True))
        if "top_k" in arch:
            blocks = params["blocks"]
            broken = {
                "dropped dense layer": (arch, {
                    k: v for k, v in params.items() if k != "dense_blocks"}),
                "dropped shared experts": (arch, {**params, "blocks": {
                    k: v for k, v in blocks.items()
                    if not k.startswith("sw_")}}),
                "top-5 for top-6": ({**arch, "top_k": arch["top_k"] - 1},
                                    params),
                "no routed_scaling_factor": ({**arch, "route_scale": 1.0},
                                             params),
                "softmax for sigmoid": ({**arch, "sigmoid": False}, params)}
            for what, (a, p) in broken.items():
                note(what, rel(
                    reference.forward_logits(p, toks, a)[0, -9:], want))
        print(json.dumps(out), flush=True)
        session.fe.close()
        del session, params
    harness.log("probe done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
