#!/usr/bin/env python3
"""The three flash-attention kernels alone, at the training cells' shapes:
device time a call, from calls chained in ONE program.

    chiprun -- python tools/flash_kernel_alone.py --config all
    chiprun -- python tools/flash_kernel_alone.py --config mistral \\
        --blocks 512x1024,512x512 --label parent \\
        --module _archive_check/parent/deepspeed_tpu/ops/pallas/flash_attention.py

One program runs forward and gradients ``--calls`` times in a loop on the
device (each call's inputs depend on the call before, so none is hoisted or
dropped) under the profiler; a kernel's time is the median duration of its
events on the device's own line of the trace, found as the benchmark's
``flash_attention_roofline`` finds them (``classify``: by operand and result
counts). Beside it the least time the algorithm needs, by the benchmark's own
rule (``benchmarks/roofline/flash_attention.py``: 2 / 2 / 3 matmuls over the
causal half at the bfloat16 peak), the share of it, and the steps the grid
walks (``step_account``, where the file has one). ``--blocks``: every
kernel at these ``block_q x block_kv`` (comma-separated: one case each);
left out, what the file chooses. ``--module``: time another file's kernels
(the parent's, a variant's) under the same cases. ``--against``: another
file's results at the same inputs beside this one's (largest absolute
difference, and each side's largest error against plain jnp attention in
float32, relative to the reference's largest value).

Nothing here is a benchmark metric: it is the instrument PERF.md's tables of
the flash kernels are read from. On a CPU it refuses to run (``--rehearse``:
a tiny case in interpret mode, to see that the script still walks; no time).
"""
import argparse
import importlib
import importlib.util
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# a chip's share of a step: sequences, their length, query / KV heads, width
CONFIGS = {
    # train-mistral7b-zero3-4chip: 1 x 4,096 a chip, a group of 4
    "mistral": dict(B=1, S=4096, N=32, K=8, D=128),
    # train-pythia69b-zero3-1chip: 2 x 2,048, no KV sharing
    "pythia": dict(B=2, S=2048, N=32, K=32, D=128),
}
TINY = dict(B=1, S=256, N=4, K=2, D=64)
KERNELS = {"flash_fwd": "fwd", "flash_dq": "dq", "flash_dkv": "dkv"}


def load_kernel(path):
    if path is None:
        # the package exports the function under the module's own name
        return importlib.import_module(
            "deepspeed_tpu.ops.pallas.flash_attention")
    spec = importlib.util.spec_from_file_location(
        "kernel_" + str(abs(hash(path))), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def inputs(cfg, dtype, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    dims = [(cfg["B"], cfg["S"], h, cfg["D"])
            for h in (cfg["N"], cfg["K"], cfg["K"], cfg["N"])]
    return [jax.random.normal(k, d, dtype) for k, d in zip(keys, dims)]


def grads(module, blocks, causal=True):
    """``f(q, k, v, do) -> (o, dq, dk, dv)`` through the file's public entry
    (the transposes to and from ``[B*N, S, D]`` run beside the kernels and
    are no part of their events)."""
    named = {} if blocks is None else dict(block_q=blocks[0],
                                           block_kv=blocks[1])

    def one(q, k, v, do):
        o, back = jax.vjp(lambda q, k, v: module.flash_attention(
            q, k, v, causal=causal, **named), q, k, v)
        return (o,) + back(do)

    return one


def chained(one, calls):
    @jax.jit
    def run(q, k, v, do):
        def body(_, qkv):
            q, k, v = qkv
            _, dq, dk, dv = one(q, k, v, do)
            # the next call's inputs come from this one's results
            return (q + 1e-3 * dq, k + 1e-3 * dk, v + 1e-3 * dv)

        return jax.lax.fori_loop(0, calls, body, (q, k, v))

    return run


def kernel_us(run, args):
    """{kernel: [us of each event]} of one traced run of the program;
    under ``"others"`` every other operation of the device's line, as
    ``(name, us)``: the transposes to and from ``[B*N, S, D]``, delta, the
    next call's inputs, and whatever relayout XLA puts around the calls."""
    from benchmarks.roofline.flash_attention import classify
    from benchmarks.trace_reduce import ReducedTrace

    jax.block_until_ready(run(*args))                 # compile, warm
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            jax.block_until_ready(run(*args))
        trace = ReducedTrace.from_dir(logdir)
    found = {}
    for op in trace.ops[min(trace.ops)] if trace and trace.ops else []:
        kind = classify(op)       # as the benchmark tells the three apart
        if kind is not None:
            found.setdefault(kind, []).append(op.seconds * 1e6)
        else:
            found.setdefault("others", []).append(
                (op.name, op.seconds * 1e6))
    return found


def compare(module, other, cfg, blocks, dtype, seed):
    """This file's results against another's and against plain attention."""
    from deepspeed_tpu.models.transformer import dot_product_attention

    args = inputs(cfg, dtype, seed)
    mine = jax.jit(grads(module, blocks))(*args)
    theirs = jax.jit(grads(other, None))(*args)

    def plain(q, k, v, do):
        with jax.default_matmul_precision("highest"):
            o, back = jax.vjp(lambda q, k, v: dot_product_attention(
                q, k, v, causal=True), q, k, v)
            return (o,) + back(do)

    ref = jax.jit(plain)(*[a.astype(jnp.float32) for a in args])
    out = {}
    for name, a, b, r in zip(("o", "dq", "dk", "dv"), mine, theirs, ref):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        top = float(jnp.abs(r).max())
        out[name] = {
            "equal": bool(jnp.array_equal(a, b)),
            "largest_difference": float(jnp.abs(a - b).max()),
            "error_this": float(jnp.abs(a - r).max()) / top,
            "error_other": float(jnp.abs(b - r).max()) / top}
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="all",
                    help="|".join(CONFIGS) + "|all (comma-separated)")
    ap.add_argument("--module", default=None,
                    help="another flash_attention.py to time")
    ap.add_argument("--against", default=None,
                    help="another flash_attention.py to compare results with")
    ap.add_argument("--blocks", default="",
                    help="block_q x block_kv cases, e.g. 512x1024,512x512")
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--label", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/flash_kernel_alone.jsonl")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        sys.exit(f"no TPU here ({device.platform}): a kernel's time is a "
                 "chip's to give; --rehearse walks a tiny case in interpret "
                 "mode")
    from benchmarks.roofline.flash_attention import _MATMULS

    module = load_kernel(args.module)
    configs = {"tiny": TINY} if args.rehearse else {
        name: CONFIGS[name] for name in (
            CONFIGS if args.config == "all" else args.config.split(","))}
    cases = [tuple(int(n) for n in case.split("x"))
             for case in args.blocks.split(",") if case] or [None]
    dtype = jnp.float32 if args.rehearse else jnp.bfloat16
    peak = None
    if not args.rehearse:
        with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                               "peaks.json")) as f:
            peak = json.load(f)["chips"][device.device_kind][
                "bf16_flops_per_s"]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as log:
        def say(line):
            print(json.dumps(line), flush=True)
            log.write(json.dumps(line) + "\n")

        for cname, cfg in configs.items():
            rep = cfg["N"] // cfg["K"]
            for blocks in cases:
                head = {"label": args.label, "config": cname,
                        "device": device.device_kind,
                        "blocks": "chosen" if blocks is None
                        else "%dx%d" % blocks}
                if args.against:
                    say({**head, "against": args.against, **compare(
                        module, load_kernel(args.against), cfg, blocks,
                        dtype, args.seed)})
                    continue
                run = chained(grads(module, blocks), args.calls)
                ops = inputs(cfg, dtype, args.seed)
                if args.rehearse:
                    jax.block_until_ready(run(*ops))
                    times = {}
                else:
                    times = kernel_us(run, ops)
                chosen = blocks
                if chosen is None and hasattr(module, "choose_blocks"):
                    chosen = module.choose_blocks(cfg["S"], cfg["S"])
                for kernel, kind in KERNELS.items():
                    line = {**head, "kernel": kernel}
                    if kind in times:
                        us = statistics.median(times[kind])
                        need = 1e6 * _MATMULS[kind] * cfg["B"] * cfg["N"] \
                            * cfg["S"] ** 2 * cfg["D"] / peak
                        line.update(
                            us_per_call=round(us, 2), events=len(times[kind]),
                            need_us=round(need, 2),
                            roofline_pct=round(100 * need / us, 2))
                    if chosen is not None and hasattr(module, "step_account"):
                        bq, bkv = chosen
                        account = module.step_account(
                            cfg["S"], cfg["S"], True, bq, bkv, rep)[kernel]
                        line.update(block_q=bq, block_kv=bkv, **account)
                        if "us_per_call" in line:
                            # steps of the whole grid: a leading index each
                            # query head (fwd, dq) or KV head (dkv)
                            lead = cfg["B"] * (cfg["K"] if kind == "dkv"
                                               else cfg["N"])
                            line["us_per_live_step"] = round(
                                line["us_per_call"]
                                / (lead * account["live"]), 4)
                    say(line)
                if "others" in times:
                    by_name = {}
                    for name, us in times["others"]:
                        by_name[name] = by_name.get(name, 0.0) + us
                    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:8]
                    say({**head, "kernel": "others", "us_per_call": round(
                        sum(by_name.values()) / args.calls, 2),
                        "top_us_per_call": {n: round(us / args.calls, 2)
                                            for n, us in top}})


if __name__ == "__main__":
    main()
