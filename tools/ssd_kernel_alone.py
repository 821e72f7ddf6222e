#!/usr/bin/env python3
"""The chunked form of the Mamba-2 recurrence alone (``ops/pallas/ssd.py``),
at the rows a chunk tick of the two cells that run it hands a layer: device
time a call, from calls chained in ONE program under the profiler.

    chiprun -- python tools/ssd_kernel_alone.py
    chiprun -- python tools/ssd_kernel_alone.py --forms plain \\
        --module _archive_check/parent/deepspeed_tpu/ops/pallas/ssd.py

A case is a bucket's rows as the engine lays them out: the rows of the
one-row form first (they are not the chunked form's: ``rows`` leaves them
out), then the prompts' runs, then pads. The first run goes on from a
stored state, the others start at position 0. Two forms beside each other:
``kernel`` (``ssd_chunk``) and ``plain`` (``ssd_chunk_reference``; a file
without one, the parent's, gives its ``ssd_chunk``). One JSON line a case
and form: ``us_per_call`` (every operation of the device's line over the
calls), ``pieces``, ``us_per_piece``, the need ``benchmarks/roofline/
ssd_chunk.py`` reckons and the share of it, and the largest operations by
name. ``--against plain``: the kernel's results beside the plain form's.

Nothing here is a benchmark metric: it is the instrument PERF.md's table of
the form is read from. On a CPU it refuses to run (``--rehearse``: a tiny
case in interpret mode, to see that the script still walks).
"""
import argparse
import json
import os
import sys
from types import SimpleNamespace

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tools.kda_kernel_alone import device_us, load_kernel  # noqa: E402

# (heads, channels, groups, state, chunk), bucket rows, rows of the one-row
# form ahead of the runs, the runs' rows
GRANITE, NEMOTRON = (128, 64, 1, 128, 256), (128, 64, 8, 128, 128)
CASES = {
    "granite-one-run": (GRANITE, 2048, 32, (2016,)),
    "nemotron-three-runs": (NEMOTRON, 2048, 128, (400, 350, 340)),
    "granite-decode": (GRANITE, 256, 32, ()),
}
TINY = {"one-group": ((4, 64, 1, 128, 16), 64, 3, (30, 20)),
        "8-groups": ((16, 8, 8, 128, 16), 64, 3, (30, 20)),
        "decode": ((4, 64, 1, 128, 16), 16, 16, ())}


def operands(rng, case):
    """((x, delta, g, B, C, runs, rows, state, slot), pieces) of a case."""
    from deepspeed_tpu.models import hybrid as HY
    from deepspeed_tpu.ops.pallas.ssd import count_pieces, store_shape

    (nh, P, G, N, chunk), T, ahead, lengths = case
    slot, pos = np.zeros((T,), np.int32), np.zeros((T,), np.int32)
    rows = np.zeros((T,), bool)
    slot[:ahead], pos[:ahead] = 1, 5        # runs of one, each its own
    starts = ahead + np.concatenate([[0], np.cumsum(lengths)]).astype(int)
    assert starts[-1] <= T
    for n, (at, length) in enumerate(zip(starts, lengths)):
        slot[at:at + length] = 2 + n
        pos[at:at + length] = np.arange(length) + (37 if n == 0 else 0)
        rows[at:at + length] = True
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, B, C = f(T, nh, P), f(T, G, N) / 11, f(T, G, N) / 11
    delta = jnp.asarray(rng.uniform(1e-3, 1.0, (T, nh)), jnp.float32)
    g = -jnp.asarray(rng.uniform(1e-3, 2.0, (T, nh)), jnp.float32)
    slot = jnp.asarray(slot)
    state = f(len(lengths) + 2, *store_shape(nh, G, P, N))
    return (x, delta, g, B, C, HY.runs_of(slot, jnp.asarray(pos)),
            jnp.asarray(rows), state, slot), \
        count_pieces(zip(starts.tolist(), lengths), chunk)


def forms_of(module, chunk, interpret):
    plain = getattr(module, "ssd_chunk_reference", None)
    forms = {"plain": lambda *a: (plain or module.ssd_chunk)(*a, chunk)}
    if plain is not None:
        forms["kernel"] = lambda *a: module.ssd_chunk(
            *a, chunk, interpret=interpret)
    return forms


def chained(form, calls):
    """The form ``calls`` times in one program, the store carried, each
    call depending on the loop's index (so that none is hoisted) and
    feeding one element into the result (so that none is dropped)."""
    @jax.jit
    def run(x, delta, g, B, C, runs, rows, state, slot):
        def body(i, carry):
            total, state = carry
            y, state = form(x, delta + jnp.minimum(i, 0), g, B, C, runs,
                            rows, state, slot)
            return total + jnp.sum(y[:, 0, 0]), state

        return jax.lax.fori_loop(0, calls, body, (jnp.float32(0), state))

    return run


def compare(forms, args):
    """The kernel's results beside the plain form's, on one case."""
    with jax.default_matmul_precision("highest"):
        y_p, s_p = jax.jit(forms["plain"])(*args)
    y_k, s_k = jax.jit(forms["kernel"])(*args)

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    return {"y_rel": rel(y_k, y_p), "state_rel": rel(s_k, s_p),
            "finite": bool(jnp.isfinite(y_k).all())}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default="", help="only these cases")
    ap.add_argument("--forms", default="kernel,plain")
    ap.add_argument("--module", default=None, help="another ssd.py to time")
    ap.add_argument("--against", default="",
                    help="'plain': compare results and time nothing")
    ap.add_argument("--calls", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--label", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/ssd_kernel_alone.jsonl")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        sys.exit(f"no TPU here ({device.platform}): a kernel's time is a "
                 "chip's to give; --rehearse walks a tiny case in interpret "
                 "mode")
    from benchmarks.roofline.ssd_chunk import needed_bytes, needed_ops

    if args.module is None:
        from deepspeed_tpu.ops.pallas import ssd as module
    else:
        module = load_kernel(args.module)
    peaks = None
    if not args.rehearse:
        with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                               "peaks.json")) as f:
            peaks = json.load(f)["chips"][device.device_kind]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as log:
        def say(line):
            print(json.dumps(line), flush=True)
            log.write(json.dumps(line) + "\n")

        for cname, case in (TINY if args.rehearse else CASES).items():
            if args.cases and cname not in args.cases.split(","):
                continue
            (nh, P, G, N, chunk), T, _, lengths = case
            ops, pieces = operands(np.random.default_rng(args.seed), case)
            forms = forms_of(module, chunk, args.rehearse)
            head = {"label": args.label, "case": cname, "rows": T,
                    "chunk_rows": sum(lengths), "runs": len(lengths),
                    "chunk": chunk, "groups": G, "pieces": pieces,
                    "device": device.device_kind}
            if args.against:
                if pieces and "kernel" in forms:
                    say({**head, "against": args.against,
                         **compare(forms, ops)})
                continue
            for fname in args.forms.split(","):
                if fname not in forms:
                    continue
                run = chained(forms[fname], args.calls)
                line = {**head, "form": fname}
                if args.rehearse:
                    jax.block_until_ready(run(*ops))
                else:
                    by_name = device_us(run, ops)
                    us = sum(by_name.values()) / args.calls
                    line["us_per_call"] = round(us, 2)
                    if pieces:
                        model = SimpleNamespace(
                            mamba2_heads=nh, mamba2_head_dim=P,
                            mamba2_groups=G, mamba2_state=N,
                            mamba2_chunk=chunk)
                        need = 1e6 * max(
                            needed_ops(sum(lengths), model)
                            / peaks["bf16_flops_per_s"],
                            needed_bytes(len(lengths), model)
                            / peaks["hbm_bytes_per_s"])
                        line.update(us_per_piece=round(us / pieces, 2),
                                    need_us=round(need, 2),
                                    roofline_pct=round(100 * need / us, 3))
                    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:8]
                    line["top_us_per_call"] = {
                        n: round(t / args.calls, 2) for n, t in top}
                say(line)


if __name__ == "__main__":
    main()
