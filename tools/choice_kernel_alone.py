#!/usr/bin/env python3
"""A sparse layer's choice alone (``ops/pallas/sparse_choice.py`` and its
plain form ``paged.sparse_choice``), at the rows a tick of
``serve-keye-vl2-30b-longctx-closed`` hands it: device time a call, from
calls chained in ONE program under the profiler.

    chiprun -- python tools/choice_kernel_alone.py
    chiprun -- python tools/choice_kernel_alone.py --forms kernel \\
        --module _archive_check/variant/sparse_choice.py --label variant
    chiprun -- python tools/choice_kernel_alone.py --forms plain \\
        --module _archive_check/parent/deepspeed_tpu/models/paged.py

A case is a tick program's (rows, table tier) with the lengths the engine
lays there, decode rows first: a chunk tick's 24 decode rows and 2,024
rows of one prompt that ends at 2k / 9k / 17k positions (tiers 36 / 72 /
144 blocks of 128), a decode tick's 24 real rows of 256 (the others are
pads, one position each). Scores are random float32 in ``index_scores``'
layout. Two forms beside each other: ``kernel`` (the Mosaic call) and
``plain`` (the XLA bisection over 16-bit halves). ``--module`` takes
another file for one of them: one that has ``forward_paged`` is a
``paged.py`` (the parent's plain form), any other a kernel's. One JSON
line a case and form: ``us_per_call`` (every operation of the device's
line over the calls), the bytes the form reads and writes, the tiles of
rows, those that count, the planes they scan, the counting passes a
counting tile makes, ``ns_per_plane_pass`` (the kernel's time over tiles x
planes x passes: what a pass costs a ``[32, 128]`` plane), and the largest
operations by name. ``--against plain``: the kernel's mask beside the
plain form's, element for element.

Nothing here is a benchmark metric: it is the instrument PERF.md's table of
the choice is read from. On a CPU it refuses to run (``--rehearse``: tiny
cases in interpret mode, to see that the script still walks).
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

TOPK = 2048
LANES = 128
# bucket rows, table tier (blocks of 128), decode rows, a prompt's last
# position in the tick (0: no chunk), the decode rows' length
CASES = {
    "mixed-2048x36": (2048, 36, 24, 2024, 4500),
    "mixed-2048x72": (2048, 72, 24, 9000, 9100),
    "mixed-2048x144": (2048, 144, 24, 17000, 17000),
    "decode-256x36": (256, 36, 24, 0, 4500),
    "decode-256x72": (256, 72, 24, 0, 9100),
    "decode-256x144": (256, 144, 24, 0, 17000),
}
# (the second of the mixed case's three tiles has no row over ``TINY_TOPK``)
TINY_TOPK = 64
TINY = {"mixed-96x3": (96, 3, 3, 93, 380), "decode-32x2": (32, 2, 3, 0, 250)}


def operands(rng, case):
    """(scores [tier, rows, 128], lengths [rows]) of a case."""
    rows, tier, decode, end, held = case
    lengths = np.ones((rows,), np.int32)            # a pad row: position 0
    lengths[:decode] = held - rng.integers(0, 64, decode)
    if end:
        chunk = rows - decode
        lengths[decode:] = np.arange(end - chunk, end) + 1
    assert lengths.min() >= 1 and lengths.max() <= tier * LANES
    scores = jnp.asarray(rng.normal(size=(tier, rows, LANES)), jnp.float32)
    return scores, jnp.asarray(lengths)


def forms_of(module, topk, interpret):
    """{name: fn(scores, lengths) -> mask float32} of the tree's two forms,
    one of them ``module``'s where a file was named."""
    from deepspeed_tpu.models import paged
    from deepspeed_tpu.ops.pallas import sparse_choice as kernel

    if module is not None and hasattr(module, "forward_paged"):
        paged = module
    elif module is not None:
        kernel = module

    def plain(scores, lengths):
        tier, _, lanes = scores.shape
        pos = jnp.arange(tier, dtype=jnp.int32)[:, None, None] * lanes \
            + jnp.arange(lanes, dtype=jnp.int32)
        return paged.sparse_choice(
            scores, pos, lengths[None, :, None], topk, (0, 2),
            tier * lanes).astype(jnp.float32)

    return {"plain": plain,
            "kernel": lambda scores, lengths: kernel.sparse_choice(
                scores, lengths, topk, interpret=interpret)}


def chained(form, calls):
    """The form ``calls`` times in one program, each call depending on the
    loop's index (so that none is hoisted) and feeding one element into
    the result (so that none is dropped)."""
    @jax.jit
    def run(scores, lengths):
        def body(i, total):
            mask = form(scores, lengths + jnp.minimum(i, 0))
            return total + mask[0, 0, 0]

        return jax.lax.fori_loop(0, calls, body, jnp.float32(0))

    return run


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default="", help="only these cases")
    ap.add_argument("--forms", default="kernel,plain")
    ap.add_argument("--module", default=None,
                    help="another sparse_choice.py, or a paged.py")
    ap.add_argument("--against", default="",
                    help="'plain': compare masks and time nothing")
    ap.add_argument("--calls", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--label", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/choice_kernel_alone.jsonl")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        sys.exit(f"no TPU here ({device.platform}): a kernel's time is a "
                 "chip's to give; --rehearse walks tiny cases in interpret "
                 "mode")
    from deepspeed_tpu.ops.pallas.sparse_choice import count_tiles, tile_rows
    from tools.kda_kernel_alone import device_us, load_kernel

    cases, topk = (TINY, TINY_TOPK) if args.rehearse else (CASES, TOPK)
    forms = forms_of(args.module and load_kernel(args.module), topk,
                     args.rehearse)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as log:
        def say(line):
            print(json.dumps(line), flush=True)
            log.write(json.dumps(line) + "\n")

        for cname, case in cases.items():
            if args.cases and cname not in args.cases.split(","):
                continue
            scores, lengths = operands(np.random.default_rng(args.seed),
                                       case)
            tier = case[1]
            tiles, counting, planes = count_tiles(
                lengths, topk, tile_rows(tier * LANES))
            head = {"label": args.label, "case": cname, "rows": case[0],
                    "reach": tier * LANES, "tiles": tiles,
                    "tiles_counting": counting, "planes_scanned": planes,
                    "device": device.device_kind}
            if args.against:
                got, want = (np.asarray(jax.jit(forms[f])(scores, lengths))
                             for f in ("kernel", args.against))
                say({**head, "against": args.against,
                     "equal": bool((got == want).all()),
                     "differ": int((got != want).sum()),
                     "chosen": int(got.sum())})
                continue
            for fname in args.forms.split(","):
                run = chained(forms[fname], args.calls)
                # the bisection over the word's bits and the count of the
                # entries over and at the cut (the plain form: the same in
                # every tile or in none, over 16-bit halves)
                passes = 33 if counting else 0
                entries = scores.size
                line = {**head, "form": fname, "counting_passes": passes,
                        "bytes_read": 4 * entries if fname == "kernel"
                        else (4 + 2 * 32 + 4) * entries * bool(counting),
                        "bytes_written": 4 * entries}
                if args.rehearse:
                    jax.block_until_ready(run(scores, lengths))
                else:
                    by_name = device_us(run, (scores, lengths))
                    us = sum(by_name.values()) / args.calls
                    line["us_per_call"] = round(us, 2)
                    if fname == "kernel" and planes:
                        line["ns_per_plane_pass"] = round(
                            1e3 * us / (planes * passes), 3)
                    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:6]
                    line["top_us_per_call"] = {
                        n: round(t / args.calls, 2) for n, t in top}
                say(line)


if __name__ == "__main__":
    main()
