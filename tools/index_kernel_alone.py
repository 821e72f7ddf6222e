#!/usr/bin/env python3
"""A sparse layer's indexer call alone (``ops/pallas/index_scores.py``), at
the rows a tick of ``serve-keye-vl2-30b-longctx-closed`` hands it: device
time a call, from calls chained in ONE program under the profiler, at two
trip counts (the difference is the calls' own: what the program does once
drops out).

    chiprun -- python tools/index_kernel_alone.py
    chiprun -- python tools/index_kernel_alone.py --label parent \\
        --module _archive_check/parent/deepspeed_tpu/ops/pallas/index_scores.py
    chiprun -- python tools/index_kernel_alone.py \\
        --against _archive_check/parent/deepspeed_tpu/ops/pallas/index_scores.py

A case is a tick program's (rows, table tier) with the rows the engine lays
there, decode rows first: a chunk tick's 24 decode rows (a slot each) and
2,024 rows of one prompt that ends at 2k / 9k / 17k positions, a decode
tick's 24 real rows of 256 (the others are pads: slot 0, one position
each). Tiers are 36 / 72 / 144 blocks of 128; the widest tier, where the
cell's clients spend their run (their sequences hold 16k-17k), has a chunk
early, midway and at the end of its prompt. Queries and keys are random
bfloat16 over the indexer's 64 columns of the stored 128, 16 heads; the
store is one layer's 4,353 blocks. One JSON line a case: ``us_per_call``
(every operation of the device's line: the Mosaic call and what XLA lays
out for it), ``kernel_us_per_call`` (the operations named ``index_scores``
alone), the call's ``tile_steps`` (a run of rows against a step's
positions) and ``alone_steps`` (a row alone against them), ``positions_a_
step``, and the largest operations by name. ``--against <file>``: this
tree's scores beside that file's over every (row, position) under a row's
length, and times nothing.

Nothing here is a benchmark metric: it is the instrument PERF.md's table of
the indexer is read from. On a CPU it refuses to run (``--rehearse``: tiny
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

from tools.choice_kernel_alone import CASES as _SIX  # noqa: E402

#: the indexer's heads, its own columns, the stored key's, a block's positions
HEADS, COLS, WIDTH, BLOCK = 16, 64, 128, 128
#: blocks of one sparse layer's store in the cell
STORE_BLOCKS = 4353
# bucket rows, table tier (blocks), decode rows, a prompt's last position in
# the tick (0: no chunk), the decode rows' length: the choice tool's six, and
# the widest tier's chunk early and midway in its prompt
CASES = {
    **_SIX,
    "mixed-2048x144-chunk-2k": (2048, 144, 24, 2024, 17000),
    "mixed-2048x144-chunk-9k": (2048, 144, 24, 9000, 17000),
}
# (heads, columns, stored width, block, blocks of the store) of the tiny cases
TINY_DIMS = (4, 8, 128, 8, 200)
TINY = {"mixed-96x48": (96, 48, 3, 93, 380), "decode-32x32": (32, 32, 3, 0, 250)}


def operands(rng, case, dims=(HEADS, COLS, WIDTH, BLOCK, STORE_BLOCKS),
             dtype=jnp.bfloat16):
    """(q, w, store, tables, lengths, row_table) of a case."""
    rows, tier, decode, end, held = case
    H, cols, W, bs, NB = dims
    lengths = np.ones((rows,), np.int32)            # a pad row: position 0
    slot = np.zeros((rows,), np.int32)              # ... of slot 0
    lengths[:decode] = held - rng.integers(0, 64, decode)
    slot[:decode] = 1 + np.arange(decode)
    if end:
        chunk = rows - decode
        lengths[decode:] = np.arange(end - chunk, end) + 1
        slot[decode:] = decode + 1
    assert lengths.min() >= 1 and lengths.max() <= tier * bs
    slots = decode + 2
    assert (slots - 1) * tier < NB
    tables = np.zeros((slots, tier), np.int32)
    tables[1:] = (1 + rng.permutation(NB - 1)[:(slots - 1) * tier]).reshape(
        slots - 1, tier)

    def live(*shape):
        x = rng.normal(size=shape).astype(np.float32)
        x[..., cols:] = 0.0
        return jnp.asarray(x, dtype)

    w = jnp.asarray(rng.normal(size=(rows, H)), dtype)
    return (live(rows, H, W), w, live(NB, bs, W), jnp.asarray(tables),
            jnp.asarray(lengths), jnp.asarray(slot))


def count_steps(lengths, row_table, positions):
    """(tile steps, alone steps) of a call: every run of rows that share a
    slot inside a tile of ``TILE_ROWS`` walks its longest row's positions,
    ``positions`` a step; a run of one row walks alone."""
    from deepspeed_tpu.ops.pallas.index_scores import TILE_ROWS

    lengths, slot = np.asarray(lengths), np.asarray(row_table)
    pad = -len(lengths) % TILE_ROWS
    lengths = np.pad(lengths, (0, pad), constant_values=1)
    slot = np.pad(slot, (0, pad))
    tile, alone, r0 = 0, 0, 0
    while r0 < len(slot):
        r1 = r0 + 1
        while r1 % TILE_ROWS and slot[r1] == slot[r0]:
            r1 += 1
        steps = -(-int(lengths[r0:r1].max()) // positions)
        if r1 - r0 == 1:
            alone += steps
        else:
            tile += steps
        r0 = r1
    return tile, alone


def chained(kernel, calls, interpret):
    """The call ``calls`` times in one program, each call depending on the
    loop's index (so that none is hoisted) and feeding one element into the
    result (so that none is dropped)."""
    @jax.jit
    def run(q, w, store, tables, lengths, row_table):
        def body(i, total):
            scores = kernel.index_scores(
                q, w, store, tables, lengths + jnp.minimum(i, 0), row_table,
                interpret=interpret)
            return total + scores[0, 0, 0]

        return jax.lax.fori_loop(0, calls, body, jnp.float32(0))

    return run


def compare(kernel, other, args, interpret):
    """This tree's scores beside another file's, under each row's length."""
    got, want = (np.asarray(jax.jit(
        lambda *a, m=m: m.index_scores(*a, interpret=interpret))(*args))
        for m in (kernel, other))
    lengths = np.asarray(args[4])
    planes, rows, lanes = want.shape
    pos = (np.arange(planes)[:, None, None] * lanes + np.arange(lanes))
    live = pos < np.pad(lengths, (0, rows - len(lengths)))[None, :, None]
    diff = np.abs(np.where(live, got - want, 0.0))
    return {"max_abs_diff": float(diff.max()),
            "max_abs": float(np.abs(np.where(live, want, 0.0)).max()),
            "differ": int((diff > 0).sum()), "live": int(live.sum())}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default="", help="only these cases")
    ap.add_argument("--module", default=None,
                    help="another index_scores.py to time")
    ap.add_argument("--against", default="",
                    help="another index_scores.py: compare scores and time "
                    "nothing")
    ap.add_argument("--calls", default="2,6",
                    help="the two trip counts of the chained program")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--label", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/index_kernel_alone.jsonl")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        sys.exit(f"no TPU here ({device.platform}): a kernel's time is a "
                 "chip's to give; --rehearse walks tiny cases in interpret "
                 "mode")
    from deepspeed_tpu.ops.pallas import index_scores as tree
    from tools.kda_kernel_alone import device_us, load_kernel

    kernel = load_kernel(args.module) if args.module else tree
    other = args.against and load_kernel(args.against)
    cases = TINY if args.rehearse else CASES
    few, many = (int(n) for n in args.calls.split(","))
    if not 0 < few < many:
        ap.error("--calls takes two trip counts, the smaller first")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as log:
        def say(line):
            print(json.dumps(line), flush=True)
            log.write(json.dumps(line) + "\n")

        for cname, case in cases.items():
            if args.cases and cname not in args.cases.split(","):
                continue
            ops = operands(np.random.default_rng(args.seed), case,
                           *((TINY_DIMS, jnp.float32) if args.rehearse
                             else ()))
            bs = ops[2].shape[1]
            positions = kernel.step_positions(       # the timed file's own
                bs, kernel.table_cols(case[1], bs) * bs)
            tile, alone = count_steps(ops[4], ops[5], positions)
            head = {"label": args.label, "case": cname, "rows": case[0],
                    "tier": case[1], "positions_a_step": positions,
                    "tile_steps": tile, "alone_steps": alone,
                    "device": device.device_kind}
            if args.against:
                say({**head, "against": args.against,
                     **compare(kernel, other, ops, args.rehearse)})
                continue
            runs = [chained(kernel, n, args.rehearse) for n in (few, many)]
            if args.rehearse:
                for run in runs:
                    jax.block_until_ready(run(*ops))
                say(head)
                continue
            by_name = [device_us(run, ops) for run in runs]
            each = {name: (by_name[1].get(name, 0.0) - by_name[0].get(
                name, 0.0)) / (many - few) for name in by_name[1]}
            top = sorted(each.items(), key=lambda kv: -kv[1])[:6]
            say({**head, "calls": [few, many],
                 "us_per_call": round(sum(each.values()), 2),
                 "kernel_us_per_call": round(sum(
                     us for name, us in each.items()
                     if name.startswith("index_scores")), 2),
                 "top_us_per_call": {n: round(t, 2) for n, t in top}})


if __name__ == "__main__":
    main()
