#!/usr/bin/env python3
"""The movers of a share of an expert layer alone (``moe/layer.py``:
``held_rows_out``, ``held_pairs_in``, ``held_expert_act`` and their
transposes) beside the plain forms they stand in for
(``held_dispatch_gather``, ``held_combine_gather``, ``_expert_act``: a row
a pair moved, the absent masked), at the shapes the cells hand them:
device time a call, from calls chained in ONE program under the profiler.

    chiprun -- python tools/held_rows_alone.py
    chiprun -- python tools/held_rows_alone.py --cases train-0.25 \\
        --movers rows_out,pairs_in --forms live

A case is a call's rows, width, choices a row, the experts' width and the
share of the pairs that is here: the training cell's step (16,384 rows of
2,304, 8 choices, 16 experts of 896 held; a tenth, a quarter, a half and
all of the pairs here) and the three serving cells' chunk (2,048 rows) and
decode (256 rows) ticks, an eighth of whose pairs are here. A routing is
drawn for the share: each row chooses ``k`` distinct experts of ``held /
share`` evenly, and the first ``held`` are here. Six movers, each in two
forms (``plain``, ``live``): ``rows_out`` (the dispatch), ``pairs_in`` (the
combine), ``act`` (the activation), and in the training cases their
transposes ``rows_out_bwd`` (``dx``), ``pairs_in_bwd`` (``dy`` and ``dw``)
and ``act_bwd``. One JSON line a case, mover and form: ``us_per_call``
(every operation of the device's line over the calls), the sorted rows
the form covers, and the largest operations by name. ``--against plain``:
the live form's results beside the plain form's on the rows below ``n``.

Nothing here is a benchmark metric: it is the instrument PERF.md's table of
the movers is read from, and what ``held_tiles``' rule of engagement rests
on. On a CPU it refuses to run (``--rehearse``: tiny cases, to see that the
script still walks).
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# rows, width, choices a row, an expert's width, held experts, share here,
# whether the call has a backward
CASES = {
    **{f"train-{share}": (16384, 2304, 8, 896, 16, share, True)
       for share in (0.10, 0.25, 0.50, 1.0)},
    "trinity-chunk": (2048, 3072, 4, 3072, 32, 0.125, False),
    "trinity-decode": (256, 3072, 4, 3072, 32, 0.125, False),
    "kimi-chunk": (2048, 2304, 8, 1024, 32, 0.125, False),
    "kimi-decode": (256, 2304, 8, 1024, 32, 0.125, False),
    "keye-chunk": (2048, 2048, 8, 768, 16, 0.125, False),
    "keye-decode": (256, 2048, 8, 768, 16, 0.125, False),
}
TINY = {"train-0.25": (128, 256, 8, 128, 4, 0.25, True),
        "decode": (128, 256, 4, 128, 4, 0.125, False)}
MOVERS = ("rows_out", "pairs_in", "act", "rows_out_bwd", "pairs_in_bwd",
          "act_bwd")


def operands(rng, case):
    """What a call's movers take, for a routing drawn at the case's share:
    a dict of arrays and ``n``, the held pairs."""
    from deepspeed_tpu.moe import layer as MOE

    T, H, k, inter, held, share, _ = case
    router = max(int(round(held / share)), k)
    idx = np.argsort(rng.random((T, router)), axis=1)[:, :k].astype(np.int32)
    order, inv2d, sizes, here = jax.jit(
        MOE.held_group_sizes, static_argnums=(1, 2))(jnp.asarray(idx), held, 0)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.bfloat16)  # noqa: E731
    return {"x": f(T, H), "g": f(T, H), "y_s": f(T * k, H),
            "weights": jnp.asarray(rng.random((T, k)), jnp.bfloat16),
            "up": f(T * k, inter), "gate": f(T * k, inter),
            "d_act": f(T * k, inter), "order": order, "inv2d": inv2d,
            "here": here, "n": jnp.sum(sizes)}


def forms_of(mover, tiles):
    """{form: fn(operands dict) -> arrays} of one mover."""
    from deepspeed_tpu.moe import layer as MOE

    def act_bwd(form):
        def run(a):
            act = (lambda u, g: MOE._expert_act(u, g, "swiglu")) \
                if form == "plain" else \
                (lambda u, g: MOE.held_expert_act(u, g, a["n"], "swiglu",
                                                  tiles[0]))
            return jax.vjp(act, a["up"], a["gate"])[1](a["d_act"])
        return run

    return {
        "rows_out": {
            "plain": lambda a: MOE.held_dispatch_gather(
                a["x"], a["order"], a["inv2d"], a["here"]),
            "live": lambda a: MOE.held_rows_out(
                a["x"], a["order"], a["inv2d"], a["here"], a["n"], tiles)},
        "pairs_in": {
            "plain": lambda a: MOE.held_combine_gather(
                a["y_s"], a["weights"], a["order"], a["inv2d"], a["here"]),
            "live": lambda a: MOE.held_pairs_in(
                a["y_s"], a["weights"], a["order"], a["inv2d"], a["here"],
                a["n"], tiles)},
        "act": {
            "plain": lambda a: MOE._expert_act(a["up"], a["gate"], "swiglu"),
            "live": lambda a: MOE.held_expert_act(
                a["up"], a["gate"], a["n"], "swiglu", tiles[0])},
        "rows_out_bwd": {
            "plain": lambda a: MOE._held_dispatch_gather_bwd(
                (a["inv2d"], a["here"]), a["y_s"])[0],
            "live": lambda a: MOE._held_rows_out_bwd(
                tiles, (a["inv2d"], a["here"]), a["y_s"])[0]},
        "pairs_in_bwd": {
            "plain": lambda a: MOE._held_combine_gather_bwd(
                (a["y_s"], a["weights"], a["order"], a["inv2d"], a["here"]),
                a["g"])[:2],
            "live": lambda a: MOE._held_pairs_in_bwd(
                tiles, (a["y_s"], a["weights"], a["order"], a["inv2d"],
                        a["here"], a["n"]), a["g"])[:2]},
        "act_bwd": {"plain": act_bwd("plain"), "live": act_bwd("live")},
    }[mover]


def device_us(run, a):
    """({operation's name: us}, us from the first operation's start to the
    last one's end: the gaps between a device loop's trips are in it) of
    one traced run of the program."""
    import tempfile

    from benchmarks.trace_reduce import ReducedTrace

    jax.block_until_ready(run(a))                     # compile, warm
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            jax.block_until_ready(run(a))
        trace = ReducedTrace.from_dir(logdir)
    ops = trace.ops[min(trace.ops)] if trace and trace.ops else []
    by_name = {}
    for op in ops:
        by_name[op.name] = by_name.get(op.name, 0.0) + op.seconds * 1e6
    span = (max(o.end for o in ops) - min(o.start for o in ops)) * 1e6 \
        if ops else 0.0
    return by_name, span


def chained(form, calls, whole):
    """The form ``calls`` times in one program, each call depending on the
    loop's index (so that none is hoisted) and feeding one element of each
    result into the program's (so that none is dropped). The float
    operands ride the loop behind a barrier: as the loop's invariants, the
    call that hands the activation's loop its unwritten array (it takes
    the loop's operands) was hoisted out of the chain, and every call then
    copied the array before filling it (+0.7 ms; a step's layers each have
    operands of their own). ``whole``: the results behind a barrier too,
    for the plain elementwise forms, which XLA narrows to the one element
    read (the plain activation read 0.18 ms a call for 1.52); not for a
    gather, which that barrier has copied whole (+1.8 ms)."""
    @jax.jit
    def run(a):
        floats = {k: v for k, v in a.items()
                  if jnp.issubdtype(v.dtype, jnp.floating)}

        def body(i, carry):
            total, floats = carry
            floats = jax.lax.optimization_barrier(floats)
            shift = jnp.minimum(i, 0)
            out = form(dict(a, **floats, order=a["order"] + shift,
                            inv2d=a["inv2d"] + shift, n=a["n"] + shift))
            if whole:
                out = jax.lax.optimization_barrier(out)
            return total + sum(o[0].reshape(-1)[0].astype(jnp.float32)
                               for o in jax.tree.leaves(out)), floats

        return jax.lax.fori_loop(0, calls, body, (jnp.float32(0), floats))[0]

    return run


def compare(mover, tiles, a):
    """The live form's results beside the plain form's: the largest
    difference over the rows a result has to hold (the sorted rows below
    ``n``; every row of a result a row of the call), over the largest
    entry there."""
    forms = forms_of(mover, tiles)
    got, want = (jax.tree.leaves(jax.jit(forms[f])(a))
                 for f in ("live", "plain"))
    n, pairs = int(a["n"]), a["order"].shape[0]
    worst = 0.0
    for g, w in zip(got, want):
        g, w = (np.asarray(v, np.float32) for v in (g, w))
        if g.shape[0] == pairs and g.ndim == 2:
            g, w = g[:n], w[:n]
        if w.size:
            worst = max(worst, float(np.abs(g - w).max()
                                     / max(np.abs(w).max(), 1e-30)))
    return worst


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default="", help="only these cases")
    ap.add_argument("--movers", default=",".join(MOVERS))
    ap.add_argument("--forms", default="plain,live")
    ap.add_argument("--against", default="",
                    help="'plain': compare results and time nothing")
    ap.add_argument("--calls", type=int, default=4)
    ap.add_argument("--top", type=int, default=6,
                    help="operations named in a line, the largest first")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--label", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/held_rows_alone.jsonl")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        sys.exit(f"no TPU here ({device.platform}): a mover's time is a "
                 "chip's to give; --rehearse walks tiny cases")
    from deepspeed_tpu.moe.layer import held_tiles

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as log:
        def say(line):
            print(json.dumps(line), flush=True)
            log.write(json.dumps(line) + "\n")

        for cname, case in (TINY if args.rehearse else CASES).items():
            if args.cases and cname not in args.cases.split(","):
                continue
            T, H, k, inter, held, share, backward = case
            a = operands(np.random.default_rng(args.seed), case)
            tiles = held_tiles(T * k, T)
            n = int(a["n"])
            head = {"label": args.label, "case": cname, "rows": T,
                    "width": H, "k": k, "pairs": T * k, "held_pairs": n,
                    "tiles": tiles, "device": device.device_kind}
            for mover in args.movers.split(","):
                if mover.endswith("_bwd") and not backward:
                    continue
                if args.against:
                    say({**head, "mover": mover, "against": args.against,
                         "worst_rel": compare(mover, tiles, a)})
                    continue
                for fname in args.forms.split(","):
                    run = chained(forms_of(mover, tiles)[fname], args.calls,
                                  mover.startswith("act")
                                  and fname == "plain")
                    covered = T * k if fname == "plain" \
                        else -(-n // tiles[0]) * tiles[0]
                    line = {**head, "mover": mover, "form": fname,
                            "sorted_rows_covered": covered}
                    if args.rehearse:
                        jax.block_until_ready(run(a))
                    else:
                        by_name, span = device_us(run, a)
                        line["us_per_call"] = round(
                            sum(by_name.values()) / args.calls, 2)
                        line["span_us_per_call"] = round(
                            span / args.calls, 2)
                        top = sorted(by_name.items(),
                                     key=lambda kv: -kv[1])[:args.top]
                        line["top_us_per_call"] = {
                            nm: round(t / args.calls, 2) for nm, t in top}
                    say(line)


if __name__ == "__main__":
    main()
