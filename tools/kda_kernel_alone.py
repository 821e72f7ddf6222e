#!/usr/bin/env python3
"""The chunk form of the delta rule alone (``ops/pallas/kda.py``), at the
rows a tick of ``serve-kimi-linear-48b-rollout-closed`` hands it: device
time a call, from calls chained in ONE program under the profiler.

    chiprun -- python tools/kda_kernel_alone.py
    chiprun -- python tools/kda_kernel_alone.py --forms plain \\
        --module _archive_check/parent/deepspeed_tpu/ops/pallas/kda.py

A case is a bucket's rows as the engine lays them out: the rows of the
one-row form first (they are not the chunk form's: ``rows`` leaves them
out), then the prompts' runs, then pads. The first run goes on from a
stored matrix, the others start at position 0. Three forms beside each
other: ``kernel`` (``kda_chunk``), ``plain`` (``kda_chunk_reference``; a
file without one, the parent's, gives its ``kda_chunk``) and ``solve``
(``jax.scipy.linalg.solve_triangular`` alone over every chunk of the
bucket, as the plain form calls it). One JSON line a case and form:
``us_per_call`` (every operation of the device's line over the calls),
``pieces``, ``us_per_head_piece``, the need ``benchmarks/roofline/
kda_chunk.py`` reckons and the share of it, and the largest operations by
name. ``--against plain``: the kernel's results beside the plain form's.

Nothing here is a benchmark metric: it is the instrument PERF.md's table of
the form is read from. On a CPU it refuses to run (``--rehearse``: a tiny
case in interpret mode, to see that the script still walks).
"""
import argparse
import importlib.util
import json
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

#: heads, head width
HEADS = (32, 128)
# bucket rows, rows of the one-row form ahead of the runs, the runs' rows
CASES = {
    "mixed-one-run": (2048, 250, (770,)),
    "mixed-two-runs": (2048, 250, (520, 1000)),
    "decode": (256, 256, ()),
}
TINY = {"mixed": (128, 5, (70, 30)), "decode": (16, 16, ())}


def load_kernel(path):
    if path is None:
        from deepspeed_tpu.ops.pallas import kda as module
        return module
    spec = importlib.util.spec_from_file_location("kernel_under_test", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def operands(rng, case, heads):
    """((q, k, v, g, b, runs, rows, state, slot), pieces) of a case."""
    from deepspeed_tpu.models import hybrid as HY
    from deepspeed_tpu.ops.pallas.kda import count_pieces

    T, ahead, lengths = case
    N, D = heads
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
    q, k, v = f(T, N, D) / 11, f(T, N, D), f(T, N, D)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.asarray(rng.uniform(1e-3, 2.0, (T, N, D)), jnp.float32)
    b = jnp.asarray(rng.uniform(0, 1, (T, N)), jnp.float32)
    slot = jnp.asarray(slot)
    return (q, k, v, g, b, HY.runs_of(slot, jnp.asarray(pos)),
            jnp.asarray(rows), f(len(lengths) + 2, N, D, D), slot), \
        count_pieces(zip(starts.tolist(), lengths))


def solve_alone(q, k, v, g, b, runs, rows, state, slot):
    """The plain form's solve and nothing else: every chunk of the bucket
    a head, 64 x 64 unit-lower against 256 columns."""
    from deepspeed_tpu.ops.pallas.kda import CHUNK as C

    T, N, D = q.shape
    nC = -(-T // C)
    A = jnp.tril(jnp.einsum("crnd,cind->cnri", *(
        x[:nC * C].reshape(nC, -1, N, D) for x in (k * b[..., None], k))), -1)
    rhs = jnp.concatenate([v, k], axis=-1)[:nC * C].reshape(nC, -1, N, 2 * D)
    solved = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(A.shape[-1]), jnp.moveaxis(rhs, 2, 1), lower=True,
        unit_diagonal=True)
    return jnp.moveaxis(solved, 1, 2).reshape(-1, N, 2 * D)[:T, :, :D], state


def forms_of(module, interpret):
    plain = getattr(module, "kda_chunk_reference", None)
    forms = {"plain": plain or module.kda_chunk, "solve": solve_alone}
    if plain is not None:
        forms["kernel"] = lambda *a: module.kda_chunk(*a, interpret=interpret)
    return forms


def chained(form, calls):
    """The form ``calls`` times in one program, the store carried, each
    call depending on the loop's index (so that none is hoisted) and
    feeding one element into the result (so that none is dropped)."""
    @jax.jit
    def run(q, k, v, g, b, runs, rows, state, slot):
        def body(i, carry):
            total, state = carry
            o, state = form(q, k, v, g, b + jnp.minimum(i, 0), runs, rows,
                            state, slot)
            return total + jnp.sum(o[:, 0, 0]), state

        return jax.lax.fori_loop(0, calls, body, (jnp.float32(0), state))

    return run


def device_us(run, args):
    """{operation's name: us} of one traced run of the program."""
    from benchmarks.trace_reduce import ReducedTrace

    jax.block_until_ready(run(*args))                 # compile, warm
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            jax.block_until_ready(run(*args))
        trace = ReducedTrace.from_dir(logdir)
    by_name = {}
    for op in trace.ops[min(trace.ops)] if trace and trace.ops else []:
        by_name[op.name] = by_name.get(op.name, 0.0) + op.seconds * 1e6
    return by_name


def compare(module, args, interpret):
    """The kernel's results beside the plain form's, on one case."""
    forms = forms_of(module, interpret)
    with jax.default_matmul_precision("highest"):
        o_p, s_p = jax.jit(forms["plain"])(*args)
    o_k, s_k = jax.jit(forms["kernel"])(*args)

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    return {"o_rel": rel(o_k, o_p), "state_rel": rel(s_k, s_p),
            "finite": bool(jnp.isfinite(o_k).all())}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default="", help="only these cases")
    ap.add_argument("--forms", default="kernel,plain,solve")
    ap.add_argument("--module", default=None, help="another kda.py to time")
    ap.add_argument("--against", default="",
                    help="'plain': compare results and time nothing")
    ap.add_argument("--calls", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--label", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/kda_kernel_alone.jsonl")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        sys.exit(f"no TPU here ({device.platform}): a kernel's time is a "
                 "chip's to give; --rehearse walks a tiny case in interpret "
                 "mode")
    from benchmarks.roofline.kda_chunk import needed_bytes, needed_ops

    module = load_kernel(args.module)
    cases, heads = (TINY, (2, 128)) if args.rehearse else (CASES, HEADS)
    peaks = None
    if not args.rehearse:
        with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                               "peaks.json")) as f:
            peaks = json.load(f)["chips"][device.device_kind]
    forms = forms_of(module, args.rehearse)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as log:
        def say(line):
            print(json.dumps(line), flush=True)
            log.write(json.dumps(line) + "\n")

        for cname, case in cases.items():
            if args.cases and cname not in args.cases.split(","):
                continue
            ops, pieces = operands(np.random.default_rng(args.seed), case,
                                   heads)
            head = {"label": args.label, "case": cname, "rows": case[0],
                    "chunk_rows": sum(case[2]), "runs": len(case[2]),
                    "pieces": pieces, "device": device.device_kind}
            if args.against:
                if pieces:
                    say({**head, "against": args.against,
                         **compare(module, ops, args.rehearse)})
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
                    if pieces and fname != "solve":
                        need = 1e6 * max(
                            needed_ops(sum(case[2]), *heads)
                            / peaks["bf16_flops_per_s"],
                            needed_bytes(len(case[2]), *heads)
                            / peaks["hbm_bytes_per_s"])
                        line.update(
                            us_per_head_piece=round(
                                us / (pieces * heads[0]), 3),
                            need_us=round(need, 2),
                            roofline_pct=round(100 * need / us, 3))
                    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:8]
                    line["top_us_per_call"] = {
                        n: round(t / args.calls, 2) for n, t in top}
                say(line)


if __name__ == "__main__":
    main()
