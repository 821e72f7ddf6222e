#!/usr/bin/env python3
"""The paged-attention kernel alone, at a serving configuration's shapes:
device time a call, from calls chained in ONE program.

    chiprun -- python tools/paged_kernel_alone.py --config trinity|keye|lfm2
    chiprun -- python tools/paged_kernel_alone.py --config all \\
        --module _archive_check/parent/deepspeed_tpu/ops/pallas/paged_attention.py

A single jitted call's host clock holds ~600 us of dispatch (PERF.md section
6, PR 32), more than most of these calls take. So a program runs the call n
times in a loop on the device and is timed at two trip counts; the
difference over the difference of the counts is the call's own time. Each
case is a tick's rows as the engine lays them out (decode rows first, then a
prompt chunk's rows, then pads up to the bucket) over a pool of the cell's
size, block ids drawn without order. One JSON line a case: ``us_per_call``,
the fetch steps the call walks and how many of them are open
(``count_steps``), the cache positions a step carries, ``us_per_step``, and
the call's ``walks`` (runs of rows under one table, the tree's
``count_walks`` of the timed module's tiles) with ``us_per_walk``.
``--module``: time another file's kernel (the parent's, a variant's) under
the same cases, its own geometry counted; a file without ``count_steps``
reports no steps. The ``chosen`` form (a sparse layer's masked walk, Keye's)
takes a drawn choice in the planes ``sparse_choice`` writes.

Nothing here is a benchmark metric: it is the instrument PERF.md's step-cost
tables are read from. On a CPU it refuses to run (``--rehearse``: tiny
cases in interpret mode, to see that the script still walks).
"""
import argparse
import importlib.util
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.ops.pallas.paged_attention import count_walks  # noqa: E402

_LANES = 128
# heads, KV heads, head width, block size, then the cell's calls: name ->
# (kernel options, blocks of the pool the call reads, table columns) and its
# ticks: name -> (bucket rows, decode rows, their context, chunk rows, the
# chunk's first position[, the contexts' spread either way: a twentieth of
# the context where none is given]); ``chosen``: the call takes a sparse
# layer's choice, that many positions a row
CONFIGS = {
    # serve-trinity-large-agentctx-closed: 24 clients at ~10.5k context, a
    # prompt's 2,024-row chunks at 0 .. 8k (mean ~5k), rings of 192 blocks
    "trinity": dict(
        heads=(48, 8, 128), bs=32, products="bfloat16", row_table=True,
        calls={"swa_attention": dict(window=4096, ring=192, slots=29,
                                     cols=360),
               "global_attention": dict(window=None, blocks=12288, slots=29,
                                        cols=360)},
        ticks={"chunk": (2048, 24, 10500, 2024, 4048),
               "chunk_late": (2048, 24, 10500, 2024, 8096),
               "decode": (256, 24, 10500, 0, 0)}),
    # serve-mistral7b-chat-steady-v2: 41 decode rows at ~600, chunks of ~470;
    # ``*_spread``: contexts over 150 .. 1,150 as the cell's prompts
    # (lognormal about 512, 64 .. 1,536) and answers spread them
    "mistral": dict(
        heads=(32, 8, 128), bs=32, products="float32",
        calls={"paged_attention": dict(window=None, blocks=2400, cols=64)},
        ticks={"chunk": (512, 41, 600, 471, 0),
               "decode": (64, 41, 600, 0, 0),
               "decode_short": (64, 41, 300, 0, 0),
               "chunk_spread": (512, 41, 650, 471, 0, 500),
               "decode_spread": (64, 41, 650, 0, 0, 500)}),
    # serve-pythia69b-decode-closed: 20 rows at ~450 of 32 KV heads
    "pythia": dict(
        heads=(32, 32, 128), bs=32, products="float32",
        calls={"paged_attention": dict(window=None, blocks=640, cols=24)},
        ticks={"chunk": (512, 19, 450, 100, 0),
               "decode": (64, 20, 450, 0, 0)}),
    # serve-phi4flash-reason-closed: 64 rows at ~2.2k, paired heads first
    "phi4flash": dict(
        heads=(40, 10, 128), bs=32, products="float32", heads_first=True,
        scale=0.125,
        calls={"window_paged_attention": dict(window=512, ring=32, slots=73,
                                              cols=136),
               "shared_paged_attention": dict(window=None, blocks=10900,
                                              cols=136)},
        ticks={"chunk": (512, 63, 2200, 449, 512),
               "decode": (64, 64, 2200, 0, 0)}),
    # serve-moonlight16b-longdoc-closed: the latent pool, one row a position
    "moonlight": dict(
        heads=(16, 1, 640), bs=32, latent=(512, 192 ** -0.5),
        calls={"latent_paged_attention": dict(window=None, blocks=4352,
                                              cols=256)},
        ticks={"chunk": (512, 15, 5500, 496, 4608),
               "decode": (64, 15, 5500, 0, 0)}),
    # serve-lfm2-24b-concurrent-closed: 256 rows at 300-2,000 of 8 KV heads
    # of 64, two to a pool row (the kernel sees 4 of 128)
    "lfm2": dict(
        heads=(32, 4, 128), bs=32, products="bfloat16", row_table=True,
        scale=0.125,
        calls={"global_attention": dict(window=None, blocks=40960, cols=64)},
        ticks={"chunk": (2048, 255, 1150, 512, 0, 850),
               "decode": (256, 256, 1150, 0, 0, 850)}),
    # serve-keye-vl2-30b-longctx-closed: 24 clients at ~17k, a prompt's
    # 2,024-row chunks at 0 .. 16k (mean ~8k), each row choosing 2,048
    "keye": dict(
        heads=(32, 4, 128), bs=128, products="bfloat16", row_table=True,
        calls={"sparse_attention": dict(window=None, blocks=4353, cols=144,
                                        chosen=2048)},
        ticks={"chunk": (2048, 24, 17000, 2024, 7000),
               "chunk_late": (2048, 24, 17000, 2024, 14000),
               "decode": (256, 24, 17000, 0, 0)}),
    # serve-ouro-2.6b-cot-closed: 10 rows at 64-480 of 16 KV heads, 192 such
    # calls a tick (a cache layer a pass and a layer: 193 blocks each)
    "ouro": dict(
        heads=(16, 16, 128), bs=32, products="float32",
        calls={"paged_attention": dict(window=None, blocks=193, cols=16)},
        ticks={"chunk": (512, 9, 272, 112, 0, 208),
               "decode": (64, 10, 272, 0, 0, 208)}),
}
TINY = dict(
    heads=(8, 2, 64), bs=8, products="float32",
    calls={"paged_attention": dict(window=None, blocks=96, cols=8),
           "window_paged_attention": dict(window=24, blocks=96, cols=8)},
    ticks={"chunk": (64, 3, 40, 50, 7), "decode": (32, 5, 40, 0, 0)})
# the choice's call: one table a sequence, planes of one lane width
TINY_CHOSEN = dict(
    heads=(8, 2, 64), bs=8, products="float32", row_table=True,
    calls={"sparse_attention": dict(window=None, blocks=200, cols=32,
                                    chosen=60)},
    ticks={"chunk": (64, 3, 200, 50, 70), "decode": (32, 5, 200, 0, 0)})


def load_kernel(path):
    if path is None:
        from deepspeed_tpu.ops.pallas import paged_attention as module
        return module
    spec = importlib.util.spec_from_file_location("kernel_under_test", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def tick_rows(rng, tick, bs):
    """(lengths, sequence of each row or 0 for a pad, new-table flags) of a
    tick: decode rows of sequences 1 .., a chunk of the next, pads."""
    T, n_decode, context, chunk, start = tick[:5]
    lengths, seq = np.ones((T,), np.int32), np.zeros((T,), np.int32)
    spread = tick[5] if len(tick) > 5 else max(context // 20, 1)
    lengths[:n_decode] = rng.integers(context - spread, context + spread,
                                      n_decode)
    seq[:n_decode] = np.arange(1, n_decode + 1)
    lengths[n_decode:n_decode + chunk] = start + 1 + np.arange(chunk)
    seq[n_decode:n_decode + chunk] = n_decode + 1
    starts = np.concatenate([[True], seq[1:] != seq[:-1]])
    return lengths, seq, starts


def operands(rng, cfg, call, tick, dtype):
    """The call's arrays for a tick's rows: (q, pools, tables, lengths,
    row_table or None, the choice or None) and the rows' (lengths,
    starts). The choice is drawn, ``chosen`` of a row's positions on
    average and every position of a shorter row, in planes of one lane
    width as ``sparse_choice`` writes them: what a step costs does not
    depend on which positions they are."""
    N, K, D = cfg["heads"]
    bs, T = cfg["bs"], tick[0]
    lengths, seq, starts = tick_rows(rng, tick, bs)
    n_seq, cols = int(seq.max()) + 1, call["cols"]
    assert int(lengths.max()) <= cols * bs, "a context past the table"
    if "ring" in call:
        # a sequence's slot is a ring: column c names its block c % ring
        RB = call["ring"]
        assert n_seq <= call["slots"]
        blocks = call["slots"] * RB
        by_seq = (np.arange(n_seq) * RB)[:, None] + np.arange(cols) % RB
    else:
        blocks = call["blocks"]
        need = -(-lengths // bs)
        per_seq = np.zeros((n_seq,), np.int64)
        np.maximum.at(per_seq, seq, need)
        per_seq[0] = 0
        ids = rng.permutation(np.arange(1, blocks))[:per_seq.sum()]
        by_seq = np.zeros((n_seq, cols), np.int32)
        at = 0
        for s, n in enumerate(per_seq):
            by_seq[s, :n] = ids[at:at + n]
            at += n
    by_seq[0] = 0                                  # the pad rows' table
    if cfg.get("latent"):
        shape = (blocks, bs, D)
    elif cfg.get("heads_first"):
        shape = (blocks, K, bs, D)
    else:
        shape = (blocks, bs, K, D)
    key = jax.random.PRNGKey(int(rng.integers(2 ** 31)))
    kq, kk, kv, kc = jax.random.split(key, 4)
    q = jax.random.normal(kq, (T, N, D), dtype)
    pools = [jax.random.normal(kk, shape, dtype)]
    if not cfg.get("latent"):
        pools.append(jax.random.normal(kv, shape, dtype))
    if cfg.get("row_table"):
        tables, which = by_seq.astype(np.int32), seq
    else:
        tables, which = by_seq[seq].astype(np.int32), None
    chosen = None
    if call.get("chosen"):
        assert cols * bs % _LANES == 0 and T % 32 == 0
        share = np.minimum(call["chosen"] / lengths, 1.0).astype(np.float32)
        at = jnp.arange(cols * bs, dtype=jnp.int32).reshape(-1, 1, _LANES)
        chosen = ((jax.random.uniform(kc, (cols * bs // _LANES, T, _LANES))
                   < jnp.asarray(share)[None, :, None])
                  & (at < jnp.asarray(lengths)[None, :, None])
                  ).astype(jnp.float32)
    return (q, pools, jnp.asarray(tables), jnp.asarray(lengths),
            None if which is None else jnp.asarray(which),
            chosen), (lengths, starts)


def chained(module, cfg, name, call, interpret):
    """``f(n, q, pools, tables, lengths, which, chosen)``: the call n times
    in one program, each depending on the loop's index (so that none is
    hoisted) and feeding one element into the result (so that none is
    dropped)."""
    if cfg.get("latent"):
        value_dim, scale = cfg["latent"]

        def one(q, pools, tables, lengths, which, chosen):
            return module.latent_paged_attention(
                q, pools[0], tables, lengths, value_dim, scale,
                interpret=interpret)
    else:
        options = dict(window=call["window"], name=name,
                       mxu_dtype=jnp.dtype(cfg["products"]),
                       heads_first=cfg.get("heads_first", False),
                       scale=cfg.get("scale"))

        def one(q, pools, tables, lengths, which, chosen):
            return module.paged_attention(
                q, pools[0], pools[1], tables, lengths, interpret=interpret,
                row_table=which, **options,
                **({} if chosen is None else {"chosen": chosen}))

    @jax.jit
    def run(n, q, pools, tables, lengths, which, chosen):
        def body(i, total):
            out = one(q, pools, tables, lengths + jnp.minimum(i, 0), which,
                      chosen)
            return total + out[0, 0, 0].astype(jnp.float32)

        return jax.lax.fori_loop(0, n, body, jnp.float32(0))

    return run


def seconds(run, n, args, repeats):
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        run(n, *args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="trinity",
                    help="|".join(CONFIGS) + "|all (comma-separated)")
    ap.add_argument("--ticks", default="", help="only these ticks")
    ap.add_argument("--module", default=None,
                    help="another paged_attention.py to time")
    ap.add_argument("--calls", default="8,40",
                    help="the two trip counts that are differenced")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--label", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/paged_kernel_alone.jsonl")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        sys.exit(f"no TPU here ({device.platform}): a kernel's time is a "
                 "chip's to give; --rehearse walks tiny cases in interpret "
                 "mode")
    module = load_kernel(args.module)
    configs = {"tiny": TINY, "tiny_chosen": TINY_CHOSEN} if args.rehearse \
        else {
        name: CONFIGS[name] for name in (
            CONFIGS if args.config == "all" else args.config.split(","))}
    n1, n2 = (1, 2) if args.rehearse else map(int, args.calls.split(","))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as log:
        for cname, cfg in configs.items():
            D = cfg["heads"][2]
            dtype = jnp.float32 if args.rehearse else jnp.bfloat16
            for name, call in cfg["calls"].items():
                run = chained(module, cfg, name, call, args.rehearse)
                for tname, tick in cfg["ticks"].items():
                    if args.ticks and tname not in args.ticks.split(","):
                        continue
                    rng = np.random.default_rng(args.seed)
                    ops, (lengths, starts) = operands(rng, cfg, call, tick,
                                                      dtype)
                    seconds(run, n1, ops, 1)              # compile, warm
                    t1 = seconds(run, n1, ops, args.repeats)
                    t2 = seconds(run, n2, ops, args.repeats)
                    line = {"label": args.label, "config": cname,
                            "call": name, "tick": tname, "rows": tick[0],
                            "device": device.device_kind,
                            "us_per_call": round(
                                (t2 - t1) / (n2 - n1) * 1e6, 2)}
                    if hasattr(module, "count_steps"):
                        # the geometry of the kernel that was timed
                        value_dim = cfg["latent"][0] if cfg.get("latent") \
                            else D
                        _, bs, R, P = module._geometry(
                            ops[0], ops[1], value_dim,
                            cfg.get("heads_first", False))
                        steps, open_ = module.count_steps(
                            lengths, starts, R, P * bs, call["window"])
                        if call.get("chosen"):  # no step of a choice is open
                            open_ = 0
                        walks = count_walks(starts, R)
                        line.update(step_positions=P * bs, steps=steps,
                                    open_steps=open_, us_per_step=round(
                                        line["us_per_call"] / steps, 4),
                                    walks=walks, us_per_walk=round(
                                        line["us_per_call"] / walks, 4))
                    print(json.dumps(line), flush=True)
                    log.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
