#!/usr/bin/env python3
"""The latent paged-attention kernel alone, one layer call, against the jnp
path, at the widths of a configuration file; and the experts' grouped
matmul at its tick shapes under several tilings.

    chiprun -- python benchmarks/tools/latent_kernel_alone.py \
        [--config moonlight-16b-a3b] [--gmm 1]

Cases: 32 decode rows at 8k context in the 64-row bucket; 15 decode rows
at 5.3k beside a 496-row chunk whose context ends at 512 and at 7,168 in
the 512-row bucket. The jnp path gathers every row's whole table, so it is
given 16 rows of each case (the decode rows, or the chunk's first and last
eight). Prints one JSON line a case: microseconds a call (median of 20
after 3 warm-up calls, fenced), the largest relative difference over the
compared rows, and the floor of the case's bytes and operations.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def timed(fn, *args, n=20):
    import jax

    for _ in range(3):
        jax.block_until_ready(fn(*args))
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return 1e6 * sorted(out)[len(out) // 2]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="moonlight-16b-a3b")
    ap.add_argument("--gmm", type=int, default=1)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import device as devmod, manifest, model_config
    from deepspeed_tpu.models import paged as PG
    from deepspeed_tpu.ops.pallas.paged_attention import \
        latent_paged_attention

    dev = devmod.describe(1, False)
    peaks = devmod.peaks_for(dev["kind"])
    conf = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", args.config + ".json"))
    cfg = model_config.build(conf, "serve")
    bs, NB, MB = 32, 4352, 256
    W, kvr = PG.latent_row_width(cfg), cfg.kv_lora_rank
    used = kvr + cfg.qk_rope_head_dim
    rng = np.random.default_rng(0)
    key = jax.random.key(0)
    pool = jax.random.normal(key, (NB, bs, W), jnp.bfloat16)
    pool = pool.at[:, :, used:].set(0)
    w_kv_b = (0.02 * jax.random.normal(jax.random.key(1), (
        kvr, cfg.num_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)))
    ).astype(jnp.bfloat16)
    scale = PG.mla_softmax_scale(cfg)

    def case(name, Tn, rows):
        """rows: (length, table id); id 0 the pad rows."""
        n_tab = max(t for _, t in rows)
        tabs = np.zeros((n_tab + 1, MB), np.int32)
        for t in range(1, n_tab + 1):
            tabs[t] = rng.integers(1, NB, MB)
        rows = rows + [(1, 0)] * (Tn - len(rows))
        tables = jnp.asarray(np.stack([tabs[t] for _, t in rows]))
        lengths = jnp.asarray(np.array([n for n, _ in rows], np.int32))
        q = jax.random.normal(jax.random.key(2), (
            Tn, cfg.num_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim),
            jnp.bfloat16)

        @jax.jit
        def kernel(q, pool, tables, lengths):
            return PG._absorbed(q, w_kv_b, cfg, lambda q_row:
                                latent_paged_attention(
                                    q_row, pool, tables, lengths, kvr, scale))

        @jax.jit
        def plain(q, pool, tables, lengths):
            return PG.paged_mla_attention_reference(q, pool, tables, lengths,
                                                    w_kv_b, cfg)

        real = [i for i, (_, t) in enumerate(rows) if t > 0]
        pick = real[:16] if len(real) <= 32 else real[:8] + real[-8:]
        pick = jnp.asarray(pick)
        got = kernel(q, pool, tables, lengths)[pick].astype(jnp.float32)
        want = plain(q[pick], pool, tables[pick],
                     lengths[pick]).astype(jnp.float32)
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        seqs = {t: n for n, t in rows if t > 0}
        moved = sum(-(-n // bs) for n in seqs.values()) * bs * used * 2
        ops = 2 * cfg.num_heads * (used + kvr) * sum(
            n for n, t in rows if t > 0)
        print(json.dumps({
            "case": name, "kernel_us": timed(kernel, q, pool, tables,
                                             lengths),
            "jnp_us_16_rows": timed(plain, q[pick], pool, tables[pick],
                                    lengths[pick]),
            "rel_diff": rel,
            "floor_bytes_us": 1e6 * moved / peaks["hbm_bytes_per_s"],
            "floor_ops_us": 1e6 * ops / peaks["bf16_flops_per_s"]}),
            flush=True)

    case("32 decode rows at 8k", 64, [(8192, i + 1) for i in range(32)])
    for end in (512, 7168):
        case(f"15 decode rows at 5.3k + 496-row chunk ending at {end}", 512,
             [(5300 + 7 * i, i + 1) for i in range(15)]
             + [(end - 495 + i, 16) for i in range(496)])

    if args.gmm:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        E, H, F = cfg.n_experts, cfg.hidden_size, cfg.moe_ffn
        for M, active in ((512 * cfg.moe_top_k, E), (64 * cfg.moe_top_k, 50)):
            sizes = np.zeros((E,), np.int32)
            sizes[:active] = M // active
            sizes[0] += M - sizes.sum()
            sizes = jnp.asarray(sizes)
            for K, N, tilings in (
                    (H, F, [(512, 1024, 128), (512, 1024, F), (512, 512, F),
                            (256, 1024, F), (128, 1024, F), (128, 2048, F)]),
                    (F, H, [(512, 128, 1024), (512, F, 1024), (512, F, 2048),
                            (256, F, 2048), (128, F, 2048), (128, F, 1024)])):
                x = jax.random.normal(key, (M, K), jnp.bfloat16)
                w = jax.random.normal(key, (E, K, N), jnp.bfloat16)
                for tm, tk, tn in tilings:
                    tm = min(tm, M)
                    if M % tm:
                        continue
                    try:
                        f = jax.jit(lambda x, w, s, t=(tm, tk, tn): gmm(
                            x, w, s, x.dtype, t))
                        us = timed(f, x, w, sizes)
                        err = None
                    except Exception as e:  # noqa: BLE001 - report and go on
                        us, err = None, repr(e)[:200]
                    moved = 2 * (active * K * N + M * K + M * N)
                    print(json.dumps({
                        "gmm": [M, K, N], "active": active,
                        "tiling": [tm, tk, tn], "us": us, "error": err,
                        "floor_bytes_us": 1e6 * moved
                        / peaks["hbm_bytes_per_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
