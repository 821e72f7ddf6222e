#!/usr/bin/env python3
"""Record the small device trace kept under ``benchmarks/testdata/``.

Run on the chip (``chiprun --chips 4 -- python benchmarks/tools/capture_testdata.py``):
a few milliseconds of real work that holds everything the reducer has to
recognise — the program's flash forward/backward and paged kernels (Mosaic
custom calls), plain fusions inside a ``while``, an all-gather and an
all-reduce across the chips that are there, host spans
(``jax.profiler.TraceAnnotation``) and a deliberate idle gap under a span.
Writes the ``.xplane.pb`` and a text summary of its planes, lines and event
names to ``chiprun_out/trace_probe/``. Not part of any cell.
"""
from __future__ import annotations

import collections
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"needs a TPU, found {devs[0].platform}", file=sys.stderr)
        return 1
    n = len(devs)
    out = os.path.join("chiprun_out", "trace_probe")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 512, 4, 128), jnp.bfloat16)
    k = jax.random.normal(key, (1, 512, 2, 128), jnp.bfloat16)

    @jax.jit
    def flash_step(q, k):
        def loss(q, k):
            return flash_attention(q, k, k, causal=True).astype(
                jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1))(q, k)

    pool = jax.random.normal(key, (64, 32, 2, 128), jnp.bfloat16)
    pq = jax.random.normal(key, (8, 4, 128), jnp.bfloat16)
    tables = jnp.asarray(np.arange(1, 33).reshape(8, 4), jnp.int32)
    lengths = jnp.full((8,), 100, jnp.int32)
    paged = jax.jit(paged_attention)

    @jax.jit
    def scan_mm(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, None, length=6)[0]

    x = jnp.ones((512, 512), jnp.bfloat16)
    w = jnp.ones((512, 512), jnp.bfloat16) * 0.001

    coll = None
    if n > 1:
        mesh = Mesh(np.array(devs), ("data",))
        ws = jax.device_put(jnp.ones((n * 256, 1024), jnp.bfloat16),
                            NamedSharding(mesh, P("data", None)))
        xs = jax.device_put(jnp.ones((n * 8, n * 256), jnp.bfloat16),
                            NamedSharding(mesh, P("data", None)))

        @jax.jit
        def coll(xs, ws):
            y = xs @ ws          # all-gather of ws
            return jnp.sum(y.astype(jnp.float32))   # all-reduce

    def run_all():
        with jax.profiler.TraceAnnotation("probe.flash"):
            jax.block_until_ready(flash_step(q, k))
        with jax.profiler.TraceAnnotation("probe.paged"):
            jax.block_until_ready(paged(pq, pool, pool, tables, lengths))
        with jax.profiler.TraceAnnotation("probe.scan"):
            jax.block_until_ready(scan_mm(x, w))
        if coll is not None:
            with jax.profiler.TraceAnnotation("probe.collective"):
                jax.block_until_ready(coll(xs, ws))
        with jax.profiler.TraceAnnotation("probe.sleep"):
            time.sleep(0.002)

    run_all()   # compile outside the trace
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("probe.outer"):
        run_all()
        run_all()
    jax.profiler.stop_trace()

    pb = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    keep = os.path.join(out, f"v5e_{n}chip_probe.xplane.pb")
    shutil.copy(pb, keep)
    shutil.rmtree(os.path.join(out, "plugins"))
    data = jax.profiler.ProfileData.from_file(keep)
    lines_out = [f"file bytes {os.path.getsize(keep)}"]
    for plane in data.planes:
        lines_out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            lines_out.append(f"  LINE {line.name!r} events={len(evs)} "
                             f"distinct={len(names)}")
            shown = 0
            for e in evs:
                if names[e.name] < 0:
                    continue
                names[e.name] = -1
                stats = {k: (str(v)[:120]) for k, v in e.stats}
                lines_out.append(
                    f"    {e.name[:100]!r} start_ns={e.start_ns:.0f} "
                    f"dur_ns={e.duration_ns:.0f} stats={stats}")
                shown += 1
                if shown >= 40:
                    break
    text = "\n".join(lines_out)
    with open(os.path.join(out, "summary.txt"), "w") as f:
        f.write(text)
    print(text[-20000:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
