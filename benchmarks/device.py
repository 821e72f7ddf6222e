"""The device a run is on, and the benchmark's own table of peaks.

The table is the yardstick's copy (``peaks.json``): a later PR may change
``deepspeed_tpu/utils/chip_specs.py`` and may not change this. A device
that is not a TPU of a kind in the table is an error, never a default.
"""
from __future__ import annotations

import os
from typing import Any, Dict

from benchmarks.manifest import BENCH_DIR, load_json


class NoChip(RuntimeError):
    pass


def peaks_for(kind: str) -> Dict[str, float]:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["chips"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in benchmarks/peaks.json "
                     f"(known: {sorted(table)})")
    return table[kind]


def describe(chips: int, rehearse: bool) -> Dict[str, Any]:
    """Platform, kind and count as JAX reports them; raises ``NoChip``
    unless the first ``chips`` devices are TPUs the peak table knows (a
    rehearsal takes whatever JAX has, and says so)."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chip(s); JAX has {len(devs)} "
                     f"{dev.platform} device(s)")
    if rehearse:
        return info
    if dev.platform != "tpu":
        raise NoChip(f"JAX's first device is {dev.platform!r} "
                     f"({dev.device_kind}), not a TPU; nothing falls back "
                     "to the CPU (--rehearse walks the control flow)")
    peaks_for(dev.device_kind)
    return info


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes held so far on the fullest of the chips used, over the
    life of the process (JAX has no way to reset it): the arrays' peak
    (``peak_bytes_in_use``) plus the peak of what the runtime set aside for
    programs' temporaries (``peak_bytes_reserved``). On the v5e the first
    does not count a running program's temporaries and the second is
    exactly their size (a program with 1.5 GiB of temporaries raised the
    first by 3 MB and the second by 1.5 GiB; PERF.md, PR 22), and the two
    come from separate parts of the memory, so they add where both peaks
    were held at once, as in a steady window; the runtime gives the second
    up when arrays need the room, so over a whole process the sum is an
    upper limit. 0 where the backend keeps no count (a rehearsal on the
    CPU)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0)) for s in stats)


def arrays_peak_bytes(chips: int) -> int:
    """``peak_bytes_in_use`` of the fullest chip over the life of the
    process: arrays only, the reference comparison's included."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def program_peak_bytes(before_check: int, after_check: int,
                       at_end: int) -> int:
    """The program's own peak, without the benchmark's reference
    comparison, which runs float32 copies on the same device. The peak is
    read when set-up and warm-up have run every program of the window
    (``before_check``), after the comparison, and at the end of the run:
    if the window went above the comparison, the program made the peak;
    if not, the program's peak is what it had reached before the
    comparison started."""
    return at_end if at_end > after_check else before_check
