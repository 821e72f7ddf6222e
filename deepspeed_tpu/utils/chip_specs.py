"""Chip datasheet facts shared by bench.py, the step report and the
autotune planner: one table, so they can never disagree about a chip's
peak. Stdlib-only — importable from the bench orchestrator before jax
loads.
"""
from __future__ import annotations

from typing import Optional

# bf16 peak TFLOP/s per chip, by TPU generation
PEAK_BF16_TFLOPS = {"v4": 275.0, "v5e": 197.0, "v5 lite": 197.0,
                    "v5p": 459.0, "v6e": 918.0, "v6 lite": 918.0}

# HBM GB/s per chip, by TPU generation — the referent for MEMORY-bound
# phases (the elementwise optimizer update streams state; pricing it at
# the matmul peak would understate it by orders of magnitude)
HBM_GBPS = {"v4": 1228.0, "v5e": 819.0, "v5 lite": 819.0,
            "v5p": 2765.0, "v6e": 1640.0, "v6 lite": 1640.0}

_GiB = 1024 ** 3

# HBM CAPACITY bytes per chip, by TPU generation — the referent for the
# memlint OOM pre-flight gate (a predicted peak over this refuses the
# job before any chip time is spent). CPU hosts have no datasheet row:
# the gate there arms only from an explicit memlint.hbm_budget_bytes.
# v5p's datasheet 95 is decimal GB, not GiB — reading it as GiB would
# overstate the budget ~7.4 GB and let the gate pass a job that OOMs.
HBM_CAPACITY_BYTES = {"v4": 32 * _GiB, "v5e": 16 * _GiB,
                      "v5 lite": 16 * _GiB, "v5p": 95 * 10 ** 9,
                      "v6e": 32 * _GiB, "v6 lite": 32 * _GiB}


class UnknownChipError(LookupError):
    """A TPU whose ``device_kind`` has no datasheet row."""


def lookup_chip(table: dict, device_kind: str, default, what: str):
    """``table``'s row for a PJRT ``device_kind`` string. A kind that names
    no TPU (the CPU hosts of the test tier) gets ``default``; a TPU missing
    from the table is an error — pricing it at another generation's rate
    would put a wrong peak under every utilization computed from it."""
    kind = (device_kind or "").lower()
    for key, value in table.items():
        if key in kind:
            return value
    if "tpu" in kind:
        raise UnknownChipError(
            f"TPU device_kind {device_kind!r} has no {what} row "
            f"(known: {sorted(table)}) — add its datasheet value")
    return default


def chip_peak_tflops(device_kind: str,
                     default: Optional[float] = None) -> Optional[float]:
    """Peak bf16 TFLOP/s for a PJRT ``device_kind`` string; ``default``
    for a non-TPU kind (CPU hosts have no meaningful peak)."""
    return lookup_chip(PEAK_BF16_TFLOPS, device_kind, default,
                       "peak bf16 TFLOP/s")


def chip_hbm_gbps(device_kind: str,
                  default: Optional[float] = None) -> Optional[float]:
    """Datasheet HBM GB/s for a PJRT ``device_kind``; ``default`` for a
    non-TPU kind (CPU hosts: caller picks a documented host rate)."""
    return lookup_chip(HBM_GBPS, device_kind, default, "HBM GB/s")


def chip_hbm_bytes(device_kind: str,
                   default: Optional[int] = None) -> Optional[int]:
    """Datasheet HBM capacity bytes for a PJRT ``device_kind``;
    ``default`` (usually None) for a non-TPU kind — the datasheet-less
    CPU tier must opt in with an explicit budget, never inherit a TPU
    part's capacity."""
    return lookup_chip(HBM_CAPACITY_BYTES, device_kind, default,
                       "HBM capacity")
