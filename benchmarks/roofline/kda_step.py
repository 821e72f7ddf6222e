"""What the one-row form of the delta rule needs
(``ops/pallas/kda.py::kda_step``; its Mosaic call is named ``kda_step`` in
the trace, one call a ``kda`` layer a tick).

A row that is a run of one (a decode row) reads its sequence's state, a
``[keys, values]`` matrix a head in float32, and writes it back: ``2 x
heads x D x D x 4`` bytes a row a layer (4,194,304 at 32 heads of 128),
against ~3 MFLOP on the vector unit: memory-bound whatever the tick. The
rows are a run-time value: the program writes the tick's count on its span
(``decode_tick``'s ``kda_step_rows``) and ``tick_attrs`` joins the span to
the tick's run on the device, so a call's need is its own tick's. The
row's vectors (query, key, value, decay, step size: 20 KB) are left out: a
lower bound. A program without ``kda_step_rows`` gives nothing to read.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

NAME = "kda_step"


def classify(op) -> Optional[str]:
    return "kda_step" if op.is_mosaic and op.name.startswith(NAME) else None


def state_bytes(model) -> int:
    """A sequence's state in one layer: heads x keys x values, float32."""
    return 4 * model.kda_heads * model.kda_head_dim ** 2


def needed_bytes(step_rows: int, model) -> float:
    """One call's: every row's state once in and once out."""
    return 2.0 * step_rows * state_bytes(model)


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    from benchmarks.roofline import tick_attrs

    ticks = [(t, its) for t, its in tick_attrs.calls_by_tick(
        tick_attrs.per_tick(run), calls) if "kda_step_rows" in t]
    if not calls or not ticks or not getattr(run.model, "kda_heads", 0):
        return None
    total = sum(len(its) * needed_bytes(t["kda_step_rows"], run.model)
                for t, its in ticks) / run.peaks["hbm_bytes_per_s"]
    return total, "memory"
