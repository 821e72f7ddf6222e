"""What the one-row form of the Mamba-2 recurrence needs
(``ops/pallas/ssd.py::ssd_step``; its Mosaic call is named ``ssd_step`` in
the trace, one call a ``mamba2`` layer a tick).

A row that is a run of one (a decode row) reads its sequence's state, a
``[channels, state]`` matrix a head in float32, and writes it back: ``2 x
heads x P x N x 4`` bytes a row a layer (8,388,608 at 128 heads of 64 x
128), against ~4 MFLOP on the vector unit: memory-bound whatever the tick.
The rows are a run-time value: the program writes the tick's count on its
span (``decode_tick``'s ``ssd_step_rows``) and ``tick_attrs`` joins the span
to the tick's run on the device, so a call's need is its own tick's. The
row's vectors (x, B, C, the step size and the decay, laid out a group a
tile: 36 KB a group) are left out: a lower bound. A program without
``ssd_step_rows`` gives nothing to read.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

NAME = "ssd_step"


def classify(op) -> Optional[str]:
    return "ssd_step" if op.is_mosaic and op.name.startswith(NAME) else None


def state_bytes(model) -> int:
    """A sequence's state in one layer: heads x channels x state, float32."""
    return 4 * model.mamba2_heads * model.mamba2_head_dim * model.mamba2_state


def needed_bytes(step_rows: int, model) -> float:
    """One call's: every row's state once in and once out."""
    return 2.0 * step_rows * state_bytes(model)


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    from benchmarks.roofline import tick_attrs

    ticks = [(t, its) for t, its in tick_attrs.calls_by_tick(
        tick_attrs.per_tick(run), calls) if "ssd_step_rows" in t]
    if not calls or not ticks or not getattr(run.model, "mamba2_heads", 0):
        return None
    total = sum(len(its) * needed_bytes(t["ssd_step_rows"], run.model)
                for t, its in ticks) / run.peaks["hbm_bytes_per_s"]
    return total, "memory"
