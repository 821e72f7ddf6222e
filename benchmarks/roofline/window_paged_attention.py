"""What ``window_paged_attention`` needs: the dense paged kernel over a
window layer's RING (``shared_paged_attention.py`` beside this file has the
operands and the counting; this differs in what a sequence must have read).

A row sees its last ``window`` positions alone, so a sequence with rows in
the tick needs the positions inside its rows' windows, not its whole cache:
``min(position + 1, window)`` for a decode row, ``min(a + c, c + window -
1)`` for a chunk of ``c`` rows from position ``a``. Lengths are run-time
values; the engine writes their sum over the tick's sequences on the tick's
span (``window_positions``) and, for the operations, the sum over its
prompt rows of the positions each scores (``window_attended``). A program
without the attributes (no window layers: every other model) gives nothing
to read.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from benchmarks.roofline import shared_paged_attention as shared

NAME = "window_paged_attention"


def classify(op) -> Optional[str]:
    return "window" if op.is_mosaic and op.name.startswith(NAME) else None


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    return shared.least_seconds_of(
        run, calls, lambda t, bs: t.get("window_positions"),
        lambda t: t.get("window_attended"))
