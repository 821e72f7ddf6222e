"""What ``global_attention`` needs: the dense paged kernel over a FULL
layer's own block range, in a stack of ``window`` and ``full`` layers of
the standard block (``swa_attention.py`` beside this file has the operands
and the counting; this differs in what a sequence must have read: every
cached block, from the client's tick log, and every position up to a
prompt row's own, ``prompt_attended`` on the tick's span).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from benchmarks.roofline import swa_attention as swa

NAME = "global_attention"


def classify(op) -> Optional[str]:
    return "global" if op.is_mosaic and op.name.startswith(NAME) else None


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    return swa.least_seconds_of(
        run, calls, lambda t, bs: t["blocks"] * bs,
        lambda t: t.get("prompt_attended"))
