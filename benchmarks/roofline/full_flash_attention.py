"""What the flash kernels' FULL calls need in a stack of window and full
layers: the calls named ``flash_fwd`` / ``flash_dq`` / ``flash_dkv``, told
from the window calls by name (``window_flash_attention.py`` beside this
file, whose count this is with no window: ``S^2 / 2`` rows x columns a
head, times the 2 / 2 / 3 matmuls). ``flash_attention.py`` finds its calls
by operand counts and would take such a stack's window calls, and its
``gmm``s, for full ones.
"""
from __future__ import annotations

from typing import Optional

from benchmarks.roofline import window_flash_attention

least_seconds = window_flash_attention.least_seconds


def classify(op) -> Optional[str]:
    """``fwd``, ``dq`` or ``dkv`` for a flash kernel call under no window,
    else None."""
    found = window_flash_attention.named(op)
    return found[0] if found and not found[1] else None
