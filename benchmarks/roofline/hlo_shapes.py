"""Shapes out of an HLO instruction's text, as the trace names its events:
``%name = <result type> opcode(<operand type> %operand, ...), attrs``.
"""
from __future__ import annotations

import re
from typing import List, Tuple

Shape = Tuple[str, Tuple[int, ...]]

_SHAPE = re.compile(r"\b(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)"
                    r"\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}


def _shapes(segment: str) -> List[Shape]:
    return [(d, tuple(int(x) for x in dims.split(",") if x))
            for d, dims in _SHAPE.findall(segment)]


def split(text: str, opcode: str = "custom-call") -> Tuple[List[Shape], List[Shape]]:
    """(result shapes, operand shapes) of the instruction."""
    head, _, rest = text.partition(f" {opcode}(")
    operands = rest.split("), ", 1)[0]
    return _shapes(head.split("=", 1)[-1]), _shapes(operands)


def nbytes(shape: Shape) -> int:
    n = _BYTES[shape[0]]
    for d in shape[1]:
        n *= d
    return n
