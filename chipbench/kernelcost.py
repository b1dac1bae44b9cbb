"""Bytes and operations that a kernel call must move and compute.

``hlo_bytes`` reads them from the result and operand shapes of an HLO
instruction, as the trace's text of it gives them; ``fused_rs_update_cost`` works
them out from the kernel's arguments, for the check that the two agree.
"""
from __future__ import annotations

import re

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
            "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
            "f64": 8}
SHAPE = re.compile(r"\b(" + "|".join(ITEMSIZE) + r")\[([\d,]*)\]")


def shape_bytes(text: str) -> int:
    """Sum of the sizes of every array shape written in ``text``."""
    total = 0
    for dtype, dims in SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * ITEMSIZE[dtype]
    return total


def hlo_bytes(op) -> int:
    """Bytes an instruction moves if it reads each operand and writes its
    result once; ``op`` has the ``result`` and ``operands`` text of a
    ``tracereduce.Op``."""
    return shape_bytes(op.result) + shape_bytes(op.operands)


def fused_rs_update_cost(k: int, n: int, wire_itemsize: int,
                         block_n: int = 2048) -> tuple[int, int]:
    """(bytes, flops) of one ``fused_rs_update`` call on a ``(k, n)``
    receive, padded to whole blocks: it reads the receive, the float32
    parameters, momentum, decay mask and learning rate, and writes the
    float32 parameters and momentum. Per element it adds ``k`` chunks and
    does seven more operations (scale, decay, momentum, step)."""
    npad = -(-n // block_n) * block_n
    read = k * npad * wire_itemsize + 3 * npad * 4 + 4
    write = 2 * npad * 4
    return read + write, (k + 7) * npad
