"""Plain references, one module per statement class, numpy and decimal only.

Each module has `reference(data)` (built once, after the window has closed,
from the arrays the seed made) and `compare(rows, ref, fresh=None)`, which
returns None where the wire answer `rows` says exactly what the reference
says and else a short text naming the first difference. `fresh=(lo, hi)`, in
a cell whose traffic inserts rows, is the range of insert counts the answer's
snapshot may legitimately hold (acknowledged before it was sent ... sent
before it came back). Nothing here imports the program.
"""

from __future__ import annotations

import decimal

import numpy as np


def unscaled(text: str, scale: int) -> int:
    """Exact unscaled integer of a wire DECIMAL at `scale` digits; raises
    ValueError where the text has more fractional digits than that."""
    v = decimal.Decimal(text).scaleb(scale)
    if v != v.to_integral_value():
        raise ValueError(f"{text!r} has more than {scale} fractional digits")
    return int(v)


def as_bfloat16(values) -> "np.ndarray":
    """float32 values rounded to the nearest bfloat16 (8 bits of mantissa,
    ties to even), kept as float32: what a device path that sorts or sums
    bfloat16 keys would see. For the controls; numpy has no bfloat16."""
    bits = np.asarray(values, dtype=np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return bits.view(np.float32)
