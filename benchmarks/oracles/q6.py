"""TPC-H Q6 (validation parameters): sum(l_extendedprice * l_discount)."""

from __future__ import annotations

import numpy as np

from . import unscaled
from ..datagen.tpch import parse_date

CHUNK = 50_000_000


def selected(li, sl=slice(None)) -> np.ndarray:
    """Q6's predicate over the rows of `sl`."""
    d1, d2 = parse_date("1994-01-01"), parse_date("1995-01-01")
    ship = li["l_shipdate"][sl]
    return ((ship >= d1) & (ship < d2)
            & (li["l_discount"][sl] >= 5) & (li["l_discount"][sl] <= 7)
            & (li["l_quantity"][sl] < 2400))


def revenue(li) -> int:
    total, n = 0, len(li["l_shipdate"])
    for lo in range(0, n, CHUNK):
        sl = slice(lo, min(lo + CHUNK, n))
        m = selected(li, sl)
        total += int((li["l_extendedprice"][sl][m].astype(np.int64)
                      * li["l_discount"][sl][m]).sum())
    return total


def reference(data):
    """Totals the answer may show: base, then base + each prefix of the
    cell's inserts (one entry where the traffic inserts nothing)."""
    totals = [revenue(data["lineitem"])]
    for batch in data.get("rf1", ()):
        totals.append(totals[-1] + revenue(batch))
    return totals


def compare(rows, ref, fresh=None, key=None):
    if len(rows) != 1 or len(rows[0]) != 1:
        return f"q6: {len(rows)} rows"
    got = unscaled(rows[0][0], 4)
    lo, hi = fresh if fresh is not None else (0, 0)
    if got in ref[lo:hi + 1]:
        return None
    return f"q6: {got} not in {ref[lo:hi + 1][:3]} (inserts {lo}..{hi})"


def render(total: int) -> list[list[str]]:
    """The wire rows of an exact total: DECIMAL with four digits."""
    return [[f"{total // 10000}.{total % 10000:04d}"]]


def control_rows(data) -> list[list[str]]:
    """The control: the same sum accumulated in float32, as a device path
    without exact limb sums would, rendered as the wire would."""
    li = data["lineitem"]
    m = selected(li)
    total = float((li["l_extendedprice"][m].astype(np.float32)
                   * li["l_discount"][m].astype(np.float32)).sum(
                       dtype=np.float32))
    return render(int(round(total)))
