"""TPC-H Q14, promotion effect (2.4.14, validation parameter 1995-09-01)
over the join set, straight from the query text: of the revenue
l_extendedprice * (1 - l_discount) of the lines shipped in that month, the
percentage from parts whose p_type starts with PROMO. numpy int64: the two
sums are exact integers at four decimals.

`promo_revenue` = 100.00 * sum / sum is a DECIMAL(.,2) times a DECIMAL(.,4)
sum (six decimals, exact) divided by a DECIMAL(.,4) sum, so the program
returns it at 6 + 4 = 10 decimals (MySQL's div_precision_increment),
rounded ONCE from the exact quotient, half away from zero: the reference is
(promo_sum * 10**12 + total // 2) // total for the non-negative sums here,
compared digit for digit. No line in the month: one row holding NULL."""

from __future__ import annotations

import numpy as np

from . import unscaled
from ..datagen.tpch import parse_date
from .q7 import dec, lookup

PREFIX = "PROMO"
RATIO_SCALE = 10


def sums(jdata, dtype=np.int64) -> dict:
    """{"promo": promo revenue, "total": all revenue}, both unscaled at 4
    digits, multiplied and accumulated in `dtype` (float32 is the
    control)."""
    d1, d2 = parse_date("1995-09-01"), parse_date("1995-10-01")
    part = jdata["part"]
    types, tcodes = part["p_type"]
    promo_code = np.array([t.startswith(PREFIX) for t in types])
    p_promo = lookup(part["p_partkey"], promo_code[np.asarray(tcodes)])
    li = jdata["lineitem"]
    m = (li["l_shipdate"] >= d1) & (li["l_shipdate"] < d2)
    promo = p_promo[li["l_partkey"][m]]
    vol = li["l_extendedprice"][m].astype(dtype) * \
        (dtype(100) - li["l_discount"][m].astype(dtype))
    return {"promo": int(vol[promo == 1].sum(dtype=dtype)),
            "total": int(vol[promo >= 0].sum(dtype=dtype))}


def ratio(promo_sum: int, total: int) -> int:
    """100 * promo / total unscaled at RATIO_SCALE digits, half up."""
    return (promo_sum * 10 ** (RATIO_SCALE + 2) + total // 2) // total


def reference(data):
    both = sums(data["joins"])
    return both["promo"], both["total"]


def render(data, both: dict) -> list[list]:
    """The wire row of a {"promo", "total"}."""
    if not both["total"]:
        return [[None]]
    return [[dec(ratio(both["promo"], both["total"]), RATIO_SCALE)]]


def compare(rows, ref, fresh=None, key=None):
    promo, total = ref
    if len(rows) != 1 or len(rows[0]) != 1:
        return f"q14: {len(rows)} rows, expected one of one column"
    if total == 0:
        return None if rows[0][0] is None else f"q14: {rows[0][0]} != NULL"
    got = unscaled(rows[0][0], RATIO_SCALE)
    want = ratio(promo, total)
    return None if got == want else \
        f"q14: {got} != {want} (sums {promo}, {total})"
