"""TPC-H Q12, shipping modes and order priority (2.4.12, validation
parameters MAIL, SHIP, 1994-01-01) over the join set, straight from the
query text: by l_shipmode, the lines received in 1994 that were committed
before they were received and shipped before they were committed, counted
by whether their order's priority is 1-URGENT or 2-HIGH, or neither.
numpy; the counts are compared as integers."""

from __future__ import annotations

import numpy as np

from ..datagen.tpch import parse_date
from .q7 import lookup

MODES = ("MAIL", "SHIP")
HIGH = ("1-URGENT", "2-HIGH")


def counts_by_mode(jdata) -> dict:
    """{shipmode: (high_line_count, low_line_count)}."""
    d1, d2 = parse_date("1994-01-01"), parse_date("1995-01-01")
    o = jdata["orders"]
    prios, pcodes = o["o_orderpriority"]
    is_high = np.isin(np.asarray(pcodes),
                      [list(prios).index(p) for p in HIGH])
    o_high = lookup(o["o_orderkey"], is_high)       # -1: no such order
    li = jdata["lineitem"]
    modes, mcodes = li["l_shipmode"]
    mcodes = np.asarray(mcodes)
    base = (li["l_commitdate"] < li["l_receiptdate"]) & \
        (li["l_shipdate"] < li["l_commitdate"]) & \
        (li["l_receiptdate"] >= d1) & (li["l_receiptdate"] < d2)
    high = o_high[li["l_orderkey"]]
    out = {}
    for mode in MODES:
        m = base & (mcodes == list(modes).index(mode)) & (high >= 0)
        if m.any():
            out[mode] = (int((high[m] == 1).sum()), int((high[m] == 0).sum()))
    return out


def reference(data):
    return counts_by_mode(data["joins"])


def compare(rows, ref, fresh=None, key=None):
    got = {r[0]: (int(r[1]), int(r[2])) for r in rows}
    if got != ref or len(got) != len(rows):
        return f"q12: {got} != {ref}"
    order = [r[0] for r in rows]
    return None if order == sorted(order) else "q12: modes not ordered"
