"""Filtered row scan of lineitem (March 1995, discount 0.10, quantity < 3),
ordered by (l_orderkey, l_linenumber): every row, every column."""

from __future__ import annotations

import numpy as np

from . import unscaled
from ..datagen.tpch import parse_date


def reference(data):
    li = data["lineitem"]
    m = ((li["l_shipdate"] >= parse_date("1995-03-01"))
         & (li["l_shipdate"] <= parse_date("1995-03-31"))
         & (li["l_discount"] == 10) & (li["l_quantity"] < 300))
    idx = np.flatnonzero(m)
    idx = idx[np.lexsort((li["l_linenumber"][idx], li["l_orderkey"][idx]))]
    return [(int(li["l_orderkey"][i]), int(li["l_linenumber"][i]),
             int(li["l_quantity"][i]), int(li["l_extendedprice"][i]))
            for i in idx]


def compare(rows, ref, fresh=None, key=None):
    if not ref:
        return "row_scan: the reference selects nothing"
    got = [(int(r[0]), int(r[1]), unscaled(r[2], 2), unscaled(r[3], 2))
           for r in rows]
    if got == ref:
        return None
    return (f"row_scan: {len(got)} rows vs {len(ref)}; first {got[:2]} "
            f"vs {ref[:2]}")
