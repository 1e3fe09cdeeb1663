"""TPC-H Q3 (validation parameters) over the join set: copy of bench.py's
`q3_oracle` (PR 23)."""

from __future__ import annotations

import numpy as np

from . import unscaled
from ..datagen.tpch import parse_date


def top10(jdata):
    """Exact top-10 (orderkey, revenue_unscaled) for TPC-H Q3."""
    cutoff = parse_date("1995-03-15")
    segs, ccodes = jdata["customer"]["c_mktsegment"]
    bld = list(segs).index("BUILDING")
    cust = jdata["customer"]["c_custkey"]
    cust_ok = np.zeros(int(cust.max()) + 1, bool)
    cust_ok[cust[np.asarray(ccodes) == bld]] = True
    o = jdata["orders"]
    o_ok = (o["o_orderdate"] < cutoff) & cust_ok[o["o_custkey"]]
    span = int(o["o_orderkey"].max()) + 1
    ok_arr = np.zeros(span, bool)
    ok_arr[o["o_orderkey"][o_ok]] = True
    odate = np.zeros(span, np.int64)
    odate[o["o_orderkey"][o_ok]] = o["o_orderdate"][o_ok]
    li = jdata["lineitem"]
    lm = (li["l_shipdate"] > cutoff) & ok_arr[li["l_orderkey"]]
    rev = np.zeros(span, np.int64)
    np.add.at(rev, li["l_orderkey"][lm],
              li["l_extendedprice"][lm] * (100 - li["l_discount"][lm]))
    nz = np.nonzero(rev)[0]
    top = nz[np.lexsort((nz, odate[nz], -rev[nz]))[:10]]
    return [(int(k), int(rev[k])) for k in top]


def reference(data):
    return top10(data["joins"])


def compare(rows, ref, fresh=None, key=None):
    got = [(int(r[0]), unscaled(r[1], 4)) for r in rows]
    return None if got == ref else f"q3: {got[:3]} != {ref[:3]}"
