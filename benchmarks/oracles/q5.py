"""TPC-H Q5 (validation parameters) over the join set: copy of bench.py's
`q5_oracle` (PR 23)."""

from __future__ import annotations

import numpy as np

from . import unscaled
from ..datagen.tpch import parse_date


def revenue_by_nation(jdata):
    """Exact (nation, revenue_unscaled) rows for TPC-H Q5 (ASIA/1994)."""
    d1, d2 = parse_date("1994-01-01"), parse_date("1995-01-01")
    rnames, rcodes = jdata["region"]["r_name"]
    asia = list(rnames).index("ASIA")
    r_ok = np.asarray(rcodes) == asia
    reg_ok = np.zeros(int(jdata["region"]["r_regionkey"].max()) + 1, bool)
    reg_ok[jdata["region"]["r_regionkey"][r_ok]] = True
    nat = jdata["nation"]
    n_ok = reg_ok[nat["n_regionkey"]]
    nspan = int(nat["n_nationkey"].max()) + 1
    nat_ok = np.zeros(nspan, bool)
    nat_ok[nat["n_nationkey"][n_ok]] = True
    cust = jdata["customer"]
    cspan = int(cust["c_custkey"].max()) + 1
    c_nat = np.full(cspan, -1, np.int64)
    c_nat[cust["c_custkey"]] = cust["c_nationkey"]
    supp = jdata["supplier"]
    sspan = int(supp["s_suppkey"].max()) + 1
    s_nat = np.full(sspan, -1, np.int64)
    s_nat[supp["s_suppkey"]] = supp["s_nationkey"]
    o = jdata["orders"]
    o_ok = (o["o_orderdate"] >= d1) & (o["o_orderdate"] < d2)
    ospan = int(o["o_orderkey"].max()) + 1
    o_cnat = np.full(ospan, -1, np.int64)
    o_cnat[o["o_orderkey"][o_ok]] = c_nat[o["o_custkey"][o_ok]]
    li = jdata["lineitem"]
    lnat = s_nat[li["l_suppkey"]]
    onat = o_cnat[li["l_orderkey"]]
    m = (lnat >= 0) & (lnat == onat) & nat_ok[np.clip(lnat, 0, None)]
    rev = np.zeros(nspan, np.int64)
    np.add.at(rev, lnat[m],
              li["l_extendedprice"][m] * (100 - li["l_discount"][m]))
    return {int(k): int(rev[k]) for k in np.nonzero(rev)[0]}


def reference(data):
    jdata = data["joins"]
    names, _ = jdata["nation"]["n_name"]
    by_name = {nm: int(k) for nm, k in zip(
        names, jdata["nation"]["n_nationkey"])}
    return by_name, revenue_by_nation(jdata)


def compare(rows, ref, fresh=None, key=None):
    by_name, want = ref
    got = {by_name[r[0]]: unscaled(r[1], 4) for r in rows}
    if got != want or len(got) != len(rows):
        return f"q5: {got} != {want}"
    revs = [unscaled(r[1], 4) for r in rows]
    return None if revs == sorted(revs, reverse=True) else "q5: not ordered"
