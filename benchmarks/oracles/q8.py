"""TPC-H Q8, national market share (2.4.8, validation parameters BRAZIL,
AMERICA, ECONOMY ANODIZED STEEL) over the join set, straight from the query
text: by year of o_orderdate (1995, 1996), the volume
l_extendedprice * (1 - l_discount) of the lines of that part type ordered by
customers of the region, and the part of it supplied from the nation. numpy
int64: the two sums a year are exact integers at four decimals.

`mkt_share` is a DECIMAL division of two DECIMAL(.,4) sums, so the program
returns it at 4 + 4 = 8 decimals (MySQL's div_precision_increment), rounded
ONCE from the exact quotient, half away from zero: the reference is
(nation_sum * 10**8 + total // 2) // total for the non-negative sums here,
compared digit for digit."""

from __future__ import annotations

import numpy as np

from . import unscaled
from ..datagen.tpch import parse_date
from .q7 import dec, lookup, years

NATION, REGION, P_TYPE = "BRAZIL", "AMERICA", "ECONOMY ANODIZED STEEL"
SHARE_SCALE = 8


def code_of(column, text: str) -> int:
    """The code of `text` in a (vocabulary, codes) column; -1 if absent."""
    vocab = list(column[0])
    return vocab.index(text) if text in vocab else -1


def sums(jdata, dtype=np.int64) -> dict:
    """{year: (nation's volume, all volume)}, both unscaled at 4 digits,
    multiplied and accumulated in `dtype` (float32 is the control)."""
    d1, d2 = parse_date("1995-01-01"), parse_date("1996-12-31")
    reg = jdata["region"]
    r_ok = lookup(reg["r_regionkey"],
                  np.asarray(reg["r_name"][1]) == code_of(reg["r_name"],
                                                          REGION), 0)
    nat = jdata["nation"]
    n_in_region = lookup(nat["n_nationkey"], r_ok[nat["n_regionkey"]], 0)
    n_is_nation = lookup(
        nat["n_nationkey"],
        np.asarray(nat["n_name"][1]) == code_of(nat["n_name"], NATION), 0)
    cust = jdata["customer"]
    c_ok = lookup(cust["c_custkey"], n_in_region[cust["c_nationkey"]], 0)
    supp = jdata["supplier"]
    s_is = lookup(supp["s_suppkey"], n_is_nation[supp["s_nationkey"]])
    part = jdata["part"]
    p_ok = lookup(part["p_partkey"],
                  np.asarray(part["p_type"][1]) == code_of(part["p_type"],
                                                           P_TYPE), 0)
    o = jdata["orders"]
    o_pass = (o["o_orderdate"] >= d1) & (o["o_orderdate"] <= d2) & \
        (c_ok[o["o_custkey"]] > 0)
    o_ok = lookup(o["o_orderkey"], o_pass, 0)
    o_year = lookup(o["o_orderkey"], years(o["o_orderdate"]), 0)
    li = jdata["lineitem"]
    m = (o_ok[li["l_orderkey"]] > 0) & (p_ok[li["l_partkey"]] > 0) & \
        (s_is[li["l_suppkey"]] >= 0)
    vol = li["l_extendedprice"][m].astype(dtype) * \
        (dtype(100) - li["l_discount"][m].astype(dtype))
    yr = o_year[li["l_orderkey"][m]]
    mine = s_is[li["l_suppkey"][m]] > 0
    return {int(y): (int(vol[(yr == y) & mine].sum(dtype=dtype)),
                     int(vol[yr == y].sum(dtype=dtype)))
            for y in np.unique(yr)}


def share(nation_sum: int, total: int) -> int:
    """The quotient unscaled at SHARE_SCALE digits, rounded half up."""
    return (nation_sum * 10 ** SHARE_SCALE + total // 2) // total


def reference(data):
    return sums(data["joins"])


def render(data, by_year: dict) -> list[list[str]]:
    """The wire rows of a {year: (nation's sum, total)}."""
    return [[str(y), dec(share(a, b), SHARE_SCALE)]
            for y, (a, b) in sorted(by_year.items()) if b]


def compare(rows, ref, fresh=None, key=None):
    want = {y: share(a, b) for y, (a, b) in ref.items() if b}
    got = {int(r[0]): unscaled(r[1], SHARE_SCALE) for r in rows}
    if got != want or len(got) != len(rows):
        return f"q8: {got} != {want} (sums {ref})"
    order = [int(r[0]) for r in rows]
    return None if order == sorted(order) else "q8: years not ordered"
