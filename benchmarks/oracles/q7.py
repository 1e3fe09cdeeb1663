"""TPC-H Q7, volume shipping (2.4.7, validation parameters FRANCE, GERMANY)
over the join set, straight from the query text: revenue =
sum(l_extendedprice * (1 - l_discount)) of the lines shipped in 1995-1996
from a supplier of one of the two nations to a customer of the other, by
(supplier's nation, customer's nation, year of l_shipdate). numpy int64;
revenue compared digit for digit at the wire's four decimals."""

from __future__ import annotations

import numpy as np

from . import unscaled
from ..datagen.tpch import parse_date

NATION_1, NATION_2 = "FRANCE", "GERMANY"


def lookup(keys, values, fill=-1) -> np.ndarray:
    """values by key, as an array indexed by the key (keys are unique)."""
    out = np.full(int(np.max(keys)) + 1, fill, dtype=np.int64)
    out[np.asarray(keys)] = values
    return out


def years(days) -> np.ndarray:
    """Calendar year of each day number (days since 1970-01-01)."""
    return np.asarray(days).astype("datetime64[D]").astype(
        "datetime64[Y]").astype(np.int64) + 1970


def dec(v: int, scale: int) -> str:
    """A non-negative unscaled integer as the wire's DECIMAL text."""
    return f"{v // 10 ** scale}.{v % 10 ** scale:0{scale}d}"


def sums(jdata, dtype=np.int64) -> dict:
    """{(supp_nation, cust_nation, year): revenue unscaled at 4 digits},
    multiplied and accumulated in `dtype` (int64 is exact; float32 is the
    control)."""
    d1, d2 = parse_date("1995-01-01"), parse_date("1996-12-31")
    nat = jdata["nation"]
    names = list(nat["n_name"][0])
    nkey = {nm: int(nat["n_nationkey"][i])
            for nm, i in zip(names, np.asarray(nat["n_name"][1]))}
    k1, k2 = nkey[NATION_1], nkey[NATION_2]
    s_nat = lookup(jdata["supplier"]["s_suppkey"],
                   jdata["supplier"]["s_nationkey"])
    c_nat = lookup(jdata["customer"]["c_custkey"],
                   jdata["customer"]["c_nationkey"])
    o = jdata["orders"]
    o_cnat = lookup(o["o_orderkey"], c_nat[o["o_custkey"]])
    li = jdata["lineitem"]
    sn = s_nat[li["l_suppkey"]]
    cn = o_cnat[li["l_orderkey"]]
    m = (((sn == k1) & (cn == k2)) | ((sn == k2) & (cn == k1))) & \
        (li["l_shipdate"] >= d1) & (li["l_shipdate"] <= d2)
    vol = li["l_extendedprice"][m].astype(dtype) * \
        (dtype(100) - li["l_discount"][m].astype(dtype))
    yr = years(li["l_shipdate"][m])
    first = sn[m] == k1           # else the supplier is of NATION_2
    out = {}
    for is_first, a, b in ((True, NATION_1, NATION_2),
                           (False, NATION_2, NATION_1)):
        for y in np.unique(yr):
            g = (first == is_first) & (yr == y)
            if g.any():
                out[(a, b, int(y))] = int(vol[g].sum(dtype=dtype))
    return out


def reference(data):
    return sums(data["joins"])


def render(data, groups: dict) -> list[list[str]]:
    """The wire rows of a {group: revenue}."""
    return [[a, b, str(y), dec(v, 4)]
            for (a, b, y), v in sorted(groups.items())]


def compare(rows, ref, fresh=None, key=None):
    got = {(r[0], r[1], int(r[2])): unscaled(r[3], 4) for r in rows}
    if got != ref or len(got) != len(rows):
        return f"q7: {got} != {ref}"
    order = [(r[0], r[1], int(r[2])) for r in rows]
    return None if order == sorted(order) else "q7: groups not ordered"
