"""TPC-H Q10, returned item reporting (2.4.10, validation parameter
1993-10-01) over the join set, straight from the query text: the 20
customers who lost most revenue l_extendedprice * (1 - l_discount) on lines
returned (l_returnflag 'R') of orders of that quarter, with their name,
balance, nation, address, phone and comment. numpy int64, revenue digit for
digit at four decimals. ORDER BY revenue alone does not order equal
revenues, so an answer is right where its revenues are the 20 largest in
order and every row is its customer's own revenue and attributes."""

from __future__ import annotations

import decimal

import numpy as np

from . import unscaled
from ..datagen.tpch import parse_date
from .q7 import dec, lookup

LIMIT = 20
RETURNED = "R"


def sums(jdata, dtype=np.int64) -> dict:
    """{"revenue": dtype[c_custkey]}: revenue unscaled at 4 digits, 0
    where none, multiplied and accumulated in `dtype` (float32 is the
    control)."""
    d1, d2 = parse_date("1993-10-01"), parse_date("1994-01-01")
    o = jdata["orders"]
    o_ok = (o["o_orderdate"] >= d1) & (o["o_orderdate"] < d2)
    o_cust = lookup(o["o_orderkey"], np.where(o_ok, o["o_custkey"], -1))
    li = jdata["lineitem"]
    flags, codes = li["l_returnflag"]
    cust = o_cust[li["l_orderkey"]]
    m = (np.asarray(codes) == list(flags).index(RETURNED)) & (cust >= 0)
    rev = np.zeros(int(jdata["customer"]["c_custkey"].max()) + 1, dtype)
    np.add.at(rev, cust[m], li["l_extendedprice"][m].astype(dtype)
              * (dtype(100) - li["l_discount"][m].astype(dtype)))
    return {"revenue": rev}


def reference(data):
    jdata = data["joins"]
    rev = sums(jdata)["revenue"]
    top = np.sort(rev[rev > 0])[::-1][:LIMIT]
    return jdata, rev, [int(v) for v in top]


def _text(column, i: int) -> str:
    vocab, codes = column
    return vocab[int(codes[i])]


def _attributes(jdata, ck: int) -> tuple:
    """(name, balance, nation, address, phone, comment) of a customer."""
    cust, nat = jdata["customer"], jdata["nation"]
    i = int(np.nonzero(cust["c_custkey"] == ck)[0][0])
    n = int(np.nonzero(nat["n_nationkey"] == cust["c_nationkey"][i])[0][0])
    return (_text(cust["c_name"], i),
            decimal.Decimal(int(cust["c_acctbal"][i])).scaleb(-2),
            _text(nat["n_name"], n), _text(cust["c_address"], i),
            _text(cust["c_phone"], i), _text(cust["c_comment"], i))


def render(data, by_customer: dict) -> list[list[str]]:
    """The wire rows of the LIMIT largest of a {"revenue": array}."""
    rev = np.asarray(by_customer["revenue"])
    rows = []
    for ck in np.argsort(-rev, kind="stable")[:LIMIT]:
        if rev[ck] > 0:
            name, bal, nation, addr, phone, comment = _attributes(
                data["joins"], int(ck))
            rows.append([str(ck), name, dec(int(rev[ck]), 4), str(bal),
                         nation, addr, phone, comment])
    return rows


def compare(rows, ref, fresh=None, key=None):
    jdata, rev, top = ref
    got = [unscaled(r[2], 4) for r in rows]
    if got != top:
        return f"q10: revenues {got[:3]}... != {top[:3]}..."
    row_of = lookup(jdata["customer"]["c_custkey"],
                    np.arange(len(jdata["customer"]["c_custkey"])))
    seen = set()
    for r in rows:
        ck = int(r[0])
        if ck in seen or not 0 <= ck < len(rev) or row_of[ck] < 0:
            return f"q10: customer {ck} twice or unknown"
        seen.add(ck)
        want = (int(rev[ck]),) + _attributes(jdata, ck)
        have = (unscaled(r[2], 4), r[1], decimal.Decimal(r[3]), r[4], r[5],
                r[6], r[7])
        if have != want:
            return f"q10 customer {ck}: {have} != {want}"
    return None
