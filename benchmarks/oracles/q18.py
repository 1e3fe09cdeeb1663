"""TPC-H Q18, large volume customer (2.4.18, validation parameter QUANTITY
300), over the join set: copy of the plain reference tests/tpch_subq_ref.py
(numpy; LINEITEM grouped by sorting its order keys, l_quantity summed in
int64). ORDER BY o_totalprice desc, o_orderdate does not order two orders
equal in both, so an answer is right where its rows are, in order, the
reference's sort keys and each row is one of the reference's rows with
those keys; at the LIMIT a tie may be cut either way."""

from __future__ import annotations

import datetime

import numpy as np

from . import unscaled

EPOCH = datetime.date(1970, 1, 1)


def _text(column, i: int) -> str:
    vocab, codes = column
    return vocab[int(codes[i])]


def _day(d: int) -> str:
    return (EPOCH + datetime.timedelta(days=int(d))).isoformat()


def _row_of(keys) -> np.ndarray:
    """key -> row index (keys unique), -1 where absent."""
    keys = np.asarray(keys)
    out = np.full(int(keys.max()) + 1, -1, dtype=np.int64)
    out[keys] = np.arange(len(keys))
    return out


def q18(jdata, quantity: int = 300, limit: int = 100) -> list[tuple]:
    """Every order whose lines' quantities sum above `quantity`, as
    (c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice x100,
    sum(l_quantity) x100), ordered by o_totalprice desc, o_orderdate;
    rows past `limit` are kept while they tie with the last one (a
    LIMIT may cut a tie either way)."""
    li = jdata["lineitem"]
    order = np.argsort(li["l_orderkey"], kind="stable")
    keys = li["l_orderkey"][order]
    starts = np.flatnonzero(np.concatenate(([True], keys[1:] != keys[:-1])))
    totals = np.add.reduceat(li["l_quantity"][order].astype(np.int64),
                             starts)
    big = totals > quantity * 100
    okeys, qty = keys[starts][big], totals[big]
    o, c = jdata["orders"], jdata["customer"]
    oi = _row_of(o["o_orderkey"])[okeys]
    keep = oi >= 0
    okeys, qty, oi = okeys[keep], qty[keep], oi[keep]
    ci = _row_of(c["c_custkey"])[o["o_custkey"][oi]]
    rows = [(_text(c["c_name"], int(ci[k])), int(c["c_custkey"][ci[k]]),
             int(okeys[k]), _day(o["o_orderdate"][oi[k]]),
             int(o["o_totalprice"][oi[k]]), int(qty[k]))
            for k in range(len(okeys)) if ci[k] >= 0]
    rows.sort(key=lambda r: (-r[4], r[3]))
    if len(rows) > limit:
        last = rows[limit - 1][3:5]
        end = limit
        while end < len(rows) and rows[end][3:5] == last:
            end += 1
        rows = rows[:end]
    return rows



def reference(data):
    return q18(data["joins"])


def compare(rows, ref, fresh=None, key=None):
    want = ref[:100]
    if len(rows) != len(want):
        return f"q18: {len(rows)} rows, the reference has {len(want)}"
    pool = set(ref)
    seen = set()
    for i, (r, w) in enumerate(zip(rows, want)):
        got = (r[0], int(r[1]), int(r[2]), r[3], unscaled(r[4], 2),
               unscaled(r[5], 2))
        if got[3:5] != w[3:5]:
            return f"q18 row {i}: sort keys {got[3:5]} != {w[3:5]}"
        if got not in pool or got in seen:
            return f"q18 row {i}: {got} is not a row of the reference"
        seen.add(got)
    return None
