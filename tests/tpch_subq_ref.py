"""Plain numpy references of TPC-H Q18 and Q21 over generated arrays
(the `generate_tpch` layout: decimals x100, dates as days since
1970-01-01, strings as (vocabulary, codes)). Nothing here imports the
program; benchmarks/oracles/q18.py and q21.py are copies of it.

Q18 groups LINEITEM by sorting its order keys (no reliance on storage
order) and sums l_quantity in int64. Q21 follows the SQL literally: the
EXISTS / NOT EXISTS ask whether the order has a supplier other than the
line's own among all its lines / among its late lines, answered from the
per-order SETS of distinct suppliers (np.unique over (order, supplier)
pairs)."""

from __future__ import annotations

import datetime

import numpy as np

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


def _pair_sets(orderkey, suppkey, rows):
    """(distinct suppliers per order, {(order, supplier)} membership) of
    the lines `rows` selects."""
    base = int(suppkey.max()) + 1
    pairs = np.unique(orderkey[rows].astype(np.int64) * base
                      + suppkey[rows])
    per_order = np.bincount(pairs // base,
                            minlength=int(orderkey.max()) + 1)
    return per_order, pairs, base


def q21(jdata, nation: str = "SAUDI ARABIA", limit: int = 100
        ) -> list[tuple]:
    """(s_name, numwait) ordered by numwait desc, s_name."""
    li = jdata["lineitem"]
    ok, sk = li["l_orderkey"], li["l_suppkey"]
    late = li["l_receiptdate"] > li["l_commitdate"]
    every = np.ones(len(ok), bool)
    n_all, _, _ = _pair_sets(ok, sk, every)
    n_late, late_pairs, base = _pair_sets(ok, sk, late)
    mine = ok.astype(np.int64) * base + sk
    # EXISTS: a supplier of the order other than the line's own
    other = n_all[ok] - 1 > 0
    # NOT EXISTS: a late line of another supplier
    own_late = np.isin(mine, late_pairs)
    other_late = n_late[ok] - own_late > 0
    o = jdata["orders"]
    vocab, codes = o["o_orderstatus"]
    f_orders = np.zeros(int(o["o_orderkey"].max()) + 1, bool)
    f_orders[o["o_orderkey"][np.asarray(codes) == vocab.index("F")]] = True
    s, n = jdata["supplier"], jdata["nation"]
    nvocab, ncodes = n["n_name"]
    nk = n["n_nationkey"][np.asarray(ncodes) == nvocab.index(nation)]
    s_ok = np.zeros(int(s["s_suppkey"].max()) + 1, bool)
    s_ok[s["s_suppkey"][np.isin(s["s_nationkey"], nk)]] = True
    hit = late & other & ~other_late & f_orders[ok] & s_ok[sk]
    counts = np.bincount(sk[hit], minlength=len(s_ok))
    srow = _row_of(s["s_suppkey"])
    rows = [(_text(s["s_name"], int(srow[k])), int(counts[k]))
            for k in np.flatnonzero(counts)]
    rows.sort(key=lambda r: (-r[1], r[0]))
    return rows[:limit]
