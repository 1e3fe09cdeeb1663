"""TPC-H Q21, suppliers who kept orders waiting (2.4.21, validation
parameter NATION SAUDI ARABIA), over the join set: copy of the plain
reference tests/tpch_subq_ref.py, which follows the SQL literally — the
EXISTS / NOT EXISTS ask whether the order has a supplier other than the
line's own among all its lines / its late lines, answered from the
per-order sets of distinct suppliers. s_name is unique, so ORDER BY numwait
desc, s_name orders every row: compared row for row."""

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


def reference(data):
    return q21(data["joins"])


def compare(rows, ref, fresh=None, key=None):
    got = [(r[0], int(r[1])) for r in rows]
    if got != ref:
        for i, (g, w) in enumerate(zip(got, ref)):
            if g != w:
                return f"q21 row {i}: {g} != {w}"
        return f"q21: {len(got)} rows, the reference has {len(ref)}"
    return None
