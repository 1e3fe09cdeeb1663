"""TPC-H refresh function RF1, the lineitem part: new orders' lineitems.

Each insert is one order: 1-7 lineitem rows (TPC-H 4.2.3), all 16 columns,
under a new l_orderkey above the loaded ones. The order sizes follow a fixed cycle, so
every seed inserts the same number of rows. Values are copied from rows of
the loaded table that the seed picks (so every value stays inside the loaded
columns' ranges); line 1 of every order is picked among the rows that pass
Q6's predicate, so that each acknowledged insert moves Q6's answer and a scan
that misses one is seen.
"""

from __future__ import annotations

import datetime as _dt

import numpy as np

from .tpch import parse_date

ORDER_SIZES = (4, 1, 7, 3, 5, 2, 6)
COLS = ("l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
        "l_extendedprice", "l_discount", "l_tax", "l_returnflag",
        "l_linestatus", "l_shipdate", "l_commitdate", "l_receiptdate",
        "l_shipinstruct", "l_shipmode", "l_comment")


def _date(days: int) -> str:
    return (_dt.date(1970, 1, 1) + _dt.timedelta(days=int(days))).isoformat()


def _money(cents: int) -> str:
    return f"{int(cents) // 100}.{int(cents) % 100:02d}"


def orders(li: dict, vocab: dict, seed: int, n_orders: int,
           sizes=ORDER_SIZES) -> tuple[list[str], list[dict]]:
    """(INSERT statements, the same rows as column arrays, one per order).
    `li` holds the loaded columns, `vocab` the texts of the coded ones."""
    rng = np.random.default_rng([seed, 0x5F1])
    n = len(li["l_orderkey"])
    d1, d2 = parse_date("1994-01-01"), parse_date("1995-01-01")
    # Q6-qualifying rows among a seeded sample (a full mask of 60M rows is
    # not needed to find a few hundred)
    sample = rng.integers(0, n, min(n, 200_000 + 400 * n_orders))
    q = sample[(li["l_shipdate"][sample] >= d1) & (li["l_shipdate"][sample] < d2)
               & (li["l_discount"][sample] >= 5)
               & (li["l_discount"][sample] <= 7)
               & (li["l_quantity"][sample] < 2400)]
    if len(q) == 0:
        raise ValueError("no Q6-qualifying row in the sample")
    next_key = int(li["l_orderkey"].max()) + 1
    sqls, batches = [], []
    for j in range(n_orders):
        size = sizes[j % len(sizes)]
        src = np.concatenate(([q[j % len(q)]], rng.integers(0, n, size - 1)))
        batch = {c: np.asarray(li[c][src]) for c in COLS}
        batch["l_orderkey"] = np.full(size, next_key + j, dtype=np.int64)
        batch["l_linenumber"] = np.arange(1, size + 1, dtype=np.int64)

        def text(col: str, i: int) -> str:
            return vocab[col][int(batch[col][i])]

        rows = []
        for i in range(size):
            rows.append(
                f"({next_key + j}, {int(batch['l_partkey'][i])}, "
                f"{int(batch['l_suppkey'][i])}, {i + 1}, "
                f"{_money(batch['l_quantity'][i])}, "
                f"{_money(batch['l_extendedprice'][i])}, "
                f"{_money(batch['l_discount'][i])}, "
                f"{_money(batch['l_tax'][i])}, "
                f"'{text('l_returnflag', i)}', "
                f"'{text('l_linestatus', i)}', "
                f"'{_date(batch['l_shipdate'][i])}', "
                f"'{_date(batch['l_commitdate'][i])}', "
                f"'{_date(batch['l_receiptdate'][i])}', "
                f"'{text('l_shipinstruct', i)}', "
                f"'{text('l_shipmode', i)}', '{text('l_comment', i)}')")
        sqls.append("insert into lineitem values " + ", ".join(rows))
        batches.append(batch)
    return sqls, batches
