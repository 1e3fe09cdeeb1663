"""sysbench's sbtest table and key streams from a seed (numpy only).

Schema and value shapes of sysbench 1.0 `oltp_common.lua`: id 1..table_size,
k uniform in 1..table_size, c ten groups of eleven digits joined by '-'
(119 characters), pad five such groups (59). Keys: `rand-type=uniform`.
"""

from __future__ import annotations

import numpy as np

DDL = ("create table sbtest1 (id int not null primary key, "
       "k int not null default 0, c char(120) not null default '', "
       "pad char(60) not null default '', key k_1 (k))")


def _groups(rng: np.random.Generator, n: int, groups: int) -> list[str]:
    d = rng.integers(0, 10**11, size=(n, groups))
    return ["-".join(f"{int(v):011d}" for v in row) for row in d]


def generate(table_size: int, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {"id": np.arange(1, table_size + 1, dtype=np.int64),
            "k": rng.integers(1, table_size + 1, table_size, dtype=np.int64),
            "c": _groups(rng, table_size, 10),
            "pad": _groups(rng, table_size, 5)}


def insert_statements(t: dict, batch: int = 2000) -> list[str]:
    out = []
    for lo in range(0, len(t["id"]), batch):
        hi = min(lo + batch, len(t["id"]))
        out.append("insert into sbtest1 values " + ",".join(
            f"({int(t['id'][i])},{int(t['k'][i])},'{t['c'][i]}',"
            f"'{t['pad'][i]}')" for i in range(lo, hi)))
    return out


def key_stream(table_size: int, seed: int, stream: int, n: int) -> list[int]:
    """n keys for one connection, uniform over all ids (`rand-type=
    uniform`); `stream` keeps the connections' streams apart. Two writers
    can meet on one id: the program then answers the loser with error 9007
    (write conflict), and the client retries, as sysbench does for the
    errors it is told to ignore (`retry_on` in the statement's file)."""
    rng = np.random.default_rng([seed, stream])
    return [int(x) for x in rng.integers(1, table_size + 1, n)]
