"""TPC-H Q1 (validation parameters): exact int64 sums per group and the
AVG columns re-derived from them at the scale the wire returned."""

from __future__ import annotations

import decimal

import numpy as np

from . import unscaled
from ..datagen.tpch import parse_date

CHUNK = 16_000_000
NAMES = ("qty", "base", "disc_price", "charge", "disc", "count")
FLAG = {"A": 0, "R": 1, "N": 2}
STATUS = {"F": 0, "O": 1}


def sums(li) -> np.ndarray:
    """int64[6 groups, 6 sums]; group = returnflag * 2 + linestatus. One
    masked pass per group: int64 sums are exact (the largest, charge, stays
    under 2**63 up to SF100)."""
    cutoff = parse_date("1998-12-01") - 90
    acc = np.zeros((6, len(NAMES)), dtype=np.int64)
    n = len(li["l_shipdate"])
    for lo in range(0, n, CHUNK):
        sl = slice(lo, min(lo + CHUNK, n))
        m = li["l_shipdate"][sl] <= cutoff
        key = (li["l_returnflag"][sl].astype(np.int64) * 2
               + li["l_linestatus"][sl])
        qty = li["l_quantity"][sl].astype(np.int64)
        ext = li["l_extendedprice"][sl].astype(np.int64)
        disc = li["l_discount"][sl].astype(np.int64)
        tax = li["l_tax"][sl].astype(np.int64)
        dp = ext * (100 - disc)
        cols = (qty, ext, dp, dp * (100 + tax), disc)
        for k in range(6):
            mk = m & (key == k)
            for j, v in enumerate(cols):
                acc[k, j] += int(v[mk].sum())
            acc[k, 5] += int(mk.sum())
    return acc


def reference(data):
    accs = [sums(data["lineitem"])]
    for batch in data.get("rf1", ()):
        accs.append(accs[-1] + sums(batch))
    return accs


def _one(rows, acc):
    groups = {k: acc[k] for k in range(6) if acc[k, 5]}
    if len(rows) != len(groups):
        return f"q1: {len(rows)} groups, reference {len(groups)}"
    order = [(r[0], r[1]) for r in rows]
    if order != sorted(order):
        return "q1: groups not ordered"
    for r in rows:
        w = groups.get(FLAG[r[0]] * 2 + STATUS[r[1]])
        if w is None:
            return f"q1: group {r[0]}/{r[1]} not in the reference"
        got = (unscaled(r[2], 2), unscaled(r[3], 2), unscaled(r[4], 4),
               unscaled(r[5], 6), int(r[9]))
        want = (int(w[0]), int(w[1]), int(w[2]), int(w[3]), int(w[5]))
        if got != want:
            return f"q1 {r[0]}/{r[1]}: {got} != {want}"
        # AVG = SUM / COUNT at the returned scale, MySQL rounds half up
        for col, total in ((6, w[0]), (7, w[1]), (8, w[4])):
            q = decimal.Decimal(r[col])
            exact = (decimal.Decimal(int(total)).scaleb(-2)
                     / decimal.Decimal(int(w[5]))).quantize(
                         q, rounding=decimal.ROUND_HALF_UP)
            if q != exact:
                return f"q1 {r[0]}/{r[1]} avg col {col}: {q} != {exact}"
    return None


def compare(rows, ref, fresh=None, key=None):
    lo, hi = fresh if fresh is not None else (0, 0)
    why = None
    for acc in ref[lo:hi + 1]:
        why = _one(rows, acc)
        if why is None:
            return None
    return f"{why} (inserts {lo}..{hi})"


def render(acc: np.ndarray) -> list[list[str]]:
    """The wire rows of exact sums (AVG at six digits, half up)."""
    def dec(v: int, scale: int) -> str:
        return f"{v // 10 ** scale}.{v % 10 ** scale:0{scale}d}"

    def avg(total: int, count: int) -> str:
        return str((decimal.Decimal(int(total)).scaleb(-2)
                    / decimal.Decimal(int(count))).quantize(
                        decimal.Decimal("0.000001"),
                        rounding=decimal.ROUND_HALF_UP))
    rows = []
    for flag, f in sorted(FLAG.items()):
        for status, s in sorted(STATUS.items()):
            w = [int(x) for x in acc[f * 2 + s]]
            if w[5]:
                rows.append([flag, status, dec(w[0], 2), dec(w[1], 2),
                             dec(w[2], 4), dec(w[3], 6), avg(w[0], w[5]),
                             avg(w[1], w[5]), avg(w[4], w[5]), str(w[5])])
    return rows


def control_rows(data) -> list[list[str]]:
    """The control: every sum accumulated in float32."""
    li = data["lineitem"]
    cutoff = parse_date("1998-12-01") - 90
    m = li["l_shipdate"] <= cutoff
    key = li["l_returnflag"].astype(np.int64) * 2 + li["l_linestatus"]
    f32 = np.float32
    qty, ext = li["l_quantity"].astype(f32), li["l_extendedprice"].astype(f32)
    disc, tax = li["l_discount"].astype(f32), li["l_tax"].astype(f32)
    dp = ext * (f32(100) - disc)
    acc = np.zeros((6, len(NAMES)), dtype=np.int64)
    for k in range(6):
        mk = m & (key == k)
        for j, v in enumerate((qty, ext, dp, dp * (f32(100) + tax), disc)):
            acc[k, j] = int(round(float(v[mk].sum(dtype=f32))))
        acc[k, 5] = int(mk.sum())
    return render(acc)
