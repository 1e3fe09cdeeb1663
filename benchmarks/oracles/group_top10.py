"""GROUP BY l_orderkey, sum(l_quantity), top 10 by (sum desc, key)."""

from __future__ import annotations

import numpy as np

from . import as_bfloat16, unscaled

CONTROL = "bfloat16"  # float32 holds every group's sum exactly


def reference(data):
    li = data["lineitem"]
    # bincount accumulates float64: exact while every sum < 2**53
    sums = np.bincount(li["l_orderkey"],
                       weights=li["l_quantity"]).astype(np.int64)
    keys = np.flatnonzero(np.bincount(li["l_orderkey"]))
    order = np.lexsort((keys, -sums[keys]))[:10]
    return [(int(keys[i]), int(sums[keys[i]])) for i in order]


def compare(rows, ref, fresh=None, key=None):
    got = [(int(r[0]), unscaled(r[1], 2)) for r in rows]
    return None if got == ref else f"group_top10: {got[:2]} != {ref[:2]}"


def _render(pairs) -> list[list[str]]:
    return [[str(k), f"{v // 100}.{v % 100:02d}"] for k, v in pairs]


def render_exact(data, ref) -> list[list[str]]:
    return _render(ref)


def control_rows(data) -> list[list[str]]:
    """The control: the groups' sums held in bfloat16 and ranked so. Sums
    near the top (about 340.00) then tie in steps of 2.56, and the ten the
    control returns, with the sums it shows, are not the reference's."""
    li = data["lineitem"]
    sums = np.bincount(li["l_orderkey"], weights=li["l_quantity"])
    keys = np.flatnonzero(np.bincount(li["l_orderkey"]))
    low = as_bfloat16(sums[keys])
    order = np.lexsort((keys, -low))[:10]
    return _render([(int(keys[i]), int(low[i])) for i in order])
