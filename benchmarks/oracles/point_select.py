"""sysbench oltp_point_select: `c` of the row with the key; nothing in the
cell's traffic changes `c`, so the loaded value is the answer."""

from __future__ import annotations


def reference(data):
    t = data["sbtest"]
    return {int(i): c for i, c in zip(t["id"], t["c"])}


def compare(rows, ref, fresh=None, key=None):
    want = [[ref[key]]]
    got = [list(r) for r in rows]
    return None if got == want else f"point_select id={key}: {got} != {want}"
