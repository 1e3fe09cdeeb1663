"""RF1 insert of one order's lineitems: acknowledged with its row count.
That the rows are then seen is checked through q6 and q1 (`fresh`)."""

from __future__ import annotations


def reference(data):
    return [len(b["l_orderkey"]) for b in data["rf1"]]


def compare(rows, ref, fresh=None, key=None):
    return None if rows == [[ref[key]]] else (
        f"rf1 order {key}: acked {rows}, sent {ref[key]} rows")
