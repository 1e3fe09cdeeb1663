"""sysbench oltp_update_index: `UPDATE sbtest1 SET k=k+1 WHERE id=?`, each
its own transaction. The statement's own answer is "1 row affected"; the
state it leaves is checked by run.py's read-back after the window: k of a
row = loaded k + the acknowledged updates of that row."""

from __future__ import annotations


def reference(data):
    t = data["sbtest"]
    return {int(i): int(k) for i, k in zip(t["id"], t["k"])}


def compare(rows, ref, fresh=None, key=None):
    return None if rows == [[1]] else f"update_index id={key}: acked {rows}"
