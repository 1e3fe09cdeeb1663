"""Drive a rehearsal of one cell with the timed path broken underneath.

    python benchmarks/tests/faults.py <fault> --workload ... (run.py's args)

The faults a cell of this benchmark can have: `answer_altered` (a value of
a result row changed where the server encodes it, one row in 40) and
`state_unchanged` (every fifth UPDATE acknowledged without changing its
row), `log_not_synced` (the store opened with sync_log=off where the
configuration states commit). Each has to come out `correct: false`.
`none` plants nothing; `one_hot_row` plants no fault but sends both writers
to ids 1-2, so that write conflicts (error 9007) come and the client's
retries are driven: that run has to come out correct.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def answer_altered() -> None:
    import tidb_tpu.server.packet as P

    inner = P.text_row
    calls = [0]

    def text_row(values):
        vals = list(values)
        calls[0] += 1
        if calls[0] % 40 == 0 and vals:
            vals[0] = 1 if vals[0] != 1 else 2
        return inner(vals)

    P.text_row = text_row


def state_unchanged() -> None:
    from tidb_tpu.session.session import Session

    inner = Session.execute
    calls = [0]

    def execute(self, sql, *a, **kw):
        if isinstance(sql, str) and sql.startswith("UPDATE sbtest1 SET k=k+1"):
            calls[0] += 1
            if calls[0] % 5 == 0:
                sql = sql.replace("k=k+1", "k=k+0")
        return inner(self, sql, *a, **kw)

    Session.execute = execute


def log_not_synced() -> None:
    import tidb_tpu.store.storage as ST

    inner = ST.Storage.__init__

    def init(self, path=None, *a, **kw):
        kw["sync_log"] = "off"
        return inner(self, path, *a, **kw)

    ST.Storage.__init__ = init


def one_hot_row() -> None:
    from benchmarks.datagen import sysbench

    inner = sysbench.key_stream

    def key_stream(table_size, seed, stream, n):
        return inner(2, seed, stream, n)

    sysbench.key_stream = key_stream


def main(argv) -> int:
    from benchmarks import run

    os.environ["JAX_PLATFORMS"] = "cpu"
    {"none": lambda: None, "answer_altered": answer_altered,
     "state_unchanged": state_unchanged, "log_not_synced": log_not_synced,
     "one_hot_row": one_hot_row}[argv[1]]()
    return run.main(argv[2:] + ["--rehearse-cpu"])


if __name__ == "__main__":
    sys.exit(main(sys.argv))
