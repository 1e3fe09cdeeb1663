"""Multi-device execution: sharded partial aggregation must match the
single-device path exactly (8 virtual CPU devices, see conftest)."""

import jax
import pytest

from tidb_tpu.bench.tpch import TPCH_Q1, TPCH_Q6, load_lineitem
from sharded_client import sharded_client
from tidb_tpu.session import Session

N_ROWS = 20_000


@pytest.fixture(scope="module")
def sessions():
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    single = Session()
    load_lineitem(single, N_ROWS)
    dist = Session(single.storage, cop=sharded_client(single.storage))
    return single, dist


class TestShardedAgg:
    def test_q6_matches_single_device(self, sessions):
        single, dist = sessions
        assert dist.query(TPCH_Q6) == single.query(TPCH_Q6)

    def test_q1_matches_single_device(self, sessions):
        single, dist = sessions
        rows_d = dist.query(TPCH_Q1)
        rows_s = single.query(TPCH_Q1)
        assert rows_d == rows_s
        assert len(rows_d) >= 4  # all (flag, status) groups present

    def test_scalar_agg_on_mesh(self, sessions):
        _, dist = sessions
        n = dist.query("select count(*) from lineitem")[0][0]
        assert n == N_ROWS

    def test_mvcc_overlay_on_mesh(self, sessions):
        single, dist = sessions
        dist.execute(
            "insert into lineitem values (999999, 1, 1, 1, 10.00, 1000.00, "
            "0.05, 0.02, 'N', 'O', '1998-01-01', '1998-01-10', '1998-01-20')")
        n = dist.query("select count(*) from lineitem")[0][0]
        assert n == N_ROWS + 1
        assert single.query("select count(*) from lineitem")[0][0] == \
            N_ROWS + 1


def test_dist_fragment_join_agg_device_path(monkeypatch):
    """Join fragments run probe-sharded with replicated build tables under
    the mesh — device path, no host fallback (VERDICT: shard the rest of
    the distributed tier)."""
    import numpy as np

    import tidb_tpu.copr.fragment as F
    from tidb_tpu.session import Session

    def boom(frag, snaps):
        raise AssertionError("host fragment fallback under mesh")
    monkeypatch.setattr(F, "_host_fragment", boom)

    single = Session()
    single.execute("CREATE TABLE d (k INT NOT NULL PRIMARY KEY, "
                   "g VARCHAR(4))")
    single.execute("CREATE TABLE f (id INT NOT NULL PRIMARY KEY, k INT, "
                   "v DECIMAL(8,2))")
    single.execute("INSERT INTO d VALUES (1,'a'),(2,'b'),(3,'a')")
    rows = ",".join(f"({i},{(i % 3) + 1},{i % 40}.50)" for i in range(900))
    single.execute("INSERT INTO f VALUES " + rows)
    safe = single.storage.safe_ts()
    for st in single.storage.tables.values():
        st.compact(safe)

    dist = Session(single.storage,
                   cop=sharded_client(single.storage, jax.devices()[:8]))
    q = ("SELECT g, SUM(v), COUNT(*), MIN(v), MAX(v) FROM f, d "
         "WHERE f.k = d.k GROUP BY g ORDER BY g")
    got = dist.query(q)
    monkeypatch.undo()
    want = single.query(q)
    assert got == want


def test_dist_topn_and_rows(monkeypatch):
    import tidb_tpu.copr.fragment as F  # noqa: F401
    from tidb_tpu.session import Session

    single = Session()
    single.execute("CREATE TABLE s (a INT NOT NULL PRIMARY KEY, b INT)")
    rows = ",".join(f"({i},{(i * 37) % 1000})" for i in range(2000))
    single.execute("INSERT INTO s VALUES " + rows)
    safe = single.storage.safe_ts()
    for st in single.storage.tables.values():
        st.compact(safe)
    dist = Session(single.storage,
                   cop=sharded_client(single.storage, jax.devices()[:8]))
    for q in ("SELECT a, b FROM s ORDER BY b DESC, a LIMIT 9",
              "SELECT a FROM s WHERE b < 50 ORDER BY a"):
        assert dist.query(q) == single.query(q), q
