"""Join fragments: device gather-join pushdown + host fallback.

Path-assertion tests (which engine ran the query) mirror the reference's
explaintest plan checks (cmd/explaintest/r/tpch.result pins cop/root task
splits); fallback tests pin the runtime gates (overlay rows, wide spans).
"""

import numpy as np
import pytest

import tidb_tpu.copr.fragment as F
from tidb_tpu.plan.fragment import PhysFragmentRead
from tidb_tpu.session import Session


@pytest.fixture
def star():
    """Fact table + two dimension tables (PK-keyed), snowflake chain:
    fact.cust -> customer.ck, customer.nk -> nation.nk."""
    s = Session()
    s.execute("""CREATE TABLE nation (
        nk INT NOT NULL PRIMARY KEY, nname VARCHAR(20))""")
    s.execute("""CREATE TABLE customer (
        ck INT NOT NULL PRIMARY KEY, nk INT, seg VARCHAR(10))""")
    s.execute("""CREATE TABLE fact (
        fid INT NOT NULL PRIMARY KEY, cust INT, amount DECIMAL(10,2),
        qty INT)""")
    s.execute("INSERT INTO nation VALUES (1,'de'),(2,'fr'),(3,'jp')")
    s.execute("""INSERT INTO customer VALUES
        (10,1,'auto'),(11,2,'auto'),(12,3,'steel'),(13,1,'steel')""")
    rng = np.random.default_rng(5)
    rows = []
    for i in range(400):
        cust = int(rng.choice([10, 11, 12, 13, 99]))  # 99 dangles
        rows.append(f"({i},{cust},{(i % 50) + 0.25},{i % 7})")
    s.execute("INSERT INTO fact VALUES " + ",".join(rows))
    _fold(s)
    return s


def _fold(s):
    """Fold committed deltas into column epochs (the steady state bulk
    loads land in; fresh INSERTs live in the MVCC overlay until then)."""
    safe = s.storage.safe_ts()
    for store in s.storage.tables.values():
        store.compact(safe)


JOIN_AGG = """
    SELECT nname, SUM(amount), COUNT(*)
    FROM fact, customer, nation
    WHERE fact.cust = customer.ck AND customer.nk = nation.nk
      AND seg = 'auto' AND qty < 5
    GROUP BY nname ORDER BY nname
"""

JOIN_ROWS = """
    SELECT fid, nname FROM fact, customer, nation
    WHERE fact.cust = customer.ck AND customer.nk = nation.nk
      AND qty = 3 AND seg = 'steel' ORDER BY fid
"""


def _plan_has_fragment(s, sql):
    from tidb_tpu.plan import PlanBuilder, optimize
    from tidb_tpu.sql.parser import parse_one

    plan = optimize(PlanBuilder(s.catalog, s.current_db).build_select(
        parse_one(sql)), s.storage.stats)

    def walk(p):
        if isinstance(p, PhysFragmentRead):
            return True
        return any(walk(c) for c in p.children)

    return walk(plan)


def _oracle(s, sql):
    """Same query with fragment recognition disabled (host join engine)."""
    import tidb_tpu.plan.fragment as PF
    orig = PF.apply_fragments
    PF.apply_fragments = lambda p: p
    try:
        return s.query(sql)
    finally:
        PF.apply_fragments = orig


def test_join_agg_planned_as_fragment(star):
    assert _plan_has_fragment(star, JOIN_AGG)


def test_join_agg_device_path(star, monkeypatch):
    """The snowflake aggregation must run on the device path — the host
    interpreter is a fallback, not the route (VERDICT: path assertions)."""
    def boom(frag, snaps):
        raise AssertionError("host fragment fallback taken")
    monkeypatch.setattr(F, "_host_fragment", boom)
    got = star.query(JOIN_AGG)
    assert [r[0] for r in got] == ["de", "fr"]  # jp customers are 'steel'
    want = _oracle(star, JOIN_AGG)
    assert got == want


def test_join_rows_device_path(star, monkeypatch):
    def boom(frag, snaps):
        raise AssertionError("host fragment fallback taken")
    monkeypatch.setattr(F, "_host_fragment", boom)
    got = star.query(JOIN_ROWS)
    assert got == _oracle(star, JOIN_ROWS)
    assert len(got) > 0


def test_dangling_keys_drop(star):
    """INNER semantics: fact rows pointing at absent customers vanish."""
    total = star.query("SELECT COUNT(*) FROM fact")[0][0]
    joined = star.query("""
        SELECT COUNT(*) FROM fact, customer
        WHERE fact.cust = customer.ck""")[0][0]
    dangling = star.query(
        "SELECT COUNT(*) FROM fact WHERE cust = 99")[0][0]
    assert joined == total - dangling


def test_null_join_keys_drop(star):
    star.execute("INSERT INTO fact VALUES (9001, NULL, 5.00, 3)")
    got = star.query("""
        SELECT COUNT(*) FROM fact, customer WHERE fact.cust = customer.ck
          AND fid = 9001""")
    assert got == [(0,)]


def test_overlay_build_rows_fall_back(star, monkeypatch):
    """Uncommitted rows on a build table force the host interpreter —
    results must stay correct either way."""
    called = {}
    orig = F._host_fragment

    def spy(frag, snaps):
        called["yes"] = True
        return orig(frag, snaps)
    monkeypatch.setattr(F, "_host_fragment", spy)
    star.execute("BEGIN")
    star.execute("INSERT INTO customer VALUES (14, 2, 'auto')")
    star.execute("INSERT INTO fact VALUES (9100, 14, 3.50, 1)")
    got = star.query(JOIN_AGG)
    star.execute("ROLLBACK")
    assert called.get("yes"), "expected host fallback for overlay build rows"
    # fr gains the new in-txn row's 3.50
    want = _oracle(star, JOIN_AGG)
    assert [r[0] for r in got] == [r[0] for r in want]


def test_committed_build_rows_visible(star):
    star.execute("INSERT INTO customer VALUES (15, 3, 'auto')")
    star.execute("INSERT INTO fact VALUES (9200, 15, 100.00, 1)")
    got = star.query(JOIN_AGG)
    assert "jp" in [r[0] for r in got]
    assert got == _oracle(star, JOIN_AGG)


def test_wide_key_span_falls_back(monkeypatch):
    s = Session()
    s.execute("CREATE TABLE dim (k BIGINT NOT NULL PRIMARY KEY, v INT)")
    s.execute("CREATE TABLE f (id INT NOT NULL PRIMARY KEY, k BIGINT)")
    s.execute("INSERT INTO dim VALUES (1, 10), (100000000, 20)")
    s.execute("INSERT INTO f VALUES (1, 1), (2, 100000000), (3, 5)")
    called = {}
    orig = F._host_fragment

    def spy(frag, snaps):
        called["yes"] = True
        return orig(frag, snaps)
    monkeypatch.setattr(F, "_host_fragment", spy)
    got = s.query("""
        SELECT SUM(v), COUNT(*) FROM f, dim WHERE f.k = dim.k
        GROUP BY v ORDER BY v""")
    assert called.get("yes"), "span gate should route to host"
    assert got == [(10, 1), (20, 1)]


def test_fragment_vs_host_differential(star):
    """Every supported shape agrees with the fragment-disabled engine."""
    queries = [
        JOIN_AGG,
        JOIN_ROWS,
        """SELECT nname, MIN(qty), MAX(qty), AVG(amount)
           FROM fact, customer, nation
           WHERE fact.cust = customer.ck AND customer.nk = nation.nk
           GROUP BY nname ORDER BY nname""",
        """SELECT COUNT(*) FROM fact, customer
           WHERE fact.cust = customer.ck AND amount > 20""",
    ]
    for q in queries:
        assert star.query(q) == _oracle(star, q), q


# ---------------- high-cardinality TopN aggregation ----------------

@pytest.fixture
def highcard():
    s = Session()
    s.execute("CREATE TABLE hc (k INT NOT NULL PRIMARY KEY, g INT, "
              "v DECIMAL(8,2))")
    rng = np.random.default_rng(11)
    rows = []
    for i in range(20000):
        rows.append(f"({i},{int(rng.integers(0, 18000))},"
                    f"{int(rng.integers(1, 500))}.25)")
    s.execute("INSERT INTO hc VALUES " + ",".join(rows))
    _fold(s)
    return s


HC_Q = ("SELECT g, SUM(v) AS sv, COUNT(*) FROM hc GROUP BY g "
        "ORDER BY sv DESC LIMIT 7")


def test_highcard_topn_device_path(highcard, monkeypatch):
    """GROUP BY over ~14k distinct keys (beyond the dense-segment cap)
    with an ORDER BY ... LIMIT consumer runs the sorted-run candidate
    kernel on device, digest-equal to the host engine."""
    def boom(frag, snaps):
        raise AssertionError("host fragment fallback taken")
    monkeypatch.setattr(F, "_host_fragment", boom)
    ran = {}
    orig = F._run_frag_batch

    def spy(cop, frag, snaps, prepared, spans, builds, overlay, mode=None):
        ran["mode"] = mode
        return orig(cop, frag, snaps, prepared, spans, builds, overlay,
                    mode=mode)
    monkeypatch.setattr(F, "_run_frag_batch", spy)
    got = highcard.query(HC_Q)
    assert ran.get("mode") == "hc", f"expected hc path, got {ran}"
    assert got == _oracle(highcard, HC_Q)
    assert len(got) == 7


def test_highcard_topn_join_device_path(star, monkeypatch):
    """Q3-shaped: join + high-cardinality group key + TopN; the dependent
    group keys (nation name via the join) ride along without sorting."""
    # widen fact ids into a high-card group key
    q = ("SELECT fid, nname, SUM(amount) AS sa FROM fact, customer, nation "
         "WHERE fact.cust = customer.ck AND customer.nk = nation.nk "
         "GROUP BY fid, nname ORDER BY sa DESC LIMIT 5")
    got = star.query(q)
    assert got == _oracle(star, q)
    assert len(got) == 5


def test_highcard_group_key_order(highcard):
    q = "SELECT g, SUM(v) FROM hc GROUP BY g ORDER BY g LIMIT 9"
    assert highcard.query(q) == _oracle(highcard, q)


def test_device_error_fails_the_statement(star, monkeypatch):
    """An error from the device compiler or runtime (Mosaic/XLA refusing
    a program) is the statement's typed error — not a slow, correct
    host(fragment:compile) answer that hides that the chip did nothing.
    The typed planner gates (_Fallback reasons: the overlay and key-span
    tests above) still reach the host interpreter, and so does the one
    counted degrade, a program that did not fit HBM."""
    import jax

    from tidb_tpu import obs
    from tidb_tpu.copr.client import CopClient
    from tidb_tpu.copr.eval import DeviceError
    from tidb_tpu.errno import CodedError

    def refuse(msg):
        def raiser(*a, **kw):
            raise jax.errors.JaxRuntimeError(msg)
        return raiser

    want = star.query(JOIN_AGG)  # the device answer, oracle-checked above
    mosaic = "INTERNAL: Mosaic failed to compile TPU kernel: bad layout"
    monkeypatch.setattr(F, "_device_fragment", refuse(mosaic))
    with monkeypatch.context() as m:
        m.setattr(F, "_host_fragment", refuse("host fallback taken"))
        with pytest.raises(DeviceError, match="Mosaic failed") as ei:
            star.query(JOIN_AGG)
    assert isinstance(ei.value, CodedError)

    # HBM exhaustion degrades, tagged and counted
    before = obs.FRAG_FALLBACKS.get(reason="device-oom")
    monkeypatch.setattr(F, "_device_fragment", refuse(
        "RESOURCE_EXHAUSTED: out of memory in memory space hbm"))
    assert star.query(JOIN_AGG) == want
    assert "host(fragment:device-oom)" in star.last_engines
    assert obs.FRAG_FALLBACKS.get(reason="device-oom") == before + 1

    # the single-table device path has no degrade at all
    monkeypatch.setattr(CopClient, "_run_batch", refuse(mosaic))
    with pytest.raises(DeviceError, match="Mosaic failed"):
        star.query("SELECT SUM(amount) FROM fact WHERE qty < 5")
