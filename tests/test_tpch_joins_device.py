"""TPC-H's join queries answered by device programs alone (PR 35).

Q3, Q5, Q7, Q8, Q10, Q12 and Q14 over the eight-table join set at a small
scale, loaded and analysed as the benchmark's cell `tpch10_joins` loads it
(database `joins`, statistics taken: with statistics Q7's and Q8's derived
tables plan as TWO stacked projections over the join tree, which is what
used to strand their aggregation on the host). Every answer is held to two
references that share no code: the sqlite oracle of tests/tpch_oracle.py
and the numpy reference of benchmarks/oracles/<q>.py, so the two check each
other. The host interpreter is disabled throughout: a gate that trips fails
the test instead of degrading.
"""

from __future__ import annotations

import decimal
import importlib
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tidb_tpu.copr.fragment as F  # noqa: E402
import tidb_tpu.copr.rowpack as RP  # noqa: E402
from benchmarks.datagen import tpch  # noqa: E402
from tidb_tpu import obs  # noqa: E402
from tidb_tpu.bench.tpch_data import TPCH_DDL, load_table  # noqa: E402
from tidb_tpu.bench.tpch_queries import TPCH_QUERIES  # noqa: E402
from tidb_tpu.session import Session  # noqa: E402

from tpch_oracle import load_sqlite, rows_equal, to_sqlite_sql  # noqa: E402

SF = 0.02
SEED = 35
QUERIES = ("q3", "q5", "q7", "q8", "q10", "q12", "q14")
# the tag each statement's one fragment read carries when the device
# aggregates it (dense segments, every group by sorted runs, final cut)
DEVICE_AGG_TAGS = ("device[agg]", "device[group]", "device[fat]")


def _load(session: Session, data: dict) -> None:
    session.execute("create database joins")
    session.execute("use joins")
    for name in data:
        load_table(session, name, data[name])
    for name in data:
        session.execute(f"analyze table {name}")


@pytest.fixture(scope="module")
def joins():
    data = tpch.generate_tpch(SF, SEED)
    session = Session()
    _load(session, data)
    conn = load_sqlite(data, TPCH_DDL)
    yield session, conn, data
    conn.close()


@pytest.fixture
def device_only(monkeypatch):
    def refuse(frag, snaps):
        raise AssertionError("host fragment interpreter taken")
    monkeypatch.setattr(F, "_host_fragment", refuse)


def _wire(rows) -> list[list]:
    """Session rows as the MySQL text protocol hands them to an oracle."""
    return [[None if c is None else str(c) for c in r] for r in rows]


def _fetched() -> float:
    return obs.FRAG_FETCHED_ROWS.get()


@pytest.mark.parametrize("qname", QUERIES)
def test_query_is_exact_from_device_programs_alone(joins, device_only, qname):
    session, conn, data = joins
    sql = TPCH_QUERIES[qname]
    reads0 = {m: obs.FRAG_READS.get(mode=m)
              for m in ("agg", "group", "fat", "hc", "rows", "topn")}
    got = session.query(sql)
    engines = list(session.last_engines)
    # (b) device programs alone, the aggregation inside the fragment
    assert engines and all(e in DEVICE_AGG_TAGS for e in engines), engines
    reads = {m: obs.FRAG_READS.get(mode=m) - v for m, v in reads0.items()}
    assert reads["rows"] == 0 and reads["topn"] == 0, reads
    assert sum(reads.values()) == len(engines), (reads, engines)
    # (a) both references
    want = [tuple(r) for r in conn.execute(to_sqlite_sql(sql)).fetchall()]
    ok, msg = rows_equal(got, want, ordered=False)
    assert ok, f"{qname} against sqlite: {msg}"
    assert want, f"{qname}: the data selects nothing"
    mod = importlib.import_module(f"benchmarks.oracles.{qname}")
    why = mod.compare(_wire(got), mod.reference({"joins": data}))
    assert why is None, why


@pytest.mark.parametrize("qname", ("q7", "q8"))
def test_derived_table_fetches_groups_not_joined_rows(joins, device_only,
                                                      qname):
    """(c) what Q7's 181 GB plan was made of: the read brings back its
    groups (four and two here), never the joined rows."""
    session, _, _ = joins
    before = _fetched()
    got = session.query(TPCH_QUERIES[qname])
    assert _fetched() - before == len(got)
    assert 0 < len(got) <= 8


def _frag_note(session, sql: str) -> str:
    with obs.SpanCollector() as sc:
        session.query(sql)
    notes = [label.strip() for label, _, _ in sc.rows()
             if label.strip().startswith("copr.fragment ")]
    assert len(notes) == 1, notes
    return notes[0]


def test_fragment_span_names_mode_and_build_rows(joins, device_only):
    session, _, data = joins
    note = _frag_note(session, TPCH_QUERIES["q12"])
    assert "mode agg" in note
    assert f"{len(data['orders']['o_orderkey'])} build rows" in note


# ---- what the change makes reachable --------------------------------------

TWO_ALIASES = """
select sn, cn, count(*) as c, sum(volume) as v
from (select n1.n_name as sn, n2.n_name as cn,
             l_extendedprice * (1 - l_discount) as volume
      from supplier, lineitem, orders, customer, nation n1, nation n2
      where s_suppkey = l_suppkey and o_orderkey = l_orderkey
        and c_custkey = o_custkey
        and s_nationkey = n1.n_nationkey and c_nationkey = n2.n_nationkey
        and n1.n_regionkey = 3 and n2.n_regionkey = 1
        and n2.n_name <> 'BRAZIL') as t
group by sn, cn order by sn, cn
"""

CROSS_OR = """
select supp_nation, cust_nation, l_year, sum(volume) as revenue
from (select n1.n_name as supp_nation, n2.n_name as cust_nation,
             extract(year from l_shipdate) as l_year,
             l_extendedprice * (1 - l_discount) as volume
      from supplier, lineitem, orders, customer, nation n1, nation n2
      where s_suppkey = l_suppkey and o_orderkey = l_orderkey
        and c_custkey = o_custkey
        and s_nationkey = n1.n_nationkey and c_nationkey = n2.n_nationkey
        and ({cond})
        and l_shipdate between date '1995-01-01' and date '1996-12-31'
     ) as shipping
group by supp_nation, cust_nation, l_year
order by supp_nation, cust_nation, l_year
"""
CROSS_OR_CASES = {
    # no such pair of nations: both disjuncts false on every joined row
    "nothing": "(n1.n_name = 'FRANCE' and n2.n_name = 'ATLANTIS') "
               "or (n1.n_name = 'ATLANTIS' and n2.n_name = 'FRANCE')",
    # one disjunct or the other holds on every joined row
    "everything": "(n1.n_nationkey >= n2.n_nationkey and n2.n_regionkey >= 0)"
                  " or (n1.n_nationkey < n2.n_nationkey and "
                  "n1.n_regionkey >= 0)",
    "one_pair": "(n1.n_name = 'JAPAN' and n2.n_name = 'KENYA') "
                "or (n1.n_name = 'KENYA' and n2.n_name = 'JAPAN')",
}


def _same_as_sqlite(session, conn, sql, expect_rows=True):
    got = session.query(sql)
    want = [tuple(r) for r in conn.execute(to_sqlite_sql(sql)).fetchall()]
    ok, msg = rows_equal(got, want, ordered=False)
    assert ok, msg
    assert bool(want) == expect_rows, len(want)
    return got


def test_one_build_table_under_two_aliases(joins, device_only):
    """NATION joined twice, each alias with filters of its own."""
    session, conn, _ = joins
    got = _same_as_sqlite(session, conn, TWO_ALIASES)
    assert all(e in DEVICE_AGG_TAGS for e in session.last_engines)
    assert {r[1] for r in got} == {"ARGENTINA", "CANADA", "PERU",
                                   "UNITED STATES"}


@pytest.mark.parametrize("case", sorted(CROSS_OR_CASES))
def test_cross_build_or(joins, device_only, case):
    """An OR across two builds is a selection on the gathered columns."""
    session, conn, _ = joins
    sql = CROSS_OR.format(cond=CROSS_OR_CASES[case])
    got = _same_as_sqlite(session, conn, sql, expect_rows=case != "nothing")
    assert all(e in DEVICE_AGG_TAGS for e in session.last_engines)
    if case == "everything":
        assert len(got) == 25 * 25 * 2


@pytest.fixture
def shipments():
    """A fact table over two small builds: fact rows whose keys dangle,
    a build row whose grouped column is NULL, dates on both sides of a
    year boundary."""
    s = Session()
    s.execute("create table port (pk int not null primary key, "
              "pname varchar(12), zone int)")
    s.execute("create table carrier (ck int not null primary key, "
              "home int, cname varchar(12))")
    s.execute("create table shipment (sid int not null primary key, "
              "carrier int, shipped date, amount decimal(10,2), disc "
              "decimal(4,2))")
    s.execute("insert into port values (1,'kiel',1),(2,'brest',1),"
              "(3,NULL,2),(4,'cadiz',NULL)")
    s.execute("insert into carrier values (10,1,'a'),(11,2,'b'),(12,3,'c'),"
              "(13,4,'d'),(14,9,'dangles')")
    rng = np.random.default_rng(35)
    days = ("1995-12-30", "1995-12-31", "1996-01-01", "1996-01-02",
            "1996-12-31", "1997-01-01")
    rows = []
    for i in range(600):
        c = int(rng.choice([10, 11, 12, 13, 14, 77]))  # 77: no carrier
        car = "NULL" if i % 97 == 0 else str(c)
        rows.append(f"({i},{car},'{days[i % len(days)]}',"
                    f"{(i % 40) + 0.75},{(i % 11) / 100})")
    s.execute("insert into shipment values " + ",".join(rows))
    safe = s.storage.safe_ts()
    for store in s.storage.tables.values():
        store.compact(safe)
    for t in ("port", "carrier", "shipment"):
        s.execute(f"analyze table {t}")
    return s


BY_PORT_AND_YEAR = """
select pname, y, sum(net) as total, count(*) as n
from (select p.pname as pname, extract(year from shipped) as y,
             amount * (1 - disc) as net
      from shipment, carrier, port p
      where shipment.carrier = carrier.ck and carrier.home = p.pk) as t
group by pname, y order by pname, y
"""


def _by_hand(session) -> list[tuple]:
    """BY_PORT_AND_YEAR from three single-table reads, joined and summed
    here in Python decimals: shares nothing with the fragment path."""
    D = decimal.Decimal
    ports = {r[0]: r[1] for r in session.query("select pk, pname from port")}
    homes = {r[0]: r[1] for r in session.query("select ck, home from carrier")}
    acc: dict = {}
    for car, day, amount, disc in session.query(
            "select carrier, shipped, amount, disc from shipment"):
        if car not in homes or homes[car] not in ports:
            continue
        k = (ports[homes[car]], day.year)
        total, n = acc.get(k, (D(0), 0))
        acc[k] = (total + D(str(amount)) * (1 - D(str(disc))), n + 1)
    return sorted(((k[0], k[1], v[0], v[1]) for k, v in acc.items()),
                  key=lambda r: (r[0] is not None, r[0] or "", r[1]))


def _plain(rows) -> list[tuple]:
    return [(r[0], r[1], decimal.Decimal(str(r[2])), r[3]) for r in rows]


def test_null_group_key_and_dangling_probe_keys(shipments, device_only):
    """Rows whose key finds no build row (or is NULL) leave the join; a
    NULL in a gathered group column is a group of its own."""
    got = shipments.query(BY_PORT_AND_YEAR)
    assert all(e in DEVICE_AGG_TAGS for e in shipments.last_engines), \
        shipments.last_engines
    assert _plain(got) == _by_hand(shipments)
    assert {r[0] for r in got} == {None, "kiel", "brest", "cadiz"}
    joined = shipments.query(
        "select count(*) from shipment where carrier in (10,11,12,13)")
    assert sum(r[3] for r in got) == joined[0][0]


def test_computed_group_key_at_a_year_boundary(shipments, device_only):
    got = shipments.query(BY_PORT_AND_YEAR)
    years = {r[1] for r in got}
    assert years == {1995, 1996, 1997}
    per_day = shipments.query("""
        select shipped, count(*) from shipment, carrier, port
        where shipment.carrier = carrier.ck and carrier.home = port.pk
        group by shipped""")
    by_year: dict = {}
    for day, n in per_day:
        by_year[day.year] = by_year.get(day.year, 0) + n
    assert {y: sum(r[3] for r in got if r[1] == y) for y in years} == by_year


def test_overlay_row_on_a_build_table_takes_the_typed_fallback(shipments):
    """An uncommitted row on a build table is the `build-overlay` gate:
    the host interpreter answers, counted and tagged, and stays exact."""
    before = obs.FRAG_FALLBACKS.get(reason="build-overlay")
    shipments.execute("begin")
    shipments.execute("insert into port values (9,'oslo',3)")
    got = shipments.query(BY_PORT_AND_YEAR)
    engines = list(shipments.last_engines)
    want = _by_hand(shipments)
    shipments.execute("rollback")
    assert engines == ["host(fragment:build-overlay)"], engines
    assert obs.FRAG_FALLBACKS.get(reason="build-overlay") == before + 1
    assert _plain(got) == want
    assert "oslo" in {r[0] for r in got}      # carrier 14's home


# ---- predicates size the dense key space (copr/bounds.implied_domains) ----

def _exprs():
    from tidb_tpu.chunk.column import Dictionary
    from tidb_tpu.plan.expr import Call, Col, Const
    from tidb_tpu.types.field_type import (
        FieldType, TypeKind, bigint_type, date_type, varchar_type)
    boolean = FieldType(TypeKind.BOOLEAN)
    names = Dictionary(["ALGERIA", "FRANCE", "GERMANY", "PERU"])
    s, d, n = varchar_type(25), date_type(), bigint_type()

    def call(op, *args, extra=None):
        return Call(op, list(args), boolean, extra)

    def eq(i, text):
        return call("eq", Col(i, s), Const(text, s))
    return names, call, eq, Col, Const, s, d, n


def _domains(conds, base=0, width=4):
    from tidb_tpu.copr.bounds import implied_domains
    names = _exprs()[0]
    dicts = [names, names, None, None][:width]
    bounds = [(0, 3), (0, 3), (8000, 10500), (-50, 50)][:width]
    return implied_domains([(conds, base)], dicts, bounds)


def test_an_or_of_pairs_pins_both_string_keys():
    _, call, eq, *_ = _exprs()
    q7 = call("or", call("and", eq(0, "FRANCE"), eq(1, "GERMANY")),
              call("and", eq(0, "GERMANY"), eq(1, "FRANCE")))
    sets, bounds = _domains([q7])
    assert sets == {0: (1, 2), 1: (1, 2)}
    assert bounds == [(0, 3), (0, 3), (8000, 10500), (-50, 50)]


@pytest.mark.parametrize("case", ["or_one_side_free", "and_narrows",
                                  "in_values", "absent_value", "ne_is_free",
                                  "flipped_const"])
def test_what_a_predicate_pins_of_a_string_key(case):
    _, call, eq, Col, Const, s, _, _ = _exprs()
    conds, want = {
        # a disjunct that says nothing of column 1 leaves it free
        "or_one_side_free": (
            [call("or", call("and", eq(0, "FRANCE"), eq(1, "PERU")),
                  eq(0, "PERU"))], {0: (1, 3)}),
        "and_narrows": (
            [call("in_values", Col(0, s), extra=["FRANCE", "PERU"]),
             eq(0, "PERU")], {0: (3,)}),
        "in_values": (
            [call("in_values", Col(1, s), extra=["PERU", "ALGERIA"])],
            {1: (0, 3)}),
        # a value no row holds has no code: the key can take none
        "absent_value": ([eq(0, "ATLANTIS")], {0: ()}),
        "ne_is_free": ([call("ne", Col(0, s), Const("PERU", s))], {}),
        "flipped_const": ([call("eq", Const("PERU", s), Col(1, s))],
                          {1: (3,)}),
    }[case]
    assert _domains(conds)[0] == want


@pytest.mark.parametrize("case", ["between", "strict", "eq", "other_type",
                                  "under_or", "table_base"])
def test_what_a_predicate_pins_of_an_integer_key(case):
    _, call, _, Col, Const, _, d, n = _exprs()
    free = [(0, 3), (0, 3), (8000, 10500), (-50, 50)]
    conds, base, want = {
        "between": ([call("ge", Col(2, d), Const(9131, d)),
                     call("le", Col(2, d), Const(9861, d))], 0,
                    {2: (9131, 9861)}),
        "strict": ([call("gt", Col(3, n), Const(-3, n)),
                    call("lt", Const(7, n), Col(3, n))], 0, {3: (8, 50)}),
        "eq": ([call("eq", Col(3, n), Const(4, n))], 0, {3: (4, 4)}),
        # a constant of another type compares after a cast: left alone
        "other_type": ([call("ge", Col(2, d), Const(9131, n))], 0, {}),
        # only top-level conjuncts tighten a range
        "under_or": ([call("or", call("ge", Col(3, n), Const(0, n)),
                           call("le", Col(3, n), Const(-9, n)))], 0, {}),
        # a table's own filters sit at its base in the combined space
        "table_base": ([call("le", Col(0, d), Const(9000, d))], 2,
                       {2: (8000, 9000)}),
    }[case]
    got = _domains(conds, base)[1]
    assert got == [want.get(i, b) for i, b in enumerate(free)]


def test_q7_fills_27_slots_not_6084(joins, device_only, monkeypatch):
    """Two nations a side and two years, a NULL slot each: the dense
    space is 3 x 3 x 3, summed by the loop strategy as Q5's 26 slots are,
    where the dictionary's and the epoch's ranges would ask for a one-hot
    of 26 x 26 x 9 columns a row."""
    session, _, _ = joins
    seen = {}
    inner = F._run_frag_batch

    def spy(cop, frag, snaps, prepared, *a, **kw):
        seen.update(cards=prepared["__dense_cards__"],
                    remaps=prepared["__key_remaps__"],
                    strategy=prepared["__strategy__"])
        return inner(cop, frag, snaps, prepared, *a, **kw)
    monkeypatch.setattr(F, "_run_frag_batch", spy)
    got = session.query(TPCH_QUERIES["q7"])
    assert session.last_engines == ["device[agg]"]
    assert seen["cards"] == [3, 3, 3] and seen["strategy"] == "loop"
    assert [r is not None and len(r) for r in seen["remaps"]] == [2, 2, False]
    assert len(got) == 4


PINNED_GROUPS = {
    # the group key under IN, with a NULL-keyed row outside the list
    "in_list": "select pname, count(*), sum(amount) from shipment, carrier, "
               "port where shipment.carrier = carrier.ck and carrier.home = "
               "port.pk and pname in ('kiel', 'cadiz', 'nowhere') "
               "group by pname order by pname",
    # a value the dictionary lacks: no group at all
    "absent": "select pname, count(*) from shipment, carrier, port where "
              "shipment.carrier = carrier.ck and carrier.home = port.pk and "
              "pname = 'nowhere' group by pname",
    # the single-table pushdown takes the same space
    "one_table": "select cname, count(*) from carrier where cname in "
                 "('a', 'c', 'dangles') and home < 9 group by cname "
                 "order by cname",
    # a year under a date range, on both sides of the boundary
    "year_range": "select extract(year from shipped) as y, count(*), "
                  "sum(amount) from shipment, carrier where "
                  "shipment.carrier = carrier.ck and shipped between "
                  "'1995-12-31' and '1996-01-01' group by y order by y",
}


@pytest.mark.parametrize("case", sorted(PINNED_GROUPS))
def test_pinned_group_keys_decode_to_their_values(shipments, device_only,
                                                  case):
    import tidb_tpu.plan.fragment as PF
    sql = PINNED_GROUPS[case]
    got = shipments.query(sql)
    assert all(e.startswith("device") for e in shipments.last_engines), \
        shipments.last_engines
    # the same statement through the host's join and aggregation
    with pytest.MonkeyPatch.context() as m:
        m.setattr(PF, "apply_fragments", lambda p: p)
        want = shipments.query(sql + " ")
    assert got == want
    assert (len(got) == 0) == (case == "absent")
    if case == "one_table":   # both sides took the device's dense space
        assert got == [("a", 1), ("c", 1)]


# ---- the sorted-run body packs the passing rows first ----------------------

@pytest.fixture
def small_epochs_compact(monkeypatch):
    """The packing is for epochs of 4 M rows and more; here, any."""
    monkeypatch.setattr(F, "HC_COMPACT_MIN_ROWS", 1)
    calls = []
    inner = F._compact_rows

    def spy(cols, mask, cap, read, nullable):
        calls.append((mask.shape[0], cap, len(read)))
        return inner(cols, mask, cap, read, nullable)
    monkeypatch.setattr(F, "_compact_rows", spy)
    return calls


def _packed(path: str) -> float:
    return obs.HC_PACK.get(path=path)


def _q10_is_right(session, conn, data):
    got = session.query(TPCH_QUERIES["q10"])
    assert session.last_engines == ["device[fat]"]
    want = [tuple(r) for r in conn.execute(
        to_sqlite_sql(TPCH_QUERIES["q10"])).fetchall()]
    ok, msg = rows_equal(got, want, ordered=False)
    assert ok, msg
    mod = importlib.import_module("benchmarks.oracles.q10")
    assert mod.compare(_wire(got), mod.reference({"joins": data})) is None


def test_q10_sorts_a_packed_buffer(joins, device_only, small_epochs_compact):
    """Under 1% of LINEITEM passes Q10's predicates: the body sorts and
    gathers a buffer a 32nd of the epoch, and the answer is the same."""
    session, conn, data = joins
    before = _packed("packed")
    _q10_is_right(session, conn, data)
    assert _packed("packed") == before + 1
    (n, cap, read), = small_epochs_compact      # traced once
    assert cap == n // F.HC_COMPACT_DIV
    # c_custkey and the revenue's two columns are packed; the six keys
    # c_custkey determines are read at the candidates alone
    assert read == 3
    passing = session.query(
        "select count(*) from lineitem, orders where l_orderkey = o_orderkey"
        " and l_returnflag = 'R' and o_orderdate >= date '1993-10-01' and "
        "o_orderdate < date '1994-01-01'")[0][0]
    assert 0 < passing <= cap


@pytest.mark.parametrize("qname", ("q2", "q11", "q17", "q18", "q20"))
def test_packed_sorted_runs_of_the_other_queries(joins, small_epochs_compact,
                                                 qname):
    """Every other TPC-H statement whose GROUP BY takes the sorted-run
    body (all groups, HAVING or top-k candidates), packed where storage
    order does not group its keys: same answers. Most overflow the 32nd
    (no selective predicate) and run whole."""
    session, conn, _ = joins
    sql = TPCH_QUERIES[qname]
    got = session.query(sql)
    # Q18's IN over GROUP BY ... HAVING is a run-statistics gate of the
    # one read that groups its orders by their LINEITEM runs
    if qname == "q18":
        assert any(e.startswith(("device[group", "device[hc", "device[fat"))
                   and e.endswith("+runstat]")
                   for e in session.last_engines), session.last_engines
    else:
        assert any(e in ("device[group]", "device[hc]")
                   for e in session.last_engines), session.last_engines
    want = [tuple(r) for r in conn.execute(to_sqlite_sql(sql)).fetchall()]
    ok, msg = rows_equal(got, want, ordered=qname == "q2")
    assert ok, f"{qname}: {msg}"


def test_rows_that_overflow_the_buffer_run_whole(joins, device_only,
                                                 small_epochs_compact,
                                                 monkeypatch):
    """A buffer too short for the passing rows: the statement is answered
    by the whole-epoch program, exactly, and is not packed again."""
    session, conn, data = joins
    monkeypatch.setattr(F, "HC_COMPACT_DIV", 4096)   # a 64-row buffer
    dense = session.cop._hc_dense
    before = set(dense)
    counts = {p: _packed(p) for p in ("packed", "spilled", "whole")}
    _q10_is_right(session, conn, data)
    assert len(small_epochs_compact) == 1 and len(dense - before) == 1
    _q10_is_right(session, conn, data)
    assert len(small_epochs_compact) == 1    # remembered: no second try
    assert {p: _packed(p) - v for p, v in counts.items()} == {
        "packed": 0, "spilled": 1, "whole": 1}
    with session.cop._lock:
        dense.difference_update(dense - before)


def test_packed_column_with_nulls_keeps_its_validity(device_only,
                                                     small_epochs_compact):
    """A column the packed body reads holds NULLs, another holds none:
    COUNT and SUM over each agree with the host's join and aggregation."""
    import tidb_tpu.plan.fragment as PF
    s = Session()
    s.execute("create table dim (k int not null primary key, w int)")
    s.execute("create table fact (id int not null primary key, d int, "
              "g int, v int, u int)")
    s.execute("insert into dim values " + ",".join(
        f"({k},{int(k == 7)})" for k in range(40)))   # 1 row in 60 passes
    n = 20_000
    rng = np.random.default_rng(37)
    v = rng.integers(-50, 50, n)
    store = s.storage.table_store(s.catalog.table("test", "fact").id)
    store.bulk_load([np.arange(n, dtype=np.int64),
                     rng.integers(0, 60, n), rng.integers(0, 15_000, n),
                     v, rng.integers(0, 9, n)],
                    [None, None, None, rng.random(n) < 0.7, None])
    s.storage.table_store(s.catalog.table("test", "dim").id).compact(
        s.storage.safe_ts())
    for t in ("dim", "fact"):
        s.execute(f"analyze table {t}")
    sql = ("select g, count(v), sum(v), count(u), sum(u) from fact, dim "
           "where fact.d = dim.k and dim.w = 1 group by g "
           "order by sum(v) desc, g limit 5")
    before = _packed("packed")
    got = s.query(sql)
    assert s.last_engines == ["device[fat]"], s.last_engines
    assert _packed("packed") == before + 1 and len(small_epochs_compact) == 1
    with pytest.MonkeyPatch.context() as m:
        m.setattr(PF, "apply_fragments", lambda p: p)
        want = s.query(sql + " ")
    assert got == want


def test_pack_counter_is_rendered_at_zero_from_the_first_client():
    import subprocess
    code = ("from tidb_tpu import obs\n"
            "from tidb_tpu.copr.client import CopClient\n"
            "assert 'hc_pack_total{' not in obs.PROCESS_METRICS.render()\n"
            "CopClient()\n"
            "print(obs.PROCESS_METRICS.render())\n")
    out = subprocess.run([sys.executable, "-c", code], text=True,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, timeout=120, check=True,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    for path in ("packed", "spilled", "whole"):
        assert f'tidb_copr_hc_pack_total{{path="{path}"}} 0' in out.stdout
    assert obs.lint_metrics([obs.PROCESS_METRICS]) == []


def _q10_passing(data) -> np.ndarray:
    """LINEITEM's rows that pass Q10's predicates, in storage order."""
    from benchmarks.datagen.tpch import parse_date
    from benchmarks.oracles.q7 import lookup
    o, li = data["orders"], data["lineitem"]
    ok = (o["o_orderdate"] >= parse_date("1993-10-01")) & \
        (o["o_orderdate"] < parse_date("1994-01-01"))
    in_quarter = lookup(o["o_orderkey"], np.where(ok, 1, -1))
    flags, codes = li["l_returnflag"]
    return (np.asarray(codes) == list(flags).index("R")) & \
        (in_quarter[li["l_orderkey"]] > 0)


def test_rows_clustered_in_one_tile_still_pack(joins, device_only,
                                               small_epochs_compact,
                                               monkeypatch):
    """Tiles of 128 rows: a tile holds more passing rows than its even
    share of the buffer, and the statement still packs them all (the
    overflow rule is the buffer's, not a tile's)."""
    from tidb_tpu.copr.placement import _bucket
    session, conn, data = joins
    monkeypatch.setattr(RP, "TILE", 128)
    passing = _q10_passing(data)
    n = _bucket(len(passing))
    cap = n // F.HC_COMPACT_DIV
    per_tile = np.bincount(np.flatnonzero(passing) // 128)
    assert per_tile.max() > cap // (n // 128) and passing.sum() <= cap
    dense = set(session.cop._hc_dense)
    before = _packed("packed")
    monkeypatch.setattr(session.cop, "_kernels", {})   # traced anew
    _q10_is_right(session, conn, data)
    assert len(small_epochs_compact) == 1
    assert session.cop._hc_dense == dense
    assert _packed("packed") == before + 1


# ---- the packing itself, against numpy ------------------------------------

def _mask_case(case: str, n: int, tile: int, cap: int) -> np.ndarray:
    rng = np.random.default_rng(37)
    m = np.zeros(n, bool)
    if case == "one_full_tile":
        m[3 * tile:4 * tile] = True
    elif case == "all_in_last_tile":
        last = (n - 1) // tile * tile
        m[rng.choice(np.arange(last, n), min(cap, n - last) // 2,
                     replace=False)] = True
    elif case in ("total_is_cap", "total_is_cap_plus_one"):
        extra = case.endswith("plus_one")
        m[rng.choice(n, cap + extra, replace=False)] = True
    elif case in ("density_2pct", "ragged_last_tile"):
        m = rng.random(n) < 0.02
    return m


PACK_CASES = {   # case -> rows of the epoch (tiles of 256 rows)
    "none_pass": 8192,
    "one_full_tile": 8192,
    "all_in_last_tile": 8192,
    "total_is_cap": 8192,
    "total_is_cap_plus_one": 8192,
    "ragged_last_tile": 8192 + 300,
    "density_2pct": 16384,
}


@pytest.mark.parametrize("case", sorted(PACK_CASES))
def test_compact_rows_packs_what_numpy_packs(case, monkeypatch):
    """`_compact_rows` against `np.flatnonzero(mask)[:cap]`: the epoch row
    of every slot, the buffer's mask, the overflow flag and each packed
    column; a column that holds NULLs keeps its own validity, a NULL-free
    one is valid exactly where a slot holds a row."""
    import jax.numpy as jnp
    tile = 256
    monkeypatch.setattr(RP, "TILE", tile)
    n = PACK_CASES[case]
    cap = 512
    mask = _mask_case(case, n, tile, cap)
    rng = np.random.default_rng(7)
    data = [rng.integers(-2**31, 2**31, n, dtype=np.int64).astype(np.int32),
            rng.random(n).astype(np.float32),
            rng.integers(0, 9, n, dtype=np.int32)]
    valid0 = rng.random(n) < 0.8          # column 0 holds NULLs
    cols = [(jnp.asarray(data[0]), jnp.asarray(valid0)),
            (jnp.asarray(data[1]), jnp.ones(n, bool)),
            (jnp.asarray(data[2]), jnp.ones(n, bool))]
    packed, keep, over, src = F._compact_rows(
        cols, jnp.asarray(mask), cap, {0, 1}, (0,))
    want = np.flatnonzero(mask)[:cap]
    k, total = len(want), int(mask.sum())
    src, keep = np.asarray(src), np.asarray(keep)
    assert bool(over) == (total > cap)
    np.testing.assert_array_equal(keep, np.arange(cap) < total)
    np.testing.assert_array_equal(src[:k], want)
    assert src.min() >= 0 and src.max() < n    # every slot can be read at
    (d0, v0), (d1, v1), stand_in = [
        (np.asarray(d), np.asarray(v)) for d, v in packed]
    np.testing.assert_array_equal(d0[:k], data[0][want])
    np.testing.assert_array_equal(v0[:k], valid0[want])
    np.testing.assert_array_equal(d1[:k], data[1][want])
    np.testing.assert_array_equal(v1, keep)
    np.testing.assert_array_equal(stand_in[0], keep)   # column 2 not read


# ---- the control of `correct` ----------------------------------------------
# benchmarks/tools/control.py builds LINEITEM alone, so it cannot take a
# cell over the join set; the same control, per oracle, at SF1 on numpy:
# the sum accumulated in float32 has to come out as NOT correct.

@pytest.fixture(scope="module")
def sf1():
    return {"joins": tpch.generate_tpch(1.0, SEED)}


@pytest.mark.parametrize("qname", ("q7", "q8", "q10", "q14"))
def test_float32_sums_disagree(sf1, qname):
    """Every sum of the class differs in float32, and the exact rendering
    agrees; where the answer shows the sums to the digit (all but Q8's
    share, whose eight decimals of 0.03 are under float32's seven digits),
    the float32 answer comes out as not correct."""
    mod = importlib.import_module(f"benchmarks.oracles.{qname}")
    ref = mod.reference(sf1)
    exact, low = mod.sums(sf1["joins"]), mod.sums(sf1["joins"], np.float32)
    assert set(exact) == set(low)
    assert all(np.any(np.asarray(exact[k]) != np.asarray(low[k]))
               for k in exact), (exact, low)
    assert mod.compare(mod.render(sf1, exact), ref) is None
    if qname != "q8":
        assert mod.compare(mod.render(sf1, low), ref) is not None


def test_q12_counts_need_no_control(sf1):
    """Q12 sums ones: float32 holds every count under 2**24 exactly, so
    no lower precision of the sum exists to fail (the class's answers are
    still compared as integers)."""
    ref = importlib.import_module("benchmarks.oracles.q12").reference(sf1)
    assert ref and all(0 < c < 1 << 24 for pair in ref.values()
                       for c in pair)
