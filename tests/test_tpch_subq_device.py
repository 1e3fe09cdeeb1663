"""TPC-H Q18 and Q21 served by ONE device fragment read each, from per-run
statistics of LINEITEM (stored clustered by l_orderkey): the IN over
GROUP BY l_orderkey HAVING sum(l_quantity) > 300, and the EXISTS / NOT
EXISTS on l_orderkey with a residual on l_suppkey, are each a statistic of
the probe row's own run (plan/fragment.py FragRunGate, copr/runstat.py).

Every answer is held to the plain numpy reference of tests/tpch_subq_ref.py
(whose copies are benchmarks/oracles/q18.py and q21.py). The data is the
benchmark's join set at a small scale with crafted orders: an order whose
quantities sum to exactly 300 (excluded) and one at 300.01, orders tied on
the ORDER BY, and for Q21 an order with one supplier on every line, one
with a supplier repeated on several lines and one with two late
suppliers."""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tidb_tpu.copr.fragment as F  # noqa: E402
from benchmarks.datagen import tpch  # noqa: E402
from benchmarks.oracles import q18 as bench_q18, q21 as bench_q21  # noqa: E402
from tidb_tpu import obs  # noqa: E402
from tidb_tpu.analysis.registry import DEVICE_FRAGMENT_MODES  # noqa: E402
from tidb_tpu.bench.tpch_data import load_table  # noqa: E402
from tidb_tpu.bench.tpch_queries import TPCH_QUERIES  # noqa: E402
from tidb_tpu.session import Session  # noqa: E402

import tpch_subq_ref as REF  # noqa: E402

SF = 0.05
SEED = 42
TILE = 5000   # the tiled test's rows a tile (a 6 144-row bucket)
Q18, Q21 = TPCH_QUERIES["q18"], TPCH_QUERIES["q21"]


def _starts(keys) -> np.ndarray:
    return np.flatnonzero(np.concatenate(([True], keys[1:] != keys[:-1])))


def _craft(data) -> dict:
    """Plant the boundary, tie and supplier cases; returns what was
    planted."""
    li, o, s, n = (data[t] for t in ("lineitem", "orders", "supplier",
                                     "nation"))
    ok = li["l_orderkey"]
    st = _starts(ok)
    lines = np.diff(np.append(st, len(ok)))
    qty = li["l_quantity"]
    sums = np.add.reduceat(qty.astype(np.int64), st)
    orow = REF._row_of(o["o_orderkey"])
    planted = {}
    # Q18: 300.00 exactly (6 lines of 50) and 300.01 (6 of 50, one of 0.01)
    six = [i for i in np.flatnonzero(lines == 6) if sums[i] < 30000][:1]
    seven = [i for i in np.flatnonzero(lines == 7) if sums[i] < 30000][:1]
    qty[st[six[0]]:st[six[0]] + 6] = 5000
    qty[st[seven[0]]:st[seven[0]] + 7] = [5000] * 6 + [1]
    planted["at_300"] = int(ok[st[six[0]]])
    planted["above_300"] = int(ok[st[seven[0]]])
    # ties on the ORDER BY: three passing orders at the top price, two of
    # them on one date
    passing = np.flatnonzero(np.add.reduceat(qty.astype(np.int64), st)
                             > 30000)[:3]
    tied = [int(ok[st[i]]) for i in passing]
    top = int(o["o_totalprice"].max()) + 1
    for k in tied:
        o["o_totalprice"][orow[k]] = top
    o["o_orderdate"][orow[tied[2]]] = o["o_orderdate"][orow[tied[0]]]
    o["o_orderdate"][orow[tied[1]]] = o["o_orderdate"][orow[tied[0]]] + 1
    planted["tied"] = tied
    # Q21: F orders of three lines, suppliers of SAUDI ARABIA
    vocab, codes = o["o_orderstatus"]
    f_keys = set(o["o_orderkey"][np.asarray(codes) == vocab.index("F")])
    three = [i for i in np.flatnonzero(lines == 3) if int(ok[st[i]]) in f_keys]
    nvocab, ncodes = n["n_name"]
    saudi = n["n_nationkey"][list(ncodes).index(nvocab.index("SAUDI ARABIA"))]
    supp = s["s_suppkey"][s["s_nationkey"] == saudi]
    sk, commit, receipt = li["l_suppkey"], li["l_commitdate"], \
        li["l_receiptdate"]

    def plant(i, suppliers, late):
        a = st[i]
        sk[a:a + 3] = suppliers
        receipt[a:a + 3] = commit[a:a + 3] + np.where(late, 5, -1)
        return int(ok[a])

    planted["one_supplier"] = plant(three[0], [supp[0]] * 3,
                                    [True, True, True])
    planted["repeated"] = plant(three[1], [supp[1], supp[1], supp[2]],
                                [True, True, False])
    planted["two_late"] = plant(three[2], [supp[3], supp[4], supp[5]],
                                [True, True, False])
    planted["supp"] = [int(x) for x in supp[:6]]
    # orders that straddle the tile edges of the tiled test: Q21's
    # repeated supplier with its other supplier past the edge, and an
    # order above 300 whose two parts are each at or below it
    edges = np.arange(TILE, len(ok), TILE)
    runs = np.searchsorted(st, edges, side="right") - 1
    run_of = runs[st[runs] < edges][:2]   # runs the edge cuts in two
    taken = {k for v in planted.values()
             for k in (v if isinstance(v, list) else [v])}
    assert not {int(ok[st[i]]) for i in run_of} & taken
    a, m = st[run_of[0]], lines[run_of[0]]
    sk[a:a + m] = [supp[1]] * (m - 1) + [supp[2]]
    receipt[a:a + m] = commit[a:a + m] + np.where(np.arange(m) < m - 1, 5, -1)
    codes[orow[int(ok[a])]] = vocab.index("F")
    planted["edge_repeated"] = int(ok[a])
    a, m = st[run_of[1]], lines[run_of[1]]
    qty[a:a + m] = -(-30100 // m)
    planted["edge_above_300"] = int(ok[a])
    return planted


def _load(session: Session, data: dict) -> None:
    session.execute("create database joins")
    session.execute("use joins")
    for name in data:
        load_table(session, name, data[name])
    for name in data:
        session.execute(f"analyze table {name}")


@pytest.fixture(scope="module")
def subq():
    data = tpch.generate_tpch(SF, SEED)
    planted = _craft(data)
    session = Session()
    _load(session, data)
    return session, data, planted


@pytest.fixture
def no_split(monkeypatch):
    """A gated read that the device refuses fails the test."""
    inner = F._device_fragment

    def device(cop, frag, snaps):
        try:
            return inner(cop, frag, snaps)
        except F._Fallback as e:
            raise AssertionError(f"runstat refused: {e.reason}") from e
    monkeypatch.setattr(F, "_device_fragment", device)


def _q18_rows(rows) -> list[tuple]:
    from benchmarks.oracles import unscaled
    return [(r[0], int(r[1]), int(r[2]), str(r[3]), unscaled(str(r[4]), 2),
             unscaled(str(r[5]), 2)) for r in rows]


def _same_q18(got: list[tuple], ref: list[tuple], limit: int):
    """None where `got` is, in order, the reference's sort keys with one
    of its rows each (ties at the LIMIT may be cut either way)."""
    want = ref[:limit]
    if len(got) != len(want):
        return f"{len(got)} rows, reference {len(want)}"
    pool = set(ref)
    for i, (g, w) in enumerate(zip(got, want)):
        if g[3:5] != w[3:5] or g not in pool:
            return f"row {i}: {g} against {w}"
    if len(set(got)) != len(got):
        return "a row twice"
    return None


def _gated(tag: str) -> bool:
    """A device fragment read with run-statistics gates, whatever its
    body: device[<body>+runstat]."""
    return tag.startswith("device[") and tag.endswith("+runstat]")


def _gated_reads() -> dict:
    return {m: obs.FRAG_READS.get(mode=m) for m in DEVICE_FRAGMENT_MODES
            if m.endswith("+runstat")}


def _one_read(session, sql: str) -> list:
    before = _gated_reads()
    got = session.query(sql)
    (tag,) = session.last_engines
    assert _gated(tag), tag
    moved = {m: n - before[m] for m, n in _gated_reads().items()
             if n != before[m]}
    assert moved == {tag[len("device["):-1]: 1}, moved
    return got


def test_the_benchmark_oracles_are_copies_of_the_reference(subq):
    _, data, _ = subq
    jd = {"joins": data}
    assert bench_q18.reference(jd) == REF.q18(data)
    assert bench_q21.reference(jd) == REF.q21(data)


def test_q18_is_one_gated_read_and_exact(subq, no_split):
    session, data, planted = subq
    got = _q18_rows(_one_read(session, Q18))
    ref = REF.q18(data)
    assert _same_q18(got, ref, 100) is None, _same_q18(got, ref, 100)
    # the three planted ties lead, the two dated alike first
    assert {r[2] for r in got[:2]} == {planted["tied"][0],
                                       planted["tied"][2]}
    assert got[2][2] == planted["tied"][1]
    wire = [[str(c) for c in r] for r in session.query(Q18)]
    assert bench_q18.compare(wire, bench_q18.reference({"joins": data})) \
        is None


def test_q18_boundary_is_strict(subq, no_split):
    session, data, planted = subq
    sql = Q18.replace("limit 100", "limit 100000")
    got = _q18_rows(_one_read(session, sql))
    ref = REF.q18(data, limit=100000)
    assert _same_q18(got, ref, 100000) is None
    keys = {r[2] for r in got}
    assert planted["at_300"] not in keys
    assert planted["above_300"] in keys


def test_q18_passes_more_orders_than_the_having_buffer(subq, no_split):
    """Every order passes `> 1`: more groups than HAVING_CAP (65 536),
    which the membership plan's candidate buffer cannot hold."""
    session, data, _ = subq
    sql = Q18.replace("> 300", "> 1")
    got = _q18_rows(_one_read(session, sql))
    ref = REF.q18(data, quantity=1)
    assert len(REF.q18(data, quantity=1, limit=10**7)) > \
        F.FragmentDAG.HAVING_CAP
    assert _same_q18(got, ref, 100) is None


def test_q21_is_one_gated_read_and_exact(subq, no_split):
    session, data, planted = subq
    got = [(r[0], int(r[1])) for r in _one_read(session, Q21)]
    ref = REF.q21(data)
    assert got == ref
    wire = [[str(c) for c in r] for r in session.query(Q21)]
    assert bench_q21.compare(wire, bench_q21.reference({"joins": data})) \
        is None
    # the planted orders: only the repeated supplier's two late lines count
    full = dict(REF.q21(data, limit=10**6))
    drop = REF.q21({**data, "lineitem": _without_orders(
        data["lineitem"], [planted["repeated"]])}, limit=10**6)
    name = {k: f"Supplier#{k:09d}" for k in planted["supp"]}
    assert full[name[planted["supp"][1]]] - dict(drop).get(
        name[planted["supp"][1]], 0) == 2
    for k in (planted["supp"][0], planted["supp"][3], planted["supp"][4]):
        d = dict(REF.q21({**data, "lineitem": _without_orders(
            data["lineitem"], [planted["one_supplier"],
                               planted["two_late"]])}, limit=10**6))
        assert full.get(name[k], 0) == d.get(name[k], 0)


def _without_orders(li: dict, keys) -> dict:
    keep = ~np.isin(li["l_orderkey"], keys)
    return {c: (v[0], np.asarray(v[1])[keep]) if isinstance(v, tuple)
            else v[keep] for c, v in li.items()}


IN_HAVING_BY_PRIORITY = """
select o_orderpriority, count(*) as n, sum(l_quantity) as q
from orders, lineitem
where o_orderkey = l_orderkey
  and o_orderkey in (select l_orderkey from lineitem
                     group by l_orderkey having sum(l_quantity) > 300)
group by o_orderpriority
order by o_orderpriority
"""


def _by_priority(data) -> list[tuple]:
    passing = {r[2] for r in REF.q18(data, limit=10**7)}
    li, o = data["lineitem"], data["orders"]
    vocab, codes = o["o_orderpriority"]
    prio = dict(zip(o["o_orderkey"].tolist(), np.asarray(codes).tolist()))
    out: dict = {}
    for k, q in zip(li["l_orderkey"].tolist(), li["l_quantity"].tolist()):
        if k in passing:
            p = vocab[prio[k]]
            n, s = out.get(p, (0, 0))
            out[p] = (n + 1, s + q)
    return sorted((p, n, s) for p, (n, s) in out.items())


def test_runs_split_by_tile_edges_are_totalled_across_them(
        subq, no_split, monkeypatch):
    """5 000-row tiles (a 6 144-row bucket: padding after each tile's
    rows) cut order runs at every edge, the planted ones among them;
    the tiled reads borrow each neighbour's edge rows and answer
    exactly."""
    session, data, planted = subq
    monkeypatch.setattr(session.cop, "TILE_ROWS", TILE)
    tiled = []
    inner = F._run_frag_tiled

    def spy(*a, **kw):
        tiled.append(1)
        return inner(*a, **kw)
    monkeypatch.setattr(F, "_run_frag_tiled", spy)
    got = [(r[0], int(r[1])) for r in _one_read(session, Q21)]
    assert got == REF.q21(data)
    got = [(r[0], int(r[1]), _unscaled(r[2]))
           for r in _one_read(session, IN_HAVING_BY_PRIORITY)]
    assert got == _by_priority(data)
    assert len(tiled) == 2
    # the planted orders count: the repeated supplier's lines before the
    # edge see the other supplier after it; the split order passes
    assert planted["edge_above_300"] in {
        r[2] for r in REF.q18(data, limit=10**7)}
    repeated = dict(REF.q21({**data, "lineitem": _without_orders(
        data["lineitem"], [planted["edge_repeated"]])}, limit=10**6))
    name = f"Supplier#{planted['supp'][1]:09d}"
    assert dict(REF.q21(data, limit=10**6))[name] > repeated.get(name, 0)


def _unscaled(v) -> int:
    from benchmarks.oracles import unscaled
    return unscaled(str(v), 2)


def test_explain_analyze_names_one_engine_and_the_span_the_gates(subq):
    session, _, _ = subq
    rows = session.query("explain analyze " + Q21)
    engines = [e for r in rows for e in r if isinstance(e, str)
               and e.startswith(("device", "host("))]
    assert len(engines) == 1 and _gated(engines[0]), rows
    with obs.SpanCollector() as sc:
        session.query(Q21)
    notes = [label.strip() for label, _, _ in sc.rows()
             if label.strip().startswith("copr.fragment ")]
    mode = engines[0][len("device["):-1]
    assert len(notes) == 1 and f"mode {mode}," in notes[0], notes
    assert "gate exists" in notes[0] and "gate not_exists" in notes[0]


def test_gate_counter_moves_once_a_gate_a_read(subq):
    session, _, _ = subq
    kinds = ("exists", "not_exists", "in_having")
    before = {k: obs.RUNSTAT_GATES.get(kind=k) for k in kinds}
    session.query(Q21)
    session.query(Q18)
    after = {k: obs.RUNSTAT_GATES.get(kind=k) - before[k] for k in kinds}
    assert after == {"exists": 1, "not_exists": 1, "in_having": 1}


def test_counters_render_at_zero_from_the_first_client():
    code = ("from tidb_tpu import obs\n"
            "from tidb_tpu.copr.client import CopClient\n"
            "assert 'runstat_gates_total{' not in "
            "obs.PROCESS_METRICS.render()\n"
            "CopClient()\n"
            "print(obs.PROCESS_METRICS.render())\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=120, check=True,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    for kind in ("exists", "not_exists", "in_having"):
        assert f'tidb_copr_runstat_gates_total{{kind="{kind}"}} 0' \
            in out.stdout
    for body in ("agg", "group", "hc", "fat"):
        assert f'tidb_copr_fragment_reads_total{{mode="{body}+runstat"}} 0' \
            in out.stdout


@pytest.mark.parametrize("longest", [1, 2, 3, 4, 7, 8, 9, 33])
def test_run_totals_are_each_runs_total_at_every_row(longest):
    """copr/runstat.py's doubling scans against numpy per-run reductions,
    for runs up to `longest` rows (a power of two and one past it among
    them), with rows that must not count at the identity."""
    import jax.numpy as jnp
    from tidb_tpu.copr import runstat as RS

    rng = np.random.default_rng(longest)
    lens = rng.integers(1, longest + 1, 300)
    lens[7] = longest
    key = np.repeat(np.arange(len(lens)) * 3, lens).astype(np.int32)
    v = rng.integers(-50, 50, len(key)).astype(np.int32)
    live = rng.random(len(key)) < 0.7
    got = RS.run_totals(jnp.asarray(key), [
        (jnp.asarray(np.where(live, v, 0)), "sum"),
        (jnp.asarray(np.where(live, v, RS.I32_MAX)), "min"),
        (jnp.asarray(np.where(live, v, RS.I32_MIN)), "max")],
        RS.steps_for(longest))
    st = _starts(key)
    want = [np.add.reduceat(np.where(live, v, 0), st),
            np.minimum.reduceat(np.where(live, v, RS.I32_MAX), st),
            np.maximum.reduceat(np.where(live, v, RS.I32_MIN), st)]
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.repeat(w, lens))


# ---- snapshots the gates cannot or can still read --------------------------

@pytest.fixture
def small():
    data = tpch.generate_tpch(0.01, SEED + 1)
    session = Session()
    _load(session, data)
    return session, data


def test_gates_on_two_keys_keep_the_plan_without_them(small):
    """The program totals every gate over one key's runs: an EXISTS on
    l_partkey beside a NOT EXISTS on the run key is not a gated read,
    whichever gate the planner meets first, and answers exactly."""
    session, data = small
    li = data["lineitem"]
    ok, pk, sk = li["l_orderkey"], li["l_partkey"], li["l_suppkey"]

    def suppliers(k) -> np.ndarray:
        pairs = np.unique(np.stack([k, sk], 1), axis=0)
        keys, n = np.unique(pairs[:, 0], return_counts=True)
        return n[np.searchsorted(keys, k)]

    want = int(((suppliers(ok) == 1) & (suppliers(pk) > 1)).sum())
    assert want > 0
    sql = ("select count(*) from lineitem l1 where exists (select * from "
           "lineitem l2 where l2.l_partkey = l1.l_partkey and "
           "l2.l_suppkey <> l1.l_suppkey) and not exists (select * from "
           "lineitem l3 where l3.l_orderkey = l1.l_orderkey and "
           "l3.l_suppkey <> l1.l_suppkey)")
    assert session.query(sql) == [(want,)]
    assert not any(_gated(e) for e in session.last_engines), \
        session.last_engines


def test_private_mask_keeps_the_gates_and_an_overlay_reads_on_the_host(
        small):
    """A DELETE hides a base row (a private mask: the runs still stand,
    the row counts nowhere); an INSERT puts a row in the MVCC overlay,
    apart from its order's run: the host fragment interpreter then
    answers, totalling each gate per order key, and the tag says why.
    Both exact."""
    session, data = small
    li = data["lineitem"]
    ok, qty = li["l_orderkey"], li["l_quantity"]
    st = _starts(ok)
    sums = np.add.reduceat(qty.astype(np.int64), st)
    # a passing order that one line's deletion drops to 300 or below
    j = next(i for i in np.flatnonzero(sums > 30000)
             if sums[i] - qty[st[i]] <= 30000)
    jkey = int(ok[st[j]])
    session.execute(f"delete from lineitem where l_orderkey = {jkey} "
                    f"and l_linenumber = {int(li['l_linenumber'][st[j]])}")
    keep = np.ones(len(ok), bool)
    keep[st[j]] = False
    data_del = {**data, "lineitem": {
        c: (v[0], np.asarray(v[1])[keep]) if isinstance(v, tuple)
        else v[keep] for c, v in li.items()}}
    sql = Q18.replace("limit 100", "limit 100000")
    got = _q18_rows(session.query(sql))
    (tag,) = session.last_engines
    assert _gated(tag), tag
    ref = REF.q18(data_del, limit=100000)
    assert _same_q18(got, ref, 100000) is None
    assert jkey not in {r[2] for r in got}
    # an order short of 300 that a new line of 50 lifts above it
    li2 = data_del["lineitem"]
    st2 = _starts(li2["l_orderkey"])
    sums2 = np.add.reduceat(li2["l_quantity"].astype(np.int64), st2)
    k = next(i for i in np.flatnonzero((sums2 <= 30000) & (sums2 > 25000)))
    a = st2[k]
    kkey = int(li2["l_orderkey"][a])
    row = {c: (v[0][int(v[1][a])] if isinstance(v, tuple) else v[a])
           for c, v in li2.items()}
    vals = []
    for c in ("l_orderkey", "l_partkey", "l_suppkey"):
        vals.append(str(int(row[c])))
    vals.append("8")
    vals.append("50.00")
    for c in ("l_extendedprice", "l_discount", "l_tax"):
        vals.append(f"{int(row[c]) / 100:.2f}")
    for c in ("l_returnflag", "l_linestatus"):
        vals.append(f"'{row[c]}'")
    for c in ("l_shipdate", "l_commitdate", "l_receiptdate"):
        vals.append(f"'{REF._day(row[c])}'")
    for c in ("l_shipinstruct", "l_shipmode", "l_comment"):
        vals.append(f"'{row[c]}'")
    session.execute(f"insert into lineitem values ({', '.join(vals)})")
    got = _q18_rows(session.query(sql))
    engines = list(session.last_engines)
    assert engines == ["host(fragment:runstat-overlay)"], engines
    added = {c: (v[0], np.append(np.asarray(v[1]), list(v[0]).index(
        row[c]))) if isinstance(v, tuple) else np.append(
            v, 5000 if c == "l_quantity" else 8 if c == "l_linenumber"
            else row[c]) for c, v in li2.items()}
    ref = REF.q18({**data_del, "lineitem": added}, limit=100000)
    assert _same_q18(got, ref, 100000) is None
    assert kkey in {r[2] for r in got}


@pytest.mark.parametrize("query", ["q18", "q21", "in_having_by_priority"])
def test_the_host_interpreter_applies_the_gates(subq, monkeypatch, query):
    """A snapshot the device refuses takes the host fragment interpreter,
    which totals each gate per key value over every visible row: the
    same answers as the reference, planted orders included."""
    session, data, _ = subq

    def refuse(cop, frag, snaps):
        raise F._Fallback("refused")
    monkeypatch.setattr(F, "_device_fragment", refuse)
    if query == "in_having_by_priority":
        got = [(r[0], int(r[1]), _unscaled(r[2]))
               for r in session.query(IN_HAVING_BY_PRIORITY)]
        assert got == _by_priority(data)
    elif query == "q18":
        sql = Q18.replace("limit 100", "limit 100000")
        got = _q18_rows(session.query(sql))
        ref = REF.q18(data, limit=100000)
        assert _same_q18(got, ref, 100000) is None
    else:
        got = [(r[0], int(r[1])) for r in session.query(
            Q21.replace("limit 100", "limit 100000"))]
        assert got == REF.q21(data, limit=100000)
    assert list(session.last_engines) == ["host(fragment:refused)"]


def test_a_run_key_with_nulls_reads_on_the_host():
    """NULL keys form no group: a NULL-keyed row has no other row of its
    order and is in no IN list. A key column with a NULL is not run-ordered
    (copr/client.py _runs_ordered), so the host interpreter answers."""
    session = Session()
    session.execute("create database n")
    session.execute("use n")
    session.execute("create table t (k int, v int)")
    info = session.catalog.table("n", "t")
    k = np.array([0, 0, 1, 1, 2, 2, 3], np.int64)   # NULL, NULL, 1, ...
    v = np.array([1, 2, 1, 2, 3, 3, 4], np.int64)
    session.storage.table_store(info.id).bulk_load(
        [k, v], valids=[k > 0, None])
    session.execute("analyze table t")
    sql = ("select count(*), sum(v) from t t1 where {} exists (select * "
           "from t t2 where t2.k = t1.k and t2.v <> t1.v)")
    assert session.query(sql.format("")) == [(2, 3)]
    assert session.query(sql.format("not")) == [(5, 13)]
    assert list(session.last_engines) == ["host(fragment:runstat-unordered)"]


# ---- the rank body's group-key score, packed from the run-start rows -------

@pytest.fixture
def packed_score(monkeypatch):
    """The packing is for the TPU and epochs of 4 M rows and more; here,
    any epoch, with the TPU's kernels interpreted."""
    import jax
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(F, "RANK_PACK_MIN_ROWS", 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        yield


def _scored() -> dict:
    return {p: obs.RANK_SCORE.get(path=p) for p in ("packed", "gather")}


def _moved(before: dict) -> dict:
    return {p: n - before[p] for p, n in _scored().items()}


@pytest.mark.parametrize("limit", [100, 100000])
def test_q18_through_the_packed_score_is_exact(subq, no_split, packed_score,
                                               limit):
    """Q18 orders by o_totalprice, a group key: the rank body reads it at
    each order's first LINEITEM row by packing, and answers exactly, the
    planted ties and the 300 boundary among the answers."""
    session, data, planted = subq
    before = _scored()
    got = _q18_rows(_one_read(
        session, Q18.replace("limit 100", f"limit {limit}")))
    assert _moved(before) == {"packed": 1, "gather": 0}
    ref = REF.q18(data, limit=limit)
    assert _same_q18(got, ref, limit) is None, _same_q18(got, ref, limit)
    keys = {r[2] for r in got}
    assert planted["above_300"] in keys and planted["at_300"] not in keys
    assert {r[2] for r in got[:2]} == {planted["tied"][0],
                                       planted["tied"][2]}
    assert got[2][2] == planted["tied"][1]


@pytest.mark.parametrize("n0,bucket", [(1000, 1024), (5003, 8192),
                                       (70001, 131072)])
def test_packed_score_key_is_the_key_at_every_run_start(n0, bucket):
    """The key packed from the staged run-start flags equals the gather at
    r0 at every rank below nd, on buckets with pad rows (holding values
    of their own) and nd off a multiple of 128."""
    from types import SimpleNamespace

    import jax.numpy as jnp
    from tidb_tpu.copr import streamseg as SS
    from tidb_tpu.copr.client import CopClient

    rng = np.random.default_rng(n0)
    key = np.repeat(np.arange(n0), rng.integers(1, 8, n0))[:n0]
    meta = SS.rank_meta([key])
    nd = meta["nd"]
    assert nd % 128 and bucket > n0
    snap = SimpleNamespace(epoch=SimpleNamespace(epoch_id=-1),
                           store=SimpleNamespace(table=SimpleNamespace(id=-1)))
    prepared = {"__rank_meta__": meta}
    gathered = F._stage_rank_aux(CopClient(), snap, prepared, bucket)
    packed = F._stage_rank_aux(CopClient(), snap,
                               {**prepared, "__rank_pack__": True}, bucket)
    flags = np.asarray(packed["run_start"])
    assert flags.shape == (bucket,) and not flags[n0:].any()
    assert np.array_equal(np.flatnonzero(flags), _starts(key))
    col = jnp.asarray(rng.integers(-2**31, 2**31 - 1, bucket,
                                   dtype=np.int32))
    want, how = F._key_at_ranks(col, gathered, meta)
    got, path = F._key_at_ranks(col, packed, meta)
    assert (how, path) == ("gather", "packed")
    assert got.shape == want.shape == (meta["nd_pad"],)
    assert np.array_equal(np.asarray(got)[:nd], np.asarray(want)[:nd])


@pytest.mark.parametrize("case", ["null_score", "masked_run"])
def test_packed_score_keeps_nulls_and_masked_runs_in_place(packed_score,
                                                           case):
    """A score key with NULLs (they lead an ascending order) and runs whose
    rows the WHERE passes none of (the best-scored among them): the packed
    body agrees with the host's join and aggregation."""
    import tidb_tpu.plan.fragment as PF
    s = Session()
    s.execute("create table d (k int not null primary key, p int)")
    s.execute("create table f (id int not null primary key, k int not null,"
              " v int)")
    rng = np.random.default_rng(43)
    keys = 3001
    p = rng.permutation(keys) * 7
    lens = rng.integers(1, 8, keys)
    k = np.repeat(np.arange(keys), lens)
    v = rng.integers(1, 100, len(k))
    p_valid = np.ones(keys, bool)
    if case == "null_score":
        p_valid[rng.choice(keys, 5, replace=False)] = False
        sql = ("select f.k, d.p, sum(f.v) from f, d where f.k = d.k "
               "group by f.k, d.p order by d.p, f.k limit 20")
    else:
        # every row of the ten best-priced orders fails the WHERE
        v[np.isin(k, np.argsort(-p)[:10])] = -1
        sql = ("select f.k, d.p, sum(f.v) from f, d where f.k = d.k and "
               "f.v > 0 group by f.k, d.p order by d.p desc, f.k limit 20")
    s.storage.table_store(s.catalog.table("test", "d").id).bulk_load(
        [np.arange(keys, dtype=np.int64), p], [None, p_valid])
    s.storage.table_store(s.catalog.table("test", "f").id).bulk_load(
        [np.arange(len(k), dtype=np.int64), k, v], [None, None, None])
    for t in ("d", "f"):
        s.execute(f"analyze table {t}")
    before = _scored()
    got = s.query(sql)
    assert s.last_engines == ["device[fat]"], s.last_engines
    assert _moved(before) == {"packed": 1, "gather": 0}
    with pytest.MonkeyPatch.context() as m:
        m.setattr(PF, "apply_fragments", lambda p: p)
        want = s.query(sql + " ")
    assert got == want
    if case == "null_score":
        assert [r[1] for r in got[:5]] == [None] * 5


@pytest.mark.parametrize("path", ["packed", "gather", "off_tpu"])
def test_rank_score_counter_moves_once_a_q18_read(subq, monkeypatch, path):
    """One count a Q18 read, under the path its program was traced with:
    packed on the TPU at or above RANK_PACK_MIN_ROWS, gather below it and
    anywhere off the TPU."""
    import jax
    from jax.experimental.pallas import tpu as pltpu

    session, _, _ = subq
    monkeypatch.setattr(F, "RANK_PACK_MIN_ROWS",
                        1 << 40 if path == "gather" else 1)
    if path != "off_tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = _scored()
    with pltpu.force_tpu_interpret_mode():
        session.query(Q18)
        session.query(Q18)
    taken = "packed" if path == "packed" else "gather"
    assert _moved(before) == {"packed": 0, "gather": 0, taken: 2}


def test_rank_score_counter_ignores_an_aggregate_score(subq, packed_score):
    """GROUP BY l_orderkey ordered by its SUM (heavy's group_top10 shape)
    takes the rank path with an aggregate score: neither label moves."""
    session, _, _ = subq
    scores = []
    inner = F._hc_rank_body

    def spy(frag, prepared, cols, mask, aux):
        scores.append(frag.hc.score)
        return inner(frag, prepared, cols, mask, aux)
    from tidb_tpu.copr.client import CopClient
    fresh = Session(session.storage, cop=CopClient())
    fresh.execute("use joins")
    before = _scored()
    with pytest.MonkeyPatch.context() as m:
        m.setattr(F, "_hc_rank_body", spy)
        fresh.query("select l_orderkey, sum(l_quantity) from lineitem "
                    "group by l_orderkey order by 2 desc, 1 limit 10")
    assert [kind for kind, _ in scores] == ["agg"]
    assert _moved(before) == {"packed": 0, "gather": 0}


def test_rank_score_counter_renders_at_zero_from_the_first_client():
    code = ("from tidb_tpu import obs\n"
            "from tidb_tpu.copr.client import CopClient\n"
            "assert 'rank_score_total{' not in obs.PROCESS_METRICS.render()\n"
            "CopClient()\n"
            "print(obs.PROCESS_METRICS.render())\n")
    out = subprocess.run([sys.executable, "-c", code], text=True,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, timeout=120, check=True,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    for path in ("packed", "gather"):
        assert f'tidb_copr_rank_score_total{{path="{path}"}} 0' in out.stdout
    assert obs.lint_metrics([obs.PROCESS_METRICS]) == []
