"""Exact sums past the int64 worst-case bound (copr/sumexact.combine_terms).

`_prepare_agg` used to send a SUM to the host interpreter whenever largest
value x rows reached 2**62, whatever the true total (TPC-H Q1's sum_charge
from SF 6.8 up). Now the device serves it and the host recombines the int32
limb partials in arithmetic that cannot wrap; a total that really does not
fit int64 is the statement's out-of-range error. Every expected value here
comes from Python integers over the arrays the table was loaded from:
nothing of the program.
"""

import jax
import numpy as np
import pytest

from tidb_tpu import obs
from tidb_tpu.bench.tpch import TPCH_Q1, TPCH_Q6, load_lineitem
from tidb_tpu.copr import mesh as M
from tidb_tpu.copr import sumexact as SE
from tidb_tpu.copr.client import CopClient
from tidb_tpu.errno import ER_DATA_OUT_OF_RANGE
from tidb_tpu.session import Session

N = 3000
DDL = ("create table {name} (id bigint primary key, f int, s int, "
       "p decimal(15,2), d decimal(15,2), x decimal(15,2), q decimal(15,2), "
       "day int)")
# TPC-H Q1's shape over that table: four sums, three averages, a count
Q1 = ("select f, s, sum(q), sum(p), sum(p*(1-d)), sum(p*(1-d)*(1+x)), "
      "avg(q), avg(p), avg(d), count(*) from {name} where day <= 900 "
      "group by f, s order by f, s")
SUM3 = "select sum(p*(1-d)*(1+x)) from {name}"
AVG3 = "select f, avg(p*(1-d)*(1+x)) from {name} group by f order by f"


def arrays(seed: int, wide: bool) -> dict[str, np.ndarray]:
    """Columns in cents / hundredths. `wide`: a few rows carry the largest
    price int32 staging holds and factors of 327.00, so that largest value
    x rows = 2.3e18 x 3000 passes 2**62 while every group's true sum stays
    far inside int64."""
    rng = np.random.default_rng(seed)
    a = {"id": np.arange(N, dtype=np.int64),
         "f": rng.integers(0, 3, N).astype(np.int64),
         "s": rng.integers(0, 2, N).astype(np.int64),
         "p": rng.integers(100, 10_000_000, N).astype(np.int64),
         "d": rng.integers(0, 11, N).astype(np.int64),
         "x": rng.integers(0, 9, N).astype(np.int64),
         "q": rng.integers(100, 5001, N).astype(np.int64),
         "day": rng.integers(0, 1000, N).astype(np.int64)}
    if wide:
        a["p"][::500] = 2**31 - 1 - np.arange(len(a["p"][::500]))
        a["d"][7] = -32600
        a["x"][11] = 32600
    return a


def load(session: Session, name: str, a: dict[str, np.ndarray]) -> None:
    session.execute(DDL.format(name=name))
    info = session.catalog.table(session.current_db, name)
    session.storage.table_store(info.id).bulk_load(
        [a[c.name] for c in info.columns])


def half_up(num: int, den: int) -> int:
    q, r = divmod(abs(num), den)
    q += 2 * r >= den
    return q if num >= 0 else -q


def q1_reference(a) -> list[tuple]:
    """Python integers only. Each value as (unscaled, scale)."""
    out = []
    for f in range(3):
        for s in range(2):
            k = np.nonzero((a["f"] == f) & (a["s"] == s)
                           & (a["day"] <= 900))[0]
            if not len(k):
                continue
            p = [int(v) for v in a["p"][k]]
            d = [int(v) for v in a["d"][k]]
            x = [int(v) for v in a["x"][k]]
            q = [int(v) for v in a["q"][k]]
            n = len(k)
            out.append((
                f, s, (sum(q), 2), (sum(p), 2),
                (sum(pi * (100 - di) for pi, di in zip(p, d)), 4),
                (sum(pi * (100 - di) * (100 + xi)
                     for pi, di, xi in zip(p, d, x)), 6),
                (half_up(sum(q) * 10**4, n), 6),
                (half_up(sum(p) * 10**4, n), 6),
                (half_up(sum(d) * 10**4, n), 6), n))
    return out


def plain(rows) -> list[tuple]:
    """Result rows with every decimal as (unscaled, scale)."""
    return [tuple((v.unscaled, v.scale) if hasattr(v, "unscaled") else v
                  for v in r) for r in rows]


def engines(session, sql) -> set:
    return {r[3] for r in session.execute("EXPLAIN ANALYZE " + sql).rows
            if r[3]}


def counted(session, sql) -> tuple[list, float, float]:
    w0 = obs.SUM_RECOMBINE.get(width="wide")
    n0 = obs.SUM_RECOMBINE.get(width="int64")
    rows = session.execute(sql).rows
    return (rows, obs.SUM_RECOMBINE.get(width="wide") - w0,
            obs.SUM_RECOMBINE.get(width="int64") - n0)


@pytest.fixture(scope="module")
def db():
    """{'single': Session, 'mesh': Session over the same storage, and the
    arrays of the tables `wide`, `narrow`, `toobig`, `many`}. The mesh is the conftest's eight virtual
    devices, sharding from 512 rows."""
    assert len(jax.devices()) >= 8
    single = Session(cop=CopClient())
    single.execute("create database w")
    single.execute("use w")
    data = {"wide": arrays(5, True), "narrow": arrays(6, False),
            "toobig": arrays(7, True)}
    # eight rows of 2.3e18 in one group: 1.8e19 > 2**63
    # 1500 groups of two rows on a key whose span (10 500) is past the
    # dense segment space: the sorted-run fragment path serves it
    data["many"] = dict(data["wide"], day=np.arange(N, dtype=np.int64) // 2 * 7)
    big = data["toobig"]
    big["f"][:] = 0
    big["p"][:8], big["d"][:8], big["x"][:8] = 2**31 - 1, -32600, 32600
    for name, a in data.items():
        load(single, name, a)
    plane = M.MeshPlane(M.MeshConfig(enabled=True, shard_threshold_rows=512))
    mesh = Session(single.storage, cop=plane.client_for(single.storage))
    mesh.execute("use w")
    return {"single": single, "mesh": mesh, **data}


WHERE = ("single", "mesh")
TAG = {"single": "device", "mesh": "device@mesh8"}


@pytest.mark.parametrize("where", WHERE)
def test_q1_shape_past_the_bound_is_exact_on_the_device(db, where):
    """(a) Q1's four sums: one of them (sum_charge's shape) is past the
    bound and takes the wide route, three stay int64; avg(q), avg(p),
    avg(d) are int64 sums too."""
    sql = Q1.format(name="wide")
    rows, wide, narrow = counted(db[where], sql)
    assert plain(rows) == q1_reference(db["wide"])
    assert (wide, narrow) == (1, 6)
    assert engines(db[where], sql) == {TAG[where]}


@pytest.mark.parametrize("where", WHERE)
def test_sum_and_avg_past_the_bound(db, where):
    a = db["wide"]
    tot = sum(int(p) * (100 - int(d)) * (100 + int(x))
              for p, d, x in zip(a["p"], a["d"], a["x"]))
    rows, wide, narrow = counted(db[where], SUM3.format(name="wide"))
    assert plain(rows) == [((tot, 6),)] and (wide, narrow) == (1, 0)
    want = []
    for f in range(3):
        k = a["f"] == f
        t = sum(int(p) * (100 - int(d)) * (100 + int(x))
                for p, d, x in zip(a["p"][k], a["d"][k], a["x"][k]))
        want.append((f, (half_up(t * 10**4, int(k.sum())), 10)))
    rows, wide, narrow = counted(db[where], AVG3.format(name="wide"))
    assert plain(rows) == want and (wide, narrow) == (1, 0)
    assert engines(db[where], AVG3.format(name="wide")) == {TAG[where]}


@pytest.mark.parametrize("where", WHERE)
def test_the_fragment_path_takes_the_same_route(db, where):
    """GROUP BY over many groups with a TopN consumer (copr/fragment.py's
    sorted-run candidates): the bound is the table's, so the sum is wide
    though a group holds two rows."""
    sql = ("select day, sum(p*(1-d)*(1+x)) v from many group by day "
           "order by v desc, day limit 5")
    a = db["many"]
    ref: dict = {}
    for k, p, d, x in zip(a["day"], a["p"], a["d"], a["x"]):
        ref[int(k)] = ref.get(int(k), 0) \
            + int(p) * (100 - int(d)) * (100 + int(x))
    want = sorted(ref.items(), key=lambda kv: (-kv[1], kv[0]))[:5]
    rows, wide, narrow = counted(db[where], sql)
    assert plain(rows) == [(k, (v, 6)) for k, v in want]
    assert (wide, narrow) == (1, 0)
    (tag,) = engines(db[where], sql)
    assert tag.startswith("device[") and ("@mesh8" in tag) == (
        where == "mesh")


@pytest.mark.parametrize("where", WHERE)
def test_under_the_bound_the_same_dag_takes_int64(db, where):
    """(b) the spec-sized values: no aggregate is wide, the answer is the
    reference's."""
    sql = Q1.format(name="narrow")
    rows, wide, narrow = counted(db[where], sql)
    assert plain(rows) == q1_reference(db["narrow"])
    assert (wide, narrow) == (0, 7)
    assert engines(db[where], sql) == {TAG[where]}


@pytest.mark.parametrize("where", WHERE)
def test_a_total_that_cannot_fit_is_out_of_range(db, where):
    """(c) the statement fails with MySQL's out-of-range error and
    returns nothing; the host interpreter is not asked."""
    s = db[where]
    host0 = obs.COPR_REQUESTS.get(engine="host")
    with pytest.raises(SE.SumOutOfRange) as e:
        s.execute(AVG3.format(name="toobig"))
    assert e.value.errno == ER_DATA_OUT_OF_RANGE
    assert e.value.sqlstate == "22003"
    assert obs.COPR_REQUESTS.get(engine="host") == host0


def test_partials_that_fit_alone_but_not_together(db):
    """Epoch and overlay partials are each exact; their sum in the final
    merge must not wrap either."""
    s = db["single"]
    a = arrays(8, False)
    a["p"][:3], a["d"][:3], a["x"][:3] = 2**31 - 1, -32600, 32600
    load(s, "edge", a)              # 3 x 2.3e18 = 6.9e18 < 2**63
    sql = SUM3.format(name="edge")
    tot = sum(int(p) * (100 - int(d)) * (100 + int(x))
              for p, d, x in zip(a["p"], a["d"], a["x"]))
    assert plain(s.execute(sql).rows) == [((tot, 6),)]
    # (bulk_load gives handles 1..N: fresh ids start well past them)
    for i in range(2):              # + 2 x 2.3e18 in the overlay
        s.execute(f"insert into edge values ({N + 10 + i}, 0, 0, 21474836.47, "
                  f"-326.00, 326.00, 1.00, 1)")
    with pytest.raises(SE.SumOutOfRange):
        s.execute(sql)
    s.execute(f"delete from edge where id >= {N}")
    s.execute(f"insert into edge values ({N + 20}, 0, 0, 1.00, 0.00, 0.00, "
              f"1.00, 1)")
    assert plain(s.execute(sql).rows) == [((tot + 100 * 100 * 100, 6),)]


@pytest.mark.parametrize("shards", [1, 4])
def test_the_shares_add_up(shards):
    """(d) the limb partials of the shards of a table, merged as the mesh
    merges them and recombined wide, equal Python's sum over the whole
    table: a 46-bit value as two terms (hi << 15, lo) of 3 limbs each."""
    rng = np.random.default_rng(shards)
    n, segments = 4096 * shards, 5
    v = rng.integers(-2**45, 2**45, n)
    seg = rng.integers(-1, segments, n).astype(np.int32)
    terms = [((v >> 15).astype(np.int32), 15),
             ((v & 0x7FFF).astype(np.int32), 0)]
    parts = []
    for t, _ in terms:
        per_shard = [np.asarray(SE.seg_sum_partials(
            t[i::shards], seg[i::shards], segments, 3))
            for i in range(shards)]
        parts.append(SE.merge_additive(per_shard))
    want = [sum(int(x) for x in v[seg == k]) for k in range(segments)]
    shifts = [s for _, s in terms]
    assert SE.combine_terms(parts, shifts, wide=True).tolist() == want
    assert SE.combine_terms(parts, shifts, wide=False).tolist() == want
    # the same partials scaled past int64: wide refuses, nothing wraps
    with pytest.raises(SE.SumOutOfRange):
        SE.combine_terms(parts, [s + 20 for s in shifts], wide=True)


MESH4_SQL = {
    "q1": TPCH_Q1,
    "q6": TPCH_Q6,
    # the ranked key alone: rows that tie on it may come in any order
    "topn": ("select l_extendedprice from lineitem "
             "order by l_extendedprice desc limit 10"),
    "row_scan": ("select l_orderkey, l_quantity, l_extendedprice from "
                 "lineitem where l_discount = 0.10 and l_quantity < 3 "
                 "order by l_orderkey, l_quantity, l_extendedprice"),
}


@pytest.fixture(scope="module")
def mesh4():
    single = Session(cop=CopClient())
    load_lineitem(single, 20_000)
    plane = M.MeshPlane(M.MeshConfig(enabled=True, axis_size=4,
                                     shard_threshold_rows=512))
    return single, Session(single.storage,
                           cop=plane.client_for(single.storage))


@pytest.mark.parametrize("cls", sorted(MESH4_SQL))
def test_four_devices_answer_as_one(mesh4, cls):
    """(e) above the shard threshold the benchmark's mesh classes read
    bit-identical on `device` and `device@mesh4`."""
    single, mesh = mesh4
    sql = MESH4_SQL[cls]
    assert mesh.query(sql) == single.query(sql)
    assert engines(single, sql) == {"device"}
    assert engines(mesh, sql) == {"device@mesh4"}
