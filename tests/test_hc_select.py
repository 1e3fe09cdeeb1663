"""The hc bodies' candidate pick (copr/topnsel.py `candidates`, ISSUE 33).

A GROUP BY ... ORDER BY ... LIMIT k over more groups than the dense path
holds ranks every group by an f32 score and hands the decode the
`HCTopN.cap` best (65 536 for a HAVING consumer). The pick is exact by
score: by blocks wherever maxima + candidates fit a quarter of the scores
(ties to the lower index, `jax.lax.top_k`'s own order), by
`approx_max_k(recall_target=1.0)` where they do not, never by a
whole-array `top_k`. Checked on the helper alone, on the shape rule, on
the counter that says which path a coprocessor read took, and through
SQL against the host interpreter and sqlite, with ties where they hurt.
"""

import functools
import os
import sqlite3
import subprocess
import sys
import unittest.mock as mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tidb_tpu import obs
from tidb_tpu.copr import fragment as FR
from tidb_tpu.copr import topnsel
from tidb_tpu.copr.client import CopClient
from tidb_tpu.plan.fragment import FragmentDAG, HCTopN
from tidb_tpu.session import Session

from sharded_client import sharded_client

CAP10 = HCTopN(("agg", 0), True, 10).cap  # 74: LIMIT 10's buffer
# lengths the block path takes for CAP10: a multiple of its block, a
# multiple of 128 only (streamseg's nd_pad), and of nothing at all
SIZES = [1 << 18, 200_064, 150_001]
KS = [1, CAP10, 260]


# ---------------- the shape rule ----------------

def _traced_path(n: int, k: int) -> str:
    taken: list = []
    jax.jit(lambda s: topnsel.candidates(s, k, taken)).lower(
        jax.ShapeDtypeStruct((n,), jnp.float32))
    return taken[0]


@pytest.mark.parametrize("n,k,want", [
    (15_000_064, CAP10, "block"),      # heavy's group_top10: L = 512
    (15_000_064, FragmentDAG.HAVING_CAP, "approx"),
    (4_194_304, FragmentDAG.HAVING_CAP, "approx"),
    (1_024, CAP10, "approx"),          # a tiny group space
    (39_040, CAP10, "approx"),         # one block short of paying
    (39_168, CAP10, "block"),
    (150_001, CAP10, "block"),         # no block length divides it
    (128, 128, "approx"),              # the buffer is the whole array
])
def test_path_follows_the_static_shapes(n, k, want):
    """`taken` holds what the trace does; the whole-array `top_k`
    ("full", the TopN's fall-back) is not among an hc body's paths."""
    assert topnsel.HC_PATHS == ("block", "approx")
    assert _traced_path(n, k) == want


def test_block_length_rule_with_a_ragged_tail():
    assert topnsel.block_len(15_000_064, CAP10, ragged=True) == 512
    assert topnsel.block_len(15_000_064, CAP10) == 512  # 29 297 blocks
    for n in SIZES:
        for k in KS:
            L = topnsel.block_len(n, k, ragged=True)
            assert L is not None and L >= 128 and L & (L - 1) == 0
            assert 4 * (-(-n // L) + k * L) <= n
    # the TopN's rule is what it was: a tile no block divides sorts whole
    assert topnsel.block_len(150_001, 10) is None
    assert topnsel.block_len(150_001, 10, ragged=True) == 128
    assert topnsel.block_len(1 << 20, 10) == \
        topnsel.block_len(1 << 20, 10, ragged=True) == 256


# ---------------- the helper against top_k ----------------

NULL_DESC = np.float32(-1e38)  # a NULL score under DESC: above -inf only


def _scores(kind: str, n: int, k: int) -> np.ndarray:
    """Per-group scores as the hc bodies build them: f32 sums of small
    integers (ties are the normal case), -inf for a rank that is no
    group, -1e38 / +inf for a NULL score (DESC / ASC)."""
    rng = np.random.default_rng(n % 1013 + 11 * k + len(kind))
    if kind == "small_sums":
        # sum(l_quantity)-like: at most 350, thousands of ties a value
        s = rng.integers(1, 351, n).astype(np.float32)
    elif kind == "tie_straddles_kth":
        # a long run of equal values from before the k-th place to far
        # past it, spread over many blocks, a few better ones
        s = rng.integers(1, 300, n).astype(np.float32)
        s[rng.choice(n, 3 * k + 40, replace=False)] = 340.0
        s[rng.choice(n, max(k // 2, 1), replace=False)] = 350.0
    elif kind == "tie_inside_one_block":
        s = rng.integers(1, 300, n).astype(np.float32)
        at = n // 2 + 37
        s[at:at + k + 30] = 340.0
        s[[0, n - 1]] = 340.0
    elif kind == "all_equal":
        s = np.full(n, 7.0, np.float32)
    elif kind == "no_group":
        s = np.full(n, -np.inf, np.float32)
    elif kind == "fewer_than_k_finite":
        # the buffer fills up with -inf entries: real ones, lowest index
        # first, never the padded tail
        s = np.full(n, -np.inf, np.float32)
        live = rng.choice(n, k // 2, replace=False)
        s[live] = rng.integers(1, 50, len(live)).astype(np.float32)
    elif kind == "only_the_tail_is_live":
        s = np.full(n, -np.inf, np.float32)
        s[n - 5:] = [3.0, 1.0, 3.0, 2.0, 3.0]
    elif kind == "null_scores":
        s = -rng.integers(1, 351, n).astype(np.float32)      # ASC
        where = rng.random(n)
        s[where < 0.001] = np.inf          # NULL first under ASC
        s[(where > 0.4) & (where < 0.6)] = NULL_DESC
        s[where > 0.9] = -np.inf
    else:
        raise AssertionError(kind)
    return s


KINDS = ["small_sums", "tie_straddles_kth", "tie_inside_one_block",
         "all_equal", "no_group", "fewer_than_k_finite",
         "only_the_tail_is_live", "null_scores"]


@functools.lru_cache(maxsize=None)
def _programs(k: int):
    return (jax.jit(lambda s: topnsel.candidates(s, k)),
            jax.jit(lambda s: jax.lax.top_k(s, k)[1]))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("n", SIZES)
def test_candidates_equal_top_k(n, k, kind):
    assert topnsel.block_len(n, k, ragged=True) is not None
    pick, whole = _programs(k)
    score = jnp.asarray(_scores(kind, n, k))
    got, want = np.asarray(pick(score)), np.asarray(whole(score))
    assert got.dtype == want.dtype and got.shape == (k,)
    assert got.max() < n, "a padded index was picked"
    assert (got == want).all(), (got[:12], want[:12])


@pytest.mark.parametrize("n,k", [(1_024, CAP10), (4_096, 1_024),
                                 (39_040, CAP10)])
def test_approx_path_is_exact_by_score(n, k):
    """Where blocks do not pay the site does what it did: exact by score
    (which equal-scored entry it keeps is the implementation's)."""
    assert _traced_path(n, k) == "approx"
    pick, whole = _programs(k)
    for kind in ("small_sums", "tie_straddles_kth", "null_scores"):
        s = _scores(kind, n, k)
        got = np.asarray(pick(jnp.asarray(s)))
        assert len(set(got.tolist())) == k and got.max() < n
        assert (s[got] == s[np.asarray(whole(jnp.asarray(s)))]).all()


# ---------------- through SQL ----------------

G = 50_000           # groups: 391 blocks of 128 ranks, so cap 74 pays
RUN_SQL = [
    # ties at the 10th place (8 groups at places 6-13) and at the 74th
    # (40 groups at places 60-99): the statement's second key decides
    "select g, sum(v) from {t} group by g order by sum(v) desc, g limit 10",
    # the same at the low end, behind three NULL sums (first under ASC)
    "select g, sum(v) from {t} group by g order by sum(v), g limit 10",
    "select g, count(*) from {t} group by g order by count(*) desc, g "
    "limit 10",
    # the limit ends inside the run of ties / just before / just after it
    "select g, sum(v) from {t} group by g order by sum(v) desc, g limit 5",
    "select g, sum(v) from {t} group by g order by sum(v) desc, g limit 13",
    "select g, sum(v) from {t} group by g order by 2 desc, 1 desc limit 10",
    # a WHERE that empties some groups: their ranks score -inf
    "select g, sum(v), count(*) from {t} where w < 7 group by g "
    "order by sum(v) desc, g limit 10",
    # AVG ranks by a quotient; NULL sums last under DESC
    "select g, sum(w), avg(v) from {t} group by g order by sum(w) desc, g "
    "limit 10",
]
# a run of ties from the 10th place past the 74th: no buffer can prove
# the cut, whatever picks it; the answer is still the oracle's
TIE_PAST_CAP_SQL = "select g, sum(w) from {t} group by g " \
    "order by sum(w), g limit 10"
JOIN_SQL = [
    # Q3-shaped: join + filter on the build side + agg + TopN
    "select g, sum(v) as rev, dt from {t}, d where g = dg and dt < 20 "
    "group by g, dt order by rev desc, g limit 10",
    "select g, sum(v) as rev, dt from {t}, d where g = dg and dt >= 5 "
    "group by g, dt order by rev, g limit 10",
]
HAVING_SQL = "select g, sum(v) from {t} group by g having sum(v) > 940 " \
    "order by g"


def _plant(values, free, runs):
    """Write each (value, how many groups) of `runs` over groups drawn
    from `free` (a shuffled pool), so no planted group is planted twice."""
    for val, count in runs:
        for _ in range(count):
            values[free.pop()] = val


def _corpus_arrays():
    rng = np.random.default_rng(33)
    sums = rng.integers(100, 500, G)
    reps = rng.integers(1, 3, G)
    free = list(rng.permutation(G))
    high = [(1000 - i, 1) for i in range(5)] + [(900, 8)] + \
        [(800 - i, 1) for i in range(46)] + [(700, 40)]
    low = [(1 + i, 1) for i in range(5)] + [(10, 8)] + \
        [(20 + i, 1) for i in range(46)] + [(80, 40)]
    _plant(sums, free, high + low)
    _plant(reps, free, [(100 - i, 1) for i in range(5)] + [(90, 8)] +
           [(80 - i, 1) for i in range(46)] + [(30, 40)])
    null_groups = [free.pop() for _ in range(3)]
    first = np.concatenate([[0], np.cumsum(reps)[:-1]])
    n = int(reps.sum())
    g = np.repeat(np.arange(G, dtype=np.int64) * 3 + 1, reps)
    v = np.zeros(n, np.int64)
    v[first] = sums                       # a group's sum sits on one row
    v_valid = np.ones(n, bool)
    v_valid[np.isin(g, np.asarray(null_groups) * 3 + 1)] = False
    w = rng.integers(0, 10, n)            # sum(w): a few values, all tied
    return g, v, v_valid, w


def _bulk(session, name, ddl, cols, valids=None):
    session.execute(ddl)
    info = session.catalog.table("test", name)
    session.storage.table_store(info.id).bulk_load(cols, valids)


@pytest.fixture(scope="module")
def corpus():
    """`r`: rows in group order (the rank-space body); `u`: the same rows
    shuffled (the sorted-run body); `d`: one row a group to join."""
    g, v, v_valid, w = _corpus_arrays()
    n = len(g)
    base = Session(cop=CopClient())
    ddl = "create table {} (k bigint primary key, g bigint, v int, w int)"
    _bulk(base, "r", ddl.format("r"),
          [np.arange(n, dtype=np.int64), g, v, w],
          [None, None, v_valid, None])
    p = np.random.default_rng(34).permutation(n)
    _bulk(base, "u", ddl.format("u"),
          [np.arange(n, dtype=np.int64), g[p], v[p], w[p]],
          [None, None, v_valid[p], None])
    dg = np.arange(G, dtype=np.int64) * 3 + 1
    _bulk(base, "d", "create table d (dg bigint primary key, dt int)",
          [dg, np.random.default_rng(35).integers(0, 25, G)])
    for t in ("r", "u", "d"):
        base.execute(f"analyze table {t}")
    return base


ALL_SQL = [q.format(t=t) for t in ("r", "u")
           for q in RUN_SQL + [TIE_PAST_CAP_SQL, HAVING_SQL] + JOIN_SQL]


@pytest.fixture(scope="module")
def host_rows(corpus):
    """Every statement answered with the fragment gate shut (the host
    interpreter), and the single-table ones by sqlite as well."""
    def deny_fragment(cop, frag, snaps):
        raise FR._Fallback("forced-host")

    host = Session(corpus.storage, cop=CopClient())
    with mock.patch.object(FR, "_device_fragment", deny_fragment):
        rows = {sql: host.query(sql) for sql in ALL_SQL}
    g, v, v_valid, w = _corpus_arrays()
    db = sqlite3.connect(":memory:")
    db.execute("create table r (g integer, v integer, w integer)")
    db.executemany("insert into r values (?, ?, ?)", [
        (int(a), int(b) if ok else None, int(c))
        for a, b, ok, c in zip(g, v, v_valid, w)])
    for q in RUN_SQL[:7] + [TIE_PAST_CAP_SQL, HAVING_SQL]:
        lite = db.execute(q.format(t="r")).fetchall()
        for t in ("r", "u"):
            ours = [tuple(None if x is None else int(x) for x in row)
                    for row in rows[q.format(t=t)]]
            assert ours == lite, q
    return rows


_SESSIONS: dict = {}


def _session(corpus, mode="single"):
    s = _SESSIONS.get(mode)
    if s is None or s.storage is not corpus.storage:
        cop = CopClient() if mode == "single" else \
            sharded_client(corpus.storage, jax.devices()[:4])
        s = _SESSIONS[mode] = Session(corpus.storage, cop=cop)
    return s


def _hc_reads(fn):
    before = [obs.HC_SELECT.get(path=p) for p in topnsel.HC_PATHS]
    out = fn()
    return (out,) + tuple(obs.HC_SELECT.get(path=p) - b
                          for p, b in zip(topnsel.HC_PATHS, before))


def _engines(session, sql):
    return {r[3] for r in session.execute(
        "EXPLAIN ANALYZE " + sql).rows if r[3]}


@pytest.mark.parametrize("sql", RUN_SQL)
@pytest.mark.parametrize("table", ["r", "u"])
def test_group_topn_matches_host(corpus, host_rows, table, sql):
    """`r` runs the rank-space body over ~50 000 ranks, `u` the
    sorted-run body over its rows: both pick by blocks, and the device's
    ten groups are the host's, ties included."""
    sql = sql.format(t=table)
    s = _session(corpus)
    rows, block, approx = _hc_reads(lambda: s.query(sql))
    assert rows == host_rows[sql], sql
    assert (block, approx) == (1, 0), (sql, block, approx)
    eng = _engines(s, sql)
    assert eng and all(e.startswith("device[") for e in eng), (sql, eng)


@pytest.mark.parametrize("table", ["r", "u"])
def test_ties_past_the_buffer_still_answer_exactly(corpus, host_rows, table):
    sql = TIE_PAST_CAP_SQL.format(t=table)
    s = _session(corpus)
    rows, block, approx = _hc_reads(lambda: s.query(sql))
    assert rows == host_rows[sql] and (block, approx) == (1, 0)
    assert "host(fragment:hc-boundary)" in _engines(s, sql)


@pytest.mark.parametrize("sql", JOIN_SQL)
@pytest.mark.parametrize("table", ["r", "u"])
def test_join_group_topn_matches_host(corpus, host_rows, table, sql):
    sql = sql.format(t=table)
    s = _session(corpus)
    rows, block, approx = _hc_reads(lambda: s.query(sql))
    assert rows == host_rows[sql], sql
    assert (block, approx) == (1, 0), (sql, block, approx)
    eng = _engines(s, sql)
    assert eng and all(e.startswith("device[") for e in eng), (sql, eng)


@pytest.mark.parametrize("table", ["r", "u"])
def test_having_keeps_the_approx_path(corpus, host_rows, table):
    """A HAVING consumer's buffer is 65 536 entries: more than the ranks
    (`r`) or a large part of the rows (`u`), so blocks cannot pay and the
    site compiles what it compiled before."""
    sql = HAVING_SQL.format(t=table)
    s = _session(corpus)
    rows, block, approx = _hc_reads(lambda: s.query(sql))
    assert rows == host_rows[sql] and len(rows) == 5
    assert (block, approx) == (0, 1)
    assert "device[hc]" in _engines(s, sql)


@pytest.mark.parametrize("sql", RUN_SQL[:3])
def test_group_topn_on_a_mesh_matches_host(corpus, host_rows, sql):
    """Four devices, groups hash-partitioned over them: each ranks its
    own rows and the counter reads the path of the shape a shard ranks."""
    assert len(jax.devices()) >= 4, "conftest must provide the devices"
    sql = sql.format(t="u")
    s = _session(corpus, "mesh4")
    rows, block, approx = _hc_reads(lambda: s.query(sql))
    assert rows == host_rows[sql], sql
    assert block + approx == 1
    assert any(e.startswith("device[") and e.endswith("@mesh4")
               for e in _engines(s, sql)), sql


# ---------------- the counter ----------------

def test_counter_is_rendered_at_zero_before_any_hc_read():
    code = ("from tidb_tpu import obs\n"
            "from tidb_tpu.copr.client import CopClient\n"
            "assert 'hc_select_total{' not in obs.PROCESS_METRICS.render()\n"
            "CopClient()\n"
            "print(obs.PROCESS_METRICS.render())\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=120, check=True,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert 'tidb_copr_hc_select_total{path="block"} 0' in out.stdout
    assert 'tidb_copr_hc_select_total{path="approx"} 0' in out.stdout
    assert 'path="full"} 0' in out.stdout  # the TopN's, not an hc label
    assert 'tidb_copr_hc_select_total{path="full"}' not in out.stdout


def test_counter_moves_by_one_a_read_under_its_path(corpus):
    small = Session(cop=CopClient())
    small.execute("create table s (k bigint primary key, g bigint, v int)")
    gs = np.repeat(np.arange(10_000, dtype=np.int64), 2)
    info = small.catalog.table("test", "s")
    small.storage.table_store(info.id).bulk_load(
        [np.arange(20_000, dtype=np.int64), gs, gs % 977], None)
    small.execute("analyze table s")
    sql = "select g, sum(v) from s group by g order by sum(v) desc, g limit 3"
    # 10 000 ranks against a buffer of 67: too few for blocks to pay
    rows, block, approx = _hc_reads(lambda: small.query(sql))
    assert [int(r[1]) for r in rows] == [1952] * 3
    assert (block, approx) == (0, 1)
    s = _session(corpus)
    for n in (1, 2):
        _, block, approx = _hc_reads(lambda: [
            s.query(RUN_SQL[0].format(t="r")) for _ in range(n)])
        assert (block, approx) == (n, 0)
    # no other read moves it: a dense GROUP BY, a TopN, a scan
    _, block, approx = _hc_reads(lambda: [
        s.query("select w, count(*) from r group by w order by w"),
        s.query("select k, v from r order by v desc limit 10"),
        s.query("select count(*) from r where w < 3")])
    assert (block, approx) == (0, 0)
    text = obs.PROCESS_METRICS.render()
    assert 'tidb_copr_hc_select_total{path="block"}' in text
    assert 'tidb_copr_hc_select_total{path="approx"}' in text
    assert obs.lint_metrics([obs.PROCESS_METRICS]) == []
