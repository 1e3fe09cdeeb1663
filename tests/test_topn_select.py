"""The TopN's exact block-select top-k (copr/topnsel.py, ISSUE 27).

`topnsel.select(score, k)` must return exactly `jax.lax.top_k(score,
k)[1]` — the same rows in the same order — on every shape and input:
that is what makes the device TopN bit-identical to the host's stable
sort when ten rows tie on the key. Checked on the helper alone, through
SQL on one device, tiled, on a four-device mesh and in the fused
join+TopN fragment, and on the counter that says which path a
coprocessor read took.
"""

import functools
import os
import subprocess
import sys
import unittest.mock as mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tidb_tpu import obs
from tidb_tpu.copr import fragment as FR
from tidb_tpu.copr import mesh as M
from tidb_tpu.copr import topnsel
from tidb_tpu.copr.client import CopClient
from tidb_tpu.session import Session

I32_MIN = np.iinfo(np.int32).min
I32_MAX = np.iinfo(np.int32).max
F32_MAX = np.finfo(np.float32).max

SHORT = 4096  # a tile of a few blocks: the whole-tile top_k stays (k > 1)
SIZES = [1 << 20, 1 << 22, SHORT]
KS = [1, 10, 74, 1000]


def test_block_length_rule():
    # the cells' shapes: one chip's tile and a mesh shard of it, k = 10
    assert topnsel.block_len(1 << 22, 10) == 512
    assert topnsel.block_len(1 << 20, 10) == 256
    for b in SIZES[:2]:
        for k in KS:
            L = topnsel.block_len(b, k)
            assert L is not None and L >= 128 and L & (L - 1) == 0
            assert b % L == 0 and 4 * (b // L + k * L) <= b
    # pointless shapes keep the whole-tile top_k: a short tile, k close
    # to the number of blocks, a tile no block length divides
    assert all(topnsel.block_len(SHORT, k) is None for k in KS[1:])
    assert topnsel.block_len(SHORT, 1) == 128  # 32 maxima + 128 rows
    assert topnsel.block_len(1 << 20, 4096) is None
    assert topnsel.block_len((1 << 20) + 8, 10) is None
    assert topnsel.block_len(256, 10) is None and \
        topnsel.block_len(256, 1) is None
    # ONE rule: maxima + candidates within a quarter of the tile; for
    # ten winners that is 8 192 rows and up
    assert topnsel.block_len(4096, 10) is None
    assert topnsel.block_len(8192, 10) == 128


@pytest.mark.parametrize("b,k,want", [(1 << 20, 10, "block"),
                                      (SHORT, 10, "full"),
                                      (SHORT, 1, "block")])
def test_select_records_the_path_it_traces(b, k, want):
    """`taken` holds the path of the trace itself: what the counter
    counts a read under, not the rule asked again beside the program."""
    taken: list = []
    jax.jit(lambda s: topnsel.select(s, k, taken)).lower(
        jax.ShapeDtypeStruct((b,), jnp.int32))
    assert taken == [want]


def _scores(kind: str, b: int, k: int, dtype) -> np.ndarray:
    """One tile's scores as _topn_body builds them: live keys, NULL-key
    sentinels and the drop sentinel below everything."""
    rng = np.random.default_rng(b % 1009 + 7 * k + len(kind))
    flt = dtype == np.float32
    drop = -np.inf if flt else I32_MIN
    top = np.float32(104949.5) if flt else 10_494_950
    if kind == "distinct":
        s = rng.permutation(b).astype(dtype)
    elif kind == "equal":
        s = np.full(b, 7, dtype)
    elif kind == "max_across_blocks":
        # the maximum more than k times, over many blocks and tiles' ends
        s = rng.integers(0, 1000, b).astype(dtype)
        s[rng.choice(b, 3 * k + 5, replace=False)] = top
        s[[0, b - 1]] = top
    elif kind == "max_inside_one_block":
        # more than k copies of the maximum inside ONE 128-row run, a few
        # elsewhere: the winners come from one block, in row order
        s = rng.integers(0, 1000, b).astype(dtype)
        at = (b // 2) + 37
        s[at:at + min(k + 20, 90)] = top
        s[rng.choice(b, 4, replace=False)] = top
    elif kind == "all_dropped":
        s = np.full(b, drop, dtype)
    elif kind == "fewer_than_k_live":
        s = np.full(b, drop, dtype)
        live = rng.choice(b, max(k // 2, 1) if k > 1 else 0, replace=False)
        s[live] = rng.integers(0, 50, len(live)).astype(dtype)
    elif kind == "null_sentinels":
        # NULL keys rank first (ASC: +inf / I32_MAX) or last but above the
        # dropped rows (DESC: -finfo.max / I32_MIN + 1)
        s = rng.integers(-1000, 1000, b).astype(dtype)
        where = rng.random(b)
        s[where < 0.001] = np.inf if flt else I32_MAX
        s[(where > 0.5) & (where < 0.7)] = -F32_MAX if flt else I32_MIN + 1
        s[where > 0.999] = drop
    else:
        raise AssertionError(kind)
    return s


KINDS = ["distinct", "equal", "max_across_blocks", "max_inside_one_block",
         "all_dropped", "fewer_than_k_live", "null_sentinels"]


@functools.lru_cache(maxsize=None)
def _programs(k: int):
    return (jax.jit(lambda s: topnsel.select(s, k)),
            jax.jit(lambda s: jax.lax.top_k(s, k)[1]))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("b", SIZES)
@pytest.mark.parametrize("dtype", [np.int32, np.float32],
                         ids=["int32", "f32"])
def test_select_equals_top_k(dtype, b, k, kind):
    select, whole = _programs(k)
    score = jnp.asarray(_scores(kind, b, k, dtype))
    got, want = np.asarray(select(score)), np.asarray(whole(score))
    assert got.dtype == want.dtype and got.shape == (k,)
    assert (got == want).all(), (got[:12], want[:12])


@pytest.mark.parametrize("b", [1 << 20, SHORT])
def test_select_ranks_floats_in_top_k_total_order(b):
    """Bit patterns the engine never stores rank as top_k ranks them:
    NaN first, +0.0 before -0.0."""
    rng = np.random.default_rng(3)
    s = rng.standard_normal(b).astype(np.float32)
    s[rng.choice(b, 40, replace=False)] = np.nan
    s[rng.choice(b, b // 3, replace=False)] = 0.0
    s[rng.choice(b, b // 3, replace=False)] = -0.0
    s = np.where(np.isnan(s) | (s == 0), s, -np.abs(s))
    for k in (10, 74):
        select, whole = _programs(k)
        assert (np.asarray(select(jnp.asarray(s)))
                == np.asarray(whole(jnp.asarray(s)))).all()


# ---------------- through SQL ----------------

N_ROWS = 40_000
TILE = 8192          # five tiles; a tile of 8192 rows selects by blocks
MESH_TILE = 4 * TILE  # two tiles, each cut into four shards of 8192

SCAN_SQL = [
    # the maximum forty times over: ten winners by row order
    "select k, a from t order by a desc limit 10",
    # NULL keys first in ASC, more of them than the limit
    "select k, a from t order by a limit 10",
    # NULL keys last in DESC and still part of the answer
    "select k, a from t where a is null or a < -990 order by a desc limit 12",
    # a WHERE that drops most rows, and one that leaves under ten a tile
    "select k, a, c from t where c > 10 order by a desc limit 10",
    "select k, a, c from t where c > 98 and a > 900 order by a limit 10",
    # the two-key topnpack composite, ties on both keys
    "select k, a, b from t order by a desc, b limit 12",
    "select k, a, b from t where c < 50 order by b, a desc limit 9",
    # a float key with ties
    "select k, x from t order by x desc limit 10",
    "select k, x from t order by x limit 7",
]

JOIN_SQL = [
    "select k, y, b from t, d where c = dk order by y desc, b limit 10",
    "select k, y, a from t, d where c = dk and a > 0 order by y, a desc "
    "limit 8",
]


def _bulk(session, name, ddl, cols, valids=None):
    session.execute(ddl)
    info = session.catalog.table("test", name)
    session.storage.table_store(info.id).bulk_load(cols, valids)


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(27)
    base = Session(cop=CopClient())
    k = np.arange(N_ROWS, dtype=np.int64)
    a = rng.integers(-1000, 1000, N_ROWS)
    a[rng.choice(N_ROWS, 40, replace=False)] = 5000   # duplicated extreme
    a[20_000:20_030] = 5000                           # ... inside one block
    a[rng.choice(N_ROWS, 25, replace=False)] = -5000
    a_valid = rng.random(N_ROWS) > 0.01               # 400 NULL keys
    b = rng.integers(0, 5, N_ROWS)
    c = rng.integers(0, 100, N_ROWS)
    x = rng.integers(-50, 50, N_ROWS) / 4.0
    _bulk(base, "t",
          "create table t (k bigint primary key, a int, b int, c int, "
          "x double)",
          [k, a, b, c, x], [None, a_valid, None, None, None])
    _bulk(base, "d", "create table d (dk bigint primary key, y int)",
          [np.arange(100, dtype=np.int64), rng.integers(0, 6, 100)])
    return base


@pytest.fixture(scope="module")
def host_rows(corpus):
    """Every statement answered with the device gates shut: the stable
    host sort the device TopN must match row for row."""
    def deny_topn(self, dag, col_bounds, prepared):
        return "forced-host (test)"

    def deny_fragment(cop, frag, snaps):
        raise FR._Fallback("forced-host")

    host = Session(corpus.storage, cop=CopClient())
    with mock.patch.object(CopClient, "_prepare_topn", deny_topn), \
            mock.patch.object(FR, "_device_fragment", deny_fragment):
        return {sql: host.query(sql) for sql in SCAN_SQL + JOIN_SQL}


_SESSIONS: dict = {}


def _session(corpus, mode):
    s = _SESSIONS.get(mode)
    if s is not None and s.storage is corpus.storage:
        return s
    if mode.startswith("mesh4"):
        assert len(jax.devices()) >= 8, "conftest must provide 8 devices"
        plane = M.MeshPlane(M.MeshConfig(
            enabled=True, axis_size=4, shard_threshold_rows=512))
        cop = plane.client_for(corpus.storage)
        cop.TILE_ROWS = TILE if mode == "mesh4_short" else MESH_TILE
    else:
        cop = CopClient()
        if mode == "tiled":
            cop.TILE_ROWS = TILE
    s = _SESSIONS[mode] = Session(corpus.storage, cop=cop)
    return s


def _block_reads(fn):
    before = obs.TOPN_SELECT.get(path="block"), \
        obs.TOPN_SELECT.get(path="full")
    out = fn()
    return out, obs.TOPN_SELECT.get(path="block") - before[0], \
        obs.TOPN_SELECT.get(path="full") - before[1]


def _engines(session, sql):
    return {r[3] for r in session.execute(
        "EXPLAIN ANALYZE " + sql).rows if r[3]}


@pytest.mark.parametrize("sql", SCAN_SQL)
@pytest.mark.parametrize("mode", ["single", "tiled", "mesh4"])
def test_scan_topn_matches_host(corpus, host_rows, mode, sql):
    s = _session(corpus, mode)
    rows, block, full = _block_reads(lambda: s.query(sql))
    assert rows == host_rows[sql], (mode, sql)
    # every shape here is long enough for blocks: one read, block path
    assert (block, full) == (1, 0), (mode, sql, block, full)
    tag = "device@mesh4" if mode == "mesh4" else "device"
    assert tag in _engines(s, sql), (mode, sql)


@pytest.mark.parametrize("sql", JOIN_SQL)
@pytest.mark.parametrize("mode", ["single", "tiled", "mesh4"])
def test_join_topn_fragment_matches_host(corpus, host_rows, mode, sql):
    s = _session(corpus, mode)
    rows, block, full = _block_reads(lambda: s.query(sql))
    assert rows == host_rows[sql], (mode, sql)
    assert (block, full) == (1, 0), (mode, sql, block, full)
    assert any("device[topn]" in e for e in _engines(s, sql)), (mode, sql)


def test_counter_follows_the_shape_the_program_ranks(corpus, host_rows):
    """Tiles of 8 192 rows cut four ways: each device ranks 2 048 rows
    and sorts them whole, though the rule over the tile says `block`.
    The counter reads what the program was traced with."""
    assert topnsel.block_len(TILE, 10) and not topnsel.block_len(TILE // 4, 10)
    s = _session(corpus, "mesh4_short")
    sql = SCAN_SQL[0]
    rows, block, full = _block_reads(lambda: s.query(sql))
    assert rows == host_rows[sql] and (block, full) == (0, 1)
    assert "device@mesh4" in _engines(s, sql)


# ---------------- the counter ----------------

def test_counter_is_rendered_at_zero_before_any_topn():
    """A fresh process with one client and no statement: both paths are
    on /metrics at 0 (a counter that never moved is not rendered)."""
    code = ("from tidb_tpu import obs\n"
            "from tidb_tpu.copr.client import CopClient\n"
            "assert 'topn_select_total{' not in obs.PROCESS_METRICS.render()\n"
            "CopClient()\n"
            "print(obs.PROCESS_METRICS.render())\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=120, check=True,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert 'tidb_copr_topn_select_total{path="block"} 0' in out.stdout
    assert 'tidb_copr_topn_select_total{path="full"} 0' in out.stdout


def test_counter_moves_by_one_a_read_under_its_path(corpus):
    small = Session(cop=CopClient())
    small.execute("create table s (k bigint primary key, a int)")
    small.execute("insert into s values " + ", ".join(
        f"({i}, {i % 7})" for i in range(50)))
    small.execute("analyze table s")
    sql = "select k, a from s order by a desc limit 5"
    rows, block, full = _block_reads(lambda: small.query(sql))
    assert [r[1] for r in rows] == [6] * 5 and (block, full) == (0, 1)
    tiled = _session(corpus, "tiled")
    for n in (1, 2):
        _, block, full = _block_reads(lambda: [
            tiled.query(SCAN_SQL[0]) for _ in range(n)])
        assert (block, full) == (n, 0)
    text = obs.PROCESS_METRICS.render()
    assert 'tidb_copr_topn_select_total{path="block"}' in text
    assert 'tidb_copr_topn_select_total{path="full"}' in text
    assert obs.lint_metrics([obs.PROCESS_METRICS]) == []
