"""The host decode of fetched packed row bitmasks (copr/rowbits.py).

`rowbits.decode` against the decode it replaced (unpack every tile,
concatenate, nonzero) over masks of every density, tile layouts and
LIMITs; and the row-returning reads that call it (copr/client.py
`_run_rows` on one device, tiled, and row-sharded over four virtual
devices; the row mode of `_run_frag_batch` and `_run_frag_tiled`) against
the host engine's answers.
"""

import jax
import numpy as np
import pytest

import tidb_tpu.copr.fragment as F
import tidb_tpu.plan.fragment as PF
from tidb_tpu.bench.tpch import load_lineitem
from tidb_tpu.copr import rowbits
from tidb_tpu.copr.client import CopClient
from tidb_tpu.session import Session
from sharded_client import sharded_client


def _dense(packs, counts, tile_rows, limit=None):
    """The decode before copr/rowbits.py (tiles of `tile_rows` flags but
    the last)."""
    parts = [np.unpackbits(p).astype(bool)[:c] for p, c in zip(packs, counts)]
    idx = np.nonzero(np.concatenate(parts))[0] if parts \
        else np.zeros(0, np.int64)
    return idx if limit is None else idx[:limit]


def _flags(n, kind, rng):
    m = np.zeros(n, bool)
    if kind == "one":
        m[n // 3] = True
    elif kind == "sparse":
        m[rng.random(n) < 1 / 23_000] = True
        m[[0, n - 1]] = True
    elif kind == "every64":
        m[5::64] = True
    elif kind == "sixteen_a_word":
        words = m[:n - n % 64].reshape(-1, 64)  # a view of m
        rows = np.arange(len(words))
        for _ in range(16):
            words[rows, rng.integers(0, 64, len(words))] = True
    elif kind == "half":
        m[rng.random(n) < 0.5] = True
    elif kind == "all":
        m[:] = True
    return m


def _packs(n, tile_rows, bucket, kind, seed=7):
    """(packs, counts) of an epoch of `n` rows in tiles of `tile_rows`,
    each padded to `bucket` flags with set padding bits (the decode must
    drop them by the tile's count, whatever they hold)."""
    flags = _flags(n, kind, np.random.default_rng(seed))
    packs, counts = [], []
    for lo in range(0, n, tile_rows):
        cnt = min(tile_rows, n - lo)
        tile = np.ones(bucket, bool)
        tile[:cnt] = flags[lo:lo + cnt]
        packs.append(np.packbits(tile))
        counts.append(cnt)
    return packs, counts, flags


# (rows, tile_rows, bucket): one tile; many tiles with a last one of 1 000
# rows (a multiple of neither 8 nor 64); a bucket of 1 064 flags packs to
# 133 bytes, a length that is not a multiple of 8
LAYOUTS = {
    "one_tile": (100_000, 1 << 17, 1 << 17),
    "many_tiles": (7 * 16_384 + 1_000, 16_384, 16_384),
    "ragged_bytes": (5 * 1_064 + 777, 1_064, 1_064),
}
KINDS = ["empty", "one", "sparse", "every64", "sixteen_a_word", "half",
         "all"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_decode_equals_the_dense_decode(layout, kind):
    n, tile_rows, bucket = LAYOUTS[layout]
    packs, counts, flags = _packs(n, tile_rows, bucket, kind)
    got = rowbits.decode(packs, counts, tile_rows)
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, np.flatnonzero(flags))
    np.testing.assert_array_equal(got, _dense(packs, counts, tile_rows))


def _limits(flags, tile_rows):
    """LIMITs that end inside a tile, at a tile boundary and past the
    last row."""
    idx = np.flatnonzero(flags)
    first_tile = int(np.sum(idx < tile_rows))
    return {"inside": max(1, first_tile // 2) if first_tile else 1,
            "boundary": first_tile, "past": len(idx) + 5}


@pytest.mark.parametrize("where", ["inside", "boundary", "past"])
@pytest.mark.parametrize("kind", ["sparse", "every64", "half", "all"])
def test_decode_stops_at_the_limit(kind, where):
    n, tile_rows, bucket = LAYOUTS["many_tiles"]
    packs, counts, flags = _packs(n, tile_rows, bucket, kind)
    limit = _limits(flags, tile_rows)[where]
    got = rowbits.decode(packs, counts, tile_rows, limit=limit)
    np.testing.assert_array_equal(
        got, _dense(packs, counts, tile_rows, limit=limit))
    assert len(got) == min(limit, int(flags.sum()))


def test_tiles_past_the_limit_are_not_decoded():
    n, tile_rows, bucket = LAYOUTS["many_tiles"]
    packs, counts, flags = _packs(n, tile_rows, bucket, "half")
    packs[2:] = [None] * (len(packs) - 2)  # reading one of these raises
    got = rowbits.decode(packs, counts, tile_rows, limit=10)
    np.testing.assert_array_equal(got, np.flatnonzero(flags)[:10])


# ---------------- the reads that decode ----------------

ROW_SCAN = ("select l_orderkey, l_linenumber, l_quantity, l_extendedprice "
            "from lineitem where l_shipdate between date '1995-03-01' "
            "and date '1995-03-31' and l_discount = 0.10 and l_quantity < 3 "
            "order by l_orderkey, l_linenumber")
ROW_QUERIES = {
    "row_scan": ROW_SCAN,
    "sparse": "select l_orderkey, l_linenumber, l_extendedprice from "
              "lineitem where l_discount = 0.10 and l_quantity < 3 "
              "order by l_orderkey, l_linenumber",
    "dense": "select l_orderkey, l_linenumber from lineitem "
             "where l_quantity < 40 order by l_orderkey, l_linenumber",
    "limit": "select l_orderkey, l_quantity from lineitem "
             "where l_quantity < 10 limit 700",
}
N_ROWS = 100_000


def _host_cop():
    """A client whose every read is the host engine's."""
    cop = CopClient()
    cop._prepare = lambda dag, snap, sparse_gate=True: (None, "host oracle")
    return cop


@pytest.fixture(scope="module")
def lineitem():
    s = Session()
    load_lineitem(s, N_ROWS)
    host = Session(s.storage, cop=_host_cop())
    return s, {name: host.query(sql) for name, sql in ROW_QUERIES.items()}


@pytest.fixture
def decodes(monkeypatch):
    """The calls of `rowbits.decode`, one a list entry."""
    calls = []
    orig = rowbits.decode

    def spy(*args, **kw):
        calls.append(args)
        return orig(*args, **kw)
    monkeypatch.setattr(rowbits, "decode", spy)
    return calls


@pytest.mark.parametrize("placement", ["single", "tiled", "mesh4"])
@pytest.mark.parametrize("name", list(ROW_QUERIES))
def test_row_reads_answer_as_the_host(lineitem, decodes, placement, name):
    s, want = lineitem
    if placement == "mesh4":
        cop = sharded_client(s.storage, devices=jax.devices()[:4])
        cop.TILE_ROWS = 32_768
    else:
        cop = CopClient()
        if placement == "tiled":
            cop.TILE_ROWS = 16_384
    assert Session(s.storage, cop=cop).query(ROW_QUERIES[name]) == want[name]
    assert len(decodes) == 1  # served by the device's bitmask
    assert len(want[name]) > 0


def test_the_oracle_is_the_host_engine(lineitem, decodes):
    s, want = lineitem
    host = Session(s.storage, cop=_host_cop())
    assert host.query(ROW_QUERIES["sparse"]) == want["sparse"]
    assert decodes == []


# ---------------- fragments in row mode ----------------

@pytest.fixture(scope="module")
def star():
    s = Session()
    s.execute("CREATE TABLE dim (dk INT NOT NULL PRIMARY KEY, "
              "seg VARCHAR(10))")
    s.execute("CREATE TABLE fact (fid INT NOT NULL PRIMARY KEY, dk INT, "
              "qty INT, amount DECIMAL(10,2))")
    s.execute("INSERT INTO dim VALUES (1,'auto'),(2,'steel'),(3,'auto')")
    rng = np.random.default_rng(5)
    rows = [f"({i},{int(rng.integers(1, 5))},{int(rng.integers(0, 500))},"
            f"{i % 97}.25)" for i in range(5000)]
    s.execute("INSERT INTO fact VALUES " + ",".join(rows))
    safe = s.storage.safe_ts()
    for store in s.storage.tables.values():
        store.compact(safe)
    return s


FRAG_ROWS = {
    "sparse": "SELECT fid, seg, amount FROM fact, dim WHERE fact.dk = "
              "dim.dk AND qty = 7 AND seg = 'auto' ORDER BY fid",
    "dense": "SELECT fid, seg FROM fact, dim WHERE fact.dk = dim.dk "
             "AND qty < 400 ORDER BY fid",
}


def _host_join(s, sql):
    orig = PF.apply_fragments
    PF.apply_fragments = lambda p: p
    try:
        return s.query(sql)
    finally:
        PF.apply_fragments = orig


@pytest.mark.parametrize("name", list(FRAG_ROWS))
@pytest.mark.parametrize("run", ["_run_frag_batch", "_run_frag_tiled"])
def test_fragment_row_mode_answers_as_the_host(star, monkeypatch, decodes,
                                               run, name):
    def boom(frag, snaps):
        raise AssertionError("host fragment fallback taken")
    monkeypatch.setattr(F, "_host_fragment", boom)
    modes = []
    orig = getattr(F, run)

    def spy(cop, frag, snaps, prepared, spans, builds, *rest, **kw):
        modes.append(rest[-1] if rest else kw.get("mode"))
        return orig(cop, frag, snaps, prepared, spans, builds, *rest, **kw)
    monkeypatch.setattr(F, run, spy)
    cop = CopClient()
    if run == "_run_frag_tiled":
        cop.TILE_ROWS = 1024  # 5 000 probe rows: 5 tiles
    sql = FRAG_ROWS[name]
    got = Session(star.storage, cop=cop).query(sql)
    assert "rows" in modes
    assert len(decodes) == 1
    want = _host_join(star, sql)
    assert got == want and len(want) > 0
