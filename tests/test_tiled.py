"""Tiled (multi-tile) device execution must be bit-identical to single-tile.

The tiling seam (copr/client.py _stage_tiles) is the TPU answer to the
reference's region-task split + streaming coprocessor (reference:
store/tikv/coprocessor.go:248 buildCopTasks, distsql/stream.go): epochs
larger than TILE_ROWS stream through the fused kernels as fixed-shape
tiles whose partials merge exactly (limb sums are additive; min/max merge
against sentinels; float blocks concatenate and the host sums in f64).

These tests force tiny TILE_ROWS so a few thousand rows exercise the
multi-tile paths, and compare against the default single-tile client.
"""

import numpy as np
import pytest

from tidb_tpu.bench.tpch import TPCH_Q1, TPCH_Q6, load_lineitem
from tidb_tpu.copr.client import CopClient
from sharded_client import sharded_client
from tidb_tpu.session import Session

N_ROWS = 4096
TILE = 1024  # -> 4 tiles


@pytest.fixture(scope="module")
def sessions():
    single = Session()
    load_lineitem(single, N_ROWS)
    tiled_cop = CopClient()
    tiled_cop.TILE_ROWS = TILE
    tiled = Session(single.storage, cop=tiled_cop)
    return single, tiled


QUERIES = [
    ("q1", TPCH_Q1),
    ("q6", TPCH_Q6),
    ("minmax", "SELECT l_returnflag, MIN(l_quantity), MAX(l_quantity), "
               "MIN(l_shipdate), MAX(l_extendedprice), COUNT(*) "
               "FROM lineitem GROUP BY l_returnflag ORDER BY l_returnflag"),
    ("topn", "SELECT l_orderkey, l_extendedprice FROM lineitem "
             "ORDER BY l_extendedprice DESC, l_orderkey LIMIT 9"),
    ("rows", "SELECT l_orderkey, l_quantity FROM lineitem "
             "WHERE l_quantity < 3.00 ORDER BY l_orderkey, l_linenumber"),
    ("scalar", "SELECT SUM(l_extendedprice * l_discount) FROM lineitem "
               "WHERE l_shipdate >= '1994-01-01'"),
]


@pytest.mark.parametrize("name,sql", QUERIES)
def test_tiled_matches_single(sessions, name, sql):
    single, tiled = sessions
    assert tiled.query(sql) == single.query(sql)


def test_tiles_actually_split(sessions):
    single, tiled = sessions
    tiled.query(TPCH_Q6)
    tile_keys = [k for k in tiled.cop._col_cache if k[0] == "tile"]
    assert tile_keys, "multi-tile staging did not engage"
    tis = {k[-1] for k in tile_keys}
    assert tis == {0, 1, 2, 3}


def test_tiled_with_overlay_and_deletes(sessions):
    """Tiles cover the base epoch; txn deltas ride the overlay batch."""
    single, tiled = sessions
    s = Session(single.storage, cop=tiled.cop)
    s.execute("BEGIN")
    s.execute("DELETE FROM lineitem WHERE l_orderkey <= 40")
    s.execute("INSERT INTO lineitem VALUES "
              "(999999, 1, 1, 1, 1.00, 100.00, 0.05, 0.02, 'A', 'F', "
              "'1994-06-01', '1994-06-01', '1994-06-01')")
    got = s.query(TPCH_Q1)
    # oracle: default (single-tile) client over the same open transaction
    s2 = Session(single.storage)
    s2.txn = s.txn
    s2.in_explicit_txn = True
    want = s2.query(TPCH_Q1)
    s2.txn = None
    s2.in_explicit_txn = False
    s.execute("ROLLBACK")
    assert got == want


def test_tiled_distributed_mesh():
    """Tiles x shards: every tile row-sharded over the 8-device mesh."""
    single = Session()
    load_lineitem(single, N_ROWS)
    cop = sharded_client(single.storage)
    cop.TILE_ROWS = TILE
    dist = Session(single.storage, cop=cop)
    for _, sql in QUERIES:
        assert dist.query(sql) == single.query(sql)
