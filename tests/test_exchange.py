"""Mesh exchange tier: all_to_all row routing, distributed hc GROUP BY,
and partitioned (non-broadcast) joins must match single-device bit-for-bit.

Counterpart of the reference's MPP exchange modes (reference:
planner/core/fragment.go:45 hash-partition vs broadcast ExchangeSender,
store/tikv/mpp.go:372): parallel/exchange.py routes rows between devices
with one all_to_all; the sharded placement (copr/placement.py) uses it to
(a) partition group spaces for high-cardinality aggregation and (b) shard large builds by key
range with probe-row routing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sharded_client import sharded_client
from tidb_tpu.copr.placement import make_mesh
from tidb_tpu.parallel.exchange import capacity_for, mix_hash, route_rows
from tidb_tpu.session import Session

N_DEV = 8


def test_route_rows_delivers_every_row_exactly_once():
    mesh = make_mesh()
    m_total = 2048
    vals = np.arange(m_total, dtype=np.int32)
    dest_np = (vals * 7919) % N_DEV
    cap = capacity_for(m_total // N_DEV, N_DEV)

    def kern(dest, vals):
        recv, rv, ov = route_rows(dest, [vals], "shard", N_DEV, cap)
        return {"vals": recv[0].reshape(1, -1),
                "valid": rv.reshape(1, -1), "ov": ov}

    sh = NamedSharding(mesh, P("shard"))
    f = jax.jit(shard_map(
        kern, mesh=mesh, in_specs=(P("shard"), P("shard")),
        out_specs={"vals": P("shard", None), "valid": P("shard", None),
                   "ov": P()}))
    out = jax.device_get(f(jax.device_put(jnp.asarray(dest_np), sh),
                           jax.device_put(jnp.asarray(vals), sh)))
    assert int(out["ov"]) == 0
    for d in range(N_DEV):
        got = np.sort(out["vals"][d][out["valid"][d].astype(bool)])
        assert np.array_equal(got, np.sort(vals[dest_np == d])), d


def test_route_rows_detects_overflow():
    mesh = make_mesh()
    m_total = 2048
    dest_np = np.zeros(m_total, dtype=np.int32)  # all rows to device 0
    cap = 16

    def kern(dest):
        recv, rv, ov = route_rows(dest, [dest], "shard", N_DEV, cap)
        return ov

    sh = NamedSharding(mesh, P("shard"))
    f = jax.jit(shard_map(kern, mesh=mesh, in_specs=(P("shard"),),
                              out_specs=P()))
    assert int(f(jax.device_put(jnp.asarray(dest_np), sh))) > 0


def test_mix_hash_deterministic_and_spread():
    k = jnp.arange(4096, dtype=jnp.int32)
    h1 = np.asarray(mix_hash([k]))
    h2 = np.asarray(mix_hash([k]))
    assert np.array_equal(h1, h2)
    counts = np.bincount(np.abs(h1) % N_DEV, minlength=N_DEV)
    assert counts.min() > 4096 // N_DEV // 2  # roughly uniform


@pytest.fixture(scope="module")
def corpus():
    from tidb_tpu.bench.tpch_data import TPCH_DDL, generate_tpch, load_table

    single = Session()
    data = generate_tpch(0.01, 13)  # orders=15k: l_orderkey space > 8192
    for t in TPCH_DDL:
        load_table(single, t, data[t])
    return single


MESH = f"@mesh{N_DEV}"  # every tag of a sharded dispatch ends in it


def _engines(session, sql):
    return {r[3] for r in session.execute("EXPLAIN ANALYZE " + sql).rows
            if r[3]}


def test_distributed_hc_groupby(corpus):
    """Q3's full l_orderkey group space shards via the group exchange."""
    from tidb_tpu.bench.tpch_queries import TPCH_QUERIES

    dist = Session(corpus.storage, cop=sharded_client(corpus.storage))
    sql = TPCH_QUERIES["q3"]
    assert dist.query(sql) == corpus.query(sql)
    # Q3's full ORDER BY resolves, so the fused join+agg+topn cut
    # (device[fat]) serves it; device[hc] is the unfused candidate path
    assert _engines(dist, sql) & {"device[fat]" + MESH, "device[hc]" + MESH}


def test_partitioned_join(corpus):
    """Non-broadcast joins: the orders build shards by key range, probe
    rows route over the mesh, results stay bit-identical."""
    from tidb_tpu.bench.tpch_queries import TPCH_QUERIES

    cop = sharded_client(corpus.storage)
    cop.partition_join_threshold = 1000  # force orders (15k) to partition
    dist = Session(corpus.storage, cop=cop)
    for q, want_engines in (("q12", {"device[agg]"}),
                            ("q3", {"device[hc]", "device[fat]"}),
                            ("q5", {"device[agg]"})):
        sql = TPCH_QUERIES[q]
        assert dist.query(sql) == corpus.query(sql), q
        assert _engines(dist, sql) & {e + MESH for e in want_engines}, q
        part_keys = [k for k in cop._col_cache if "partb" in str(k)]
        assert part_keys, "partitioned build staging did not engage"


def test_partitioned_join_with_dml_visibility(corpus):
    """Deleted probe/build rows stay invisible through the exchange."""
    from tidb_tpu.bench.tpch_queries import TPCH_QUERIES

    cop = sharded_client(corpus.storage)
    cop.partition_join_threshold = 1000
    s = Session(corpus.storage, cop=cop)
    s.execute("BEGIN")
    s.execute("DELETE FROM orders WHERE o_orderkey < 2000")
    single = Session(corpus.storage)
    single.txn = s.txn
    single.in_explicit_txn = True
    sql = TPCH_QUERIES["q12"]
    got = s.query(sql)
    want = single.query(sql)
    single.txn = None
    single.in_explicit_txn = False
    s.execute("ROLLBACK")
    assert got == want
