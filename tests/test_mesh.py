"""Mesh plane: device-mesh sharded columnar epochs with partition-wise
execution (copr/mesh.py).

Runs under the 8 virtual CPU devices the conftest forces — the tier-1
simulation of a multi-chip host. Asserts the ISSUE-7 acceptance
criteria: results bit-identical to the single-device path for
scan/agg/TopN/join, epochs actually SHARDED (inspected via
`arr.sharding` / `addressable_shards`), sharded residency persistent
across queries, DML/epoch folds invalidating device buffers, and an
exact single-device fallback.
"""

import jax
import numpy as np
import pytest

from tidb_tpu import obs
from tidb_tpu.bench.tpch import TPCH_Q1, TPCH_Q6, load_lineitem
from tidb_tpu.copr import mesh as M
from tidb_tpu.copr.client import CopClient
from tidb_tpu.session import Session

N_ROWS = 20_000

TOPN_SQL = ("SELECT l_orderkey, l_extendedprice FROM lineitem "
            "ORDER BY l_extendedprice DESC, l_orderkey LIMIT 7")
ROWS_SQL = ("SELECT l_orderkey, l_quantity FROM lineitem "
            "WHERE l_quantity < 5.00 ORDER BY l_orderkey, l_quantity")


def make_plane(**kw):
    cfg = dict(enabled=True, shard_threshold_rows=512)
    cfg.update(kw)
    return M.MeshPlane(M.MeshConfig(**cfg))


def sharded_arrays(client):
    """All multi-device row-sharded arrays resident in a client's
    caches."""
    with client._lock:
        vals = list(client._col_cache.values()) \
            + list(client._mask_cache.values())
    out = []
    for arr in M._walk_arrays(vals):
        s = getattr(arr, "sharding", None)
        if s is None:
            continue
        if len(s.device_set) > 1 and not s.is_fully_replicated:
            out.append(arr)
    return out


def engines(session, sql):
    return {r[3] for r in session.execute(
        "EXPLAIN ANALYZE " + sql).rows if r[3]}


@pytest.fixture(scope="module")
def sessions():
    assert len(jax.devices()) >= 8, "conftest must provide 8 devices"
    single = Session(cop=CopClient())
    load_lineitem(single, N_ROWS)
    plane = make_plane()
    mesh = Session(single.storage, cop=plane.client_for(single.storage))
    return single, mesh, plane


class TestBitIdentical:
    def test_scan_agg(self, sessions):
        single, mesh, _ = sessions
        for sql in (TPCH_Q6, TPCH_Q1,
                    "select count(*), sum(l_quantity) from lineitem"):
            assert mesh.query(sql) == single.query(sql), sql

    def test_topn_and_rows(self, sessions):
        single, mesh, _ = sessions
        for sql in (TOPN_SQL, ROWS_SQL):
            assert mesh.query(sql) == single.query(sql), sql

    def test_engine_tag_names_mesh(self, sessions):
        _, mesh, plane = sessions
        eng = engines(mesh, TPCH_Q6)
        assert any("@mesh" in e for e in eng), eng


class TestShardedResidency:
    def test_epochs_sharded_across_all_devices(self, sessions):
        _, mesh, _ = sessions
        mesh.query(TPCH_Q6)
        arrs = sharded_arrays(mesh.cop)
        assert arrs, "no sharded epoch arrays resident"
        for arr in arrs:
            assert len(arr.sharding.device_set) == 8, arr.sharding
            devs = {str(sh.device) for sh in arr.addressable_shards}
            assert len(devs) == 8, devs
            # row-axis sharding: the mesh axis partitions dim 0
            spec = arr.sharding.spec
            assert tuple(spec)[0] == M.MeshPlane.AXIS, spec

    def test_residency_persists_across_queries(self, sessions):
        _, mesh, _ = sessions
        mesh.query(TPCH_Q6)  # warm
        before = obs.DEVICE_TRANSFER_BYTES.get()
        mesh.query(TPCH_Q6)
        assert obs.DEVICE_TRANSFER_BYTES.get() == before, \
            "sharded epoch re-staged on a warm query"

    def test_shard_stage_attributed(self):
        """A cold sharded query's staging records the `shard` placement
        stage — the per-operator attribution EXPLAIN ANALYZE / Top SQL
        read (the warm path records none: residency persists)."""
        single = Session(cop=CopClient())
        load_lineitem(single, 4096)
        plane = make_plane()
        mesh = Session(single.storage,
                       cop=plane.client_for(single.storage))
        mesh.query(TPCH_Q6)
        assert "shard" in mesh.last_stages, mesh.last_stages

    def test_placement_report_and_gauges(self, sessions):
        _, mesh, plane = sessions
        mesh.query(TPCH_Q6)
        rep = M.placement_report(mesh.cop)
        assert rep["sharded_arrays"] > 0
        assert len(rep["device_bytes"]) == 8
        assert all(b > 0 for b in rep["device_bytes"].values())
        per = plane.device_bytes()
        assert len(per) == 8 and sum(per.values()) > 0
        # the process plane's probe feeds the gauges the same way
        obs.MESH_DEVICES.set(plane.n_devices)
        assert obs.MESH_DEVICES.get() == 8


class TestJoins:
    @pytest.fixture(scope="class")
    def corpus(self):
        from tidb_tpu.bench.tpch_data import (
            TPCH_DDL,
            generate_tpch,
            load_table,
        )
        from tidb_tpu.bench.tpch_queries import TPCH_QUERIES

        single = Session(cop=CopClient())
        data = generate_tpch(0.01, 13)
        for t in TPCH_DDL:
            load_table(single, t, data[t])
        plane = make_plane()
        mesh = Session(single.storage,
                       cop=plane.client_for(single.storage))
        return single, mesh, TPCH_QUERIES

    def test_snowflake_joins_bit_identical(self, corpus):
        single, mesh, queries = corpus
        for q in ("q3", "q5", "q12"):
            assert mesh.query(queries[q]) == single.query(queries[q]), q

    def test_small_builds_replicate(self, corpus):
        """Dimension sides below replicate-threshold-bytes broadcast:
        fully-replicated device arrays cached per epoch."""
        _, mesh, queries = corpus
        mesh.query(queries["q5"])
        with mesh.cop._lock:
            vals = list(mesh.cop._col_cache.values())
        reps = [a for a in M._walk_arrays(vals)
                if getattr(a, "sharding", None) is not None
                and len(a.sharding.device_set) == 8
                and a.sharding.is_fully_replicated]
        assert reps, "no replicated build arrays resident"
        # broadcasting the builds counted as mesh reshard traffic
        assert obs.MESH_RESHARD_BYTES.get() > 0

    def test_build_and_probe_placements_do_not_alias(self, corpus):
        """One epoch can be BOTH a replicated broadcast build (in a
        join) and a row-sharded scan source: the two placements cache
        under distinct staging keys, so the solo scan stays genuinely
        sharded instead of hitting a replicated alias."""
        single, mesh, queries = corpus
        mesh.query(queries["q12"])  # orders is a broadcast build here
        orders = next(st for st in single.storage.tables.values()
                      if st.table.name == "orders")
        sql = ("SELECT o_orderstatus, COUNT(*) FROM orders "
               "GROUP BY o_orderstatus ORDER BY o_orderstatus")
        assert mesh.query(sql) == single.query(sql)
        eid = orders.epoch.epoch_id
        with mesh.cop._lock:
            rep_keys = [k for k in mesh.cop._col_cache
                        if k[0] == eid and k[-1] == "rep"]
            plain = [v for k, v in mesh.cop._col_cache.items()
                     if k[0] == eid and len(k) == 3
                     and isinstance(k[1], int)]
        assert rep_keys, "replicated build staging keys missing"
        sharded = [a for a in M._walk_arrays(plain)
                   if len(a.sharding.device_set) == 8
                   and not a.sharding.is_fully_replicated]
        assert sharded, "solo scan of a build table must stay sharded"

    def test_oversize_build_partitions(self, corpus):
        """A build past replicate-threshold-bytes stops replicating:
        it shards by key range and probe rows route over the mesh
        (the hash-partition exchange election by BYTES)."""
        single, _, queries = corpus
        plane = make_plane(replicate_threshold_bytes=1)
        part = Session(single.storage,
                       cop=plane.client_for(single.storage))
        got = part.query(queries["q12"])
        assert got == single.query(queries["q12"])
        assert any("partb" in str(k) for k in part.cop._col_cache), \
            "partitioned build staging did not engage"


class TestInvalidation:
    def test_dml_changes_results_and_fold_evicts(self):
        single = Session(cop=CopClient())
        plane = make_plane()
        load_lineitem(single, 4096)
        mesh = Session(single.storage,
                       cop=plane.client_for(single.storage))
        n0 = mesh.query("select count(*) from lineitem")[0][0]
        assert n0 == 4096
        # DML: overlay + visibility change must flow through the
        # sharded path (new visibility mask, same sharded epoch)
        mesh.execute("delete from lineitem where l_orderkey = 1")
        n1 = mesh.query("select count(*) from lineitem")[0][0]
        assert n1 < n0
        assert single.query("select count(*) from lineitem")[0][0] == n1
        # epoch fold (compaction) fires the storage epoch listeners:
        # the superseded epoch's device buffers evict EAGERLY
        store = next(iter(single.storage.tables.values()))
        old_eid = store.epoch.epoch_id
        with mesh.cop._lock:
            assert any(_refs_epoch(k, old_eid)
                       for k in mesh.cop._col_cache), "cache not warm"
        safe = single.storage.safe_ts()
        store.compact(safe)
        assert store.epoch.epoch_id != old_eid
        with mesh.cop._lock:
            stale = [k for k in list(mesh.cop._col_cache)
                     + list(mesh.cop._mask_cache)
                     if _refs_epoch(k, old_eid)]
        assert not stale, stale
        assert mesh.query("select count(*) from lineitem")[0][0] == n1


def _refs_epoch(key, eid) -> bool:
    return any(p == eid for p in key if isinstance(p, int))


def test_truncate_partition_keeps_epoch_listeners():
    """TRUNCATE PARTITION builds a fresh TableStore: it must re-adopt
    the storage's epoch listeners or that partition's folds would stop
    evicting the mesh client's device buffers."""
    s = Session(cop=CopClient())
    plane = make_plane()
    mc = plane.client_for(s.storage)
    s.execute("CREATE TABLE pt (a INT NOT NULL PRIMARY KEY) "
              "PARTITION BY HASH(a) PARTITIONS 2")
    s.execute("INSERT INTO pt VALUES (1),(2),(3),(4)")
    s.execute("ALTER TABLE pt TRUNCATE PARTITION p0")
    for st in s.storage.tables.values():
        assert mc.on_epoch_replaced in st.evict_hooks, st.table.name


def _spy_recorder(monkeypatch) -> list:
    """Every flight-recorder entry point, intercepted."""
    calls: list[str] = []
    for meth in ("note_pending", "collect", "note_compile"):
        orig = getattr(M.MeshFlightRecorder, meth)

        def spy(self, *a, _m=meth, _o=orig, **k):
            calls.append(_m)
            return _o(self, *a, **k)

        monkeypatch.setattr(M.MeshFlightRecorder, meth, spy)
    return calls


class TestFallback:
    def test_disabled_plane_hands_out_plain_client(self, monkeypatch):
        """A disabled plane shards nothing and queues no recorder work:
        its client is the plain class without a recorder, every epoch —
        far above the row threshold or not — is placed single, and the
        statement path never enters the flight recorder."""
        assert not make_plane(enabled=False).active
        calls = _spy_recorder(monkeypatch)
        old = M.get_plane().cfg
        try:
            M.configure(enabled=False, shard_threshold_rows=0)
            s = Session()
            assert type(s.cop) is CopClient
            assert s.cop.recorder is None
            load_lineitem(s, 2048)
            want = Session(s.storage, cop=CopClient()).query(TPCH_Q6)
            assert s.query(TPCH_Q6) == want
            assert not sharded_arrays(s.cop)
            eng = engines(s, TPCH_Q6)
            assert eng and all("@mesh" not in e for e in eng), eng
            assert calls == [], calls
            assert s.cop.take_mesh_note() is None
            assert s.cop.drain_mesh_warnings() == ()
            assert M.client_of(s.storage) is None
            assert M.shard_rows(s.storage) == []
            assert M.storage_rows(s.storage) == []
        finally:
            M.configure(enabled=old.enabled, axis_size=old.axis_size,
                        shard_threshold_rows=old.shard_threshold_rows,
                        replicate_threshold_bytes=(
                            old.replicate_threshold_bytes))

    @pytest.mark.parametrize("cfg", [dict(enabled=False),
                                     dict(axis_size=1), dict()],
                             ids=["disabled", "one-device", "active"])
    def test_client_for_is_the_one_client_class(self, cfg):
        """Whatever the plane's state, client_for hands out an object
        whose type is exactly CopClient, the same object to every
        session of a storage, and a different one to another storage;
        only an active plane gives it a flight recorder."""
        plane = make_plane(**cfg)
        st = Session(cop=CopClient()).storage
        s1 = Session(st, cop=plane.client_for(st))
        s2 = Session(st, cop=plane.client_for(st))
        assert type(s1.cop) is CopClient
        assert s1.cop is s2.cop, "sessions of one storage must share"
        assert s1.cop.plane is plane
        assert (s1.cop.recorder is not None) == plane.active
        other = Session(cop=CopClient()).storage
        assert plane.client_for(other) is not s1.cop, \
            "storages must not share"
        # another plane over the same storage gets its own client, and
        # the first plane still answers with the one it made
        assert make_plane(**cfg).client_for(st) is not s1.cop
        assert plane.client_for(st) is s1.cop

    def test_single_axis_inactive(self):
        plane = make_plane(axis_size=1)
        assert not plane.active

    def test_below_threshold_single_device_exact(self):
        """A small table under a live plane takes the EXACT single-
        device path: no multi-device arrays, plain engine tag."""
        single = Session(cop=CopClient())
        load_lineitem(single, 2048)
        plane = make_plane(shard_threshold_rows=1 << 20)
        mesh = Session(single.storage,
                       cop=plane.client_for(single.storage))
        assert mesh.query(TPCH_Q6) == single.query(TPCH_Q6)
        assert not sharded_arrays(mesh.cop)
        eng = engines(mesh, TPCH_Q6)
        assert eng and all("@mesh" not in e for e in eng), eng

    def test_default_session_uses_mesh_client(self):
        """Session() defaults route through the process plane: with 8
        devices visible the storage gets ONE shared client, attached to
        that plane and carrying its flight recorder; sessions of one
        storage share it and storages do not."""
        s1 = Session()
        s2 = Session(s1.storage)
        assert type(s1.cop) is CopClient
        assert s1.cop.plane is M.get_plane() and M.get_plane().active
        assert s1.cop.recorder is not None
        assert M.client_of(s1.storage) is s1.cop
        assert s1.cop is s2.cop, "sessions of one storage must share"
        other = Session()
        assert other.cop is not s1.cop, "storages must not share"

    def test_sharded_fact_joins_single_dimension_on_one_client(self):
        """One client, both placements: a fact table above the row
        threshold joined to a dimension table below it runs sharded
        (the probe decides; the dimension replicates) and equals the
        single-device answer, while the dimension scanned alone on the
        SAME client is placed single, without a mesh tag and without a
        multi-device array of its own."""
        single = Session(cop=CopClient())
        single.execute("create table dim (k int not null primary key, "
                       "tag varchar(8) not null)")
        single.execute("create table fact (id int not null primary key, "
                       "k int not null, v int not null)")
        single.execute("insert into dim values (1,'a'),(2,'b'),(3,'c')")
        vals = ",".join(f"({i},{i % 3 + 1},{i % 100})"
                        for i in range(1, 6001))
        single.execute(f"insert into fact values {vals}")
        single.storage.flush()
        plane = make_plane()  # threshold 512: fact shards, dim does not
        mesh = Session(single.storage,
                       cop=plane.client_for(single.storage))
        join = ("select dim.tag, sum(fact.v) from fact join dim "
                "on fact.k = dim.k group by dim.tag order by dim.tag")
        solo = "select count(*), min(k), max(k) from dim"
        assert mesh.query(join) == single.query(join)
        assert mesh.query(solo) == single.query(solo)
        assert any(e.endswith("@mesh8") for e in engines(mesh, join))
        assert engines(mesh, solo) == {"device"}
        dim = next(st for st in single.storage.tables.values()
                   if st.table.name == "dim")
        eid = dim.epoch.epoch_id
        with mesh.cop._lock:
            solo_arrays = [v for k, v in mesh.cop._col_cache.items()
                           if k[0] == eid and len(k) == 3
                           and isinstance(k[1], int)]
            spaces = {k[0] for k in mesh.cop._kernels}
        assert solo_arrays and all(
            len(a.sharding.device_set) == 1
            for a in M._walk_arrays(solo_arrays))
        assert spaces == {"single", "shard"}, spaces


class TestConfig:
    def test_mesh_section_parses(self, tmp_path):
        from tidb_tpu.config import Config, ConfigError
        p = tmp_path / "c.toml"
        p.write_text("[mesh]\nenabled = false\naxis-size = 4\n"
                     "shard-threshold-rows = 123\n"
                     "replicate-threshold-bytes = 456\n")
        cfg = Config.load(str(p))
        cfg.validate()
        assert cfg.mesh.enabled is False
        assert cfg.mesh.axis_size == 4
        assert cfg.mesh.shard_threshold_rows == 123
        assert cfg.mesh.replicate_threshold_bytes == 456
        p.write_text("[mesh]\naxis-size = -1\n")
        cfg = Config.load(str(p))
        with pytest.raises(ConfigError):
            cfg.validate()

    def test_seed_mesh_configures_process_plane(self, tmp_path):
        from tidb_tpu.config import Config
        old = M.get_plane().cfg
        try:
            p = tmp_path / "c.toml"
            p.write_text("[mesh]\nshard-threshold-rows = 777\n")
            cfg = Config.load(str(p))
            cfg.seed_mesh()
            assert M.get_plane().cfg.shard_threshold_rows == 777
        finally:
            M.configure(enabled=old.enabled, axis_size=old.axis_size,
                        shard_threshold_rows=old.shard_threshold_rows,
                        replicate_threshold_bytes=(
                            old.replicate_threshold_bytes))

    def test_status_payload(self):
        st = M.status()
        assert "enabled" in st and "devices" in st

    def test_config_section_mirrors_mesh_config(self):
        """config.MeshSection is a jax-free mirror of mesh.MeshConfig;
        they must never drift (fields AND defaults)."""
        import dataclasses
        from tidb_tpu.config import MeshSection
        mirror = {(f.name, f.default)
                  for f in dataclasses.fields(MeshSection)}
        owner = {(f.name, f.default)
                 for f in dataclasses.fields(M.MeshConfig)}
        assert mirror == owner
