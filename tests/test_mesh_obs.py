"""Mesh flight recorder: per-shard skew, HBM provenance, compile-storm
telemetry (copr/mesh.py MeshFlightRecorder + the EXPLAIN ANALYZE /
infoschema / event surfaces).

Runs under the 8 virtual CPU devices the conftest forces. Pins the
ISSUE-8 acceptance criteria: EXPLAIN ANALYZE shows per-shard rows +
skew ratio on sharded scans AND joins, a skewed join raises the
warning + tidb_events entry, the HBM provenance ledger's live bytes
sum to the per-device buffer gauge, scrapes never initialize a backend
while the plane is inactive, and the single-device CopClient statement
path does zero recorder work.
"""

from __future__ import annotations

import re
import threading

import jax
import pytest

from tidb_tpu import obs
from tidb_tpu.bench.tpch import TPCH_Q6, load_lineitem
from tidb_tpu.copr import mesh as M
from tidb_tpu.copr.client import CopClient
from tidb_tpu.session import Session
from tidb_tpu.util import failpoint

N_ROWS = 20_000

JOIN_SQL = ("select dim.tag, sum(fact.v) from fact join dim "
            "on fact.k = dim.k group by dim.tag order by dim.tag")

MESH_CELL = re.compile(r"^shards=(\d+) skew=(\d+\.\d+) "
                       r"rows=\[(-?\d+(,-?\d+)*)?\]")


def make_plane(**kw):
    cfg = dict(enabled=True, shard_threshold_rows=512)
    cfg.update(kw)
    return M.MeshPlane(M.MeshConfig(**cfg))


def mesh_cells(session, sql):
    rows = session.execute("EXPLAIN ANALYZE " + sql).rows
    return [r[5] for r in rows if r[5]]


@pytest.fixture(scope="module")
def sessions():
    assert len(jax.devices()) >= 8, "conftest must provide 8 devices"
    single = Session(cop=CopClient())
    load_lineitem(single, N_ROWS)
    plane = make_plane()
    mesh = Session(single.storage, cop=plane.client_for(single.storage))
    return single, mesh, plane


@pytest.fixture(scope="module")
def join_corpus():
    """A fact/dim join big enough to shard the probe side."""
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
    plane = make_plane()
    mesh = Session(single.storage, cop=plane.client_for(single.storage))
    return single, mesh, plane


# ==================== EXPLAIN ANALYZE mesh column ====================

class TestExplainAnalyzeMeshColumn:
    def test_sharded_scan_shape(self, sessions):
        single, mesh, plane = sessions
        cells = mesh_cells(mesh, TPCH_Q6)
        assert cells, "sharded scan produced no mesh cell"
        m = MESH_CELL.match(cells[0])
        assert m, cells[0]
        assert int(m.group(1)) == 8
        rows = [int(x) for x in m.group(3).split(",")]
        assert len(rows) == 8
        # per-shard survivors sum to the filter's total matches
        want = single.query(
            "select count(*) from lineitem where "
            "l_shipdate >= date '1994-01-01' and "
            "l_shipdate < date '1994-01-01' + interval '1' year and "
            "l_discount between 0.05 and 0.07 and l_quantity < 24"
        )[0][0]
        assert sum(rows) == want, (rows, want)

    def test_sharded_join_shape(self, join_corpus):
        single, mesh, _ = join_corpus
        assert mesh.query(JOIN_SQL) == single.query(JOIN_SQL)
        cells = mesh_cells(mesh, JOIN_SQL)
        assert cells, "sharded join produced no mesh cell"
        m = MESH_CELL.match(cells[0])
        assert m, cells[0]
        assert int(m.group(1)) == 8
        assert len(m.group(3).split(",")) == 8

    def test_single_device_has_empty_mesh_cell(self, sessions):
        single, _, _ = sessions
        rs = single.execute("EXPLAIN ANALYZE " + TPCH_Q6)
        assert rs.column_names[5] == "mesh"
        assert rs.column_names[-1] == "wait_profile"
        assert all(not r[5] for r in rs.rows), rs.rows


# ==================== skew detector ====================

class TestSkewDetector:
    def test_failpoint_skew_raises_warning_and_event(self, join_corpus):
        single, mesh, _ = join_corpus
        base = obs.MESH_SKEW_WARNINGS.get()
        with failpoint.failpoint("mesh/skew", 64.0):
            mesh.query(JOIN_SQL)
        assert obs.MESH_SKEW_WARNINGS.get() > base
        warns = [w for w in mesh.warnings if "mesh skew" in w[2]]
        assert warns, mesh.warnings
        assert "skew-warn-ratio" in warns[0][2]
        evs = [e for e in single.storage.obs.events.snapshot()
               if e["kind"] == "mesh_skew"]
        assert evs and "64.00" in evs[-1]["detail"]
        # queryable through information_schema.tidb_events too
        rows = mesh.query("select kind, severity from "
                          "information_schema.tidb_events "
                          "where kind = 'mesh_skew'")
        assert rows and rows[0][1] == "warn"

    def test_hot_range_skews_naturally(self, sessions):
        """A predicate matching only the lowest orderkeys keeps every
        survivor on shard 0 of the row-sharded epoch: skew ~= 8 crosses
        the default warn ratio with NO failpoint."""
        single, mesh, plane = sessions
        mesh.query("select count(*), sum(l_quantity) from lineitem "
                   "where l_orderkey <= 500")
        warns = [w for w in mesh.warnings if "mesh skew" in w[2]]
        assert warns, mesh.warnings
        assert obs.MESH_SKEW_RATIO.get() >= plane.cfg.skew_warn_ratio

    def test_skew_rides_topsql_and_slow_log(self, join_corpus):
        single, mesh, _ = join_corpus
        st = single.storage
        st.obs.topsql.configure(enabled=True, window_s=3600)
        mesh.execute("set tidb_slow_log_threshold = 0")
        try:
            mesh.query(JOIN_SQL)
        finally:
            mesh.execute("set tidb_slow_log_threshold = 100000")
        # per-operator max-shard share in the Top SQL rows
        rows = mesh.query(
            "select operator, max_shard_share from "
            "information_schema.tidb_top_sql "
            "where digest_text like '%fact join dim%' "
            "and operator <> '(stmt)'")
        assert rows, "no operator rows in tidb_top_sql"
        assert any(r[1] and r[1] > 0 for r in rows), rows
        # and the slow log's mesh_skew column
        rows = mesh.query(
            "select mesh_skew from information_schema.slow_query "
            "where query like '%fact join dim%'")
        assert rows and any(r[0] >= 1.0 for r in rows), rows
        st.obs.topsql.configure(enabled=False)


# ==================== per-shard ring + HBM ledger ====================

class TestRecorderSurfaces:
    def test_tidb_mesh_shards_rows(self, sessions):
        _, mesh, _ = sessions
        mesh.query(TPCH_Q6)
        rows = mesh.query(
            "select digest, kind, operator, dispatches, shards, "
            "last_shard_rows, max_skew, in_rows, out_rows "
            "from information_schema.tidb_mesh_shards")
        assert rows, "dispatch ring empty"
        ent = next(r for r in rows if r[1] == "agg")
        assert len(ent[0]) == 16 and ent[4] == 8
        assert ent[3] >= 1 and ent[7] > 0
        assert len(ent[5].split(",")) == 8

    def test_ledger_sums_to_device_buffer_gauge(self, sessions):
        _, mesh, plane = sessions
        mesh.query(TPCH_Q6)
        # back-to-back reads share one memoized walk: the '(device)'
        # total rows must equal the per-device gauge source exactly
        totals = {r[0]: (r[5], r[6])
                  for r in M.storage_rows(mesh.storage)
                  if r[3] == "total"}
        per = plane.device_bytes()
        assert len(totals) == 8
        for dev, b in per.items():
            live, peak = totals[dev]
            assert live == b, (dev, live, b)
            assert peak >= live
        # the labeled gauge the probe publishes agrees
        obs.run_gauge_probes()  # process plane may differ; set directly
        for dev, b in per.items():
            obs.DEVICE_BUFFER_BYTES.set(b, device=dev)
            assert obs.DEVICE_BUFFER_BYTES.get(device=dev) == b

    def test_ledger_classifies_replicas(self, join_corpus):
        _, mesh, _ = join_corpus
        mesh.query(JOIN_SQL)
        kinds = {r[3] for r in M.storage_rows(mesh.storage)}
        assert "epoch" in kinds
        # the dim build broadcast-replicates and the perm table rides
        # along: provenance must name them
        assert "replica" in kinds or "perm" in kinds, kinds
        # table attribution resolves through the live epoch map
        names = {r[1] for r in M.storage_rows(mesh.storage)}
        assert "fact" in names, names

    def test_ring_is_bounded(self):
        single = Session(cop=CopClient())
        load_lineitem(single, 4096)
        plane = make_plane(shard_ring_cap=3)
        mesh = Session(single.storage,
                       cop=plane.client_for(single.storage))
        for q in range(6):
            mesh.query("select count(*), sum(l_quantity) from lineitem "
                       f"where l_orderkey > {q}")
        with mesh.cop.recorder._lock:
            assert len(mesh.cop.recorder._ring) <= 3

    def test_failed_statement_discards_pending_stats(self, sessions):
        """A statement that dies before the engine collects (interrupt,
        plan error) must not leak its queued per-shard stats into the
        next statement's mesh accounting."""
        import numpy as np
        _, mesh, _ = sessions
        rec = mesh.cop.recorder
        rec.note_pending("agg", "stalepending00ff",
                         np.asarray([[5, 5]] * 8, dtype=np.int32))
        with pytest.raises(Exception):
            mesh.execute("select no_such_col from lineitem")
        assert not getattr(rec._tls, "pending", None), \
            "failed statement left pending per-shard stats queued"
        mesh.query(TPCH_Q6)
        with rec._lock:
            assert "stalepending00ff" not in rec._ring

    def test_zero_match_bits_dispatch_keeps_shard_count(self):
        """A rows-mode dispatch whose filter matches zero rows is still
        an 8-way dispatch: shards must come from the observed arrays,
        not the (all-zero, hence absent) count basis."""
        import numpy as np
        import types
        plane = make_plane()
        rec = M.MeshFlightRecorder(plane)
        bits = types.SimpleNamespace(addressable_shards=[
            types.SimpleNamespace(device=types.SimpleNamespace(id=i),
                                  data=np.zeros(4, dtype=np.uint8))
            for i in range(8)])
        rec.note_pending("frag-rows", "zeromatchbits000", {"bits": bits})
        note = rec.collect()
        assert note is not None and note["shards"] == 8
        assert note["rows"] == [0] * 8
        with rec._lock:
            assert rec._ring["zeromatchbits000"]["shards"] == 8

    def test_bits_shard_counts_axis_ordered(self):
        """Per-shard popcounts list in device-id order, not device-name
        lexicographic order ('10' must not sort between '1' and '2')."""
        import numpy as np
        import types
        shards = [types.SimpleNamespace(
            device=types.SimpleNamespace(id=i),
            data=np.asarray([0xFF] * i, dtype=np.uint8))
            for i in range(12)]
        shards.reverse()  # arrival order must not matter either
        arr = types.SimpleNamespace(addressable_shards=shards)
        counts = M._bits_shard_counts(arr)
        assert counts.tolist() == [8 * i for i in range(12)]

    def test_partitioned_join_counts_routed_bytes(self, join_corpus):
        """A partitioned-build agg join exchanges probe rows inside
        the kernel: the reshard counter and the ring's routed_bytes
        must both see the routed payload."""
        single, _, _ = join_corpus
        plane = make_plane(replicate_threshold_bytes=1)
        part = Session(single.storage,
                       cop=plane.client_for(single.storage))
        base = obs.MESH_RESHARD_BYTES.get()
        assert part.query(JOIN_SQL) == single.query(JOIN_SQL)
        assert any("partb" in str(k) for k in part.cop._col_cache), \
            "partitioned build staging did not engage"
        assert obs.MESH_RESHARD_BYTES.get() > base, \
            "routed join did not count reshard bytes"
        with part.cop.recorder._lock:
            routed = [e for e in part.cop.recorder._ring.values()
                      if e["routed_bytes"] > 0]
        assert routed, "no ring entry recorded routed bytes"
        rows = part.query(
            "select routed_bytes from "
            "information_schema.tidb_mesh_shards "
            "where routed_bytes > 0")
        assert rows, "routed bytes missing from tidb_mesh_shards"

    def test_recorder_has_no_background_thread(self, sessions):
        _, mesh, _ = sessions
        mesh.query(TPCH_Q6)
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("titpu-mesh")]

    def test_debug_payload_shape(self, sessions):
        import json
        _, mesh, _ = sessions
        mesh.query(TPCH_Q6)
        payload = M.debug_payload()
        assert set(payload) >= {"status", "dispatches", "compiles",
                                "storage"}
        json.dumps(payload)  # must stay JSON-serializable


# ==================== HBM watermark ====================

def test_hbm_watermark_event_edge_triggered():
    single = Session(cop=CopClient())
    load_lineitem(single, 4096)
    # a 1KiB "capacity" puts every device over the watermark
    plane = make_plane(hbm_bytes=1024, hbm_watermark_fraction=0.5)
    mesh = Session(single.storage, cop=plane.client_for(single.storage))
    mesh.query(TPCH_Q6)
    base = obs.MESH_HBM_WATERMARK.get(device="TFRT_CPU_0")
    plane.device_bytes()
    evs = [e for e in single.storage.obs.events.snapshot()
           if e["kind"] == "mesh_hbm_watermark"]
    assert evs, "no watermark event"
    assert obs.MESH_HBM_WATERMARK.get(device="TFRT_CPU_0") > base
    # edge-triggered: a second scrape above the line does not re-emit
    n = len(evs)
    plane.device_bytes()
    evs = [e for e in single.storage.obs.events.snapshot()
           if e["kind"] == "mesh_hbm_watermark"]
    assert len(evs) == n


# ==================== compile observability ====================

class TestCompileObservability:
    def test_compiles_counted_per_signature(self, sessions):
        _, mesh, _ = sessions
        mesh.query(TPCH_Q6)
        comps = mesh.cop.recorder.snapshot()["compiles"]
        assert comps, "no compiles observed"
        assert all(c["count"] >= 1 and c["total_s"] >= 0
                   for c in comps)
        assert obs.MESH_COMPILES.get(kind="agg") >= 1

    def test_recompile_storm_emits_event(self):
        plane = make_plane()
        rec = M.MeshFlightRecorder(plane)
        from tidb_tpu.store.storage import Storage
        st = Storage()
        rec.obs = st.obs
        base = obs.MESH_RECOMPILE_STORMS.get()
        for i in range(M.MeshFlightRecorder.STORM_COMPILES):
            rec.note_compile("agg", "sig-abc", 0.01,
                             full_key=("shard", "agg", "k", 256 << i))
        assert obs.MESH_RECOMPILE_STORMS.get() == base + 1
        evs = [e for e in st.obs.events.snapshot()
               if e["kind"] == "mesh_compile_storm"]
        assert evs and "sig-abc" in evs[0]["detail"]
        # further compiles of the same signature do not re-trip
        rec.note_compile("agg", "sig-abc", 0.01)
        assert obs.MESH_RECOMPILE_STORMS.get() == base + 1

    def test_compile_ring_bounded(self):
        plane = make_plane()
        rec = M.MeshFlightRecorder(plane)
        for i in range(M.MeshFlightRecorder.COMPILE_CAP + 32):
            rec.note_compile("agg", f"sig-{i}", 0.0)
        with rec._lock:
            assert len(rec._compiles) <= \
                M.MeshFlightRecorder.COMPILE_CAP


# ==================== scrape cost + inactive-plane hygiene ==========

class TestScrapeHygiene:
    def test_device_bytes_memoized_per_generation(self, sessions):
        _, mesh, plane = sessions
        mesh.query(TPCH_Q6)
        t1 = M.telemetry(mesh.cop)
        t2 = M.telemetry(mesh.cop)
        assert t1 is t2, "telemetry walk not memoized across scrapes"
        walks = []
        orig = M._walk_arrays

        def counting(o):
            walks.append(1)
            return orig(o)

        M._walk_arrays = counting
        try:
            plane.device_bytes()
            assert not walks, "memoized scrape still walked arrays"
            # a cache mutation invalidates the memo
            with mesh.cop._lock:
                mesh.cop._col_cache[("__probe__",)] = ()
            plane.device_bytes()
            assert walks, "cache mutation did not refresh telemetry"
        finally:
            M._walk_arrays = orig
            with mesh.cop._lock:
                del mesh.cop._col_cache[("__probe__",)]

    def test_inactive_scrape_never_inits_backend(self, monkeypatch):
        old = M.get_plane().cfg
        try:
            M.configure(enabled=False)

            def boom(*a, **k):
                raise AssertionError("scrape initialized a JAX backend")

            monkeypatch.setattr(jax, "devices", boom)
            monkeypatch.setattr(jax, "local_devices", boom,
                                raising=False)
            st = M.status()
            assert st["enabled"] is False
            obs.run_gauge_probes()
            M.debug_payload()
        finally:
            monkeypatch.undo()
            M.configure(enabled=old.enabled, axis_size=old.axis_size,
                        shard_threshold_rows=old.shard_threshold_rows,
                        replicate_threshold_bytes=(
                            old.replicate_threshold_bytes))


# ==================== zero-work on the plain client =================

def test_plain_client_statement_path_does_zero_recorder_work(
        monkeypatch):
    """A client without a plane must not touch the recorder at all: no
    pendings, no collections, no ring allocations — asserted by
    intercepting every recorder entry point. (A disabled plane's client
    is held to the same in tests/test_mesh.py::TestFallback.)"""
    calls: list[str] = []
    for meth in ("note_pending", "collect", "note_compile"):
        orig = getattr(M.MeshFlightRecorder, meth)

        def spy(self, *a, _m=meth, _o=orig, **k):
            calls.append(_m)
            return _o(self, *a, **k)

        monkeypatch.setattr(M.MeshFlightRecorder, meth, spy)
    s = Session(cop=CopClient())
    s.execute("create table z (a int primary key, b int)")
    s.execute("insert into z values (1,2),(2,3),(3,4)")
    s.query("select sum(b) from z where a >= 1")
    s.query("explain analyze select sum(b) from z where a >= 1")
    assert calls == [], calls
    # without a recorder the hooks are allocation-free constants
    assert s.cop.recorder is None
    assert s.cop.take_mesh_note() is None
    assert s.cop.drain_mesh_warnings() == ()


def test_cluster_mesh_tables_fan_out_local(sessions):
    """cluster_mesh_shards / cluster_mesh_storage materialize over the
    diag plane (single-member: the local short-circuit) with the
    instance column leading and error trailing."""
    _, mesh, _ = sessions
    mesh.query(TPCH_Q6)
    rows = mesh.query("select instance, digest, kind, error from "
                      "information_schema.cluster_mesh_shards")
    assert rows and all(r[0] == "local" and r[3] is None for r in rows)
    rows = mesh.query("select instance, device, kind, bytes, error "
                      "from information_schema.cluster_mesh_storage")
    assert rows and all(r[3] is None or r[3] >= 0 for r in rows)
