"""Cluster-wide diagnostics plane: cluster_* memtables, cross-server
trace stitching, membership, and the metrics time-series.

A two-server cluster (leader + socket follower, no shared disk) must
answer `information_schema.cluster_*` queries with rows from BOTH
servers, a TRACE crossing the wire must show the peer's span subtree
stitched into the local tree, and a dead/slow peer must degrade to an
error row + warning inside the BO_RPC budget — never a failed query
(reference: TiDB 4.0 infoschema/cluster.go + memtable_reader.go fan-out;
Dapper-style trace propagation for the cross-process spans)."""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.request

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from mysql_client import MiniClient  # noqa: E402

from tidb_tpu import obs  # noqa: E402
from tidb_tpu.rpc.client import RpcOptions  # noqa: E402
from tidb_tpu.session import Session  # noqa: E402
from tidb_tpu.store.storage import Storage  # noqa: E402
from tidb_tpu.util import failpoint  # noqa: E402

OPTS = RpcOptions(connect_timeout_ms=1000, request_timeout_ms=4000,
                  backoff_budget_ms=3000, lock_budget_ms=8000,
                  lease_ms=2000)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    yield
    failpoint.disable_all()


@pytest.fixture()
def cluster(tmp_path):
    leader = Storage(str(tmp_path / "leader"), shared=True,
                     rpc_listen="127.0.0.1:0", rpc_options=OPTS)
    follower = Storage(str(tmp_path / "follower"),
                       remote=f"127.0.0.1:{leader.rpc_server.port}",
                       rpc_options=OPTS)
    try:
        yield leader, follower
    finally:
        follower.close()
        leader.close()


# ==================== cluster_* memtables ====================

def test_cluster_info_rows_from_both_servers(cluster):
    leader, follower = cluster
    sl, sf = Session(leader), Session(follower)
    for s in (sl, sf):
        rows = s.execute(
            "select instance, type, server_id, uptime_s, error "
            "from information_schema.cluster_info").rows
        roles = {r[1] for r in rows}
        assert roles == {"leader", "follower"}, rows
        assert {r[0] for r in rows} == \
            {leader.diag_address, follower.diag_address}
        for r in rows:
            assert r[4] is None  # no error rows on the happy path
            assert r[3] >= 0


def test_cluster_statements_and_slow_query_fan_out(cluster):
    leader, follower = cluster
    sl, sf = Session(leader), Session(follower)
    sl.execute("create table t (id bigint primary key, v bigint)")
    sl.execute("insert into t values (1, 10)")
    # distinct digests on each server, and one slow entry per server
    sl.execute("set tidb_slow_log_threshold = 0")
    sf.execute("set tidb_slow_log_threshold = 0")
    sl.execute("select v from t where id = 1")
    sf.execute("select sum(v) from t")
    sl.execute("set tidb_slow_log_threshold = 100000")
    sf.execute("set tidb_slow_log_threshold = 100000")

    rows = sl.execute(
        "select instance, digest_text from "
        "information_schema.cluster_statements_summary").rows
    by_inst = {r[0]: [] for r in rows}
    for inst, text in rows:
        by_inst[inst].append(text)
    assert any("select v from t" in t
               for t in by_inst[leader.diag_address])
    assert any("select sum ( v ) from t" in t
               for t in by_inst[follower.diag_address])

    rows = sf.execute(
        "select instance, query, error from "
        "information_schema.cluster_slow_query").rows
    insts = {r[0] for r in rows if r[2] is None}
    assert leader.diag_address in insts
    assert follower.diag_address in insts


def test_cluster_processlist_shows_both_servers_connections(cluster):
    from tidb_tpu.server.server import Server

    leader, follower = cluster
    srv_l = Server(leader, host="127.0.0.1", port=0)
    srv_f = Server(follower, host="127.0.0.1", port=0)
    srv_l.start()
    srv_f.start()
    cl = cf = None
    try:
        cl = MiniClient("127.0.0.1", srv_l.port)
        cf = MiniClient("127.0.0.1", srv_f.port)
        cl.query("select 1")
        cf.query("select 1")
        s = Session(leader)
        rows = s.execute(
            "select instance, id, user, command, error "
            "from information_schema.cluster_processlist").rows
        good = [r for r in rows if r[4] is None]
        assert {r[0] for r in good} == \
            {leader.diag_address, follower.diag_address}
        assert all(r[1] is not None for r in good)
    finally:
        for c in (cl, cf):
            if c is not None:
                c.close()
        srv_f.close()
        srv_l.close()


def test_cluster_load_reports_device_telemetry(cluster):
    leader, follower = cluster
    sl = Session(leader)
    sl.execute("create table t (id bigint primary key, v bigint)")
    sl.execute("insert into t values (1, 1), (2, 2)")
    sl.execute("select sum(v) from t")  # touches the device path
    rows = sl.execute(
        "select instance, device_type, name, value from "
        "information_schema.cluster_load").rows
    names = {r[2] for r in rows}
    for want in ("tidb_device_transfer_bytes", "tidb_device_buffer_bytes",
                 "tidb_jit_cache_entries", "tidb_process_rss_bytes"):
        assert want in names, want
    rss = [r for r in rows if r[2] == "tidb_process_rss_bytes"]
    assert {r[0] for r in rss} == \
        {leader.diag_address, follower.diag_address}
    assert all(r[3] > 0 for r in rss)
    assert all(r[1] == "host" for r in rss)
    dev = [r for r in rows if r[2] == "tidb_device_transfer_bytes"]
    assert all(r[1] == "device" for r in dev)


# ==================== cross-server trace stitching ====================

def test_cross_server_trace_contains_stitched_remote_spans(cluster):
    leader, follower = cluster
    sl = Session(leader)
    rows = sl.execute(
        "trace select instance from information_schema.cluster_info").rows
    ops = [(r[0].strip(), r[0], r[1], r[2]) for r in rows
           if r[1] is not None]
    rpc_rows = [r for r in ops if r[0].startswith("rpc.diag_info")]
    remote_rows = [r for r in ops if r[0].startswith("remote.diag_info")]
    assert rpc_rows, [r[0] for r in ops]
    assert remote_rows, "no remote span subtree was stitched"
    # sane timestamps: the remote subtree sits inside its rpc span,
    # which sits inside the root (ms, with rounding slack)
    root_end = rows[0][1] + rows[0][2]
    rpc = rpc_rows[0]
    remote = remote_rows[0]
    assert remote[2] >= rpc[2] - 0.001
    assert remote[2] + remote[3] <= rpc[2] + rpc[3] + 1.0
    assert rpc[2] + rpc[3] <= root_end + 1.0
    # the remote subtree is nested DEEPER than the rpc span
    assert len(rpc[1]) - len(rpc[0]) < len(remote[1]) - len(remote[0])


def test_follower_trace_shows_rpc_spans_for_coordination(cluster):
    """A data query traced on the follower surfaces the TSO/WAL hops
    that used to be opaque wall-clock gaps."""
    leader, follower = cluster
    sl, sf = Session(leader), Session(follower)
    sl.execute("create table t (id bigint primary key, v bigint)")
    sl.execute("insert into t values (1, 10)")
    rows = sf.execute("trace select v from t").rows
    ops = [r[0].strip() for r in rows if r[1] is not None]
    assert any(o.startswith("rpc.") for o in ops), ops


# ==================== degradation: dead / slow peers ====================

def test_peer_down_failpoint_degrades_to_error_row(cluster):
    leader, follower = cluster
    sl = Session(leader)
    with failpoint.failpoint("diag/peer-down", True):
        t0 = time.monotonic()
        rows = sl.execute(
            "select instance, type, error "
            "from information_schema.cluster_info").rows
        elapsed = time.monotonic() - t0
        assert elapsed < OPTS.backoff_budget_ms / 1000.0 + 2.0
        warnings = sl.execute("show warnings").rows
    assert failpoint.hits("diag/peer-down") >= 1
    good = [r for r in rows if r[2] is None]
    bad = [r for r in rows if r[2] is not None]
    assert [r[0] for r in good] == [leader.diag_address]
    assert [r[0] for r in bad] == [follower.diag_address]
    assert "diag/peer-down" in bad[0][2]
    assert len(warnings) == 1 and warnings[0][0] == "Warning"
    assert follower.diag_address in warnings[0][2]
    # @@warning_count gates the client's SHOW WARNINGS fetch; table-less
    # reads preserve the list (MySQL), table-using statements reset it
    assert sl.execute("select @@warning_count").rows == [(1,)]
    sl.execute("select * from information_schema.engines")
    assert sl.execute("show warnings").rows == []
    assert sl.execute("select @@warning_count").rows == [(0,)]


def test_slow_peer_failpoint_still_answers(cluster):
    leader, follower = cluster
    sl = Session(leader)
    with failpoint.failpoint("diag/slow-peer", 0.05):
        rows = sl.execute(
            "select instance, error "
            "from information_schema.cluster_info").rows
    assert failpoint.hits("diag/slow-peer") >= 1
    assert {r[0] for r in rows} == \
        {leader.diag_address, follower.diag_address}
    assert all(r[1] is None for r in rows)


def test_killed_peer_degrades_within_budget(cluster):
    leader, follower = cluster
    sl = Session(leader)
    fol_addr = follower.diag_address
    assert sl.execute("select count(*) from "
                      "information_schema.cluster_info").rows == [(2,)]
    # a CRASH (no clean deregistration): the peer's endpoints vanish but
    # its membership entry survives until the lease horizon — queries in
    # that window degrade to an error row, bounded by the diag budget
    follower.diag_listener.close()
    follower._rpc_client.close()
    t0 = time.monotonic()
    rows = sl.execute(
        "select instance, error "
        "from information_schema.cluster_info").rows
    elapsed = time.monotonic() - t0
    assert elapsed < OPTS.backoff_budget_ms / 1000.0 + 5.0
    bad = [r for r in rows if r[1] is not None]
    assert [r[0] for r in bad] == [fol_addr]
    good = [r for r in rows if r[1] is None]
    assert [r[0] for r in good] == [leader.diag_address]


def test_cleanly_closed_peer_leaves_membership(cluster):
    """A clean Storage.close() deregisters: no lingering error rows, no
    spurious warnings, no per-query budget burned on the gone peer."""
    leader, follower = cluster
    sl = Session(leader)
    assert sl.execute("select count(*) from "
                      "information_schema.cluster_info").rows == [(2,)]
    follower.close()
    t0 = time.monotonic()
    rows = sl.execute(
        "select instance, error "
        "from information_schema.cluster_info").rows
    assert time.monotonic() - t0 < 2.0
    assert rows == [(leader.diag_address, None)]
    assert sl.execute("show warnings").rows == []


def test_leader_down_surfaces_error_row_on_follower(cluster):
    """A follower whose leader is gone must NOT report a silently
    shrunken single-server cluster: the leader stays listed as an error
    row + warning (the incident the cluster tables exist for)."""
    leader, follower = cluster
    leader_addr = leader.diag_address
    sf = Session(follower)
    assert len(sf.execute("select instance from "
                          "information_schema.cluster_info").rows) == 2
    leader.rpc_server.close()
    t0 = time.monotonic()
    rows = sf.execute(
        "select instance, type, error "
        "from information_schema.cluster_info").rows
    elapsed = time.monotonic() - t0
    assert elapsed < 4 * OPTS.backoff_budget_ms / 1000.0 + 5.0
    bad = {r[0]: r for r in rows if r[2] is not None}
    assert leader_addr in bad
    good = [r for r in rows if r[2] is None]
    assert [r[0] for r in good] == [follower.diag_address]
    assert sf.execute("show warnings").rows


# ==================== membership on /status ====================

def test_transport_health_and_status_carry_members(cluster):
    from tidb_tpu.server.server import Server

    leader, follower = cluster
    h = leader.transport_health()
    assert h["mode"] == "socket-leader"
    roles = {m["role"]: m for m in h["members"]}
    assert roles["leader"]["addr"] == leader.diag_address
    assert roles["follower"]["addr"] == follower.diag_address
    assert roles["follower"]["hb_age_s"] < 3 * OPTS.lease_ms / 1000.0
    assert roles["follower"]["id"] == follower.coord.node_id

    hf = follower.transport_health()
    assert hf["diag_address"] == follower.diag_address
    assert {m["role"] for m in hf["members"]} == {"leader", "follower"}

    srv = Server(follower, host="127.0.0.1", port=0,
                 status_port=0, status_host="127.0.0.1")
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.status_port}/status",
                timeout=10) as resp:
            status = json.load(resp)
        members = status["transport"]["members"]
        assert {m["role"] for m in members} == {"leader", "follower"}
    finally:
        srv.close()


# ==================== metrics history / metrics_summary ====================

def test_metrics_summary_and_history_route():
    from tidb_tpu.server.server import Server

    storage = Storage()
    srv = Server(storage, host="127.0.0.1", port=0, status_port=0)
    srv.start()
    try:
        s = Session(storage)
        s.execute("create table m (a bigint primary key, v bigint)")
        s.execute("insert into m values (1, 1), (2, 2)")
        s.execute("select sum(v) from m")
        rows = s.execute(
            "select metric_name, samples, min_value, avg_value, "
            "max_value, last_value from "
            "information_schema.metrics_summary").rows
        names = {r[0] for r in rows}
        assert "tidb_process_rss_bytes" in names
        assert any(n.startswith("tidb_queries_total") for n in names)
        for name, samples, mn, avg, mx, last in rows:
            assert samples >= 1
            assert mn <= avg <= mx
        base = f"http://127.0.0.1:{srv.status_port}"
        hist = json.loads(urllib.request.urlopen(
            base + "/debug/metrics/history", timeout=10).read())
        assert hist["interval_s"] > 0
        assert hist["samples"], "history ring is empty"
        sample = hist["samples"][-1]
        assert "ts" in sample
        assert "tidb_process_rss_bytes" in sample["values"]
        # gauges render with the gauge TYPE on /metrics
        text = urllib.request.urlopen(
            base + "/metrics", timeout=10).read().decode()
        assert "# TYPE tidb_process_rss_bytes gauge" in text
        assert "# TYPE tidb_device_buffer_bytes gauge" in text
    finally:
        srv.close()
        storage.close()


def test_metrics_summary_read_does_not_mutate_ring():
    storage = Storage()
    try:
        s = Session(storage)
        assert storage.metrics_history.snapshot() == []
        s.execute("select * from information_schema.metrics_summary")
        s.execute("select * from information_schema.metrics_summary")
        # reads fold in a transient "now" point; the ring stays intact
        assert storage.metrics_history.snapshot() == []
    finally:
        storage.close()


def test_history_ring_is_bounded():
    h = obs.MetricsHistory([obs.PROCESS_METRICS], interval_s=3600, cap=3)
    for _ in range(7):
        h.sample_now()
    assert len(h.snapshot()) == 3
    h.configure(cap=2)
    assert len(h.snapshot()) == 2
    summary = h.summary()
    assert all(st["samples"] <= 2 for st in summary.values())


# ==================== lifecycle: no leaked threads ====================

def _diag_threads() -> list[threading.Thread]:
    return [t for t in threading.enumerate() if t.is_alive()
            and t.name in ("titpu-metrics-history", "titpu-diag-accept")]


def test_shutdown_leaves_no_diag_threads(tmp_path):
    # held to the threads started after this line: the names are looked
    # up in the whole process, and under xdist a sibling test's Storage
    # that was served and never closed (Server.start starts the store's
    # sampler; only Storage.close joins it) would fail this one
    before = set(_diag_threads())

    def mine() -> list[threading.Thread]:
        return [t for t in _diag_threads() if t not in before]

    leader = Storage(str(tmp_path / "leader"), shared=True,
                     rpc_listen="127.0.0.1:0", rpc_options=OPTS)
    follower = Storage(str(tmp_path / "follower"),
                       remote=f"127.0.0.1:{leader.rpc_server.port}",
                       rpc_options=OPTS)
    s = Session(leader)
    assert len(s.execute("select instance from "
                         "information_schema.cluster_info").rows) == 2
    assert mine()  # sampler + follower listener are live
    follower.close()
    leader.close()
    # generous deadline: on a loaded CI box the joins themselves are
    # slow; what matters is that they HAPPEN (no thread survives)
    deadline = time.monotonic() + 15.0
    while mine() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert mine() == []  # close() joined them, nothing leaked
