"""Query tracing + dispatch-stage profiling surface.

Covers the TRACE span tree's dispatch stages (reference:
executor/trace.go), EXPLAIN ANALYZE's per-node stage breakdown
(util/execdetails), the @@profiling sampling profiler lifecycle
(util/profile), the /debug status routes, and metric hygiene for the
per-stage histograms.
"""

from __future__ import annotations

import threading

import pytest

from tidb_tpu import obs
from tidb_tpu.session import Session
from tidb_tpu.store.storage import Storage

from testkit import TestKit


def _q6_kit() -> TestKit:
    """A TPC-H Q6-shaped corpus: filter + scalar agg over arithmetic."""
    tk = TestKit()
    tk.must_exec("create table lineitem (l_orderkey int primary key, "
                 "l_quantity int, l_extendedprice int, l_discount int)")
    rows = ",".join(f"({i},{i % 50},{100 + i},{i % 10})"
                    for i in range(1, 201))
    tk.must_exec(f"insert into lineitem values {rows}")
    return tk


Q6 = ("select sum(l_extendedprice * l_discount) from lineitem "
      "where l_quantity < 24 and l_discount >= 1 and l_discount <= 6")


def _parse_stages(s: str) -> dict[str, float]:
    """'staging:0.2ms kernel:1.5ms' -> {'staging': 0.0002, ...}."""
    out = {}
    for part in (s or "").split():
        k, _, v = part.partition(":")
        out[k] = float(v.removesuffix("ms")) / 1e3
    return out


# ==================== TRACE ====================

def test_trace_q6_dispatch_stages():
    tk = _q6_kit()
    tk.must_query(Q6)  # warm: compile + staging caches
    rows = tk.must_query("trace " + Q6)
    ops = [r[0].strip() for r in rows]
    # the dispatch path is split into named stage spans
    assert any(o.startswith("copr.staging") for o in ops)
    assert any(o.startswith("device.dispatch") for o in ops)
    assert any(o.startswith("device.fetch") for o in ops)
    assert any(o.startswith("planner.optimize") for o in ops)
    # spans nest: every child start+duration fits inside session.run
    root = rows[0]
    assert root[0] == "session.run"
    for r in rows:
        if r[1] is not None and r[2] is not None:
            assert r[1] + r[2] <= root[2] + 1.0  # ms, rounding slack


def test_trace_stage_sum_matches_explain_analyze_wall():
    """The named dispatch stages account for the query's wall time:
    their (exclusive, additive) sum is bounded by — and a substantial
    fraction of — the root node's EXPLAIN ANALYZE time."""
    tk = _q6_kit()
    tk.must_query(Q6)  # warm
    rs = tk.session.execute("explain analyze " + Q6)
    assert rs.column_names == ["plan", "actRows", "time_ms", "engine",
                               "stages", "mesh", "wait_profile"]
    root = rs.rows[0]
    leaf = next(r for r in rs.rows if "TableRead" in r[0])
    assert "device" in leaf[3]
    stages = _parse_stages(leaf[4])
    for want in ("staging", "kernel", "device_get"):
        assert want in stages, (want, stages)
    wall_s = root[2] / 1e3
    total = sum(stages.values())
    # exclusive accounting: never more than the wall (plus rounding);
    # and the stages must explain a real fraction of it
    assert total <= wall_s * 1.10 + 1e-3
    assert total >= wall_s * 0.10


def test_trace_span_cap_bounds_the_tree():
    tk = _q6_kit()
    tk.must_exec("set tidb_trace_span_cap = 4")
    rows = tk.must_query("trace " + Q6)
    # plan rows ride along, but the span tree itself stayed bounded
    span_rows = [r for r in rows if r[1] is not None]
    assert len(span_rows) <= 4
    assert "dropped at cap" in rows[0][0]


def test_trace_served_on_debug_route_ring():
    tk = _q6_kit()
    tk.session.conn_id = 42
    tk.must_query("trace " + Q6)
    tr = tk.session.storage.obs.trace_for(42)
    assert tr is not None
    assert tr["spans"][0][0] == "session.run"
    assert tk.session.storage.obs.trace_for(99999) is None


def test_tracing_disabled_allocates_no_spans(monkeypatch):
    """The hot path must not build Span objects when no TRACE is
    active — stage()/span() only pay a TLS read + histogram update."""
    tk = _q6_kit()
    tk.must_query(Q6)  # warm compile first

    made: list[str] = []
    orig = obs.Span.__init__

    def counting(self, name, start):
        made.append(name)
        orig(self, name, start)

    monkeypatch.setattr(obs.Span, "__init__", counting)
    tk.must_query(Q6)
    assert made == []
    # and with TRACE active the same statement does build spans
    tk.must_query("trace " + Q6)
    assert made


# ==================== sampling profiler ====================

def _profiler_threads() -> list[threading.Thread]:
    return [t for t in threading.enumerate()
            if t.name == "titpu-profiler" and t.is_alive()]


def test_profiler_lifecycle_no_leaked_thread():
    tk = _q6_kit()
    assert tk.must_query("show profiles") == []
    tk.must_exec("set profiling = 1")
    tk.must_exec("set tidb_profiler_sample_hz = 400")
    tk.must_query(Q6)
    tk.must_query("select count(*) from lineitem")
    tk.must_exec("set profiling = 0")
    assert _profiler_threads() == []  # stop() joined every sampler
    profiles = tk.must_query("show profiles")
    assert len(profiles) == 2
    assert profiles[0][0] == 1 and profiles[1][0] == 2
    assert "sum(l_extendedprice" in profiles[0][2]
    assert all(p[1] > 0 for p in profiles)
    # profiling off: no new entries
    tk.must_query(Q6)
    assert len(tk.must_query("show profiles")) == 2


def test_profiler_history_size_trims_ring():
    tk = _q6_kit()
    tk.must_exec("set profiling = 1")
    tk.must_exec("set profiling_history_size = 3")
    for _ in range(5):
        tk.must_query("select count(*) from lineitem")
    tk.must_exec("set profiling = 0")
    profiles = tk.must_query("show profiles")
    assert len(profiles) == 3
    assert [p[0] for p in profiles] == [3, 4, 5]  # oldest evicted


def test_show_profile_names_host_frames():
    """A host-heavy statement's profile names engine-side frames."""
    tk = _q6_kit()
    tk.must_exec("set profiling = 1")
    tk.must_exec("set tidb_profiler_sample_hz = 997")
    # host-tier work: string group keys force the numpy fallback path,
    # and 40k generated rows keep the statement on-CPU long enough to
    # catch samples at ~1kHz
    tk.must_exec("create table h (a int primary key, b int)")
    rows = ",".join(f"({i},{i % 97})" for i in range(4000))
    tk.must_exec(f"insert into h values {rows}")
    tk.must_query("select b, count(*) from h group by b order by b")
    tk.must_exec("set profiling = 0")
    rows = tk.must_query("show profile")
    assert rows, "profiler captured no frames"
    frames = " ".join(r[0] for r in rows)
    if "no samples" not in frames:
        # host-tier hot frames are attributable to real code locations
        assert "(" in frames and ".py:" in frames
        assert all(r[2] >= 0 for r in rows)
    # SHOW PROFILE FOR QUERY n addresses one ring entry
    qid = tk.must_query("show profiles")[-1][0]
    assert tk.must_query(f"show profile for query {qid}") is not None
    with pytest.raises(Exception, match="no profile"):
        tk.must_query("show profile for query 9999")


def test_information_schema_profiling_rows():
    tk = _q6_kit()
    tk.must_exec("set profiling = 1")
    tk.must_exec("set tidb_profiler_sample_hz = 400")
    tk.must_query(Q6)
    tk.must_exec("set profiling = 0")
    rows = tk.must_query(
        "select query_id, seq, state, duration, samples "
        "from information_schema.profiling")
    # fast statements can land between ticks; the ring entry still
    # exists, rows appear when samples were caught
    for qid, seq, state, duration, samples in rows:
        assert qid == 1 and seq >= 1 and samples >= 0
        assert isinstance(state, str) and state


def test_profile_tree_rows_aggregation():
    p = obs.Profile({("a (x.py:1)", "b (x.py:2)"): 3,
                     ("a (x.py:1)", "c (x.py:3)"): 1}, hz=100.0,
                    duration_s=0.04)
    rows = p.tree_rows()
    assert rows[0][0] == "a (x.py:1)" and rows[0][2] == 4
    assert rows[1][0] == "  b (x.py:2)" and rows[1][2] == 3
    assert p.hot_frames()[0] == ("b (x.py:2)", 3)
    assert p.total_samples == 4


# ==================== slow log breakdown ====================

def test_slow_log_carries_digest_and_stages():
    tk = _q6_kit()
    tk.must_exec("set tidb_slow_log_threshold = 0")
    tk.must_query(Q6)
    tk.must_exec("set tidb_slow_log_threshold = 100000")
    rs = tk.session.execute("show slow queries")
    assert rs.column_names == ["Time", "DB", "Duration_ms", "Query",
                               "Plan_digest", "Stages", "Mem_max",
                               "Spill_count", "Wait_profile"]
    ent = next(r for r in rs.rows if "l_extendedprice" in r[3])
    assert len(ent[4]) == 32  # digest joins against statements_summary
    digests = {r[0] for r in tk.must_query(
        "select digest from information_schema.statements_summary")}
    assert ent[4] in digests
    stages = _parse_stages(ent[5])
    assert "kernel" in stages and "staging" in stages
    # the JSON surface carries the same fields
    raw = tk.session.storage.obs.slow_queries()
    e = next(e for e in raw if "l_extendedprice" in e["sql"])
    assert e["plan_digest"] == ent[4]
    assert "kernel" in e["stages"]
    # information_schema.slow_query exposes them to SQL too
    rows = tk.must_query(
        "select plan_digest, stages from information_schema.slow_query "
        "where query like '%l_extendedprice%'")
    assert rows and rows[0][0] == ent[4]


# ==================== metric hygiene ====================

def test_every_metric_family_has_tidb_prefix():
    tk = _q6_kit()
    tk.must_query(Q6)
    for reg in (tk.session.storage.obs.metrics, obs.PROCESS_METRICS):
        for fam in reg.families():
            assert fam.startswith("tidb_"), fam
        for line in reg.render().splitlines():
            if line and not line.startswith("#"):
                assert line.startswith("tidb_"), line


def test_histogram_text_format_order_and_labels():
    tk = _q6_kit()
    tk.must_query(Q6)
    text = (tk.session.storage.obs.render()
            + obs.PROCESS_METRICS.render())
    lines = text.splitlines()
    hist_fams = [ln.split()[2] for ln in lines
                 if ln.startswith("# TYPE") and ln.endswith("histogram")]
    assert "tidb_dispatch_stage_duration_seconds" in hist_fams
    for fam in hist_fams:
        fam_lines = [ln for ln in lines
                     if ln.startswith(fam) and not ln.startswith("#")]
        assert fam_lines, fam
        # per series: ascending le buckets, +Inf == count, then
        # _sum and _count (prometheus text-format order)
        i = 0
        while i < len(fam_lines):
            assert fam_lines[i].startswith(fam + "_bucket{le="), \
                fam_lines[i]
            prev = -1.0
            while "+Inf" not in fam_lines[i]:
                le = float(fam_lines[i].split('le="')[1].split('"')[0])
                assert le > prev
                prev = le
                i += 1
            inf_count = int(fam_lines[i].split()[-1])
            i += 1
            assert fam_lines[i].startswith(fam + "_sum")
            i += 1
            assert fam_lines[i].startswith(fam + "_count")
            assert int(fam_lines[i].split()[-1]) == inf_count
            i += 1


def test_sub_millisecond_buckets_exist():
    b = obs.Histogram.BUCKETS
    assert b[0] <= 1e-5 and 0.0001 in b and 0.0005 in b
    assert list(b) == sorted(b)
    # a 50µs observation is distinguishable from a 500µs one
    h = obs.Histogram("tidb_x", "")
    h.observe(0.00005)
    h.observe(0.0005)
    counts, _, total = h.snapshot()
    assert total == 2 and counts[b.index(0.00005)] == 1


def test_duplicate_registration_type_mismatch_raises():
    r = obs.Registry()
    r.counter("tidb_thing_total")
    with pytest.raises(TypeError):
        r.histogram("tidb_thing_total")
    with pytest.raises(TypeError):
        r.gauge("tidb_thing_total")
    # same-type re-registration returns the same instance
    assert r.counter("tidb_thing_total") is r.counter("tidb_thing_total")


def test_gauge_exposition_and_dup_guard():
    r = obs.Registry()
    g = r.gauge("tidb_gauge_thing", "a gauge")
    g.set(3.0, device="0")
    g.inc(2.0, device="0")
    g.dec(1.0, device="0")
    g.set(7.5)
    text = r.render()
    assert "# TYPE tidb_gauge_thing gauge" in text
    assert 'tidb_gauge_thing{device="0"} 4' in text
    assert "tidb_gauge_thing 7.5" in text
    with pytest.raises(TypeError):
        r.counter("tidb_gauge_thing")
    assert r.gauge("tidb_gauge_thing") is g
    # the process registry's device-telemetry gauges keep the tidb_
    # prefix contract (the prefix test walks them too, via families())
    fams = obs.PROCESS_METRICS.families()
    for fam in ("tidb_device_transfer_bytes", "tidb_device_buffer_bytes",
                "tidb_jit_cache_entries", "tidb_process_rss_bytes"):
        assert fam in fams, fam


def test_device_telemetry_gauges_move():
    tk = _q6_kit()
    tk.session.storage.flush()  # fold deltas: base-epoch staging caches
    tk.must_query(Q6)  # stages columns + compiles a kernel
    obs.run_gauge_probes()
    assert obs.DEVICE_TRANSFER_BYTES.get() > 0
    assert obs.DEVICE_BUFFER_BYTES.get() > 0
    assert obs.JIT_CACHE_ENTRIES.get() > 0
    assert obs.PROCESS_RSS_BYTES.get() > 0


def test_dispatch_stage_cache_counters_move():
    tk = _q6_kit()
    base_hit = obs.JIT_CACHE.get(result="hit")
    base_miss = obs.JIT_CACHE.get(result="miss")
    tk.must_query(Q6)
    assert obs.JIT_CACHE.get(result="miss") > base_miss
    tk.must_query(Q6)
    assert obs.JIT_CACHE.get(result="hit") > base_hit
    assert (obs.COL_CACHE.get(result="hit")
            + obs.COL_CACHE.get(result="miss")) > 0


# ==================== /debug status routes ====================

def test_debug_routes_trace_and_profile():
    import json
    import urllib.request

    from tidb_tpu.server.server import Server

    storage = Storage()
    srv = Server(storage, host="127.0.0.1", port=0, status_port=0)
    srv.start()
    try:
        s = Session(storage)
        s.conn_id = 5
        s.execute("create table d (a int primary key)")
        s.execute("insert into d values (1),(2)")
        base = f"http://127.0.0.1:{srv.status_port}"
        with pytest.raises(Exception):
            urllib.request.urlopen(base + "/debug/trace/5", timeout=10)
        s.execute("trace select count(*) from d")
        tr = json.loads(urllib.request.urlopen(
            base + "/debug/trace/5", timeout=10).read())
        assert tr["spans"][0][0] == "session.run"
        prof = json.loads(urllib.request.urlopen(
            base + "/debug/profile?seconds=0.1&hz=200",
            timeout=10).read())
        assert prof["hz"] == 200 and "tree" in prof
        assert _profiler_threads() == []
        # /debug/mesh: the flight-recorder payload is always servable
        # (plane status + dispatch/compile rings + HBM ledger), and a
        # scrape never fails even with the plane inactive
        mesh = json.loads(urllib.request.urlopen(
            base + "/debug/mesh", timeout=10).read())
        for key in ("status", "dispatches", "compiles", "storage"):
            assert key in mesh, mesh.keys()
        assert "enabled" in mesh["status"]
    finally:
        srv.close()


# ---------------------------------------------------------------- wait-state
# attribution: typed per-statement wait ledger + profile surfaces


def test_wait_ledger_exclusive_accounting_within_wall():
    import time as _time
    led = obs.WaitLedger()
    prev = obs.install_wait_ledger(led)
    try:
        t0 = _time.perf_counter()
        with obs.wait("prewrite"):
            _time.sleep(0.02)
            # fallback frames are no-ops inside an open frame: the wire
            # time stays charged to the enclosing 2PC phase
            with obs.wait("rpc_net", fallback=True):
                _time.sleep(0.005)
            _time.sleep(0.01)
        obs.note_wait("backoff.txnLock", 0.01)
        wall = _time.perf_counter() - t0
    finally:
        obs.install_wait_ledger(prev)
    assert "rpc_net" not in led.totals, led.totals
    assert led.totals["prewrite"] >= 0.03
    assert abs(led.totals["backoff.txnLock"] - 0.01) < 1e-9
    # exclusive accounting: states never sum past the wall clock
    assert sum(led.totals.values()) <= wall * 1.05 + 0.01
    assert led.counts["prewrite"] == 1


def test_wait_ledger_nested_frames_are_exclusive():
    import time as _time
    led = obs.WaitLedger()
    prev = obs.install_wait_ledger(led)
    try:
        with obs.wait("commit_primary"):
            _time.sleep(0.01)
            with obs.wait("fsync_wait"):
                _time.sleep(0.02)
            _time.sleep(0.005)
    finally:
        obs.install_wait_ledger(prev)
    # the child's 20ms is excluded from the parent's share
    assert led.totals["fsync_wait"] >= 0.02
    assert led.totals["commit_primary"] >= 0.01
    assert led.totals["commit_primary"] < 0.03


def test_wait_profile_statement_surfaces():
    tk = _q6_kit()
    st = tk.session.storage
    st.obs.waitprofile.configure(enabled=True)
    try:
        tk.must_exec("set tidb_slow_log_threshold = 0")
        tk.must_exec("create table w (a int primary key, b int)")
        tk.must_exec("insert into w values (1, 10), (2, 20)")
        waits = dict(tk.session.last_waits)
        assert waits.get("prewrite", 0.0) > 0.0, waits
        assert "tso_wait" in waits, waits
        # the slow-log entry carries the same typed split, bounded by wall
        ent = next(e for e in st.obs.slow_queries()
                   if "insert into w" in e["sql"])
        assert ent["waits"] and ent["waits"].get("prewrite", 0) > 0
        assert sum(ent["waits"].values()) <= ent["duration_ms"] * 1.05 + 1.0
        rs = tk.must_exec("show slow queries")
        assert rs.column_names[-1] == "Wait_profile"
        row = next(r for r in rs.rows if "insert into w" in r[3])
        assert "prewrite:" in row[-1], row
        # information_schema.tidb_wait_profile: typed split with sane fracs
        rows = tk.must_query(
            "select state, wait_ms, wait_frac "
            "from information_schema.tidb_wait_profile")
        states = {r[0] for r in rows}
        assert "prewrite" in states, states
        assert all(0.0 <= r[2] <= 1.0 for r in rows), rows
        # slow_query table exposes the formatted profile column
        sq = tk.must_query(
            "select wait_profile from information_schema.slow_query "
            "where query like '%insert into w%'")
        assert any("prewrite:" in (r[0] or "") for r in sq), sq
        # EXPLAIN ANALYZE grows a wait_profile header column; a pure
        # device-path select has no kv waits, so the cell stays empty
        rs2 = tk.must_exec("explain analyze select * from w")
        assert rs2.column_names[-1] == "wait_profile"
        assert all(r[-1] == "" for r in rs2.rows), rs2.rows
        # the cell renders the active statement ledger, heaviest first
        led = obs.WaitLedger()
        led.totals.update({"prewrite": 0.002, "tso_wait": 0.0005})
        prev = obs.install_wait_ledger(led)
        try:
            cell = tk.session._wait_profile_cell()
        finally:
            obs.install_wait_ledger(prev)
        assert cell.startswith("prewrite:2ms"), cell
        assert "tso_wait:" in cell
    finally:
        tk.must_exec("set tidb_slow_log_threshold = 100000")
        st.obs.waitprofile.configure(enabled=False)
        st.obs.waitprofile.clear()


def test_wait_profile_disabled_is_zero_cost(monkeypatch):
    tk = TestKit()
    assert not tk.session.storage.obs.waitprofile.enabled

    def _poison(self, *a, **kw):
        raise AssertionError("wait-profile machinery ran while disabled")

    monkeypatch.setattr(obs.WaitLedger, "__init__", _poison)
    monkeypatch.setattr(obs.WaitProfile, "record", _poison)
    tk.must_exec("create table z (a int primary key)")
    tk.must_exec("insert into z values (1)")
    assert tk.session.last_waits == {}
    # metric families still fire with the ledger off: the histogram tier
    # is always-on, only the per-statement ledger is gated
    assert obs.WAIT_SECONDS_TOTAL.get(state="prewrite") > 0


def test_backoffer_sleep_reports_typed_wait():
    from tidb_tpu.kv.backoff import Backoffer, BO_TXN_LOCK, BO_REGION_MISS
    led = obs.WaitLedger()
    prev = obs.install_wait_ledger(led)
    before = obs.BACKOFF_EVENTS.get(kind="txnLock")
    try:
        bo = Backoffer(budget_ms=200)
        bo.sleep(BO_TXN_LOCK)
        bo.sleep(BO_REGION_MISS, wait_state="lease_wait")
    finally:
        obs.install_wait_ledger(prev)
    assert obs.BACKOFF_EVENTS.get(kind="txnLock") == before + 1
    assert led.totals.get("backoff.txnLock", 0.0) > 0.0, led.totals
    # wait_state override: lease retries land under lease_wait, not
    # backoff.regionMiss, so the profile names the cause
    assert led.totals.get("lease_wait", 0.0) > 0.0, led.totals
    assert "backoff.regionMiss" not in led.totals


def test_dominant_wait_inspection_rule():
    from tidb_tpu import obs_inspect
    st = Storage()
    wp = st.obs.waitprofile
    wp.configure(enabled=True)
    try:
        wp.record("d" * 32, "update hot set v = v + 1 where k = 9",
                  "test", 1.0, {"backoff.txnLock": 0.8, "prewrite": 0.1})
        finds = [f for f in obs_inspect.inspect(st)
                 if f.rule == "dominant-wait"]
        assert len(finds) == 1, finds
        assert "backoff.txnLock" in finds[0].details
        wp.clear()
        # below the threshold: healthy
        wp.record("e" * 32, "select 1", "test", 1.0,
                  {"backoff.txnLock": 0.2})
        assert not [f for f in obs_inspect.inspect(st)
                    if f.rule == "dominant-wait"]
        # disabled: rule stays silent regardless of ring contents
        wp.record("f" * 32, "select 2", "test", 1.0,
                  {"backoff.txnLock": 0.99})
        wp.configure(enabled=False)
        assert not [f for f in obs_inspect.inspect(st)
                    if f.rule == "dominant-wait"]
    finally:
        wp.configure(enabled=False)
        wp.clear()


# ==================== the served path's stage timeline ====================
#
# obs.stage over the whole served path (socket -> fetch): every command
# moves each stage that applies to it once; the stages are exclusive; the
# few clocked brackets (command, exec, device_get) are on two clocks (wall,
# thread CPU); and under a jax profiler session the stages are events of
# the host plane.

import contextlib
import glob
import json
import os
import subprocess
import sys
import time

from mysql_client import MiniClient, MySQLError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the stages this PR put outside the coprocessor, in path order
SERVED_STAGES = ("wire_queue", "wire_read", "parse", "admission", "exec",
                 "epilogue", "encode", "wire_write", "wire_repark")
# what EXPLAIN ANALYZE's `stages` cell may hold: the coprocessor's stages
# and the executor's own (a snapshot, a decode and a gather a read, a
# host_op a plan node), each of which a command may book more than once
COPR_CELL_STAGES = {"prepare", "staging", "transfer", "compile", "kernel",
                    "device_get", "merge", "shard", "reshard",
                    "host_fallback", "ranged",
                    "snapshot", "decode", "gather", "host_op"}
# the stages of the executor's own time, inside `exec`
EXEC_STAGES = ("snapshot", "decode", "gather", "host_op", "result_rows")
SERVED_SQL = {
    "select": Q6,
    "point": "select l_quantity from lineitem where l_orderkey = 7",
    "update": "update lineitem set l_quantity = l_quantity + 1 "
              "where l_orderkey = 7",
}


def _stage_snapshot() -> dict:
    """{stage: (wall sum, count)}, {clocked bracket: (wall, off-CPU)} and
    the command histogram's (sum, count), read in-process."""
    stages = {dict(key)["stage"]: (total, n)
              for key, _, total, n in obs.DISPATCH_STAGE_SECONDS.series()
              if key}
    off = {dict(k)["stage"]: v
           for k, v in obs.DISPATCH_STAGE_OFFCPU.samples()}
    clocked = {dict(k)["stage"]: (v, off[dict(k)["stage"]])
               for k, v in obs.DISPATCH_STAGE_CLOCKED.samples()}
    _, cmd_sum, cmd_n = obs.CONN_COMMAND_SECONDS.snapshot()
    return {"stages": stages, "clocked": clocked,
            "command": (cmd_sum, cmd_n)}


def _moved(before: dict, after: dict, name: str) -> tuple:
    """What one entry of a snapshot's `stages` or `clocked` moved by."""
    return tuple(a - b for a, b in
                 zip(after[name], before.get(name, (0.0, 0.0))))


def _reparks() -> int:
    return obs.DISPATCH_STAGE_SECONDS.snapshot(stage="wire_repark")[2]


def _await_repark(n: int) -> None:
    """Until the reactor has booked one more `wire_repark` than `n`: it
    watches the socket again, so the next command starts from a wake."""
    deadline = time.monotonic() + 10
    while _reparks() <= n and time.monotonic() < deadline:
        time.sleep(0.002)
    assert _reparks() > n


def _settled(client, sql) -> None:
    """One command, and the reactor watching its socket again: the
    re-park is booked by the reactor's thread after the answer left."""
    n = _reparks()
    client.query(sql)
    _await_repark(n)


@contextlib.contextmanager
def _serving(token_limit: int = 0):
    """A Server over the Q6 corpus and one wire client."""
    from tidb_tpu.server.server import Server

    tk = _q6_kit()
    tk.session.storage.admission.configure(tokens=token_limit)
    srv = Server(tk.session.storage, port=0, status_port=0)
    srv.start()
    n = _reparks()
    c = MiniClient("127.0.0.1", srv.port, db="test")
    try:
        _await_repark(n)  # the handshake parks the connection too
        yield c, tk.session
    finally:
        c.close()
        srv.close()
        tk.session.storage.close()  # joins the sampler Server.start began


@pytest.fixture(scope="module")
def served_deltas():
    """Per statement of SERVED_SQL the stage snapshot before and after
    ONE warm command over the wire. The server is gone again before the
    first test's own fixtures run (conftest counts listening sockets
    per test)."""
    deltas = {}
    with _serving(token_limit=8) as (c, _):  # a gate, so `admission` is one
        for name, sql in SERVED_SQL.items():
            _settled(c, sql)  # warm: compile, plan cache, worker thread
            # a thread reads its CPU clock for one command in this long
            time.sleep(2 * obs._CLOCK_EVERY_S)
            before = _stage_snapshot()
            _settled(c, sql)
            deltas[name] = (before, _stage_snapshot())
    return deltas


@pytest.mark.parametrize("stage", SERVED_STAGES)
@pytest.mark.parametrize("stmt", sorted(SERVED_SQL))
def test_served_command_moves_each_stage_once(served_deltas, stmt, stage):
    """A SELECT, a point SELECT and an UPDATE over the wire each pass
    every stage outside the coprocessor exactly once (each is the first
    command after a wake). `admission` is the WAIT for a token: all
    three pass the gate, which the fixture limits to 8 tokens, none
    waits there, and none books it."""
    before, after = served_deltas[stmt]
    n0 = before["stages"].get(stage, (0.0, 0))[1]
    n1 = after["stages"].get(stage, (0.0, 0))[1]
    assert n1 - n0 == (0 if stage == "admission" else 1)


@pytest.mark.parametrize("stmt", sorted(SERVED_SQL))
def test_served_stages_are_exclusive_and_inside_the_command(served_deltas,
                                                            stmt):
    """Per command: no stage moved by more than the coprocessor's own
    per-tile repeats allow; the exclusive stage seconds inside the
    command sum to no more than the command's; the clocked brackets
    (`command` = the command outside `exec`, `exec` = the executor with
    every stage in it but `device_get`) are never more off the CPU than
    their wall, and together with the hand-off they are the command."""
    _assert_inside_the_command(*served_deltas[stmt])


def _assert_inside_the_command(before: dict, after: dict) -> None:
    assert after["command"][1] - before["command"][1] == 1
    whole = after["command"][0] - before["command"][0]
    inside = 0.0
    for stage in after["stages"]:
        total, n = _moved(before["stages"], after["stages"], stage)
        if stage not in COPR_CELL_STAGES:
            assert n <= 1, stage
        if stage != "wire_repark":  # booked after the command's end
            inside += total
    assert 0 < inside <= whole
    brackets = 0.0
    for name in after["clocked"]:
        wall, off = _moved(before["clocked"], after["clocked"], name)
        assert -1e-9 <= off <= wall + 1e-9, name
        if name != "wire_repark":
            brackets += wall
    for name in ("command", "exec", "wire_queue"):
        assert _moved(before["clocked"], after["clocked"], name)[0] > 0
    # the brackets tile the command: reactor's stamp to the write's end
    assert brackets == pytest.approx(whole, rel=1e-6)
    in_exec = sum(_moved(before["stages"], after["stages"], s)[0]
                  for s in after["stages"]
                  if s in COPR_CELL_STAGES - {"device_get"}
                  or s in ("exec", "fast_plan", "plan_build", "prepare",
                           "result_rows"))
    assert in_exec <= _moved(before["clocked"], after["clocked"],
                             "exec")[0] + 1e-9


# ---- the executor's own time inside `exec`: a plan node's `host_op`, a
# read's `snapshot`, `decode` and `gather`, the statement's `result_rows`

EXEC_SQL = {
    # statement: (its SQL, the stages its read books beside decode)
    "row_scan": ("select k, c from f where c = 7", {"snapshot", "gather"}),
    "q6": ("select sum(v) from f where c > 0 and b < 5", {"snapshot"}),
    "topn": ("select k, c from f order by c desc limit 5", {"snapshot"}),
    "join": ("select k, x from f, dim where fg = dg and c = 7",
             {"snapshot", "gather"}),
}
FACT_ROWS, EXEC_TILE_ROWS = 3000, 1024  # three tiles a read of f
# what a primary-key point SELECT and UPDATE book, one command each
POINT_STAGES = ["encode", "epilogue", "exec", "fast_plan", "parse",
                "wire_queue", "wire_read", "wire_repark", "wire_write"]


@pytest.fixture(scope="module")
def served_reads():
    """Per statement of EXEC_SQL: the stage snapshot before and after ONE
    warm command over the wire, and its plan's node count. The fact table
    spans three tiles of the server's coprocessor client."""
    import numpy as np

    from tidb_tpu.copr import mesh
    from tidb_tpu.server.server import Server

    rng = np.random.default_rng(38)
    s = Session()
    s.execute("create table f (k bigint primary key, fg int, b int, "
              "c int, v int)")
    s.storage.table_store(s.catalog.table("test", "f").id).bulk_load(
        [np.arange(FACT_ROWS, dtype=np.int64),
         rng.integers(0, 300, FACT_ROWS), rng.integers(0, 7, FACT_ROWS),
         rng.integers(-50, 100, FACT_ROWS), rng.integers(-30, 30, FACT_ROWS)])
    s.execute("create table dim (dg bigint primary key, x int)")
    s.storage.table_store(s.catalog.table("test", "dim").id).bulk_load(
        [np.arange(300, dtype=np.int64), rng.integers(0, 40, 300)])
    mesh.client_for(s.storage).TILE_ROWS = EXEC_TILE_ROWS
    srv = Server(s.storage, port=0, status_port=0)
    srv.start()
    n = _reparks()
    c = MiniClient("127.0.0.1", srv.port, db="test")
    out = {}
    try:
        _await_repark(n)
        for name, (sql, _) in EXEC_SQL.items():
            _settled(c, sql)  # warm
            time.sleep(2 * obs._CLOCK_EVERY_S)
            before = _stage_snapshot()
            _settled(c, sql)
            out[name] = (before, _stage_snapshot(),
                         len(c.query("explain " + sql)))
    finally:
        c.close()
        srv.close()
        s.storage.close()
    return out


@pytest.mark.parametrize("stmt", sorted(EXEC_SQL))
def test_executor_stages_book_once_a_read_or_node(served_reads, stmt):
    """A served row scan, Q6, TopN and join fragment each book `decode`
    once for their one coprocessor read, whose three tiles the device
    ran, `host_op` once a plan node, `result_rows` once, and `snapshot` /
    `gather` where the path has them, never once a tile."""
    before, after, nodes = served_reads[stmt]
    moved = {s: _moved(before["stages"], after["stages"], s)[1]
             for s in after["stages"]}
    want = {"decode": 1, "host_op": nodes, "result_rows": 1,
            "snapshot": 0, "gather": 0}
    want.update(dict.fromkeys(EXEC_SQL[stmt][1], 1))
    assert {s: moved.get(s, 0) for s in want} == want
    if stmt == "join":  # the fragment's dispatch loop runs a tile a turn
        assert moved["kernel"] == -(-FACT_ROWS // EXEC_TILE_ROWS)


@pytest.mark.parametrize("stmt", sorted(EXEC_SQL))
def test_executor_stages_are_exclusive_unclocked_and_inside_exec(
        served_reads, stmt):
    """The executor's stages add up inside the command and inside the
    `exec` bracket's wall, and read no CPU clock of their own."""
    before, after, _ = served_reads[stmt]
    _assert_inside_the_command(before, after)
    assert not set(EXEC_STAGES) & set(after["clocked"])


@pytest.mark.parametrize("stmt", ["point", "update"])
def test_point_commands_book_no_executor_stage(served_deltas, stmt):
    """A primary-key point SELECT and UPDATE (the fast path) book the
    stages they always booked, and none of the executor's."""
    before, after = served_deltas[stmt]
    assert sorted(s for s in after["stages"]
                  if _moved(before["stages"], after["stages"], s)[1]) \
        == POINT_STAGES


def _burn(seconds: float) -> None:
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def test_offcpu_reads_a_wait_apart_from_work():
    """Two clocks per clocked bracket: one whose thread waited books the
    wait as off-CPU, one that computed books the CPU it burned as CPU, a
    nested bracket's wait is its own and not its parent's, and an
    unclocked stage's wait is its enclosing bracket's. Only what holds
    on a loaded host is asserted: a busy host stretches every wall and
    every wait, so no wall has an upper bound but the whole's."""
    before = _stage_snapshot()
    t0 = time.perf_counter()
    with obs.stage("t_outer", clocked=True):
        time.sleep(0.03)
        with obs.stage("t_wait", clocked=True):
            time.sleep(0.02)
        with obs.stage("t_work", clocked=True):
            _burn(0.02)
        with obs.stage("t_plain"):
            time.sleep(0.01)
    whole = time.perf_counter() - t0
    after = _stage_snapshot()
    clocked = {n: _moved(before["clocked"], after["clocked"], n)
               for n in ("t_outer", "t_wait", "t_work")}
    slack = 1e-3  # sleep()'s own entry and exit run on the CPU
    wall, off = clocked["t_wait"]
    assert 0.02 - slack <= off <= wall
    wait_share = off / wall
    wall, off = clocked["t_work"]
    assert 0 <= off <= wall
    assert wall - off >= 0.02 - 1e-9  # the CPU it burned is not a wait
    assert off / wall < wait_share
    # its own sleep and t_plain's; not t_wait's nor t_work's
    wall, off = clocked["t_outer"]
    assert 0.04 - slack <= off <= wall
    assert wall <= whole - clocked["t_wait"][0] - clocked["t_work"][0]
    assert "t_plain" not in after["clocked"]
    # the stage histogram is exclusive of EVERY nested stage, as before
    excl = {n: _moved(before["stages"], after["stages"], n)[0]
            for n in ("t_outer", "t_wait", "t_work", "t_plain")}
    assert excl["t_outer"] >= 0.03
    assert excl["t_outer"] <= whole - excl["t_wait"] - excl["t_work"] \
        - excl["t_plain"]


@pytest.mark.parametrize("wait_ms", [0.3, 0.6, 1.0])
def test_offcpu_keeps_short_neighbouring_waits_apart(wait_ms):
    """The scale of a point read: a bracket of a few hundred microseconds
    that only waits, between two that only compute, keeps its wait: none
    of it lands on a neighbour and nothing between brackets lands in
    one (each reading is from the bracket's own two edges)."""
    wait = wait_ms / 1e3
    before = _stage_snapshot()["clocked"]
    rounds = 50
    for _ in range(rounds):
        with obs.stage("t_pre", clocked=True):
            _burn(0.0005)
        time.sleep(wait)  # between brackets: belongs to none
        with obs.stage("t_mid", clocked=True):
            time.sleep(wait)
        with obs.stage("t_post", clocked=True):
            _burn(0.0005)
    after = _stage_snapshot()["clocked"]
    wall, off = _moved(before, after, "t_mid")
    assert wall >= rounds * wait
    assert 0.8 * wall <= off <= wall  # sleep()'s own entry and exit run
    for name in ("t_pre", "t_post"):
        wall, off = _moved(before, after, name)
        # what is not off-CPU is the CPU time it burned, whatever a busy
        # host preempted; a wait carried in would eat into it
        assert wall - off >= 0.95 * rounds * 0.0005, (name, wall, off)
        assert off <= wall


def test_a_thread_clocks_one_command_in_a_while(monkeypatch):
    """The CPU clock is a system call: of a stream of quick commands on
    one thread only one per _CLOCK_EVERY_S pays its four reads and books
    its brackets; every command's stages reach the histogram."""
    reads = []
    real = time.thread_time
    monkeypatch.setattr(obs.time, "thread_time",
                        lambda: reads.append(1) or real())
    n0 = obs.DISPATCH_STAGE_SECONDS.snapshot(stage="t_cmd_parse")[2]

    def serve(n: int) -> None:
        cmd = obs.command_begin(0.0)
        for _ in range(n):
            with obs.stage("t_cmd_parse"):
                pass
            with obs.stage("t_cmd_exec", clocked=True):
                pass
            cmd.end()
        cmd.close()

    seen = []

    def run() -> None:  # a thread of its own: a clock never read yet
        t0 = time.perf_counter()
        serve(50)
        seen.append((time.perf_counter() - t0, len(reads)))
        time.sleep(1.2 * obs._CLOCK_EVERY_S)
        serve(1)
        seen.append(len(reads))

    t = threading.Thread(target=run)
    t.start()
    t.join()
    (took, first), after = seen
    # begin, exec's two edges, end: four reads, for the first command
    # (and one more command per _CLOCK_EVERY_S a slow host took)
    assert first % 4 == 0
    assert 4 <= first <= 4 * (1 + int(took / obs._CLOCK_EVERY_S))
    assert after == first + 4
    assert obs.DISPATCH_STAGE_SECONDS.snapshot(
        stage="t_cmd_parse")[2] - n0 == 51


def test_admission_is_the_wait_in_the_gate():
    """`admission` is booked inside AdmissionGate, by the statement that
    waits for a token, once, for as long as it waited, nested in `exec`;
    a statement that finds a token free books nothing."""
    from tidb_tpu.util.governor import AdmissionGate

    gate = AdmissionGate(tokens=1)
    n0 = obs.DISPATCH_STAGE_SECONDS.snapshot(stage="admission")
    with gate.admit():
        pass
    assert obs.DISPATCH_STAGE_SECONDS.snapshot(stage="admission")[2] \
        == n0[2]
    assert gate.acquire() is True  # the one token is taken
    t = threading.Timer(0.05, gate.release)
    t.start()
    try:
        with obs.stage("t_exec"):
            with gate.admit():
                pass
    finally:
        t.join()
    _, total, n = obs.DISPATCH_STAGE_SECONDS.snapshot(stage="admission")
    assert n - n0[2] == 1 and total - n0[1] >= 0.04


def test_served_stage_families_are_on_metrics(served_deltas):
    text = obs.PROCESS_METRICS.render()
    for stage in SERVED_STAGES:
        if stage != "admission":  # nobody waited in the gate
            assert ('tidb_dispatch_stage_duration_seconds_count'
                    f'{{stage="{stage}"}}') in text
    for bracket in ("command", "exec", "device_get", "wire_queue",
                    "wire_repark"):
        for family in ("clocked", "offcpu"):
            assert (f'tidb_dispatch_stage_{family}_seconds_total'
                    f'{{stage="{bracket}"}}') in text
    assert "tidb_conn_command_seconds_sum" in text
    assert obs.lint_metrics([obs.PROCESS_METRICS]) == []


def test_served_path_allocates_no_spans_without_trace(monkeypatch):
    """The no-Span-when-off pin, over the wire: the new sites (wire,
    parse, admission, exec, epilogue, encode) build none either."""
    made: list[str] = []
    orig = obs.Span.__init__

    def counting(self, name, start):
        made.append(name)
        orig(self, name, start)

    with _serving() as (c, _):
        for sql in SERVED_SQL.values():
            _settled(c, sql)  # warm
        monkeypatch.setattr(obs.Span, "__init__", counting)
        gated = obs.DISPATCH_STAGE_SECONDS.snapshot(stage="admission")[2]
        for sql in SERVED_SQL.values():
            _settled(c, sql)
    assert made == []
    # an unlimited gate (the default) is no gate: no `admission` stage
    assert obs.DISPATCH_STAGE_SECONDS.snapshot(
        stage="admission")[2] == gated


def test_import_obs_does_not_import_jax():
    """A KV-only process stays jax-free: the stage mechanism takes the
    profiler's annotation class only where jax is already loaded."""
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys; import tidb_tpu.obs as o\n"
         "with o.stage('parse'): pass\n"
         "o.note_stage('wire_queue', 0.001)\n"
         "assert not [m for m in sys.modules if m == 'jax' or "
         "m.startswith('jax.')], 'jax imported'"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_profiler_session_shows_stages_in_the_host_plane(tmp_path):
    """Under jax.profiler.start_trace a served Q6 leaves titpu/kernel and
    titpu/device_get in the host plane, on the profiler's clock, with the
    program's name on them; the stage events are exclusive, so none
    encloses both (an enclosing event would own every idle gap under it);
    and the jitted program calls itself titpu_agg, not `kernel`."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with _serving() as (c, session):
        _settled(c, Q6)  # warm
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _settled(c, Q6)
        finally:
            jax.profiler.stop_trace()
        # every compiled program of the session's client is named
        progs = {getattr(k, "__name__", "")
                 for k in session.cop._kernels.values()}
    assert progs and all(p.startswith("titpu_") for p in progs), progs
    path = sorted(glob.glob(str(
        tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")))[-1]
    events = []  # (start, end, name, stats) of the host plane
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                events += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                            dict(e.stats)) for e in line.events]
    names = {e[2] for e in events}
    for stage in ("wire_read", "parse", "exec", "kernel", "device_get",
                  "encode", "wire_write"):
        assert f"titpu/{stage}" in names, sorted(names)[:40]
    assert "titpu/wire_queue" not in names  # on no one thread's timeline
    assert "PjitFunction(titpu_agg)" in names
    kernel = next(e for e in events if e[2] == "titpu/kernel")
    fetch = next(e for e in events if e[2] == "titpu/device_get"
                 and e[0] >= kernel[1])
    assert kernel[3]["prog"] == fetch[3]["prog"] == "titpu_agg"
    assert kernel[3]["conn"] == fetch[3]["conn"] and "seq" in kernel[3]
    both = [e[2] for e in events
            if e[0] <= kernel[0] and e[1] >= fetch[1]]
    assert both == []


def test_profiler_shows_the_executors_own_stages(tmp_path):
    """Under jax.profiler a served row scan's executor time is named on
    the host plane: titpu/decode, titpu/gather, titpu/host_op (with the
    plan node's `op`) and titpu/result_rows, and no event encloses any of
    them (an idle gap under one is named by it, not by titpu/exec)."""
    import jax
    from jax.profiler import ProfileData

    sql = "select l_orderkey, l_quantity from lineitem where l_quantity = 7"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with _serving() as (c, _):
        _settled(c, sql)  # warm
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _settled(c, sql)
        finally:
            jax.profiler.stop_trace()
    path = sorted(glob.glob(str(
        tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")))[-1]
    events = []  # (start, end, name, stats) of the host plane
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                events += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                            dict(e.stats)) for e in line.events]
    names = {e[2] for e in events}
    for stage in ("decode", "gather", "host_op", "result_rows"):
        assert f"titpu/{stage}" in names, sorted(names)[:40]
    assert {e[3].get("op") for e in events if e[2] == "titpu/host_op"} \
        == {"scan"}
    for ev in events:
        if ev[2] in ("titpu/decode", "titpu/gather", "titpu/host_op",
                     "titpu/result_rows"):
            assert [e[2] for e in events if e is not ev
                    and e[0] <= ev[0] and e[1] >= ev[1]] == [], ev[2]


# ---- the program each (placement, kind) runs, and the tag it answers
# under: one client class, two placements (copr/placement.py) ----

_KIND_SQL = {
    # kind: (statement, engine tag, program when single, when sharded)
    "agg": ("select sum(v) from f where c > 0",
            "device", "titpu_agg", "titpu_mesh_agg"),
    "topn": ("select k, c from f order by c desc limit 5",
             "device", "titpu_topn", "titpu_mesh_topn"),
    "rows": ("select k from f where c = 7 order by k",
             "device", "titpu_rowmask", "titpu_mesh_rows"),
    "frag_agg": ("select x, sum(v) from f, dim where fg = dg group by x",
                 "device[agg]", "titpu_frag_agg", "titpu_mesh_frag_agg"),
    "frag_hc": ("select dg, x, sum(v) from f, dim where fg = dg "
                "group by dg, x order by sum(v) desc, x limit 5",
                "device[fat]", "titpu_frag_hc", "titpu_mesh_frag_hc"),
    "frag_topn": ("select k, x, b from f, dim where fg = dg "
                  "order by x desc, b, k limit 7",
                  "device[topn]", "titpu_frag_topn", "titpu_mesh_frag_topn"),
    "frag_rows": ("select k, x from f, dim where fg = dg and c = 7 "
                  "order by k",
                  "device[rows]", "titpu_frag_rows", "titpu_mesh_frag_rows"),
}


@pytest.fixture(scope="module")
def placed_corpus():
    """A 6 000-row fact table and a 300-row dimension table, loaded in
    bulk, with every statement's single-device answer."""
    import numpy as np

    from tidb_tpu.copr.client import CopClient

    rng = np.random.default_rng(31)
    base = Session(cop=CopClient())
    n, nd = 6000, 300
    base.execute("create table f (k bigint primary key, fg int, b int, "
                 "c int, v int)")
    base.storage.table_store(base.catalog.table("test", "f").id).bulk_load(
        [np.arange(n, dtype=np.int64), rng.integers(0, nd, n),
         rng.integers(0, 7, n), rng.integers(-50, 100, n),
         rng.integers(-30, 30, n)])
    base.execute("create table dim (dg bigint primary key, x int)")
    base.storage.table_store(base.catalog.table("test", "dim").id).bulk_load(
        [np.arange(nd, dtype=np.int64), rng.integers(0, 40, nd)])
    return base, {k: base.query(v[0]) for k, v in _KIND_SQL.items()}


@pytest.mark.parametrize("kind", list(_KIND_SQL))
@pytest.mark.parametrize("placement", ["single", "sharded"])
def test_program_name_and_engine_tag(placed_corpus, monkeypatch,
                                     placement, kind):
    """Each kind of device program under each placement: the one name it
    is jitted under (what the profiler and the compile cache see) and
    the engine tag EXPLAIN ANALYZE answers with. A fresh client builds
    its programs through placement.named_jit and nowhere else."""
    from sharded_client import sharded_client
    from tidb_tpu.copr import placement as PL
    from tidb_tpu.copr.client import CopClient

    base, answers = placed_corpus
    sql, tag, single_prog, sharded_prog = _KIND_SQL[kind]
    built = []
    orig = PL.named_jit

    def spy(fn, name):
        built.append(name)
        return orig(fn, name)

    monkeypatch.setattr(PL, "named_jit", spy)
    if placement == "single":
        cop, want_prog, want_tag = CopClient(), single_prog, tag
    else:
        cop = sharded_client(base.storage)
        want_prog, want_tag = sharded_prog, tag + "@mesh8"
    s = Session(base.storage, cop=cop)
    assert sorted(s.query(sql)) == sorted(answers[kind])
    assert built == [want_prog], built
    tags = {r[3] for r in s.execute("explain analyze " + sql).rows if r[3]}
    assert tags == {want_tag}, tags
    assert built == [want_prog], "the warm run built a program again"
    assert type(cop) is CopClient and {k[0] for k in cop._kernels} == {
        "single" if placement == "single" else "shard"}


def test_parallel_imports_nothing_from_copr():
    """The arrows point one way: copr/placement -> parallel/exchange.
    No module under tidb_tpu/parallel imports tidb_tpu.copr."""
    import ast

    pdir = os.path.join(ROOT, "tidb_tpu", "parallel")
    files = [f for f in os.listdir(pdir) if f.endswith(".py")]
    assert "exchange.py" in files and "dist.py" not in files, files
    for f in files:
        with open(os.path.join(pdir, f)) as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                # `from ..copr import x`, `from .. import copr`
                mods = [(node.module or "")] + [
                    f"{node.module or ''}.{a.name}" for a in node.names]
            assert not any("copr" in m.split(".") for m in mods), (f, mods)


def _bench_statements() -> list[str]:
    return sorted(f[:-5] for f in os.listdir(
        os.path.join(ROOT, "benchmarks", "statements")))


@pytest.fixture(scope="module")
def bench_cells(tmp_path_factory):
    """{class: (its statement file, the longest `stages` cell of its
    EXPLAIN ANALYZE or the error it got)} for the benchmark's ten
    statements, from the benchmark's own loader at rehearsal scale: both
    configurations' tables in one in-memory store, served over the wire.
    The system is closed again before the first test's own fixtures run
    (conftest counts listening sockets per test)."""
    sys.path.insert(0, ROOT)
    from benchmarks.datagen import rf1
    from benchmarks.harness import system as S

    cfg = {}
    for name in ("htap_sysbench_tpch10_1chip", "tpch_sf10_1chip"):
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               name + ".json")) as f:
            cfg.update(json.load(f))
    cfg["storage"] = {"durable": False, "sync_log": "off"}
    system = S.System(cfg, 7, cfg["rehearsal_scale"],
                      str(tmp_path_factory.mktemp("bench")), lambda m: None)
    cells = {}
    try:
        for cls in _bench_statements():
            with open(os.path.join(ROOT, "benchmarks", "statements",
                                   cls + ".json")) as f:
                st = json.load(f)
            if st.get("builder") == "rf1_lineitem":
                sql = rf1.orders(system.data["lineitem"],
                                 system.data["lineitem_vocab"], 7, 1)[0][0]
            else:
                sql = st["sql"].replace("{key}", "5")
            c = MiniClient("127.0.0.1", system.port, db=st["db"])
            try:
                if st["op"] == "query":
                    c.query(sql)  # warm: no compile stage in the cell
                rows = c.query("explain analyze " + sql)
                cells[cls] = (st, max((r[4] or "" for r in rows), key=len))
            except MySQLError as e:
                cells[cls] = (st, e)
            finally:
                c.close()
    finally:
        system.close()
    return cells


@pytest.mark.parametrize("cls", _bench_statements())
def test_explain_analyze_stages_cell_keeps_its_names(bench_cells, cls):
    """benchmarks/run.py reads the longest `stages` cell of an EXPLAIN
    ANALYZE: for each of the benchmark's ten statements it holds the
    coprocessor's stage names and none of the served path's new ones
    (`exec` closes after the root node, `parse` before the first)."""
    st, cell = bench_cells[cls]
    if st["op"] != "query":
        # the harness never explains a write; the program refuses
        assert isinstance(cell, MySQLError) and "SELECT only" in str(cell)
    elif st["kind"] == "point":
        assert cell.startswith("plan_cache:")
    else:
        names = set(_parse_stages(cell))
        assert {"staging", "kernel", "device_get"} <= names <= \
            COPR_CELL_STAGES, cell
