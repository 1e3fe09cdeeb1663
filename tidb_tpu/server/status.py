"""HTTP status server: /status, /metrics, /slow-query, /debug/*.

Counterpart of the reference's status port (reference:
server/http_status.go:110-151 — /status JSON, /metrics Prometheus handler;
default port 10080, tidb-server/main.go:144; the pprof debug routes of
util/profile). Runs on a daemon thread beside the MySQL wire listener.

Debug routes:
  /debug/trace/<conn_id>  last TRACE span tree of that connection (JSON)
  /debug/profile?seconds=0.5&hz=97  one-shot whole-process sampling
      profile: hot frames + flamegraph-style call tree (JSON)
  /debug/metrics/history  the MetricsHistory ring: timestamped
      counter/gauge samples (JSON; cadence/size via the
      performance.metrics-history-* config knobs)
  /debug/failpoints  armed fault-injection points + hit counts (JSON;
      the torture harness reads this to confirm its env-armed points
      actually fired inside child server processes)
  /debug/topsql  the Top SQL attribution windows: per-digest stage
      sums, per-operator wall/stage/transfer splits, admission/
      governor outcomes (JSON; performance.topsql-* knobs)
  /debug/waitprofile  typed wait-state attribution windows: per-digest
    exclusive wait splits (tso_wait, lease_wait, backoff.{kind},
    prewrite, ...) with the dominant state of each entry (JSON)
  /debug/events  the structured server event ring: governor kills,
      admission sheds, breaker trips, elections, checkpoint/fsync
      stalls (JSON)
  /debug/mesh  the mesh flight recorder: plane status, per-digest
      per-shard dispatch accounting (rows/skew/exchange bytes),
      compile ring with recompile-storm flags, and the per-device
      HBM provenance ledger (JSON; never builds a mesh)
  /debug/replicas  the follower read tier: router knobs, per-member
    serving/closed-timestamp state, the local apply engine, and the
    routed-read outcome counters

  /debug/inspection  the automated diagnosis plane: every registered
      inspection rule evaluated over the live telemetry snapshot,
      full findings + per-rule summary (JSON; empty with zero rule
      work while diagnostics.enabled is false)
  /debug/history  the workload-history plane ([history] knobs):
      durable per-(sql_digest, plan_digest) windowed records + the
      live window, and the current plan/perf regression findings
      (JSON; empty payload while history.enabled is false)
  /debug/lockgraph  the dynamic lock-order checker
      (TIDB_TPU_LOCK_CHECK / [analysis] lock-check): instrumented
      locks, observed acquisition edges, cycles (potential
      deadlocks), blocking-under-hot-lock events, held mirror (JSON)
  /debug/keyviz  the keyspace heat plane ([heatmap] knobs): the
      time x range traffic matrix, per-range totals, an ASCII
      heatmap rendering, and the current hot-range / split-advisory
      findings (JSON; knobs-only payload while heatmap.enabled is
      false)
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlparse

from .. import obs


class StatusServer:
    def __init__(self, host: str, port: int, sql_server=None) -> None:
        self.sql_server = sql_server
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def do_GET(self):
                server_obs = (outer.sql_server.storage.obs
                              if outer.sql_server else obs.DEFAULT)
                if self.path == "/metrics":
                    # this server's registry + the process-wide one
                    # (disjoint families: copr/device counters only);
                    # probes refresh the sampled gauges (device buffer
                    # bytes, jit entries, RSS) at scrape time
                    obs.run_gauge_probes()
                    body = (server_obs.render()
                            + obs.PROCESS_METRICS.render()).encode()
                    ctype = "text/plain; version=0.0.4"
                elif self.path == "/status":
                    from . import conn as _conn
                    status = {
                        "version": _conn.SERVER_VERSION,
                        "connections": outer.sql_server.connection_count()
                        if outer.sql_server else 0,
                    }
                    if outer.sql_server is not None:
                        # multi-process transport health: mode, peer,
                        # degraded flag, retry counters, and the rpc
                        # circuit-breaker state (reference:
                        # http_status.go exposes store state the same way)
                        st = outer.sql_server.storage
                        health = getattr(st, "transport_health", None)
                        if health is not None:
                            status["transport"] = health()
                        # overload-protection plane: admission gate
                        # occupancy/sheds + governor limit/usage/kills
                        gate = getattr(st, "admission", None)
                        if gate is not None:
                            status["admission"] = gate.stats()
                        gov = getattr(st, "governor", None)
                        if gov is not None:
                            status["governor"] = gov.stats()
                        # range-sharded write leadership: the range
                        # table plus every range this process leads
                        # (id, term, closed_ts) — absent while
                        # [ranges] is disabled
                        plane = getattr(st, "ranges", None)
                        if plane is not None:
                            status["ranges"] = plane.status()
                    # which accelerator the process is on — present
                    # once a backend exists (a scrape never creates one)
                    from .. import device as _device
                    dev = _device.described()
                    if dev is not None:
                        status["device"] = dev
                    # mesh data plane: device count + per-device
                    # sharded-epoch bytes (never grabs a backend as a
                    # scrape side effect — copr/mesh.status is lazy)
                    try:
                        from ..copr import mesh as _mesh
                        status["mesh"] = _mesh.status()
                    except Exception:  # noqa: BLE001 — scrape survives
                        pass
                    # top digests by device time from the continuous
                    # attribution plane (empty while topsql disabled)
                    status["top_sql"] = {
                        "enabled": server_obs.topsql.enabled,
                        "by_device_time":
                            server_obs.topsql.top_by_device(5),
                    }
                    # automated diagnosis: finding counts by severity
                    # (zero rule work while diagnostics.enabled=false)
                    if outer.sql_server is not None:
                        try:
                            from .. import obs_inspect
                            status["inspection"] = \
                                obs_inspect.status_section(
                                    outer.sql_server.storage)
                        except Exception:  # noqa: BLE001 — scrape
                            pass           # survives a broken rule
                    body = json.dumps(status).encode()
                    ctype = "application/json"
                elif self.path == "/slow-query":
                    body = json.dumps(server_obs.slow_queries()).encode()
                    ctype = "application/json"
                elif self.path == "/statements-summary":
                    body = json.dumps(
                        server_obs.statements.snapshot()).encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/trace/"):
                    try:
                        conn_id = int(self.path.rsplit("/", 1)[-1])
                    except ValueError:
                        self.send_response(400)
                        self.end_headers()
                        return
                    tr = server_obs.trace_for(conn_id)
                    if tr is None:
                        self.send_response(404)
                        self.end_headers()
                        return
                    body = json.dumps(tr).encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/metrics/history"):
                    hist = (getattr(outer.sql_server.storage,
                                    "metrics_history", None)
                            if outer.sql_server else None)
                    if hist is None:
                        self.send_response(404)
                        self.end_headers()
                        return
                    body = json.dumps({
                        "interval_s": hist.interval_s,
                        "samples": hist.snapshot(),
                    }).encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/topsql"):
                    # raw attribution windows (oldest first): per-digest
                    # entries with stage sums, per-operator wall/stage/
                    # transfer splits, and admission/governor outcomes
                    body = json.dumps({
                        "enabled": server_obs.topsql.enabled,
                        "window_s": server_obs.topsql.window_s,
                        "digest_cap": server_obs.topsql.digest_cap,
                        "windows": server_obs.topsql.snapshot(),
                    }).encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/waitprofile"):
                    # typed wait-state attribution windows (oldest
                    # first): per-digest exclusive wait splits plus
                    # the dominant state of each entry
                    wp = server_obs.waitprofile
                    wins = wp.snapshot()
                    for w in wins:
                        ents = list(w.get("digests", {}).values())
                        if w.get("other"):
                            ents.append(w["other"])
                        for ent in ents:
                            st, frac = wp.dominant(ent)
                            ent["dominant_wait"] = st
                            ent["dominant_frac"] = round(frac, 4)
                    body = json.dumps({
                        "enabled": wp.enabled,
                        "window_s": wp.window_s,
                        "digest_cap": wp.digest_cap,
                        "windows": wins,
                    }).encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/events"):
                    body = json.dumps(
                        server_obs.events.snapshot()).encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/mesh"):
                    # flight recorder + HBM ledger; degrades to the
                    # plane status alone rather than failing the scrape
                    try:
                        from ..copr import mesh as _mesh
                        payload = _mesh.debug_payload()
                    except Exception as e:  # noqa: BLE001
                        payload = {"error": str(e)[:200]}
                    body = json.dumps(payload).encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/inspection"):
                    if outer.sql_server is None:
                        self.send_response(404)
                        self.end_headers()
                        return
                    # like /debug/mesh: a snapshot-build failure (e.g.
                    # a telemetry plane raising mid-teardown) degrades
                    # to an error payload, never a dropped connection
                    try:
                        from .. import obs_inspect
                        payload = obs_inspect.debug_payload(
                            outer.sql_server.storage)
                    except Exception as e:  # noqa: BLE001
                        payload = {"error": str(e)[:200]}
                    body = json.dumps(payload).encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/replicas"):
                    if outer.sql_server is None:
                        self.send_response(404)
                        self.end_headers()
                        return
                    # follower read tier: router knobs, per-member
                    # serving/closed-ts state, the local apply engine,
                    # and the routed-read outcome counters; degrades
                    # to an error payload like the other /debug routes
                    try:
                        from ..rpc import replica as _replica
                        payload = _replica.debug_payload(
                            outer.sql_server.storage)
                    except Exception as e:  # noqa: BLE001
                        payload = {"error": str(e)[:200]}
                    body = json.dumps(payload).encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/history"):
                    if outer.sql_server is None:
                        self.send_response(404)
                        self.end_headers()
                        return
                    # workload-history plane: knobs, durable records +
                    # the live window, and the current regression
                    # findings; degrades to an error payload like the
                    # other /debug routes
                    try:
                        payload = outer.sql_server.storage.history \
                            .debug_payload()
                    except Exception as e:  # noqa: BLE001
                        payload = {"error": str(e)[:200]}
                    body = json.dumps(payload).encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/keyviz"):
                    if outer.sql_server is None:
                        self.send_response(404)
                        self.end_headers()
                        return
                    # keyspace heat plane: knobs, the time x range
                    # traffic matrix, per-range totals, the ASCII
                    # heatmap rendering, and the current hot-range /
                    # split-advisory findings; degrades to an error
                    # payload like the other /debug routes
                    try:
                        payload = outer.sql_server.storage.heat \
                            .debug_payload()
                    except Exception as e:  # noqa: BLE001
                        payload = {"error": str(e)[:200]}
                    body = json.dumps(payload).encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/failpoints"):
                    from ..util import failpoint
                    body = json.dumps(failpoint.snapshot()).encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/lockgraph"):
                    # the dynamic lock-order checker's graph: enabled
                    # flag, instrumented locks, observed edges, cycles,
                    # blocking-under-hot-lock events, held mirror
                    from ..analysis import lockcheck
                    body = json.dumps(lockcheck.debug_payload()).encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/profile"):
                    q = parse_qs(urlparse(self.path).query)

                    def num(key, default, lo, hi):
                        import math
                        try:
                            v = float(q[key][0])
                        except (KeyError, ValueError, IndexError):
                            return default
                        if not math.isfinite(v):
                            return default
                        return min(max(v, lo), hi)

                    prof = obs.profile_process(
                        seconds=num("seconds", 0.5, 0.05, 10.0),
                        hz=num("hz", 97.0, 1.0, 1000.0))
                    body = json.dumps(prof.to_dict()).encode()
                    ctype = "application/json"
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.httpd = ThreadingHTTPServer((host, port), Handler)
        self.port = self.httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True,
                                        name="titpu-status")
        self._thread.start()

    def close(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
