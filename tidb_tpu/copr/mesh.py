"""Mesh plane: the placement policy and the mesh telemetry.

One coprocessor client serves a storage (copr/client.py CopClient);
where an epoch's rows live is a value (copr/placement.py: SINGLE or
the plane's Sharded) that the client asks for everything that differs.
This module is what CHOOSES that value, and what watches the result:

    session / executor ──> copr/client ──> copr/placement ──> parallel/exchange
                                 ▲                ▲
                           copr/mesh (policy, telemetry; hands out the client)

* **MeshPlane** — one per process (`get_plane`, `configure`). Owns the
  1-D device mesh (`jax.sharding.Mesh` over the `shard` axis), the
  policy below, and `client_for(storage)`: the storage's ONE client,
  shared by every session, attached to this plane. Configured from the
  server's `[mesh]` TOML section or the `TIDB_TPU_MESH*` variables.

* **Policy** — per TABLE EPOCH, decided once per plan node
  (executor/engine.py opens `placement_scope` around every dispatch,
  which asks `placement_for(snap)`):
  - epochs with >= `shard-threshold-rows` rows shard on the row axis
    (`NamedSharding(mesh, P('shard'))`) — the fact-table side;
  - smaller epochs are placed single — sharding a 4k-row dimension
    table across 8 chips would pay collective latency for no bandwidth;
  - join build sides REPLICATE (broadcast exchange) unless bigger than
    `replicate-threshold-bytes` or the client's row threshold, in which
    case they shard by key and probe rows route over the mesh
    (hash-partition exchange, parallel/exchange.py; the reference's MPP
    election, planner/core/fragment.go:45).
  `mesh.enabled = false` or a single visible device means every epoch
  is placed single and the client carries no recorder: the statement
  path does no mesh work at all. A backend that fails to initialise is
  an error, never "single-device".

* **Residency** — staged columns are PLACED at creation and the placed
  arrays are what the client's epoch caches hold, so a sharded epoch
  stays device-resident across queries and sessions. DML that folds a
  new epoch invalidates the old epoch's device buffers eagerly
  (Storage.add_epoch_listener).

* **MeshFlightRecorder** — per client of an active plane: per-shard
  dispatch accounting, skew warnings, compile counts and the
  recompile-storm detector, the HBM provenance ledger and watermark.

Results are bit-identical under both placements by construction: the
sharded programs produce the same exact limb partials and merge them
with native-int32 collectives (copr/placement.py).
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np

from .. import obs
from ..util import failpoint
from .client import CopClient
from .placement import AXIS, SINGLE, Sharded, make_mesh


@dataclass
class MeshConfig:
    """The `[mesh]` knobs (config.py MeshSection mirrors this)."""

    enabled: bool = True
    # devices in the mesh; 0 = every visible device
    axis_size: int = 0
    # epochs with at least this many rows shard on the row axis
    shard_threshold_rows: int = 1 << 20
    # join build sides larger than this stop replicating and shard by
    # key range (probe rows then route over the exchange)
    replicate_threshold_bytes: int = 64 << 20
    # ---- flight recorder (per-shard skew / HBM / compile telemetry) ----
    # warn (session warning + mesh_skew event) when a sharded dispatch's
    # max/mean shard-row ratio reaches this; 0 disables the warning
    skew_warn_ratio: float = 4.0
    # emit a mesh_hbm_watermark event when one device's live buffer
    # bytes cross this fraction of its capacity
    hbm_watermark_fraction: float = 0.85
    # per-device capacity override in bytes; 0 = ask the backend
    # (device.memory_stats()['bytes_limit']; unknown on CPU = disabled)
    hbm_bytes: int = 0
    # per-dispatch shard-accounting ring: digests kept per client
    shard_ring_cap: int = 256


# ==================== flight recorder ====================

def _bits_shard_counts(arr) -> np.ndarray:
    """Per-shard popcount of a P(AXIS)-sharded packed row bitmask: each
    device's local slice of the packed bits IS its survivor set."""
    counts = []
    for sh in sorted(arr.addressable_shards,
                     key=lambda s: s.device.id):
        counts.append(int(np.unpackbits(
            np.asarray(sh.data).view(np.uint8)).sum()))
    return np.asarray(counts, dtype=np.int64)


class MeshFlightRecorder:
    """Per-client mesh dispatch telemetry: a bounded ring of per-shard
    accounting keyed by plan digest, compile counts/durations with a
    recompile-storm detector, and the skew detector feeding EXPLAIN
    ANALYZE / Top SQL / the slow log / tidb_events.

    Hot-path contract: the dispatch side only APPENDS (kind, digest,
    device-array stats, routed bytes, operator) tuples to a thread-
    local list — no lock, no fetch, no sync. collect() (called by the
    engine after each dispatching plan node, i.e. after the
    statement's own device_get) fetches the tiny [n_devices, 2] stats
    arrays, computes skew, and folds everything into the ring. Only
    sharded programs queue anything, and a client of an inactive plane
    has no recorder at all (zero-work contract). No background thread
    — rings are bounded OrderedDicts trimmed at insert."""

    STORM_COMPILES = 3   # same signature compiled this often = a storm
    COMPILE_CAP = 256    # signatures kept in the compile ring
    WARN_INTERVAL_S = 10.0  # per-digest skew-warning throttle

    def __init__(self, plane: "MeshPlane") -> None:
        self.plane = plane
        # the owning storage's Observability (events sink); set by
        # MeshPlane.client_for — None for bare test clients
        self.obs = None
        self._lock = threading.Lock()
        self._ring: "OrderedDict[str, dict]" = OrderedDict()
        self._compiles: "OrderedDict[str, dict]" = OrderedDict()
        self._tls = threading.local()
        # HBM ledger of the client's caches: (col version, mask version)
        # -> telemetry dict, and per-device live-byte high-water marks
        # (guarded by the CLIENT's lock, like the caches they describe;
        # see telemetry())
        self._telemetry_memo: Optional[tuple] = None
        self._device_peak: dict[str, int] = {}

    # ---- dispatch side (hot path) --------------------------------------
    def note_pending(self, kind: str, digest: str, stats,
                     routed: int = 0, op: Optional[str] = None) -> None:
        pend = getattr(self._tls, "pending", None)
        if pend is None:
            pend = self._tls.pending = []
        if len(pend) < 128:  # bound a pathological dispatch loop
            pend.append((kind, digest, stats, int(routed), op))

    # ---- collection (after the statement's own device_get) -------------
    def collect(self) -> Optional[dict]:
        pend = getattr(self._tls, "pending", None)
        if not pend:
            return None
        self._tls.pending = []
        cap = max(int(self.plane.cfg.shard_ring_cap), 1)
        thr = float(self.plane.cfg.skew_warn_ratio)
        note_in = note_rows = None
        max_skew = 0.0
        routed_total = 0
        shards = 0
        now = time.time()
        # one fetch for every dispatch of the statement (a tile each: 72
        # at 300M rows), booked as the fetch it is
        with obs.stage("device_get", prog="titpu_mesh_stats"):
            try:
                fetched = jax.device_get(
                    [None if isinstance(st, dict) else st
                     for _, _, st, _, _ in pend])
            except Exception:  # noqa: BLE001 — telemetry only
                return None
        for (kind, digest, stats, routed, op), a in zip(pend, fetched):
            inp = rows = None
            try:
                if isinstance(stats, dict) and "bits" in stats:
                    rows = _bits_shard_counts(stats["bits"])
                else:
                    inp = a[:, 0].astype(np.int64)
                    rows = a[:, 1].astype(np.int64)
                    if (rows < 0).any():
                        rows = None  # survivors unobservable (hc path)
            except Exception:  # noqa: BLE001 — telemetry only
                continue
            basis = rows if rows is not None and rows.sum() > 0 else inp
            skew = 1.0
            share = 0.0
            if basis is not None and len(basis) and basis.sum() > 0:
                total = float(basis.sum())
                skew = float(basis.max()) / (total / len(basis))
                share = float(basis.max()) / total
            fp = failpoint.inject("mesh/skew")
            if fp:
                skew = float(fp) if isinstance(fp, (int, float)) and \
                    not isinstance(fp, bool) else 1000.0
            # shard count from the observed arrays, not `basis`: a
            # dispatch whose filter matches zero rows is still an
            # n-way dispatch (basis is None when every count is 0)
            n = len(rows) if rows is not None else (
                len(inp) if inp is not None else 0)
            shards = max(shards, n)
            max_skew = max(max_skew, skew)
            routed_total += routed
            if rows is not None:
                note_rows = rows if note_rows is None else note_rows + rows
            if inp is not None:
                note_in = inp if note_in is None else note_in + inp
            # ---- ring update (keyed by plan digest) ----
            last_rows = [int(x) for x in (
                rows if rows is not None else
                (inp if inp is not None else []))]
            warn = False
            with self._lock:
                ent = self._ring.get(digest)
                if ent is None:
                    while len(self._ring) >= cap:
                        self._ring.popitem(last=False)
                    ent = self._ring[digest] = {
                        "digest": digest, "kind": kind, "op": op or "",
                        "dispatches": 0, "shards": n, "last_rows": [],
                        "last_skew": 1.0, "max_skew": 1.0,
                        "skew_hits": [],
                        "in_rows": 0, "out_rows": 0, "routed_bytes": 0,
                        "last_seen": 0.0, "last_warn": 0.0}
                else:
                    self._ring.move_to_end(digest)
                ent["dispatches"] += 1
                ent["shards"] = n
                if op:
                    ent["op"] = op
                if last_rows:
                    ent["last_rows"] = last_rows
                if rows is not None:
                    ent["out_rows"] += int(rows.sum())
                if inp is not None:
                    ent["in_rows"] += int(inp.sum())
                ent["last_skew"] = round(skew, 4)
                ent["max_skew"] = max(ent["max_skew"], round(skew, 4))
                if thr > 0 and skew >= thr:
                    # (timestamp, skew) per dispatch that individually
                    # crossed the warn ratio, bounded — the inspection
                    # rule's "sustained AND current" evidence: it
                    # counts and grades ONLY in-window crossings, so
                    # neither the monotonic max_skew nor a lifetime
                    # hit pile can flag a long-fixed hot range
                    hits = ent.setdefault("skew_hits", [])
                    hits.append((now, round(skew, 4)))
                    del hits[:-32]
                ent["routed_bytes"] += routed
                ent["last_seen"] = now
                if thr > 0 and skew >= thr and \
                        now - ent["last_warn"] >= self.WARN_INTERVAL_S:
                    ent["last_warn"] = now
                    warn = True
            obs.MESH_SKEW_RATIO.set(skew)
            srec = obs.active_stage_recorder()
            if srec is not None and n > 1:
                srec.note_mesh(op or kind, share, skew)
            if warn:
                obs.MESH_SKEW_WARNINGS.inc()
                detail = (f"{kind} dispatch {digest}: max/mean shard "
                          f"rows {skew:.2f} >= mesh.skew-warn-ratio "
                          f"{thr:g}; rows={last_rows}")
                o = self.obs
                if o is not None:
                    o.events.record("mesh_skew", detail=detail,
                                    severity="warn")
                w = getattr(self._tls, "warnings", None)
                if w is None:
                    w = self._tls.warnings = []
                if len(w) < 16:
                    w.append("mesh skew: " + detail)
        if shards == 0:
            return None
        return {"shards": shards,
                "in": None if note_in is None
                else [int(x) for x in note_in],
                "rows": None if note_rows is None
                else [int(x) for x in note_rows],
                "skew": max_skew, "routed": routed_total}

    def drain_warnings(self) -> tuple:
        w = getattr(self._tls, "warnings", None)
        if not w:
            return ()
        self._tls.warnings = []
        return tuple(w)

    def discard_pending(self) -> None:
        """Drop this thread's queued per-shard stats without folding
        them — a failed statement's dispatches must not leak into the
        next statement's first collect()."""
        if getattr(self._tls, "pending", None):
            self._tls.pending = []

    # ---- compile observability -----------------------------------------
    def note_compile(self, kind: str, signature: str, seconds: float,
                     full_key=None) -> None:
        obs.MESH_COMPILES.inc(kind=str(kind))
        obs.MESH_COMPILE_SECONDS.inc(float(seconds))
        storm = None
        with self._lock:
            ent = self._compiles.get(signature)
            if ent is None:
                while len(self._compiles) >= self.COMPILE_CAP:
                    self._compiles.popitem(last=False)
                ent = self._compiles[signature] = {
                    "signature": signature, "kind": str(kind),
                    "count": 0, "total_s": 0.0, "last_s": 0.0,
                    "storm": False, "last_key": ""}
            else:
                self._compiles.move_to_end(signature)
            ent["count"] += 1
            ent["total_s"] = round(ent["total_s"] + float(seconds), 6)
            ent["last_s"] = round(float(seconds), 6)
            if full_key is not None:
                ent["last_key"] = str(full_key)[:200]
            if ent["count"] >= self.STORM_COMPILES and not ent["storm"]:
                ent["storm"] = True
                storm = dict(ent)
        if storm is not None:
            obs.MESH_RECOMPILE_STORMS.inc()
            o = self.obs
            if o is not None:
                o.events.record(
                    "mesh_compile_storm",
                    detail=(f"kernel signature {storm['signature']} "
                            f"({storm['kind']}) compiled "
                            f"{storm['count']}x — bucket/placement-mode "
                            f"churn re-enters XLA compile; last key "
                            f"{storm['last_key']}"),
                    severity="warn")

    # ---- read side ------------------------------------------------------
    def table_rows(self) -> list[list]:
        """information_schema.tidb_mesh_shards rows, newest first."""
        with self._lock:
            ents = [dict(e) for e in self._ring.values()]
        rows = []
        for e in reversed(ents):
            rows.append([
                e["digest"], e["kind"], e["op"], e["dispatches"],
                e["shards"],
                ",".join(str(x) for x in e["last_rows"])[:256],
                e["last_skew"], e["max_skew"], e["in_rows"],
                e["out_rows"], e["routed_bytes"],
                time.strftime("%Y-%m-%d %H:%M:%S",
                              time.localtime(e["last_seen"]))])
        return rows

    def snapshot(self) -> dict:
        """The /debug/mesh payload half owned by this recorder."""
        with self._lock:
            return {
                "dispatches": [dict(e) for e in self._ring.values()],
                "compiles": [dict(e) for e in self._compiles.values()],
            }


class MeshPlane:
    """Process-wide mesh owner: device mesh, placement policy, the
    storages' shared clients, and the per-device telemetry the gauges
    read."""

    AXIS = AXIS

    def __init__(self, cfg: Optional[MeshConfig] = None,
                 devices=None) -> None:
        self.cfg = cfg or MeshConfig()
        self._devices = devices  # explicit device list (tests)
        self._mesh = None
        self._sharded: Optional[Sharded] = None
        self._lock = threading.RLock()
        # devices currently above the HBM watermark (edge-triggered
        # mesh_hbm_watermark events)
        self._above_watermark: set[str] = set()

    # ---- mesh lifecycle ---------------------------------------------------
    @property
    def mesh_built(self) -> bool:
        return self._mesh is not None

    @property
    def mesh(self):
        """The 1-D device mesh; building it initializes the backend, so
        it stays lazy until the first placement decision asks."""
        with self._lock:
            if self._mesh is None:
                devs = self._devices
                if devs is None:
                    devs = jax.devices()
                if self.cfg.axis_size > 0:
                    devs = list(devs)[: self.cfg.axis_size]
                self._mesh = make_mesh(devs)
                self._sharded = Sharded(self._mesh, self.cfg)
            return self._mesh

    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def active(self) -> bool:
        """Enabled AND more than one device. Checking device count
        builds the mesh; a disabled plane never touches the backend.
        A backend that fails to initialise raises here — it is the
        statement's error, not a reason to call the process
        single-device."""
        if not self.cfg.enabled:
            return False
        return self.n_devices > 1

    # ---- placement policy -------------------------------------------------
    def placement_for(self, snap):
        """The placement (copr/placement.py) of one table snapshot:
        this plane's Sharded, or SINGLE. Per-EPOCH deterministic (row
        count is fixed per epoch id), so staged-array cache keys never
        see both placements for one epoch."""
        if self.active and \
                snap.epoch.num_rows >= self.cfg.shard_threshold_rows:
            return self._sharded
        return SINGLE

    # ---- shared clients ---------------------------------------------------
    def client_for(self, storage) -> CopClient:
        """The storage's shared coprocessor client under this plane:
        every session of a storage uses ONE client, so staged epochs
        and compiled kernels are held once per storage rather than once
        per connection, sharded epochs persist across queries AND
        connections, and a folded epoch can be evicted eagerly. While
        the plane is active the client carries a flight recorder whose
        events (mesh_skew / mesh_compile_storm / mesh_hbm_watermark) go
        to the storage's event ring."""
        with self._lock:
            mine = _STORAGE_CLIENTS.setdefault(storage, [])
            c = next((c for c in mine if c.plane is self), None)
            if c is None:
                c = CopClient()
                c.plane = self
                if self.active:
                    c.recorder = MeshFlightRecorder(self)
                    c.recorder.obs = getattr(storage, "obs", None)
                    # a counter that never moved is not rendered: a mesh
                    # that moved nothing must read 0 on /metrics, not be
                    # absent
                    obs.MESH_RESHARD_BYTES.inc(0)
            else:
                mine.remove(c)
            mine.append(c)  # the latest answers client_of
        # outside the plane lock: the listener hook takes storage-side
        # structures only
        if c.heat is None:
            # keyspace heat recorder: scans account per-range traffic
            c.heat = getattr(storage, "heat", None)
        if hasattr(storage, "add_epoch_listener"):
            # eager device-buffer eviction on every epoch replacement
            storage.add_epoch_listener(c.on_epoch_replaced)
        return c

    def clients(self) -> list:
        """This plane's clients that carry a flight recorder."""
        with self._lock:
            return [c for cs in _STORAGE_CLIENTS.values() for c in cs
                    if c.plane is self and c.recorder is not None]

    # ---- telemetry --------------------------------------------------------
    def device_bytes(self) -> dict[str, int]:
        """Live device-resident bytes per device across this plane's
        clients (sharded epochs count their shard; replicated builds
        count a full copy per device — that is what pins HBM). The
        per-client walk is memoized per cache generation
        (telemetry()), so scrapes between cache changes
        cost dict lookups, not an array walk. Crossing the HBM
        watermark is detected here (edge-triggered events)."""
        per: dict[str, int] = {}
        if self.mesh_built:
            for d in self._mesh.devices.flat:
                per[str(d)] = 0
        for c in self.clients():
            try:
                for dev, b in telemetry(c)["per_device"].items():
                    per[dev] = per.get(dev, 0) + b
            except Exception:  # noqa: BLE001 — telemetry only
                continue
        self._check_watermark(per)
        return per

    def device_capacity_bytes(self) -> int:
        """Per-device HBM capacity for the watermark check:
        mesh.hbm-bytes when set, else the backend's bytes_limit
        (unknown on CPU meshes = 0 = watermark disabled)."""
        if self.cfg.hbm_bytes > 0:
            return int(self.cfg.hbm_bytes)
        if not self.mesh_built:
            return 0
        try:
            ms = next(iter(self._mesh.devices.flat)).memory_stats()
            return int((ms or {}).get("bytes_limit", 0) or 0)
        except Exception:  # noqa: BLE001 — CPU devices have no stats
            return 0

    def _check_watermark(self, per: dict[str, int]) -> None:
        cap = self.device_capacity_bytes()
        if cap <= 0:
            return
        thr = cap * float(self.cfg.hbm_watermark_fraction)
        for dev, b in per.items():
            if b >= thr:
                if dev in self._above_watermark:
                    continue
                self._above_watermark.add(dev)
                obs.MESH_HBM_WATERMARK.inc(device=dev)
                detail = (f"device {dev}: {b} live buffer bytes >= "
                          f"{self.cfg.hbm_watermark_fraction:.0%} of "
                          f"{cap}-byte capacity")
                for c in self.clients():
                    o = getattr(c.recorder, "obs", None)
                    if o is not None:
                        o.events.record("mesh_hbm_watermark",
                                        detail=detail, severity="warn")
            else:
                self._above_watermark.discard(dev)

    def status(self) -> dict:
        """The /status `mesh` section (and the diag fan-out payload)."""
        out = {
            "enabled": self.cfg.enabled,
            "built": self.mesh_built,
            "devices": self.n_devices if self.mesh_built else 0,
            "shard_threshold_rows": self.cfg.shard_threshold_rows,
            "replicate_threshold_bytes":
                self.cfg.replicate_threshold_bytes,
            "skew_warn_ratio": self.cfg.skew_warn_ratio,
            "hbm_watermark_fraction": self.cfg.hbm_watermark_fraction,
        }
        if self.mesh_built:
            out["device_buffer_bytes"] = self.device_bytes()
            out["device_peak_bytes"] = self.device_peak_bytes()
            out["reshard_bytes_total"] = obs.MESH_RESHARD_BYTES.get()
        return out

    def device_peak_bytes(self) -> dict[str, int]:
        """High-water live bytes per device across this plane's
        clients (tracked at every telemetry recompute)."""
        peak: dict[str, int] = {}
        for c in self.clients():
            try:
                for dev, b in telemetry(c)["peak"].items():
                    peak[dev] = max(peak.get(dev, 0), b)
            except Exception:  # noqa: BLE001 — telemetry only
                continue
        return peak


def _walk_arrays(o):
    """Yield jax arrays nested in cache values (tuples/dicts/arrays)."""
    if isinstance(o, (tuple, list)):
        for x in o:
            yield from _walk_arrays(x)
    elif isinstance(o, dict):
        for x in o.values():
            yield from _walk_arrays(x)
    elif hasattr(o, "addressable_shards"):
        yield o


def _cached_arrays(client):
    """UNIQUE device arrays resident in a client's caches. The same
    array can sit under two keys (a replicated build under its base
    staging key AND its 'repc' re-placement key — jax.device_put to an
    identical sharding shares buffers), so byte accounting dedupes by
    identity or it would double-count every broadcast build."""
    with client._lock:
        vals = list(client._col_cache.values()) \
            + list(client._mask_cache.values())
    seen: set = set()
    for arr in _walk_arrays(vals):
        if id(arr) not in seen:
            seen.add(id(arr))
            yield arr


def _add_shard_bytes(arr, per: dict) -> None:
    """Accumulate one array's per-device resident bytes from its
    addressable shards (the one walk device_bytes and
    placement_report share)."""
    for sh in arr.addressable_shards:
        dev = str(sh.device)
        per[dev] = per.get(dev, 0) + int(sh.data.nbytes)


def _classify_key(key) -> tuple:
    """(epoch_id or None, provenance kind) for one staging-cache key —
    the HBM ledger's classification of WHAT pins the bytes: 'epoch'
    (sharded/staged scan columns + masks), 'replica' (broadcast join
    builds), 'perm' (join permutation tables), 'partition'
    (key-partitioned builds), 'aligned' (epoch-aligned join columns),
    'rankaux' (streamseg metadata)."""
    try:
        if key and key[0] == "tile":
            return int(key[1]), "epoch"
        k1 = key[1] if len(key) > 1 else None
        if isinstance(k1, str):
            kind = {"perm": "perm", "perm-rep": "perm",
                    "partb": "partition", "aligned": "aligned",
                    "repc": "replica", "repv": "replica",
                    "repvis": "replica", "rankaux": "rankaux",
                    "semibm": "perm", "semibm-rep": "perm"}.get(k1, k1)
            return int(key[0]), kind
        if key and key[-1] == "rep":
            return int(key[0]), "replica"
        if key and isinstance(key[0], int):
            return int(key[0]), "epoch"
    except Exception:  # noqa: BLE001 — ledger is best-effort
        pass
    return None, "other"


def telemetry(client: CopClient) -> dict:
    """Per-device live bytes + the HBM provenance ledger of one recorded
    client's caches in ONE cached-array walk, memoized per cache
    generation (the _VersionedDict mutation counters): scrapes and
    /debug/mesh reads between cache changes are dict lookups, not
    re-walks of every cached array. Also advances the per-device peak
    marks."""
    rec = client.recorder
    with client._lock:
        gen = (client._col_cache.version, client._mask_cache.version)
        memo = rec._telemetry_memo
        if memo is not None and memo[0] == gen:
            return memo[1]
        items = list(client._col_cache.items()) + \
            list(client._mask_cache.items())
        epoch_tables = {eid: tid
                        for tid, eid in client._live_epochs.items()}
    per: dict[str, int] = {}
    entries: dict[tuple, list] = {}
    seen: set = set()
    for key, val in items:
        eid, kind = _classify_key(key)
        for arr in _walk_arrays(val):
            if id(arr) in seen:
                continue  # dedupe rep aliases (see _cached_arrays)
            seen.add(id(arr))
            try:
                shards = list(arr.addressable_shards)
            except Exception:  # noqa: BLE001 — telemetry only
                continue
            for sh in shards:
                try:
                    dev = str(sh.device)
                    b = int(sh.data.nbytes)
                except Exception:  # noqa: BLE001
                    continue
                per[dev] = per.get(dev, 0) + b
                e = entries.setdefault((dev, eid, kind), [0, 0])
                e[0] += 1
                e[1] += b
    rows = [{"device": d, "epoch": eid, "kind": k,
             "arrays": a, "bytes": b}
            for (d, eid, k), (a, b) in sorted(
                entries.items(),
                key=lambda kv: (kv[0][0], str(kv[0][1]), kv[0][2]))]
    with client._lock:
        for dev, b in per.items():
            if b > rec._device_peak.get(dev, 0):
                rec._device_peak[dev] = b
        result = {"per_device": per, "entries": rows,
                  "peak": dict(rec._device_peak),
                  "epoch_tables": epoch_tables}
        rec._telemetry_memo = (gen, result)
    return result


# ==================== process-wide plane ====================

_PLANE: Optional[MeshPlane] = None
_PLANE_LOCK = threading.Lock()

# storage -> its shared clients, one per plane that was asked for one,
# latest last (weak: a collected Storage releases its device buffers
# with it). Tests build private planes over a storage the process plane
# also serves; the diag/infoschema read side (client_of) answers from
# the latest
_STORAGE_CLIENTS: "weakref.WeakKeyDictionary" = \
    weakref.WeakKeyDictionary()


def _env_config() -> MeshConfig:
    """Embedded-use defaults: the `TIDB_TPU_MESH*` env knobs (server
    processes override via config.seed_mesh from the [mesh] section)."""
    import os

    cfg = MeshConfig()
    v = os.environ.get("TIDB_TPU_MESH")
    if v is not None:
        cfg.enabled = v not in ("0", "false", "off", "")
    for env, attr in (("TIDB_TPU_MESH_DEVICES", "axis_size"),
                      ("TIDB_TPU_MESH_SHARD_ROWS", "shard_threshold_rows"),
                      ("TIDB_TPU_MESH_REPLICATE_BYTES",
                       "replicate_threshold_bytes"),
                      ("TIDB_TPU_MESH_HBM_BYTES", "hbm_bytes"),
                      ("TIDB_TPU_MESH_RING_CAP", "shard_ring_cap")):
        raw = os.environ.get(env)
        if raw:
            try:
                setattr(cfg, attr, int(raw))
            except ValueError:
                pass
    for env, attr in (("TIDB_TPU_MESH_SKEW_RATIO", "skew_warn_ratio"),
                      ("TIDB_TPU_MESH_HBM_FRACTION",
                       "hbm_watermark_fraction")):
        raw = os.environ.get(env)
        if raw:
            try:
                setattr(cfg, attr, float(raw))
            except ValueError:
                pass
    return cfg


def get_plane() -> MeshPlane:
    global _PLANE
    with _PLANE_LOCK:
        if _PLANE is None:
            _PLANE = MeshPlane(_env_config())
        return _PLANE


def configure(enabled: Optional[bool] = None,
              axis_size: Optional[int] = None,
              shard_threshold_rows: Optional[int] = None,
              replicate_threshold_bytes: Optional[int] = None,
              skew_warn_ratio: Optional[float] = None,
              hbm_watermark_fraction: Optional[float] = None,
              hbm_bytes: Optional[int] = None,
              shard_ring_cap: Optional[int] = None) -> MeshPlane:
    """Replace the process plane (server startup / tests). Existing
    sessions keep their clients; NEW sessions see the new policy."""
    global _PLANE
    cfg = _env_config()
    if enabled is not None:
        cfg.enabled = enabled
    if axis_size is not None:
        cfg.axis_size = axis_size
    if shard_threshold_rows is not None:
        cfg.shard_threshold_rows = shard_threshold_rows
    if replicate_threshold_bytes is not None:
        cfg.replicate_threshold_bytes = replicate_threshold_bytes
    if skew_warn_ratio is not None:
        cfg.skew_warn_ratio = skew_warn_ratio
    if hbm_watermark_fraction is not None:
        cfg.hbm_watermark_fraction = hbm_watermark_fraction
    if hbm_bytes is not None:
        cfg.hbm_bytes = hbm_bytes
    if shard_ring_cap is not None:
        cfg.shard_ring_cap = shard_ring_cap
    with _PLANE_LOCK:
        _PLANE = MeshPlane(cfg)
        return _PLANE


def client_for(storage) -> CopClient:
    """Default coprocessor client for a session over `storage`: the
    process plane's, shared by every session of that storage. The first
    caller initialises the JAX backend; the device line is logged
    there."""
    from .. import device
    device.describe()
    return get_plane().client_for(storage)


def status() -> dict:
    """The /status `mesh` section; never builds a mesh as a side
    effect (a scrape must not grab the TPU)."""
    with _PLANE_LOCK:
        plane = _PLANE
    if plane is None:
        return {"enabled": _env_config().enabled, "built": False,
                "devices": 0}
    return plane.status()


def client_of(storage) -> Optional[CopClient]:
    """The storage's latest EXISTING client that carries a flight
    recorder, or None — never creates one and never builds a mesh (the
    diag/infoschema read paths must not grab a backend as a side
    effect)."""
    for c in reversed(_STORAGE_CLIENTS.get(storage, ())):
        if c.recorder is not None:
            return c
    return None


def shard_rows(storage) -> list[list]:
    """information_schema.tidb_mesh_shards rows for one storage (empty
    while the mesh plane is inactive or the storage has no client)."""
    c = client_of(storage)
    return c.recorder.table_rows() if c is not None else []


def storage_rows(storage) -> list[list]:
    """information_schema.tidb_mesh_storage rows: the per-device HBM
    provenance ledger — one row per (device, table/epoch, kind) entry
    plus one '(device)' total row per device carrying live AND peak
    bytes (the live totals equal tidb_device_buffer_bytes{device})."""
    c = client_of(storage)
    if c is None:
        return []
    t = telemetry(c)
    names: dict = {}
    for eid, tid in t["epoch_tables"].items():
        store = getattr(storage, "tables", {}).get(tid)
        if store is not None:
            names[eid] = store.table.name
    rows: list[list] = []
    for e in t["entries"]:
        rows.append([e["device"], names.get(e["epoch"]), e["epoch"],
                     e["kind"], e["arrays"], e["bytes"], None])
    for dev in sorted(t["per_device"]):
        rows.append([dev, "(device)", None, "total", None,
                     t["per_device"][dev], t["peak"].get(dev, 0)])
    return rows


def debug_payload() -> dict:
    """The /debug/mesh JSON: plane status + every client's dispatch
    ring, compile ring, and HBM ledger. Never builds a mesh (a scrape
    must not grab the TPU)."""
    out: dict = {"status": status(), "dispatches": [], "compiles": [],
                 "storage": []}
    with _PLANE_LOCK:
        plane = _PLANE
    if plane is None:
        return out
    for c in plane.clients():
        snap = c.recorder.snapshot()
        out["dispatches"].extend(snap["dispatches"])
        out["compiles"].extend(snap["compiles"])
        if plane.mesh_built:
            try:
                t = telemetry(c)
                out["storage"].append({
                    "per_device": t["per_device"], "peak": t["peak"],
                    "entries": t["entries"]})
            except Exception:  # noqa: BLE001 — scrape survives
                continue
    return out


def placement_report(client: CopClient) -> dict:
    """Per-device placement of a client's device-resident buffers —
    the MULTICHIP board / bench flight payload: bytes per device (from
    `arr.sharding` / `addressable_shards`), array counts by placement,
    and an example shard spec."""
    per: dict[str, int] = {}
    n_sharded = n_replicated = n_single = 0
    shard_spec = None
    for arr in _cached_arrays(client):
        try:
            s = arr.sharding
            devs = s.device_set
            _add_shard_bytes(arr, per)
            if len(devs) <= 1:
                n_single += 1
            elif s.is_fully_replicated:
                n_replicated += 1
            else:
                n_sharded += 1
                if shard_spec is None:
                    shard_spec = str(getattr(s, "spec", s))
        except Exception:  # noqa: BLE001 — report what we can
            continue
    return {"device_bytes": per, "sharded_arrays": n_sharded,
            "replicated_arrays": n_replicated,
            "single_arrays": n_single, "shard_spec": shard_spec}


# ---- per-device gauge probe (run before every /metrics scrape and
# metrics-history sample; passes obs.lint_metrics via the registered
# family help texts in obs.py) ------------------------------------------------

def _mesh_telemetry_probe() -> None:
    with _PLANE_LOCK:
        plane = _PLANE
    if plane is None or not plane.mesh_built:
        return
    obs.MESH_DEVICES.set(plane.n_devices)
    for dev, b in plane.device_bytes().items():
        obs.DEVICE_BUFFER_BYTES.set(b, device=dev)


obs.register_gauge_probe(_mesh_telemetry_probe)


__all__ = ["MeshConfig", "MeshPlane", "MeshFlightRecorder",
           "get_plane", "configure", "client_for",
           "client_of", "status", "telemetry", "placement_report",
           "shard_rows", "storage_rows", "debug_payload"]
