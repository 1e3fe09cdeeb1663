"""Mesh plane: process-wide device mesh + placement-aware coprocessor.

The multi-chip DATA plane (ROADMAP item 2). MULTICHIP_r05 showed 8
devices visible while every fragment executed on one: the sharded
client (parallel/dist.py) existed but nothing *chose* it, and it
re-placed cached epochs onto the mesh on every dispatch. This module
owns both decisions:

* **MeshPlane** — one per process. Owns the 1-D device mesh
  (`jax.sharding.Mesh` over the `shard` axis, SNIPPETS.md [1]-[3]
  idiom), the placement policy, and the per-storage shared clients.
  Configured from the server's `[mesh]` TOML section or the
  `TIDB_TPU_MESH*` env knobs for embedded use.

* **Placement policy** — per TABLE EPOCH, decided once per plan node
  (executor/engine.py opens `placement_scope` around every dispatch):
  - epochs with >= `shard-threshold-rows` rows shard on the row axis
    (`NamedSharding(mesh, P('shard'))`) — the fact-table side;
  - smaller epochs run the unchanged single-device path — sharding a
    4k-row dimension table across 8 chips would pay collective latency
    for no bandwidth;
  - join build sides REPLICATE (broadcast exchange) unless bigger than
    `replicate-threshold-bytes` or the row threshold, in which case
    they shard by key range and probe rows route over the mesh
    (hash-partition exchange, parallel/exchange.py). This mirrors the
    reference's MPP broadcast-vs-hash-partition election
    (planner/core/fragment.go:45).

* **Persistent sharded residency** — staged columns are PLACED at
  creation (client._place_cols) and the placed arrays are what the
  epoch caches hold, so a sharded epoch stays device-resident across
  queries and sessions; `tidb_device_transfer_bytes` stops paying a
  re-shard per dispatch. DML that folds a new epoch invalidates the
  old epoch's device buffers eagerly (Storage.add_epoch_listener).

* **Single-device path** — `mesh.enabled = false`, a single visible
  device, or a below-threshold table all take the EXACT single-device
  path: `client_for` hands out the storage's shared plain CopClient
  when the plane is inactive, and MeshCopClient in `single` mode
  dispatches every hook to the base implementations. A backend that
  fails to initialise is an error, never "single-device".

Results are bit-identical to the single-device path by construction:
the sharded kernels produce the same exact limb partials and merge
with native-int32 collectives (parallel/dist.py docstring).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import obs
from ..parallel.dist import AXIS, DistCopClient, _collective_merge, \
    make_mesh, shard_map
from ..util import failpoint
from .client import CopClient, _FirstCallCompile, _dag_key, _obj_nbytes, \
    named_jit, widen32
from .eval import selection_mask


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


def epoch_nbytes(epoch) -> int:
    """Host bytes of one columnar epoch (columns + validity lanes)."""
    n = 0
    for data, valid in zip(epoch.columns, epoch.valids):
        n += int(data.nbytes)
        if valid is not None:
            n += int(valid.nbytes)
    return n


# ==================== flight recorder ====================

def _plan_digest(kind: str, identity) -> str:
    """Stable per-logical-kernel digest: the plan identity WITHOUT the
    shape bucket or placement mode — the same key the recompile-storm
    detector groups by (bucket/mode churn re-enters compile under ONE
    signature)."""
    import hashlib
    return hashlib.sha256(
        (str(kind) + "|" + str(identity)).encode()).hexdigest()[:16]


def _stat_pair(in_rows, out_rows):
    """int32[1, 2] per-shard (input rows, post-filter survivors); the
    P(AXIS) out_spec concatenates shards into [n_devices, 2]."""
    return jnp.stack([jnp.asarray(in_rows, dtype=jnp.int32),
                      jnp.asarray(out_rows, dtype=jnp.int32)])[None]


def _rows_partial_total(p):
    """Device-side total of a 1-limb 'rows' agg partial
    (int32[1, 2, segments], value = hi*4096 + lo per segment): the
    shard's post-filter survivor count, read off the partials the
    kernel already computes — no second pass over the data."""
    return jnp.sum(p[:, 0, :]) * 4096 + jnp.sum(p[:, 1, :])


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
    arrays, computes skew, and folds everything into the ring. The
    single-device CopClient never touches any of this (zero-work
    contract). No background thread — rings are bounded OrderedDicts
    trimmed at insert."""

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
    """Process-wide mesh owner: device mesh, placement policy, shared
    per-storage clients, and the per-device telemetry the gauges read."""

    AXIS = AXIS

    def __init__(self, cfg: Optional[MeshConfig] = None,
                 devices=None) -> None:
        self.cfg = cfg or MeshConfig()
        self._devices = devices  # explicit device list (tests)
        self._mesh = None
        # RLock: client_for constructs clients (which read .mesh) under
        # the same lock
        self._lock = threading.RLock()
        # storage -> shared MeshCopClient (weak: a collected Storage
        # must release its device buffers with it)
        import weakref
        self._clients: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
        # storage -> shared plain CopClient while the plane is inactive
        self._single_clients: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
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
        it stays lazy until the first active client asks."""
        with self._lock:
            if self._mesh is None:
                devs = self._devices
                if devs is None:
                    import jax
                    devs = jax.devices()
                if self.cfg.axis_size > 0:
                    devs = list(devs)[: self.cfg.axis_size]
                self._mesh = make_mesh(devs)
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
    def placement_for(self, snap) -> str:
        """'shard' | 'single' for one table snapshot. Per-EPOCH
        deterministic (row count is fixed per epoch id), so staged-
        array cache keys never see both placements for one epoch."""
        if not self.active:
            return "single"
        if snap.epoch.num_rows >= self.cfg.shard_threshold_rows:
            return "shard"
        return "single"

    # ---- shared clients ---------------------------------------------------
    def client_for(self, storage) -> "MeshCopClient":
        """The storage's shared mesh client: every session of a storage
        uses ONE client, so sharded epochs persist across queries AND
        connections, and a folded epoch can be evicted eagerly."""
        with self._lock:
            c = self._clients.get(storage)
            if c is None:
                c = MeshCopClient(self)
                self._clients[storage] = c
                # a counter that never moved is not rendered: a mesh that
                # moved nothing must read 0 on /metrics, not be absent
                obs.MESH_RESHARD_BYTES.inc(0)
        # the flight recorder's event sink: this storage's event ring
        # receives mesh_skew / mesh_compile_storm / mesh_hbm_watermark
        if c.recorder.obs is None:
            c.recorder.obs = getattr(storage, "obs", None)
        # module-level storage->client registry: the diag/infoschema
        # read side (client_of) resolves through it, so recorder rings
        # stay queryable whichever plane instance built the client
        # (tests construct private planes; latest client wins)
        _STORAGE_CLIENTS[storage] = c
        _attach_storage(c, storage)
        return c

    def single_client_for(self, storage) -> CopClient:
        """The storage's shared plain client while the plane is
        inactive (one visible device, or disabled). A client per
        session — i.e. per wire connection — would stage its own copy
        of every epoch it scans and compile its own kernels, so device
        memory and compile time would grow with the connection count."""
        with self._lock:
            c = self._single_clients.get(storage)
            if c is None:
                c = self._single_clients[storage] = CopClient()
        _attach_storage(c, storage)
        return c

    def clients(self) -> list:
        with self._lock:
            return list(self._clients.values())

    # ---- telemetry --------------------------------------------------------
    def device_bytes(self) -> dict[str, int]:
        """Live device-resident bytes per device across this plane's
        clients (sharded epochs count their shard; replicated builds
        count a full copy per device — that is what pins HBM). The
        per-client walk is memoized per cache generation
        (MeshCopClient.telemetry), so scrapes between cache changes
        cost dict lookups, not an array walk. Crossing the HBM
        watermark is detected here (edge-triggered events)."""
        per: dict[str, int] = {}
        if self.mesh_built:
            for d in self._mesh.devices.flat:
                per[str(d)] = 0
        for c in self.clients():
            try:
                for dev, b in c.telemetry()["per_device"].items():
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
                for dev, b in c.telemetry()["peak"].items():
                    peak[dev] = max(peak.get(dev, 0), b)
            except Exception:  # noqa: BLE001 — telemetry only
                continue
        return peak


def _attach_storage(c: CopClient, storage) -> None:
    """Wire a shared client to its storage, outside the plane lock (the
    listener hook takes storage-side structures only): the keyspace
    heat recorder, so scans account per-range traffic, and eager
    device-buffer eviction on every epoch replacement."""
    if c.heat is None:
        c.heat = getattr(storage, "heat", None)
    if hasattr(storage, "add_epoch_listener"):
        storage.add_epoch_listener(c.on_epoch_replaced)


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


class MeshCopClient(DistCopClient):
    """Placement-aware coprocessor client over a MeshPlane.

    Every dispatch runs under a thread-local placement mode set by
    `placement_scope` (engine.py opens it per plan node from the probe
    snapshot). In `shard` mode the DistCopClient machinery applies —
    row-sharded staging, shard_map kernels, collective merges, the
    broadcast/partition join election. In `single` mode every hook
    dispatches to the base CopClient implementation, so a small table
    behaves EXACTLY as on one device (same kernels, same cache keys
    modulo the mode prefix, same engine tags)."""

    def __init__(self, plane: MeshPlane) -> None:
        super().__init__(plane.mesh)
        self.plane = plane
        self._part_thr_rows = DistCopClient.partition_join_threshold
        # mesh flight recorder: per-shard dispatch accounting, compile
        # observability, skew detection (one per client = per storage)
        self.recorder = MeshFlightRecorder(plane)
        # (col version, mask version) -> telemetry dict; per-device
        # live-byte high-water marks (guarded by self._lock)
        self._telemetry_memo: Optional[tuple] = None
        self._device_peak: dict[str, int] = {}

    # ---- placement state ---------------------------------------------------
    def _mode(self) -> str:
        return getattr(self._tls, "mode", None) or "single"

    def _sharded(self) -> bool:
        return self._mode() == "shard"

    @contextmanager
    def _mode_scope(self, mode: str):
        prev = getattr(self._tls, "mode", None)
        self._tls.mode = mode
        try:
            yield
        finally:
            self._tls.mode = prev

    def placement_scope(self, snap):
        return self._mode_scope(self.plane.placement_for(snap))

    def execute(self, dag, snap):
        # direct callers (no engine scope): decide placement here
        if getattr(self._tls, "mode", None) is None:
            with self.placement_scope(snap):
                return super().execute(dag, snap)
        return super().execute(dag, snap)

    # ---- engine tags -------------------------------------------------------
    def _device_engine(self) -> str:
        return f"device@mesh{self._n}" if self._sharded() else "device"

    def _frag_engine(self, mode: str) -> str:
        if self._sharded():
            return f"device[{mode}]@mesh{self._n}"
        return f"device[{mode}]"

    # ---- mode-dispatched hooks --------------------------------------------
    # kernels compiled for the two modes differ (shard_map vs plain jit)
    # while their cache keys could coincide; the mode prefix keeps them
    # apart
    def _kernel(self, key, build):
        fn = super()._kernel((self._mode(),) + tuple(key), build)
        if isinstance(fn, _FirstCallCompile) and fn.on_first is None:
            # compile observability: the signature EXCLUDES the shape
            # bucket and placement mode, so bucket/mode churn that
            # re-enters compile lands on one signature — the
            # recompile-storm detector's grouping
            rec = self.recorder
            kind = str(key[0]) if key else "?"
            sig = _plan_digest(kind, key[1] if len(key) > 1 else "")
            full = (self._mode(),) + tuple(key)
            fn.on_first = lambda dt, _r=rec, _k=kind, _s=sig, _f=full: \
                _r.note_compile(_k, _s, dt, _f)
        return fn

    def _bucket_size(self, n: int) -> int:
        if self._sharded():
            return DistCopClient._bucket_size(self, n)
        return CopClient._bucket_size(self, n)

    def _place_cols(self, data, valid):
        if self._sharded():
            return DistCopClient._place_cols(self, data, valid)
        return CopClient._place_cols(self, data, valid)

    def _place_mask(self, mask):
        if self._sharded():
            return DistCopClient._place_mask(self, mask)
        return CopClient._place_mask(self, mask)

    def _with_shard_stats(self, fn, kind: str, digest: str):
        """Split a stats-augmented jitted kernel's (result, stats)
        pair: the result flows back to the unchanged base machinery;
        the tiny [n_devices, 2] per-shard stats arrays queue on the
        recorder's thread-local pending list and are fetched at
        take_mesh_note() time — AFTER the statement's own device_get,
        so no extra sync lands inside the dispatch pipeline."""
        rec = self.recorder

        def kern(*args):
            out, stats = fn(*args)
            rec.note_pending(kind, digest, stats,
                             op=obs.active_operator())
            return out

        return kern

    def _build_agg_kernel(self, dag, prepared, cards, segments):
        if not self._sharded():
            return CopClient._build_agg_kernel(
                self, dag, prepared, cards, segments)
        # the DistCopClient shard_map, plus per-shard flight-recorder
        # stats: input rows from the visibility mask, post-filter
        # survivors read off the 'rows' partial the kernel already
        # computes — both BEFORE the collective merge, so they are the
        # per-shard (not global) numbers
        body = self._agg_kernel_body(dag, prepared, cards, segments)
        sched = prepared["__agg_sched__"]

        def sharded(cols, row_mask):
            out = body(cols, row_mask)
            stats = _stat_pair(jnp.sum(row_mask.astype(jnp.int32)),
                               _rows_partial_total(out["rows"]))
            return _collective_merge(out, sched), stats

        mapped = shard_map(sharded, mesh=self.mesh,
                           in_specs=(P(AXIS), P(AXIS)),
                           out_specs=(P(), P(AXIS)))
        return self._with_shard_stats(
            named_jit(mapped, "titpu_mesh_agg"), "agg",
            _plan_digest("agg", _dag_key(dag, prepared)))

    def _build_topn_kernel(self, dag, prepared, expr, desc, n):
        if not self._sharded():
            return CopClient._build_topn_kernel(
                self, dag, prepared, expr, desc, n)
        raw = self._topn_body(dag, prepared, expr, desc, n)
        sel = dag.selection

        def body(cols, row_mask):
            out = raw(cols, row_mask)
            # survivor count re-derives the selection mask; XLA CSEs it
            # with the identical graph inside raw
            m = row_mask if sel is None else selection_mask(
                sel.conditions, widen32(list(cols)), prepared, row_mask)
            return out, _stat_pair(jnp.sum(row_mask.astype(jnp.int32)),
                                   jnp.sum(m.astype(jnp.int32)))

        mapped = shard_map(body, mesh=self.mesh,
                           in_specs=(P(AXIS), P(AXIS)),
                           out_specs=(P(None, AXIS), P(AXIS)))
        return self._with_shard_stats(
            named_jit(mapped, "titpu_mesh_topn"), "topn",
            _plan_digest("topn", _dag_key(dag, prepared)))

    def _build_rowmask_kernel(self, dag, prepared):
        if not self._sharded():
            return CopClient._build_rowmask_kernel(self, dag, prepared)
        raw = self._rowmask_body(dag, prepared)
        sel = dag.selection

        def body(cols, row_mask):
            packed = raw(cols, row_mask)
            m = row_mask if sel is None else selection_mask(
                sel.conditions, widen32(list(cols)), prepared, row_mask)
            return packed, _stat_pair(
                jnp.sum(row_mask.astype(jnp.int32)),
                jnp.sum(m.astype(jnp.int32)))

        mapped = shard_map(body, mesh=self.mesh,
                           in_specs=(P(AXIS), P(AXIS)),
                           out_specs=(P(AXIS), P(AXIS)))
        return self._with_shard_stats(
            named_jit(mapped, "titpu_mesh_rows"), "rows",
            _plan_digest("rows", _dag_key(dag, prepared)))

    def _frag_jit(self, kernel, mode, prepared):
        if not self._sharded():
            return CopClient._frag_jit(self, kernel, mode, prepared)
        rec = self.recorder
        routed = prepared.get("__part_join__") is not None or mode == "hc"
        kind = "frag-" + mode
        digest = _plan_digest(kind, tuple(prepared.get("__sig__", ())))
        build_specs = self._build_in_specs(prepared)
        if mode == "agg":
            sched = prepared["__agg_sched__"]

            def merged(pcols, pvis, builds):
                out = kernel(pcols, pvis, builds)
                stats = _stat_pair(jnp.sum(pvis.astype(jnp.int32)),
                                   _rows_partial_total(out["rows"]))
                return _collective_merge(out, sched), stats

            fn = named_jit(shard_map(
                merged, mesh=self.mesh,
                in_specs=(P(AXIS), P(AXIS), build_specs),
                out_specs=(P(), P(AXIS))), "titpu_mesh_frag_agg")
        elif mode == "hc":
            # DistCopClient's hc specs, with the per-shard stats riding
            # along; post-exchange survivors are not observable outside
            # the candidate path, so only input balance is recorded
            # (-1 = unknown survivors)
            specs = DistCopClient._hc_out_specs(prepared)

            def hc_body(pcols, pvis, builds):
                res = kernel(pcols, pvis, builds)
                stats = _stat_pair(jnp.sum(pvis.astype(jnp.int32)),
                                   jnp.int32(-1))
                return res, stats

            fn = named_jit(shard_map(
                hc_body, mesh=self.mesh,
                in_specs=(P(AXIS), P(AXIS), build_specs),
                out_specs=(specs, P(AXIS))), "titpu_mesh_frag_hc")
        elif mode == "topn":
            # fused join+topn: per-shard top-n candidate rows concatenate
            # along the k axis; survivors are not observable outside the
            # candidate cut, so only input balance is recorded
            def tp_body(pcols, pvis, builds):
                res = kernel(pcols, pvis, builds)
                stats = _stat_pair(jnp.sum(pvis.astype(jnp.int32)),
                                   jnp.int32(-1))
                return res, stats

            fn = named_jit(shard_map(
                tp_body, mesh=self.mesh,
                in_specs=(P(AXIS), P(AXIS), build_specs),
                out_specs=(P(None, AXIS), P(AXIS))), "titpu_mesh_frag_topn")
        else:
            # rows mode: the packed bitmask is already P(AXIS)-sharded;
            # each device's slice popcounts to its survivors at collect
            # time, so the kernel needs no extra outputs
            # rows fragments never route: the partitioned-join election
            # (fragment.py) is agg/hc-only — routed rows would lose
            # probe-row identity — so there are no exchange bytes to
            # account here, only the per-shard survivor popcounts
            inner = DistCopClient._frag_jit(self, kernel, mode, prepared)

            def row_kern(pcols, pvis, builds, *rest):
                out = inner(pcols, pvis, builds, *rest)
                rec.note_pending(kind, digest, {"bits": out},
                                 op=obs.active_operator())
                return out

            return row_kern

        def kern(pcols, pvis, builds, *rest):
            nbytes = 0
            if routed:
                # rows cross the mesh inside the kernel (all_to_all);
                # the collective itself is untimeable host-side, so
                # account the routed payload bytes at dispatch
                nbytes = _obj_nbytes(pcols) + _obj_nbytes([pvis])
                obs.MESH_RESHARD_BYTES.inc(nbytes)
            out, stats = fn(pcols, pvis, builds, *rest)
            rec.note_pending(kind, digest, stats, routed=nbytes,
                             op=obs.active_operator())
            return out

        return kern

    # ---- flight-recorder surface (engine + session hooks) -----------------
    def take_mesh_note(self):
        return self.recorder.collect()

    def drain_mesh_warnings(self) -> tuple:
        return self.recorder.drain_warnings()

    def discard_mesh_pending(self) -> None:
        self.recorder.discard_pending()

    def telemetry(self) -> dict:
        """Per-device live bytes + the HBM provenance ledger in ONE
        cached-array walk, memoized per cache generation (the
        _VersionedDict mutation counters): scrapes and /debug/mesh
        reads between cache changes are dict lookups, not re-walks of
        every cached array. Also advances the per-device peak marks."""
        with self._lock:
            gen = (self._col_cache.version, self._mask_cache.version)
            memo = self._telemetry_memo
            if memo is not None and memo[0] == gen:
                return memo[1]
            items = list(self._col_cache.items()) + \
                list(self._mask_cache.items())
            epoch_tables = {eid: tid
                            for tid, eid in self._live_epochs.items()}
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
        with self._lock:
            for dev, b in per.items():
                if b > self._device_peak.get(dev, 0):
                    self._device_peak[dev] = b
            result = {"per_device": per, "entries": rows,
                      "peak": dict(self._device_peak),
                      "epoch_tables": epoch_tables}
            self._telemetry_memo = (gen, result)
        return result

    def _stage_build_table(self, facade, snap):
        if self._sharded():
            return DistCopClient._stage_build_table(self, facade, snap)
        return CopClient._stage_build_table(self, facade, snap)

    def _place_build_array(self, arr, key=None):
        if self._sharded():
            return DistCopClient._place_build_array(self, arr, key)
        return CopClient._place_build_array(self, arr, key)

    def _hc_exchange_fn(self, frag, prepared):
        if self._sharded():
            return DistCopClient._hc_exchange_fn(self, frag, prepared)
        return None

    def _join_exchange_fn(self, frag, prepared, spans):
        if self._sharded():
            return DistCopClient._join_exchange_fn(
                self, frag, prepared, spans)
        return None

    def _stage_partitioned_build(self, t, snap, lo, span, j):
        # partitioned builds are only elected in shard mode
        return DistCopClient._stage_partitioned_build(
            self, t, snap, lo, span, j)

    # ---- join build election ----------------------------------------------
    @property
    def partition_join_threshold(self):
        return self._part_thr_rows if self._sharded() else None

    @partition_join_threshold.setter
    def partition_join_threshold(self, v) -> None:
        self._part_thr_rows = v

    def _partition_build(self, snap) -> bool:
        if not self._sharded():
            return False
        if CopClient._partition_build(self, snap):
            return True
        return epoch_nbytes(snap.epoch) > \
            self.plane.cfg.replicate_threshold_bytes

    @property
    def frag_axis(self):
        return AXIS if self._sharded() else None

    @property
    def hc_exchange_blocks(self) -> int:
        return self._n if self._sharded() else 1


# ==================== process-wide plane ====================

_PLANE: Optional[MeshPlane] = None
_PLANE_LOCK = threading.Lock()

# storage -> latest shared mesh client, whichever plane built it (weak:
# dies with the storage); the diag/infoschema read side resolves here
import weakref as _weakref  # noqa: E402

_STORAGE_CLIENTS: "_weakref.WeakKeyDictionary" = \
    _weakref.WeakKeyDictionary()


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
    """Default coprocessor client for a session over `storage`, shared
    by every session of that storage: the mesh client when the plane is
    active, else one plain single-device CopClient. The first caller
    initialises the JAX backend; the device line is logged there."""
    from .. import device
    device.describe()
    plane = get_plane()
    if not plane.active:
        return plane.single_client_for(storage)
    return plane.client_for(storage)


def status() -> dict:
    """The /status `mesh` section; never builds a mesh as a side
    effect (a scrape must not grab the TPU)."""
    with _PLANE_LOCK:
        plane = _PLANE
    if plane is None:
        return {"enabled": _env_config().enabled, "built": False,
                "devices": 0}
    return plane.status()


def client_of(storage) -> Optional["MeshCopClient"]:
    """The storage's EXISTING mesh client, or None — never creates one
    and never builds a mesh (the diag/infoschema read paths must not
    grab a backend as a side effect)."""
    return _STORAGE_CLIENTS.get(storage)


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
    t = c.telemetry()
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
                t = c.telemetry()
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


__all__ = ["MeshConfig", "MeshPlane", "MeshCopClient",
           "MeshFlightRecorder", "epoch_nbytes", "get_plane",
           "configure", "client_for", "client_of", "status",
           "placement_report", "shard_rows", "storage_rows",
           "debug_payload"]
