"""CopClient: the TiTPU coprocessor — executes CopDAGs as fused JAX kernels.

This is the seam component of the whole design (reference: kv.Client.Send,
kv/kv.go:317 routed by StoreType; served by unistore's closure executor,
store/mockstore/unistore/cophandler/closure_exec.go). Differences, TPU-first:

* The scan source is the table's immutable column epoch, cached on device
  and padded to shape buckets (static shapes for XLA; the coprocessor-cache
  analog of store/tikv/coprocessor_cache.go:30).
* The device programs are 64-bit-free. TPUs have no native int64/float64
  (JAX x64 mode emulates them as u32 pairs, doubling parameter counts and
  transfer bytes), so every staged column is int32 / float32 / bool and
  every kernel computes in 32-bit. Exactness is preserved by host-side
  interval analysis (bounds.py): integer columns are admitted only when
  their values fit int32, wide per-row aggregate values are decomposed
  into int32-safe shifted terms (bounds.decompose_terms), and sums are
  accumulated via the exact 12-bit-limb scheme in sumexact.py, recombined
  to int64 on the host. MySQL DECIMAL semantics (types/mydecimal.go in the
  reference) hold bit-exactly.
* scan -> selection -> aggregation/topN lower to ONE jitted program, and
  ALL outputs come back in ONE jax.device_get: XLA fuses the whole
  pipeline without materialising intermediates in HBM, and the host
  blocks on the device exactly once per query (every extra fetch is a
  host sync during which the device idles).
* Aggregation is scatter-free (TPU scatter-add serializes): group keys map
  to a dense mixed-radix segment space; small spaces (<=64) reduce via
  per-segment masked sums (XLA fuses them into one pass), larger spaces
  (<=8192) via an exact one-hot f32 einsum on the MXU (sumexact.py). This
  replaces the partial stage of the reference's two-stage hash agg
  (executor/aggregate.go:146).
* MVCC overlay rows (small, host-resident) run through the same kernels in
  a small shape bucket, and partial results merge at the final stage.

Host fallbacks (numpy) cover what the device gate rejects: columns or
expressions too wide for int32, unbounded or >8192-cardinality group keys,
min/max or float aggregates over >64 segments, multi-key/string TopN,
string ordering compares.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs

from ..chunk.column import Column, Dictionary
from ..chunk.chunk import Chunk
from ..plan.dag import CopDAG
from ..plan.expr import Call, Col, Const, PlanExpr
from ..store.table_store import TableSnapshot
from ..types.field_type import FieldType, TypeKind
from . import host_exec
from . import rowbits
from . import runstat
from . import sumexact as SE
from . import topnsel
from .bounds import (
    Bound,
    decompose_terms,
    expr_bounds,
    expr_device_safe,
    fits_int32,
    implied_domains,
    limbs_for,
)
from .eval import CompileError, DeviceError, eval_expr, selection_mask
from .npeval import NumpyEval
from .placement import SINGLE, _narrow, _plan_digest

_I32_MAX = np.int32(2**31 - 1)
_I32_MIN = np.int32(-(2**31) + 1)

# dense segment space caps per reduction strategy
MAX_LOOP_SEGMENTS = 64
# dense-vs-sort group strategy gate (_prepare_agg): an einsum over a
# segment space at least this wide whose estimated occupancy
# (rows / Π(card)) is under the per-slot floor reroutes to the
# sorted-run "group" mode — the mostly-empty one-hot matmul is
# FLOPs-bound on exactly the spaces the sort path handles in
# n log n (Q7's 6084-slot space at ~99 rows/slot, r06's 28s query)
DENSE_SPARSE_MIN_SEGMENTS = 1024
DENSE_MIN_ROWS_PER_SEGMENT = 128
MAX_DENSE_SEGMENTS = 1 << 13
# a string group key the predicates pin to at most this many values takes
# a slot a value (one compare a value a row) instead of a slot a code
MAX_KEY_REMAP = 16
# a LIKE over a dictionary column that this many codes at most match (or
# fail) compares codes instead of gathering from the code table
LIKE_COMPARE_MAX = 32

_FLOAT_BLOCKS = 32  # per-segment f32 block partials (host sums in f64)

# rows per device tile: epochs larger than this stream through the fused
# kernels as fixed-shape tiles whose partials merge exactly like per-shard
# partials (the region-task split of the reference coprocessor,
# store/tikv/coprocessor.go:248 buildCopTasks, as static-shape slices —
# one compiled kernel serves every tile)
import os as _os

TILE_ROWS_DEFAULT = int(_os.environ.get("TIDB_TPU_TILE_ROWS", 1 << 22))


# ---- device telemetry -------------------------------------------------------
# live clients, so one process-wide probe can sum staged-buffer bytes
# and jit-cache entries across sessions without per-dispatch accounting
import weakref as _weakref

_LIVE_CLIENTS: "_weakref.WeakSet" = _weakref.WeakSet()


class _VersionedDict(dict):
    """Staging-cache dict that counts mutations, so telemetry walks
    (mesh flight recorder's HBM ledger, per-device buffer gauges) can
    be memoized per cache generation instead of re-walking every
    cached array on each scrape. Mutations only flow through item
    assignment/deletion here (no update()/setdefault() call sites)."""

    __slots__ = ("version",)

    def __init__(self) -> None:
        super().__init__()
        self.version = 0

    def __setitem__(self, k, v) -> None:
        self.version += 1
        super().__setitem__(k, v)

    def __delitem__(self, k) -> None:
        self.version += 1
        super().__delitem__(k)


def _obj_nbytes(o) -> int:
    if isinstance(o, (tuple, list)):
        return sum(_obj_nbytes(x) for x in o)
    return int(getattr(o, "nbytes", 0) or 0)


def _unique_nbytes(vals, seen: set) -> int:
    """Bytes of device arrays nested in cache values, deduped by
    identity: one replicated build array sits under BOTH its staging
    key and its 'repc' re-placement key (device_put to an identical
    sharding is the same object), and counting it twice would inflate
    the buffer gauge by the whole build size."""
    if isinstance(vals, (tuple, list)):
        return sum(_unique_nbytes(x, seen) for x in vals)
    if isinstance(vals, dict):
        return sum(_unique_nbytes(x, seen) for x in vals.values())
    if id(vals) in seen:
        return 0
    seen.add(id(vals))
    return int(getattr(vals, "nbytes", 0) or 0)


def _note_transfer(*arrays) -> None:
    """Host->device staging accounting on the dispatch hot path (one
    attribute read per array; the gauge feeds cluster_load and the
    MetricsHistory ring). Bytes also attribute to the active plan
    operator on the statement's recorder (Top SQL / slow log)."""
    n = _obj_nbytes(arrays)
    obs.DEVICE_TRANSFER_BYTES.inc(n)
    obs.note_op_bytes(n)


def _device_telemetry_probe() -> None:
    buf = jit = 0
    for c in list(_LIVE_CLIENTS):
        seen: set = set()
        with c._lock:
            buf += _unique_nbytes(list(c._col_cache.values()), seen)
            buf += _unique_nbytes(list(c._mask_cache.values()), seen)
            jit += len(c._kernels)
    obs.DEVICE_BUFFER_BYTES.set(buf)
    obs.JIT_CACHE_ENTRIES.set(jit)


obs.register_gauge_probe(_device_telemetry_probe)


@dataclass
class CopResult:
    """Device/coprocessor answer: one or more partial chunks.

    For aggregation DAGs the chunks use the partial layout
    [group cols..., (val, cnt) per agg] and the final stage merges them.
    For row DAGs the chunks are already-filtered output rows."""

    chunks: list[Chunk]
    is_partial_agg: bool
    # which engine served it: "device", "host(<reason>)", "ranged"
    engine: str = "device"


class CopClient:
    TILE_ROWS = TILE_ROWS_DEFAULT

    def __init__(self) -> None:
        # per-thread placement (placement_scope); a client is shared by
        # every session of a storage, so this must be TLS
        self._tls = threading.local()
        # the mesh plane that chooses placements (copr/mesh.py MeshPlane)
        # and, while that plane is active, its flight recorder for this
        # client; both attached by MeshPlane.client_for. A bare client
        # places every epoch on one device and records nothing
        self.plane = None
        self.recorder = None
        # (epoch_id, offset, bucket) -> (device data, device valid);
        # mutation-versioned so telemetry walks memoize per generation
        self._col_cache: _VersionedDict = _VersionedDict()
        # hc fragment programs (by fragment and signature) whose passing
        # rows once overflowed the packed buffer: they run whole
        # (copr/fragment.py HC_COMPACT_DIV)
        self._hc_dense: set = set()
        # (epoch_id, bucket, digest) -> device visibility mask
        self._mask_cache: _VersionedDict = _VersionedDict()
        # compiled kernel cache
        self._kernels: dict[Any, Any] = {}
        # table_id -> last seen epoch_id, for cache eviction
        self._live_epochs: dict[int, int] = {}
        # (epoch_id, offset) -> integer (lo, hi) or None
        self._stats: dict[tuple[int, int], Bound] = {}
        # guards the caches; kernels themselves are thread-safe to call
        self._lock = threading.RLock()
        # keyspace heat recorder (obs_heat.RangeHeatRecorder), attached
        # by mesh.client_for from the owning storage: every coprocessor
        # scan accounts its table's record span on the heatmap. None on
        # bare clients; one gated attribute test per execute() when off
        self.heat = None
        # program key -> [the topnsel path its TopN or hc body was traced
        # with]
        self._select_paths: dict[Any, list] = {}
        # a counter that never moved is not rendered: a client that has
        # served no TopN (no hc fragment) yet must read 0 on /metrics,
        # not be absent
        for sel_path in topnsel.PATHS:
            obs.TOPN_SELECT.inc(0, path=sel_path)
        for sel_path in topnsel.HC_PATHS:
            obs.HC_SELECT.inc(0, path=sel_path)
        for pack_path in ("packed", "spilled", "whole"):
            obs.HC_PACK.inc(0, path=pack_path)
        for body in runstat.BODIES:
            obs.FRAG_READS.inc(0, mode=f"{body}+runstat")
        for gate_kind in runstat.KINDS:
            obs.RUNSTAT_GATES.inc(0, kind=gate_kind)
        _LIVE_CLIENTS.add(self)

    def _evict_stale(self, table_id: int, epoch_id: int) -> None:
        """Free device buffers cached for a table's superseded epochs
        (compaction/bulk_load create a fresh epoch; the old one's padded
        device copies would otherwise pin HBM for the session lifetime)."""
        with self._lock:
            old = self._live_epochs.get(table_id)
            if old is not None and epoch_id <= old:
                # a session reading an older snapshot must not evict the
                # current epoch's device buffers (shared CopClient: other
                # threads are on the newer epoch)
                return
            self._live_epochs[table_id] = epoch_id
            if old is None:
                return
            def stale(k) -> bool:  # plain or "tile"-prefixed cache keys
                if len(k) > 2 and k[1] == "aligned" and old in k[2]:
                    return True  # a build epoch on an aligned join's path
                return k[0] == old or (k[0] == "tile" and k[1] == old)

            for k in [k for k in self._col_cache if stale(k)]:
                del self._col_cache[k]
            for k in [k for k in self._mask_cache if stale(k)]:
                del self._mask_cache[k]
            for k in [k for k in self._stats if k[0] == old]:
                del self._stats[k]

    def on_epoch_replaced(self, store) -> None:
        """Storage epoch listener (bulk load / compaction / DDL rewrite)
        for a client shared across sessions: free the superseded
        epoch's device buffers NOW instead of on the next dispatch —
        a table nobody queries again would pin them for the process
        lifetime."""
        self._evict_stale(store.table.id, store.epoch.epoch_id)

    # ---- placement (copr/placement.py) -----------------------------------
    # builds larger than this many rows replicate no more under a
    # sharded placement: they shard by key (Sharded.partition_build).
    # Tests shrink it to force the partitioned path at toy scale.
    partition_join_threshold = 1 << 21

    @property
    def placement(self):
        """This thread's placement for the dispatch in flight."""
        return getattr(self._tls, "placement", None) or SINGLE

    @contextmanager
    def placement_scope(self, snap):
        """Pin this thread's placement for one dispatch, as the plane
        decides it from the snapshot's epoch (engine.py opens it per plan
        node, the fragment executor from the probe table), so every
        staging / kernel decision below sees one consistent answer."""
        plane = self.plane
        prev = getattr(self._tls, "placement", None)
        self._tls.placement = SINGLE if plane is None \
            else plane.placement_for(snap)
        try:
            yield
        finally:
            self._tls.placement = prev

    # mesh flight recorder (copr/mesh.py), read by the engine per plan
    # node and by the session per statement. A client without one pays an
    # attribute test and allocates nothing — the zero-work contract the
    # recorder tests pin
    def take_mesh_note(self):
        """Collect + return this thread's pending per-shard dispatch
        accounting (None when nothing sharded ran)."""
        rec = self.recorder
        return None if rec is None else rec.collect()

    def drain_mesh_warnings(self) -> tuple:
        """Pop this thread's pending mesh skew warnings."""
        rec = self.recorder
        return () if rec is None else rec.drain_warnings()

    def discard_mesh_pending(self) -> None:
        """Drop per-shard accounting queued by a failed statement."""
        rec = self.recorder
        if rec is not None:
            rec.discard_pending()

    # ==================== public entry ====================
    def execute(self, dag: CopDAG, snap: TableSnapshot) -> CopResult:
        try:
            if getattr(self._tls, "placement", None) is None:
                # no scope open (a direct caller, not engine.py): ask
                # the plane here
                with self.placement_scope(snap):
                    return self._execute(dag, snap)
            return self._execute(dag, snap)
        except jax.errors.JaxRuntimeError as e:
            raise DeviceError.of(e) from e

    def _execute(self, dag: CopDAG, snap: TableSnapshot) -> CopResult:
        with obs.span(f"copr.execute(t{dag.scan.table_id})") as sp:
            heat = self.heat
            if heat is not None and heat.enabled:
                # one scan note per coprocessor dispatch, split across
                # the ranges overlapping the table's record span —
                # regardless of which engine ends up serving it
                heat.note_scan(
                    dag.scan.table_id,
                    rows=snap.epoch.num_rows + len(snap.overlay_handles),
                    nbytes=_obj_nbytes(snap.epoch.columns))
            if dag.scan.ranges is not None:
                # index-ranged scan: the index permutation resolves a
                # (small) handle set; the DAG runs host-side over the
                # gathered subset (reference: IndexLookUp double read,
                # executor/distsql.go:353)
                obs.COPR_REQUESTS.inc(engine="ranged")
                with obs.stage("ranged", span_name="copr.ranged"):
                    r = host_exec.execute_ranged(dag, snap)
                r.engine = "ranged"
                if sp:
                    sp.note = "ranged"
                return r
            self._evict_stale(dag.scan.table_id, snap.epoch.epoch_id)
            with obs.stage("prepare", span_name="copr.prepare"):
                prepared, fallback = self._prepare(dag, snap)
            if fallback is not None:
                r = self._try_group_fragment(dag, snap, fallback)
                if r is not None:
                    if sp:
                        sp.note = r.engine
                    return r
                if fallback.startswith("sparse segment space"):
                    # the sort-grouped preference could not be honored
                    # (group lift ineligible or gated out): the dense
                    # einsum is still correct and still a device path —
                    # retry without the sparse gate before conceding
                    # the host
                    with obs.stage("prepare", span_name="copr.prepare"):
                        prepared, fallback = self._prepare(
                            dag, snap, sparse_gate=False)
            if fallback is not None:
                obs.COPR_REQUESTS.inc(engine="host")
                with obs.stage("host_fallback",
                               span_name="copr.host_fallback") as hsp:
                    if hsp:
                        hsp.note = fallback
                    r = host_exec.execute_host(dag, snap, fallback)
                r.engine = f"host({fallback})"
                return r
            obs.COPR_REQUESTS.inc(engine="device")
            if sp:
                sp.note = "device"

            chunks: list[Chunk] = []
            base_n = snap.epoch.num_rows
            if base_n > 0:
                with obs.span("device.batch(base)"):
                    chunks.extend(
                        self._run_batch(dag, snap, prepared, overlay=False))
            if len(snap.overlay_handles) > 0:
                with obs.span("device.batch(overlay)"):
                    chunks.extend(
                        self._run_batch(dag, snap, prepared, overlay=True))
            if not chunks:
                chunks = [self._empty_chunk(dag, snap)]
            return CopResult(chunks, is_partial_agg=dag.agg is not None,
                             engine=self.placement.engine())

    def _try_group_fragment(self, dag: CopDAG, snap: TableSnapshot,
                            reason: str) -> Optional[CopResult]:
        """Single-table GROUP BY rejected by the dense-segment gate:
        retry as a degenerate one-table fragment on the sorted-run
        all-groups path (copr/fragment.py mode "group" — sort by the
        packed group keys + segment-reduce, cap-checked candidate
        buffer) before conceding the host. Returns None when the shape
        is ineligible or one of the fragment path's typed gates
        rejects it (or the program did not fit HBM — counted as
        `device-oom`), and the caller proceeds to the original host
        fallback. Any other compiler or runtime error from the device
        is not a gate: it propagates as the statement's error."""
        if dag.agg is None or dag.topn is not None or \
                dag.limit is not None:
            return None
        if not (reason.startswith("group keys not dense-encodable")
                or reason.startswith("sparse segment space")
                or "min/max or float aggregates" in reason):
            return None
        from ..plan.dag import agg_partial_width
        if any(agg_partial_width(d) != 2 for d in dag.agg.aggs):
            return None  # hll sketches don't flow through fragments
        from . import fragment as FR
        frag = FR.lift_group_dag(dag, snap)
        if frag is None:
            return None
        try:
            with obs.span("copr.fragment") as fsp:
                if fsp:
                    fsp.note = "group-lift"
                r = FR._device_fragment(
                    self, frag, {frag.tables[0].table.id: snap})
            obs.COPR_REQUESTS.inc(engine="device-fragment")
            return r
        except (FR._Fallback, CompileError):
            return None
        except jax.errors.JaxRuntimeError as e:
            obs.FRAG_FALLBACKS.inc(reason=FR.device_refusal(e))
            return None

    # ==================== preparation (host-side resolution) ================
    def _col_stats(self, snap: TableSnapshot, off: int) -> Bound:
        """Integer (lo, hi) over valid epoch values, cached per epoch."""
        key = (snap.epoch.epoch_id, off)
        with self._lock:
            if key in self._stats:
                return self._stats[key]
        data = snap.epoch.columns[off]
        valid = snap.epoch.valids[off]
        b: Bound = None
        if data.dtype.kind in "iub" and len(data):
            vals = data if valid is None else data[valid]
            if len(vals):
                b = (int(vals.min()), int(vals.max()))
            else:
                b = (0, 0)
        elif data.dtype.kind in "iub":
            b = (0, 0)
        with self._lock:
            self._stats[key] = b
        return b

    def _runs_ordered(self, snap: TableSnapshot, offsets) -> bool:
        """True when the epoch columns at `offsets` are lexicographically
        non-decreasing in storage order with no NULLs: every group-key
        value then occupies ONE contiguous run, so segment aggregation
        needs no sort (the StreamAgg-over-ordered-input eligibility;
        reference: planner/core/exhaust_physical_plans.go getStreamAggs).
        Cached per epoch — one ~10ms host pass amortized over the epoch
        lifetime."""
        key = (snap.epoch.epoch_id, "runord", tuple(offsets))
        with self._lock:
            hit = self._stats.get(key)
        if hit is None:
            hit = _lex_runs_ordered(snap, offsets)
            with self._lock:
                self._stats[key] = hit
        return bool(hit)

    def _longest_run(self, snap: TableSnapshot, off: int) -> int:
        """Rows in the longest run of equal values of the epoch column at
        `off` (storage order, hidden rows included): the run-statistics
        gates' scan depth and tile halo. Cached per epoch."""
        key = (snap.epoch.epoch_id, "longest", off)
        with self._lock:
            hit = self._stats.get(key)
        if hit is None:
            k = snap.epoch.columns[off]
            hit = 0
            if len(k):
                starts = np.flatnonzero(k[1:] != k[:-1]) + 1
                hit = int(np.diff(starts, prepend=0,
                                  append=len(k)).max())
            with self._lock:
                self._stats[key] = hit
        return hit

    def _rank_meta(self, snap: TableSnapshot, offsets):
        """Host rank metadata for the streamseg kernel over the epoch
        columns at `offsets` (must already be run-ordered). Cached per
        epoch; None when a kernel gate fails."""
        key = (snap.epoch.epoch_id, "rankmeta", tuple(offsets))
        with self._lock:
            hit = self._stats.get(key)
        if hit is None:
            from . import streamseg as SS
            hit = SS.rank_meta(
                [snap.epoch.columns[off] for off in offsets])
            with self._lock:
                self._stats[key] = hit if hit is not None else False
        return hit or None

    def _scan_bounds(self, dag: CopDAG, snap: TableSnapshot) -> list[Bound]:
        """Per scan-column [lo, hi] covering epoch AND overlay values, so one
        kernel decision (staging width, limb count, key offset) is valid for
        both batches of an execute."""
        out: list[Bound] = []
        for off in dag.scan.col_offsets:
            b = self._col_stats(snap, off)
            if len(snap.overlay_handles):
                od = snap.overlay_columns[off]
                ov = snap.overlay_valids[off]
                if od.dtype.kind in "iub" and len(od):
                    vals = od if ov is None else od[ov]
                    if len(vals):
                        ob = (int(vals.min()), int(vals.max()))
                        b = None if b is None else (
                            min(b[0], ob[0]), max(b[1], ob[1]))
                else:
                    b = None if od.dtype.kind not in "iub" else b
            out.append(b)
        return out

    def _prepare(
        self, dag: CopDAG, snap: TableSnapshot, sparse_gate: bool = True
    ) -> tuple[Optional[dict[Any, Any]], Optional[str]]:
        """Resolve string constants/predicates against column dictionaries,
        pick the aggregation strategy, bound value ranges, and build the
        aggregate schedule (term decomposition + limb counts). Returns
        (prepared, None) for the device path or (None, reason) to force the
        host fallback."""
        prepared: dict[Any, Any] = {}
        prepared["__sig__"] = []  # deterministic cache-key payload signature
        dicts = self._scan_dicts(dag, snap)
        col_bounds = self._scan_bounds(dag, snap)
        prepared["__col_bounds__"] = col_bounds

        # int64 host columns must fit int32 to stage (staging is 32-bit-only)
        for ci, off in enumerate(dag.scan.col_offsets):
            if snap.epoch.columns[off].dtype == np.int64 and \
                    not fits_int32(col_bounds[ci]):
                return None, (
                    f"column offset {off} too wide for int32 device staging")

        try:
            exprs: list[PlanExpr] = []
            if dag.selection:
                exprs.extend(dag.selection.conditions)
            if dag.agg:
                exprs.extend(dag.agg.group_by)
                for d in dag.agg.aggs:
                    if d.arg is not None:
                        exprs.append(d.arg)
            if dag.topn:
                exprs.extend(e for e, _ in dag.topn.items)
                if dag.projections:
                    exprs.extend(dag.projections)
            for e in exprs:
                self._prepare_expr(e, dicts, prepared)
        except CompileError as ce:
            return None, str(ce)

        if dag.selection:
            for c in dag.selection.conditions:
                if not expr_device_safe(c, col_bounds):
                    return None, "filter condition too wide for int32 device"

        if dag.agg is not None:
            err = self._prepare_agg(
                dag, dicts, col_bounds, prepared,
                snap.epoch.num_rows + len(snap.overlay_handles),
                sparse_gate=sparse_gate,
                conds=[(dag.selection.conditions, 0)]
                if dag.selection else ())
            if err is not None:
                return None, err
        if dag.topn is not None:
            err = self._prepare_topn(dag, col_bounds, prepared)
            if err is not None:
                return None, err
        return prepared, None

    def _prepare_agg(self, dag, dicts, col_bounds, prepared,
                     n_rows: int, sparse_gate: bool = True,
                     conds=()) -> Optional[str]:
        """`conds`: the statement's predicates as [(conjuncts, base)]
        (bounds.implied_domains) — what they pin a group key to sizes
        its share of the dense segment space."""
        cards, offsets, remaps = self._dense_cards(
            dag, dicts, col_bounds, conds)
        if cards is None:
            return "group keys not dense-encodable on device"
        for g in dag.agg.group_by:
            if not expr_device_safe(g, col_bounds):
                return "group key too wide for int32 device"
        prepared["__dense_cards__"] = cards
        prepared["__key_offsets__"] = offsets
        prepared["__key_remaps__"] = remaps
        segments = 1
        for c in cards:
            segments *= max(c, 1)

        sched: list[dict[str, Any]] = []
        needs_loop = False
        for d in dag.agg.aggs:
            if d.arg is None or d.func == "count":
                sched.append({"kind": "count"})
                continue
            is_f = d.arg.ftype.is_float
            if d.func in ("sum", "avg"):
                if is_f:
                    sched.append({"kind": "fsum"})
                    needs_loop = True
                else:
                    terms = decompose_terms(d.arg, col_bounds)
                    if terms is None:
                        return (f"agg arg {d.arg!r} not int32-decomposable")
                    # where largest value x rows cannot prove that the
                    # total fits the int64 Horner, the host recombines
                    # the limb partials in arithmetic that cannot wrap
                    # (sumexact.combine_terms)
                    b = expr_bounds(d.arg, col_bounds)
                    if b is None:
                        return "agg arg unbounded"
                    sched.append({
                        "kind": "isum",
                        "wide": SE.needs_wide(
                            max(abs(b[0]), abs(b[1])), n_rows),
                        "terms": [
                            (t, s, limbs_for(expr_bounds(t, col_bounds),
                                             SE.LIMB_BITS))
                            for t, s in terms
                        ],
                    })
            elif d.func in ("min", "max"):
                if not is_f and not expr_device_safe(d.arg, col_bounds):
                    return "min/max arg too wide for int32 device"
                sched.append({"kind": d.func, "float": is_f})
                needs_loop = True
            elif d.func == "approx_count_distinct":
                # hashes the exact int32 value; the planner already kept
                # floats/strings host-side (plan/physical.agg_pushable)
                if is_f or not expr_device_safe(d.arg, col_bounds):
                    return "approx_count_distinct arg not int32-hashable"
                sched.append({"kind": "hll"})
            else:
                return f"agg {d.func} not on device"

        if segments <= MAX_LOOP_SEGMENTS:
            strategy = "loop"
        elif needs_loop:
            return (f"{segments} segments with min/max or float aggregates "
                    "is host-side")
        else:
            strategy = "einsum"
        if strategy == "einsum" and sparse_gate and \
                segments >= DENSE_SPARSE_MIN_SEGMENTS and \
                n_rows < segments * DENSE_MIN_ROWS_PER_SEGMENT:
            # dense-vs-sort strategy gate (ISSUE 15): the one-hot
            # einsum pays n_rows x segments FLOPs whether or not the
            # slots are occupied, so a WIDE space with thin estimated
            # occupancy (rows / Π(card) below the per-slot floor —
            # Q7's 26*26*9 = 6084-slot space holds ~4 live groups at
            # any scale) is better served by the PR 14 sorted-run
            # "group" mode, whose cost tracks n_rows log n_rows. Only
            # spaces the candidate buffer can PROVABLY hold reroute
            # (segments <= HAVING_CAP bounds the group count), so the
            # sort path cannot overflow back to the host; callers that
            # cannot take the sorted-run path retry with
            # sparse_gate=False and keep the dense einsum.
            from ..plan.fragment import FragmentDAG
            if segments <= FragmentDAG.HAVING_CAP:
                return (f"sparse segment space: {segments} slots over "
                        f"{n_rows} rows (sort-grouped path preferred)")
        prepared["__strategy__"] = strategy
        prepared["__agg_sched__"] = sched
        prepared["__sig__"].append((
            strategy, tuple(cards), tuple(offsets), tuple(remaps),
            # term EXPRESSIONS are part of the identity: the same query over
            # a different epoch can decompose differently (which factor was
            # wide) while shifts/limbs coincide — a stale kernel would wrap
            tuple(
                (s["kind"],) + tuple(
                    (repr(t), sh, L) for t, sh, L in s.get("terms", ()))
                for s in sched
            ),
        ))
        return None

    def _prepare_topn(self, dag, col_bounds, prepared) -> Optional[str]:
        # projection outputs are gathered by the kernel either way
        if dag.projections:
            for x in dag.projections:
                if x.ftype.is_string:
                    continue
                if not x.ftype.is_float and \
                        not expr_device_safe(x, col_bounds):
                    return "TopN expression too wide for int32 device"
        items = dag.topn.items
        if len(items) == 1:
            e = items[0][0]
            if e.ftype.is_string:
                return "string TopN key is host-side"
            # the sort key references the projection's output schema;
            # substitute so bounds analysis sees scan-column indices
            key = _subst_proj_cols(e, dag.projections) \
                if dag.projections else e
            if not e.ftype.is_float:
                if not expr_device_safe(key, col_bounds):
                    return "TopN expression too wide for int32 device"
                b = expr_bounds(key, col_bounds)
                # negated scores must also fit (ASC uses -v)
                if b is None or not fits_int32(b) or \
                        not fits_int32((-b[1], -b[0])):
                    return "TopN key too wide for int32 device"
            return None
        # multi-key: pack the bounded mixed-direction keys into ONE int32
        # lexicographic composite (copr/topnpack.py) — DESC via
        # complement, NULL ordering as dedicated codes; ties resolve by
        # row order on both paths (top_k is index-stable, the host merge
        # sort above is a stable lexsort)
        from . import topnpack as TP
        keys = []
        for e, desc in items:
            key = _subst_proj_cols(e, dag.projections) \
                if dag.projections else e
            keys.append((key, desc))
        specs, reason = TP.plan_pack(keys, col_bounds)
        if specs is None:
            return reason
        TP.stage_rank_tables(specs, prepared)
        prepared["__topn_pack__"] = specs
        prepared["__sig__"].append(("topnpack",) + TP.pack_sig(specs))
        return None

    def _scan_dicts(self, dag: CopDAG, snap: TableSnapshot) -> list[Optional[Dictionary]]:
        return [snap.dictionaries[off] for off in dag.scan.col_offsets]

    def _prepare_expr(
        self,
        e: PlanExpr,
        dicts: list[Optional[Dictionary]],
        prepared: dict[int, Any],
    ) -> None:
        """Resolve string consts to codes and LIKE/IN to code tables."""
        if isinstance(e, Call):
            str_col = self._plain_string_col(e.args[0]) if e.args else None
            if e.op in ("eq", "ne", "lt", "le", "gt", "ge") and len(e.args) == 2:
                a, b = e.args
                ca = self._plain_string_col(a)
                cb = self._plain_string_col(b)
                if ca is not None and isinstance(b, Const) and \
                        b.ftype.is_string:
                    self._prepare_string_cmp(e, ca, b, dicts, prepared,
                                             swapped=False)
                    return
                if cb is not None and isinstance(a, Const) and \
                        a.ftype.is_string:
                    self._prepare_string_cmp(e, cb, a, dicts, prepared,
                                             swapped=True)
                    return
                if (ca is not None) and (cb is not None):
                    da, db = dicts[ca.idx], dicts[cb.idx]
                    if da is not db:
                        raise CompileError(
                            "string compare across dictionaries is host-side"
                        )
                    if e.op not in ("eq", "ne"):
                        raise CompileError(
                            "string ordering compare is host-side for now"
                        )
                    return
                if (a.ftype.is_string or b.ftype.is_string) and e.op not in (
                    "eq", "ne"
                ):
                    raise CompileError("string compare form not supported")
            if e.op == "in_values" and str_col is not None:
                d = dicts[str_col.idx]
                assert d is not None
                codes = [d.lookup(str(v)) for v in e.extra]
                prepared[id(e)] = [c for c in codes if c >= 0] or [-1]
                prepared["__sig__"].append(tuple(prepared[id(e)]))
                for a in e.args:
                    self._prepare_expr(a, dicts, prepared)
                return
            if e.op == "like":
                if str_col is None:
                    raise CompileError("LIKE over computed strings is host-side")
                d = dicts[str_col.idx]
                assert d is not None
                import re as _re
                pat = _like_to_regex(str(e.extra))
                rx = _re.compile(pat, _re.DOTALL)
                table = np.fromiter(
                    (rx.fullmatch(v) is not None for v in d.values),
                    dtype=bool, count=len(d),
                )
                # a per-row look-up in the code table is a gather (135 M
                # elements a second on a v5e: 0.45 s over 60 M rows, PR
                # 35); where few codes match, or few do not, the row
                # compares its code with those instead
                hits, misses = np.nonzero(table)[0], np.nonzero(~table)[0]
                if len(table) and min(len(hits), len(misses)) \
                        <= LIKE_COMPARE_MAX:
                    few = hits if len(hits) <= len(misses) else misses
                    prepared[id(e)] = (few is hits,
                                       tuple(int(c) for c in few))
                    prepared["__sig__"].append(("like",) + prepared[id(e)])
                    return
                prepared[id(e)] = jnp.asarray(table) if len(table) else \
                    jnp.zeros(1, dtype=bool)
                prepared["__sig__"].append(("like", len(d)))
                return
            for a in e.args:
                self._prepare_expr(a, dicts, prepared)
        elif isinstance(e, Const) and e.ftype.is_string:
            raise CompileError("free-standing string constant on device")

    def _prepare_string_cmp(
        self,
        e: Call,
        col: Col,
        const: Const,
        dicts: list[Optional[Dictionary]],
        prepared: dict[int, Any],
        swapped: bool,
    ) -> None:
        d = dicts[col.idx]
        assert d is not None
        s = str(const.value)
        if e.op in ("eq", "ne"):
            prepared[id(const)] = d.lookup(s)
            prepared["__sig__"].append(prepared[id(const)])
            return
        raise CompileError("string ordering compare is host-side for now")

    @staticmethod
    def _plain_string_col(e: PlanExpr) -> Optional[Col]:
        if isinstance(e, Col) and e.ftype.is_string:
            return e
        return None

    def _dense_cards(
        self, dag: CopDAG, dicts: list[Optional[Dictionary]],
        col_bounds: list[Bound], conds=(),
    ) -> tuple[Optional[list[int]], Optional[list[int]], Optional[list]]:
        """Per-group-key (cardinality+1 for NULL, value offset, remap).
        String keys use dictionary codes; integer/date/decimal keys use
        epoch min/max stats — card = hi-lo+2, key = value-lo (reference
        analog: the two-stage hash agg key space, executor/aggregate.go:146,
        made dense so the reduction is a fixed-shape XLA program). Where
        the predicates `conds` pin a key (bounds.implied_domains), the
        space holds only what a passing row can carry: a string key's
        `remap` lists the few codes left (slot i = remap[i], in place of
        a slot a dictionary code), an integer key's range tightens."""
        assert dag.agg is not None
        code_sets, key_bounds = implied_domains(conds, dicts, col_bounds) \
            if conds else ({}, col_bounds)
        cards: list[int] = []
        offsets: list[int] = []
        remaps: list = []
        for g in dag.agg.group_by:
            remap = None
            if isinstance(g, Col) and g.ftype.is_string:
                d = dicts[g.idx]
                assert d is not None
                pinned = code_sets.get(g.idx)
                if pinned is not None and len(pinned) <= MAX_KEY_REMAP \
                        and len(pinned) < len(d):
                    remap = pinned
                cards.append((len(d) if remap is None else len(remap)) + 1)
                offsets.append(0)
            elif g.ftype.is_string:
                return None, None, None
            elif isinstance(g, Col) and g.ftype.kind == TypeKind.BOOLEAN:
                cards.append(3)
                offsets.append(0)
            elif g.ftype.is_float:
                return None, None, None
            else:
                b = expr_bounds(g, key_bounds)
                if b is None:
                    return None, None, None
                lo, hi = b
                card = hi - lo + 2
                if card > MAX_DENSE_SEGMENTS:
                    return None, None, None
                cards.append(card)
                offsets.append(lo)
            remaps.append(remap)
        prod = 1
        for c in cards:
            prod *= max(c, 1)
        if prod > MAX_DENSE_SEGMENTS:
            return None, None, None
        return cards, offsets, remaps

    # ==================== batch execution ====================
    def _run_batch(
        self,
        dag: CopDAG,
        snap: TableSnapshot,
        prepared: dict[Any, Any],
        overlay: bool,
    ) -> list[Chunk]:
        with obs.stage("staging", span_name="copr.staging"):
            if overlay:
                cols, row_mask, host_cols, host_mask = self._stage_inputs(
                    dag, snap, overlay=True)
                tiles = [(cols, row_mask, len(snap.overlay_handles))]
            else:
                tiles = self._stage_tiles(dag, snap)
                host_cols = host_mask = None  # lazily built, row path
        if dag.agg is not None:
            return self._run_agg(dag, snap, prepared, tiles)
        if overlay is False:
            host_cols, host_mask = self._host_view(dag, snap)
        if dag.topn is not None:
            return self._run_topn(dag, snap, prepared, tiles)
        return self._run_rows(dag, snap, prepared, tiles, host_cols,
                              host_mask)

    def _host_view(self, dag: CopDAG, snap: TableSnapshot):
        """Host numpy views of the epoch's scan columns (row-path
        projection input); validity stays None when all-valid so big
        epochs never allocate full ones-masks per query."""
        epoch = snap.epoch
        host_cols = [
            (epoch.columns[off], epoch.valids[off])
            for off in dag.scan.col_offsets
        ]
        return host_cols, snap.base_visible

    def _stage_tiles(self, dag: CopDAG, snap: TableSnapshot):
        """Device tiles covering the base epoch: [(dev_cols, vis, n_rows)].

        Epochs at or below TILE_ROWS stage as the single cached tile of
        _stage_inputs (keeps the SF1-scale path and its cache keys intact);
        larger epochs split into TILE_ROWS slices all padded to ONE shape
        bucket, so a single compiled kernel serves every tile and the
        per-tile partials merge exactly like per-shard partials."""
        epoch = snap.epoch
        n = epoch.num_rows
        if n <= self.TILE_ROWS:
            cols, vis, _, _ = self._stage_inputs(dag, snap, overlay=False)
            return [(cols, vis, n)]
        T = self.TILE_ROWS
        pl = self.placement
        b = pl.bucket_size(T)
        with self._lock:
            cacheable = self._live_epochs.get(dag.scan.table_id) \
                == epoch.epoch_id
        tiles = []
        vis_digest = snap.mask_digest
        with self._lock:
            # evict masks of superseded visibility states (same epoch+bucket,
            # different digest) — one live mask set per epoch
            for k in [k for k in self._mask_cache
                      if k[0] == "tile" and k[1] == epoch.epoch_id
                      and k[2] == b and k[3] != vis_digest]:
                del self._mask_cache[k]
        for ti in range(-(-n // T)):
            lo = ti * T
            cnt = min(lo + T, n) - lo
            dev_cols = []
            for off in dag.scan.col_offsets:
                key = ("tile", epoch.epoch_id, off, b, ti)
                with self._lock:
                    cached = self._col_cache.get(key)
                if cached is None:
                    obs.COL_CACHE.inc(result="miss")
                    data = epoch.columns[off][lo:lo + cnt]
                    valid = epoch.valids[off]
                    vslice = np.ones(cnt, bool) if valid is None \
                        else valid[lo:lo + cnt]
                    padded = _pad(_narrow_stats(
                        data, self._col_stats(snap, off)), b)
                    pvalid = _pad_bool(vslice, b)
                    with obs.stage("transfer"):
                        cached = pl.place_cols(padded, pvalid)
                    _note_transfer(cached)
                    if cacheable:
                        with self._lock:
                            self._col_cache[key] = cached
                else:
                    obs.COL_CACHE.inc(result="hit")
                dev_cols.append(cached)
            vkey = ("tile", epoch.epoch_id, b, vis_digest, ti)
            with self._lock:
                vis = self._mask_cache.get(vkey)
            if vis is None:
                pmask = _pad_bool(snap.base_visible[lo:lo + cnt], b)
                with obs.stage("transfer"):
                    vis = pl.place_mask(pmask)
                _note_transfer(vis)
                if cacheable:
                    with self._lock:
                        self._mask_cache[vkey] = vis
            tiles.append((dev_cols, vis, cnt))
        return tiles

    def _stage_inputs(self, dag: CopDAG, snap: TableSnapshot, overlay: bool,
                      build: bool = False):
        """Pad + upload scan columns as 32-bit device buffers; returns device
        (data, valid) pairs, the device row-visibility mask, host numpy
        views, and the host-side visibility mask (so paths that need no
        device work never touch the device). EVERY staged scan column and
        mask is created through the placement (host numpy in, device
        arrays out), and the PLACED arrays are what the caches hold, so a
        sharded epoch stays device-resident across queries; `build` says
        the table is staged as a join's build side."""
        offsets = dag.scan.col_offsets
        narrow = _narrow
        pl = self.placement

        if overlay:
            n = len(snap.overlay_handles)
            b = pl.bucket_size(n)
            host_cols = []
            dev_cols = []
            for ci, off in enumerate(offsets):
                data = snap.overlay_columns[off]
                valid = snap.overlay_valids[off]
                vfull = np.ones(n, bool) if valid is None else valid
                host_cols.append((data, vfull))
                with obs.stage("transfer"):
                    dev_cols.append(pl.place_cols(
                        _pad(narrow(data), b), _pad_bool(vfull, b)))
                _note_transfer(dev_cols[-1])
            mask = np.zeros(b, bool)
            mask[:n] = True
            with obs.stage("transfer"):
                dev_mask = pl.place_mask(mask)
            return dev_cols, dev_mask, host_cols, mask[:n]

        epoch = snap.epoch
        n = epoch.num_rows
        b = pl.bucket_size(n)
        with self._lock:
            # a session on an already-superseded snapshot must not re-seed
            # the cache: eviction only clears the immediately superseded
            # epoch, so stale entries would pin HBM for the client lifetime
            cacheable = self._live_epochs.get(dag.scan.table_id) \
                == epoch.epoch_id
        dev_cols = []
        host_cols = []
        sfx = pl.stage_key_suffix(build)
        for off in offsets:
            key = (epoch.epoch_id, off, b) + sfx
            data = epoch.columns[off]
            valid = epoch.valids[off]
            with self._lock:
                cached = self._col_cache.get(key)
            if cached is None:
                obs.COL_CACHE.inc(result="miss")
                padded = _pad(_narrow_stats(
                    data, self._col_stats(snap, off)), b)
                # validity stays None on the host (as _host_view): ones
                # are built for the upload only, never on a cache hit
                pvalid = _pad_bool(
                    np.ones(n, bool) if valid is None else valid, b)
                with obs.stage("transfer"):
                    cached = pl.place_cols(padded, pvalid, build)
                _note_transfer(cached)
                if cacheable:
                    with self._lock:
                        self._col_cache[key] = cached
            else:
                obs.COL_CACHE.inc(result="hit")
            dev_cols.append(cached)
            host_cols.append((data, valid))
        vis_digest = snap.mask_digest
        vis_key = (epoch.epoch_id, b, vis_digest) + sfx
        with self._lock:
            vis = self._mask_cache.get(vis_key)
        if vis is None:
            pmask = _pad_bool(snap.base_visible, b)
            with obs.stage("transfer"):
                vis = pl.place_mask(pmask, build)
            _note_transfer(vis)
            if cacheable:
                with self._lock:
                    # one live digest per (epoch, bucket): every delete/
                    # update changes the digest, and stale masks would
                    # pin HBM until the epoch is superseded (both
                    # placements of the CURRENT digest stay live)
                    for k in [k for k in self._mask_cache
                              if k[:2] == (epoch.epoch_id, b)
                              and k[2] != vis_digest]:
                        del self._mask_cache[k]
                    self._mask_cache[vis_key] = vis
        return dev_cols, vis, host_cols, snap.base_visible

    def _kernel(self, key, build):
        # programs built for the two placements differ (shard_map vs
        # plain jit) while their keys could coincide; the placement's
        # prefix keeps them apart
        full = (self.placement.key,) + tuple(key)
        with self._lock:
            k = self._kernels.get(full)
        if k is None:
            obs.JIT_CACHE.inc(result="miss")
            k = build()
            with self._lock:
                self._kernels[full] = k
            # jax.jit is lazy: trace + XLA compile happen on the FIRST
            # invocation, so that call — not build() — is the compile
            # stage (nested stages subtract, so the kernel stage keeps
            # only execute time). The raw kernel is already cached —
            # only this dispatch pays the wrapper.
            kind = str(key[0])
            fn = _FirstCallCompile(k, kind)
            rec = self.recorder
            if rec is not None:
                # compile observability: the signature EXCLUDES the shape
                # bucket and the placement, so bucket/placement churn
                # that re-enters compile lands on one signature — the
                # recompile-storm detector's grouping
                sig = _plan_digest(kind, key[1] if len(key) > 1 else "")
                fn.on_first = lambda dt: rec.note_compile(
                    kind, sig, dt, full)
            return fn
        obs.JIT_CACHE.inc(result="hit")
        return k

    # ---- aggregation path ---------------------------------------------------
    def _run_agg(self, dag, snap, prepared, tiles) -> list[Chunk]:
        agg = dag.agg
        cards: list[int] = prepared["__dense_cards__"]
        bucket = tiles[0][1].shape[0]
        key = ("agg", _dag_key(dag, prepared), bucket, tuple(cards))
        segments = 1
        for c in cards:
            segments *= max(c, 1)
        kern = self._kernel(key, lambda: self._build_agg_kernel(
            dag, prepared, cards, segments))
        # dispatches are async and queue on the device; ONE device_get
        # fetches every tile's partials with a single host sync
        from ..util import interrupt
        with obs.stage("kernel", span_name="device.dispatch",
                       prog="titpu_agg") as sp:
            if sp:
                sp.note = f"{len(tiles)} tile(s)"
            devs = []
            for cols, vis, _ in tiles:
                interrupt.check()  # KILL QUERY checkpoint between tiles
                devs.append(kern(cols, vis))
        with obs.stage("device_get", span_name="device.fetch", clocked=True,
                       prog="titpu_agg"):
            outs = jax.device_get(devs)
        with obs.stage("merge"):
            out = _merge_tile_outs(outs, prepared["__agg_sched__"])
        with obs.stage("decode"):
            group_dicts = [
                snap.dictionaries[dag.scan.col_offsets[g.idx]]
                if g.ftype.is_string and isinstance(g, Col) else None
                for g in agg.group_by
            ]
            chunk = decode_agg_partials(
                agg, prepared, cards, out, group_dicts,
                dag.output_types[len(agg.group_by):])
        return [] if chunk is None else [chunk]

    def _build_agg_kernel(self, dag, prepared, cards, segments):
        return self.placement.agg_program(
            self._agg_kernel_body(dag, prepared, cards, segments),
            prepared["__agg_sched__"], _dag_key(dag, prepared),
            self.recorder)

    def _agg_kernel_body(self, dag, prepared, cards, segments):
        """Pure (cols, row_mask) -> {partials} function. All leaves are
        int32 (exact limb partials, sentinel min/max) or f32 (block float
        sums), so a sharded placement merges them with native-int32
        psum / pmin / pmax (placement._collective_merge)."""
        agg = dag.agg
        sel = dag.selection

        def kernel(cols, row_mask):
            cols = widen32(cols)
            mask = row_mask
            if sel is not None:
                mask = selection_mask(sel.conditions, cols, prepared, mask)
            return agg_partials(agg, prepared, cards, segments, cols, mask)

        return kernel

    # ---- row path (scan/selection/projection) -------------------------------
    def _run_rows(self, dag, snap, prepared, tiles, host_cols, host_mask):
        """Device evaluates the (fused) filter and returns ONLY a packed
        bitmask — one small buffer per tile; projections are computed
        host-side over the selected subset (numpy over the epoch's host
        columns). Full-width device outputs would pay the device->host
        transfer for every row."""
        if dag.selection is None:
            # pure scan: nothing for the device to do — host mask suffices
            idx = np.nonzero(host_mask)[0]
            if dag.limit is not None and len(idx) > dag.limit.n:
                idx = idx[: dag.limit.n]
            return self._host_rows(dag, snap, host_cols, idx)
        bucket = tiles[0][1].shape[0]
        key = ("rowmask", _dag_key(dag, prepared), bucket)
        kern = self._kernel(key, lambda: self._build_rowmask_kernel(
            dag, prepared))
        with obs.stage("kernel", span_name="device.dispatch",
                       prog="titpu_rowmask"):
            devs = [kern(cols, vis) for cols, vis, _ in tiles]
        with obs.stage("device_get", span_name="device.fetch", clocked=True,
                       prog="titpu_rowmask"):
            packs = jax.device_get(devs)
        with obs.stage("decode"):
            idx = rowbits.decode(
                packs, [cnt for _, _, cnt in tiles], self.TILE_ROWS,
                limit=None if dag.limit is None else dag.limit.n)
        return self._host_rows(dag, snap, host_cols, idx)

    def _build_rowmask_kernel(self, dag, prepared):
        return self.placement.rows_program(
            self._rowmask_body(dag, prepared), _survivors(dag, prepared),
            _dag_key(dag, prepared), self.recorder)

    def _rowmask_body(self, dag, prepared):
        sel = dag.selection

        def kernel(cols, row_mask):
            cols = widen32(cols)
            mask = selection_mask(sel.conditions, cols, prepared, row_mask)
            return jnp.packbits(mask)

        return kernel

    def _host_rows(self, dag, snap, host_cols, idx) -> list[Chunk]:
        """Project the selected rows host-side (numpy): the read's
        `gather` stage."""
        with obs.stage("gather"):
            return self._gather_rows(dag, snap, host_cols, idx)

    def _gather_rows(self, dag, snap, host_cols, idx) -> list[Chunk]:
        dicts = self._scan_dicts(dag, snap)
        columns = []
        k = len(idx)
        if dag.projections is not None:
            sub = [
                (d[idx], np.ones(k, bool) if v is None else v[idx])
                for d, v in host_cols
            ]
            ev = NumpyEval(sub, dicts, k)
            for pi, e in enumerate(dag.projections):
                v, vl = ev.eval(e)
                ft = dag.output_types[pi]
                dictionary = None
                if ft.is_string and isinstance(e, Col):
                    dictionary = snap.dictionaries[dag.scan.col_offsets[e.idx]]
                columns.append(Column(
                    ft, np.asarray(v).astype(ft.np_dtype),
                    None if vl.all() else np.asarray(vl), dictionary))
        else:
            for ci, off in enumerate(dag.scan.col_offsets):
                data, vfull = host_cols[ci]
                ft = dag.output_types[ci]
                d = data[idx]
                v = np.ones(k, bool) if vfull is None else vfull[idx]
                columns.append(Column(
                    ft, d, None if v.all() else v, snap.dictionaries[off]))
        if not columns:
            return []
        return [Chunk(columns)]

    # ---- TopN path ----------------------------------------------------------
    def _run_topn(self, dag, snap, prepared, tiles):
        """Per-tile k-candidate gather; the host sort+limit above merges
        the per-tile (and per-shard) candidate chunks exactly."""
        expr, desc = dag.topn.items[0]
        n = dag.topn.n
        bucket = tiles[0][1].shape[0]
        key = ("topn", _dag_key(dag, prepared), bucket, n,
               tuple(d for _, d in dag.topn.items))
        taken = self._select_taken(key, prepared)
        kern = self._kernel(key, lambda: self._build_topn_kernel(
            dag, prepared, expr, desc, n))
        with obs.stage("kernel", span_name="device.dispatch",
                       prog="titpu_topn"):
            devs = [kern(cols, vis) for cols, vis, _ in tiles]
        obs.TOPN_SELECT.inc(path=taken[0])
        with obs.stage("device_get", span_name="device.fetch", clocked=True,
                       prog="titpu_topn"):
            outs = jax.device_get(devs)
        with obs.stage("decode"):
            chunks = [c for c in (self._topn_decode(dag, snap, out)
                                  for out in outs) if c is not None]
        return chunks

    def _select_taken(self, key, prepared) -> list:
        """The one-slot record, kept beside the program cached under
        `key`, of the path its body selects the winners by: a body built
        from `prepared` hands it to topnsel.select (a TopN) or
        topnsel.candidates (an hc fragment), which fills it in when the
        program is traced (at its first dispatch) with what it does for
        the shape it ranks. tidb_copr_topn_select_total, or
        tidb_copr_hc_select_total, counts a read under it after the
        dispatch."""
        with self._lock:
            taken = self._select_paths.setdefault(key, [])
        prepared["__select_taken__"] = taken
        return taken

    def _topn_decode(self, dag, snap, out) -> Optional[Chunk]:
        ints = out["ints"]  # int32[2 + n_int_cols*2, k]
        flts = out.get("flts")  # f32[n_flt_cols*2, k]
        picked = ints[1].astype(bool)
        columns = []
        if dag.projections is not None:
            exprs = dag.projections
        else:
            exprs = [Col(ci, ft) for ci, ft in enumerate(dag.output_types)]
        ii, fi = 0, 0
        for pi, e in enumerate(exprs):
            ft = dag.output_types[pi]
            if ft.is_float:
                data = flts[fi][picked]
                valid = flts[fi + 1][picked] > 0
                fi += 2
            else:
                data = ints[2 + ii][picked]
                valid = ints[2 + ii + 1][picked].astype(bool)
                ii += 2
            dictionary = None
            if ft.is_string and isinstance(e, Col):
                dictionary = snap.dictionaries[dag.scan.col_offsets[e.idx]]
            columns.append(Column(
                ft, data.astype(ft.np_dtype),
                None if valid.all() else valid, dictionary))
        if not columns:
            return None
        return Chunk(columns)

    def _build_topn_kernel(self, dag, prepared, expr, desc, n):
        return self.placement.topn_program(
            self._topn_body(dag, prepared, expr, desc, n),
            _survivors(dag, prepared), _dag_key(dag, prepared),
            self.recorder)

    def _topn_body(self, dag, prepared, expr, desc, n):
        sel = dag.selection
        projections = dag.projections
        if projections is not None:
            # sort items were resolved against the projection's output
            # schema; substitute so the key computes over projected values
            expr = _subst_proj_cols(expr, projections)
        if projections is not None:
            exprs = projections
        else:
            exprs = [Col(ci, ft) for ci, ft in enumerate(dag.output_types)]
        out_types = dag.output_types

        pack = prepared.get("__topn_pack__")
        taken = prepared.get("__select_taken__")

        def kernel(cols, row_mask):
            cols = widen32(cols)
            mask = row_mask
            if sel is not None:
                mask = selection_mask(sel.conditions, cols, prepared, mask)
            if pack is not None:
                # multi-key lexicographic composite (>= 0 by
                # construction); dropped rows take the int32 floor
                from . import topnpack as TP
                comp = TP.composite_score(pack, cols, prepared, eval_expr)
                score = jnp.where(mask, comp, jnp.iinfo(jnp.int32).min)
            else:
                v, vl = eval_expr(expr, cols, prepared)
                # dropped rows must score strictly below NULL-key rows
                # (DESC sorts NULLs last but they still belong in the
                # result)
                if jnp.issubdtype(v.dtype, jnp.floating):
                    null_score = jnp.inf if not desc else -jnp.finfo(
                        jnp.float32).max
                    drop_score = -jnp.inf
                    score = jnp.where(vl, v if desc else -v, null_score)
                else:
                    v32 = v.astype(jnp.int32)
                    null_score = _I32_MAX if not desc else _I32_MIN
                    drop_score = jnp.iinfo(jnp.int32).min
                    score = jnp.where(vl, v32 if desc else -v32,
                                      null_score)
                score = jnp.where(mask, score, drop_score)
            idx = topnsel.select(score, min(n, score.shape[0]), taken)
            # gather the k result rows in-kernel: the packed output is the
            # ONLY device->host transfer (k rows, not full columns)
            int_rows = [idx.astype(jnp.int32),
                        mask[idx].astype(jnp.int32)]
            flt_rows = []
            for pi, e in enumerate(exprs):
                pv, pvl = eval_expr(e, cols, prepared)
                pvk = pv[idx]
                pvlk = (pvl & mask)[idx]
                if out_types[pi].is_float:
                    flt_rows.append(pvk.astype(jnp.float32))
                    flt_rows.append(pvlk.astype(jnp.float32))
                else:
                    int_rows.append(pvk.astype(jnp.int32))
                    int_rows.append(pvlk.astype(jnp.int32))
            out = {"ints": jnp.stack(int_rows)}
            if flt_rows:
                out["flts"] = jnp.stack(flt_rows)
            return out

        return kernel

    # ---- misc ---------------------------------------------------------------
    def _empty_chunk(self, dag: CopDAG, snap: TableSnapshot) -> Chunk:
        columns = []
        if dag.agg is not None:
            for gi, g in enumerate(dag.agg.group_by):
                dictionary = None
                if isinstance(g, Col) and g.ftype.is_string:
                    dictionary = snap.dictionaries[dag.scan.col_offsets[g.idx]] \
                        if g.idx < len(dag.scan.col_offsets) else None
                columns.append(Column(
                    g.ftype, np.empty(0, g.ftype.np_dtype), None, dictionary))
            from ..plan.dag import agg_partial_starts, agg_partial_width
            starts = agg_partial_starts(
                dag.agg.aggs, len(dag.agg.group_by))
            for ai, d in enumerate(dag.agg.aggs):
                for j in range(agg_partial_width(d)):
                    vt = dag.output_types[starts[ai] + j]
                    columns.append(Column(vt, np.empty(0, vt.np_dtype)))
            return Chunk(columns)
        for i, ft in enumerate(dag.output_types):
            dictionary = None
            if ft.is_string:
                src = None
                if dag.projections is not None:
                    e = dag.projections[i]
                    if isinstance(e, Col):
                        src = dag.scan.col_offsets[e.idx]
                else:
                    src = dag.scan.col_offsets[i]
                dictionary = snap.dictionaries[src] if src is not None else None
            columns.append(Column(ft, np.empty(0, ft.np_dtype), None,
                                  dictionary))
        return Chunk(columns)


def _survivors(dag: CopDAG, prepared):
    """(cols, row_mask) -> the mask the DAG's selection leaves: what a
    sharded program counts for its per-shard survivor stat."""
    sel = dag.selection

    def survivors(cols, row_mask):
        return row_mask if sel is None else selection_mask(
            sel.conditions, widen32(list(cols)), prepared, row_mask)

    return survivors


class _FirstCallCompile:
    """Times a fresh jitted kernel's first invocation as the `compile`
    dispatch stage (jax.jit compiles lazily at first call); later calls
    delegate straight through. `on_first`, when set (the flight
    recorder's compile observer), receives the first call's wall seconds — the
    feed for compile counts/durations and recompile-storm detection."""

    __slots__ = ("fn", "note", "done", "on_first")

    def __init__(self, fn, note: str) -> None:
        self.fn = fn
        self.note = note
        self.done = False
        self.on_first = None

    def __call__(self, *args):
        if self.done:
            return self.fn(*args)
        self.done = True
        import time as _time
        t0 = _time.perf_counter()
        with obs.stage("compile", span_name="xla.compile") as sp:
            if sp:
                sp.note = self.note
            r = self.fn(*args)
        if self.on_first is not None:
            try:
                self.on_first(_time.perf_counter() - t0)
            except Exception:  # noqa: BLE001 — telemetry only
                pass
        return r


def _merge_tile_outs(outs: list[dict], sched) -> dict:
    """Merge per-tile agg partials host-side. Int limb partials are
    additive (summed in int64 so hi/lo sums can exceed int32 across many
    tiles); float block partials concatenate along the block axis (the
    host combine already sums blocks in f64); min/max merge elementwise
    against their sentinels. Mirrors the cross-shard collective merge
    (placement._collective_merge), but on fetched partials."""
    if len(outs) == 1:
        return outs[0]
    minmax = {f"m{ai}": s["kind"] for ai, s in enumerate(sched)
              if s["kind"] in ("min", "max")}
    hll_keys = {f"h{ai}" for ai, s in enumerate(sched)
                if s["kind"] == "hll"}
    merged: dict[str, np.ndarray] = {}
    for k in outs[0]:
        vals = [np.asarray(o[k]) for o in outs]
        kind = minmax.get(k)
        if kind == "min":
            merged[k] = np.minimum.reduce(vals)
        elif kind == "max" or k in hll_keys:
            # hll registers merge by elementwise max (sketch union)
            merged[k] = np.maximum.reduce(vals)
        elif k.startswith("f"):
            merged[k] = np.concatenate(vals, axis=0)
        else:
            merged[k] = SE.merge_additive(vals)
    return merged


# ==================== shared aggregation machinery ====================
# module-level so the fragment executor (copr/fragment.py) builds the same
# partial-producing programs over its joined column streams

def segment_ids(agg, cards, offsets, cols, prepared, mask):
    """Mixed-radix dense segment id; NULL key -> card-1 slot."""
    seg = jnp.zeros(mask.shape[0], dtype=jnp.int32)
    remaps = prepared.get("__key_remaps__") or [None] * len(cards)
    for g, card, off, remap in zip(agg.group_by, cards, offsets, remaps):
        v, vl = eval_expr(g, cols, prepared)
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.int32)  # boolean keys: 0/1 codes
        if remap is not None:
            # a key the predicates pin to a few codes: slot i holds
            # remap[i]; any other code belongs to a masked row
            shifted = sum(((v == code).astype(jnp.int32) * i
                           for i, code in enumerate(remap)),
                          jnp.zeros(v.shape, jnp.int32))
        else:
            shifted = (v - jnp.asarray(off, dtype=v.dtype)).astype(
                jnp.int32)
        k = jnp.where(vl, shifted, card - 1)
        k = jnp.clip(k, 0, card - 1)
        seg = seg * card + k
    return jnp.where(mask, seg, -1)


def agg_partials(agg, prepared, cards, segments, cols, mask):
    """(cols, row mask) -> {exact limb partials} per the agg schedule.
    All leaves int32 (additive, psum-safe) or f32 (block float sums)."""
    offsets = prepared["__key_offsets__"]
    sched = prepared["__agg_sched__"]
    strategy = prepared["__strategy__"]
    seg = segment_ids(agg, cards, offsets, cols, prepared, mask)
    one_hot = SE.make_one_hot(seg, segments) \
        if strategy == "einsum" else None
    ones = mask.astype(jnp.int32)
    out = {"rows": SE.seg_sum_partials(ones, seg, segments, 1,
                                       one_hot=one_hot)}
    for ai, (d, s) in enumerate(zip(agg.aggs, sched)):
        if s["kind"] == "count":
            if d.arg is not None:
                _, vl = eval_expr(d.arg, cols, prepared)
                cseg = jnp.where(vl, seg, -1)
                out[f"cnt{ai}"] = SE.seg_sum_partials(
                    ones, cseg, segments, 1, one_hot=None
                    if one_hot is None else SE.make_one_hot(cseg, segments))
            continue
        v, vl = eval_expr(d.arg, cols, prepared) \
            if s["kind"] != "isum" else (None, None)
        if s["kind"] == "isum":
            # validity from the original arg (cheap: XLA CSEs the shared
            # subexpressions with the term evals below)
            _, vl = eval_expr(d.arg, cols, prepared)
            vseg = jnp.where(vl, seg, -1)
            voh = SE.make_one_hot(vseg, segments) \
                if one_hot is not None else None
            out[f"cnt{ai}"] = SE.seg_sum_partials(
                ones, vseg, segments, 1, one_hot=voh)
            for ti, (t, shift, L) in enumerate(s["terms"]):
                tv, _ = eval_expr(t, cols, prepared)
                out[f"s{ai}_{ti}"] = SE.seg_sum_partials(
                    tv.astype(jnp.int32), vseg, segments, L, one_hot=voh)
            continue
        vseg = jnp.where(vl, seg, -1)
        if s["kind"] == "hll":
            from .analyze import N_REG, hll_bucket_rank
            out[f"cnt{ai}"] = SE.seg_sum_partials(
                ones, vseg, segments, 1, one_hot=None
                if one_hot is None else SE.make_one_hot(vseg, segments))
            v32 = v.astype(jnp.int32) if v.dtype == jnp.bool_ else v
            bucket, rank = hll_bucket_rank(v32)
            # (segments, N_REG) max-rank registers. Masked/NULL rows carry
            # seg -1, which JAX scatter WRAPS (not drops) — zero their
            # rank so the wrapped update is a no-op against the 0-init
            rank = jnp.where(vseg >= 0, rank, 0)
            out[f"h{ai}"] = jnp.zeros(
                (segments, N_REG), jnp.int32
            ).at[jnp.maximum(vseg, 0), bucket].max(rank)
            continue
        out[f"cnt{ai}"] = SE.seg_sum_partials(ones, vseg, segments, 1)
        if s["kind"] == "fsum":
            out[f"f{ai}"] = SE.float_seg_sums(
                v, vseg, segments, _FLOAT_BLOCKS)
        else:  # min / max with sentinels (kept for pmin/pmax merge)
            is_f = jnp.issubdtype(v.dtype, jnp.floating)
            if is_f:
                sent = jnp.inf if s["kind"] == "min" else -jnp.inf
            else:
                sent = _I32_MAX if s["kind"] == "min" else _I32_MIN
                v = v.astype(jnp.int32)
            vv = jnp.where(vseg >= 0, v, sent)
            red = jnp.min if s["kind"] == "min" else jnp.max
            out[f"m{ai}"] = jnp.stack([
                red(jnp.where(vseg == k, vv, sent))
                for k in range(segments)])
    return out


def decode_agg_partials(agg, prepared, cards, out, group_dicts,
                        val_types) -> Optional[Chunk]:
    """Fetched partials -> one partial-layout chunk
    [group cols..., (val, cnt) per agg] (int64 host columns), or None when
    no group matched. val_types: per-agg output types in (val, cnt) pair
    order as laid out by the planner's partial schema."""
    offsets = prepared["__key_offsets__"]
    remaps = prepared.get("__key_remaps__") or [None] * len(cards)
    sched = prepared["__agg_sched__"]
    rows_per_seg = SE.combine_partials(out["rows"])
    present = rows_per_seg > 0
    seg_idx = np.nonzero(present)[0]
    if len(seg_idx) == 0:
        return None

    columns: list[Column] = []
    codes = seg_idx.copy()
    parts: list[np.ndarray] = []
    for c in reversed(cards):
        parts.append(codes % c)
        codes = codes // c
    parts.reverse()
    for gi, g in enumerate(agg.group_by):
        card = cards[gi]
        code = parts[gi]
        ft = g.ftype
        is_null = code == (card - 1)
        if remaps[gi] is not None:  # slot -> the dictionary code it holds
            data = np.asarray(remaps[gi] + (0,))[code].astype(ft.np_dtype)
        else:
            data = (code + offsets[gi]).astype(ft.np_dtype)
        columns.append(Column(
            ft, data, None if not is_null.any() else ~is_null,
            group_dicts[gi]))

    from ..plan.dag import HLL_WORDS, agg_partial_starts
    starts = agg_partial_starts(agg.aggs, 0)  # offsets into val_types
    for ai, (d, s) in enumerate(zip(agg.aggs, sched)):
        cnt = SE.combine_partials(out[f"cnt{ai}"])[seg_idx] \
            if f"cnt{ai}" in out else rows_per_seg[seg_idx]
        val_t = val_types[starts[ai]]
        if s["kind"] == "hll":
            # byte-pack the registers into HLL_WORDS int64 words; the
            # final merge unpacks and maxes them (executor/engine.py
            # _merge_partials) — partials from overlay batches, partitions
            # or host-fallback siblings union correctly
            from .analyze import hll_pack_words
            words = hll_pack_words(np.asarray(out[f"h{ai}"])[seg_idx])
            for w in range(HLL_WORDS):
                columns.append(Column(
                    FieldType(TypeKind.BIGINT, nullable=False),
                    words[:, w].copy()))
            columns.append(Column(
                FieldType(TypeKind.BIGINT, nullable=False),
                cnt.astype(np.int64)))
            continue
        if s["kind"] == "count":
            vcol = Column(val_t, cnt.astype(np.int64))
        elif s["kind"] == "isum":
            val = SE.combine_terms(
                [out[f"s{ai}_{ti}"] for ti in range(len(s["terms"]))],
                [shift for _, shift, _ in s["terms"]],
                wide=s["wide"], sel=seg_idx)
            vcol = Column(val_t, val.astype(val_t.np_dtype),
                          None if (cnt > 0).all() else (cnt > 0))
        elif s["kind"] == "fsum":
            val = SE.combine_float(out[f"f{ai}"])[seg_idx]
            vcol = Column(val_t, val.astype(val_t.np_dtype),
                          None if (cnt > 0).all() else (cnt > 0))
        else:  # min / max — sentinel-filled where empty; cnt gates
            val = np.asarray(out[f"m{ai}"])[seg_idx]
            val = np.where(cnt > 0, val, 0)
            vcol = Column(val_t, val.astype(val_t.np_dtype),
                          None if (cnt > 0).all() else (cnt > 0))
        columns.append(vcol)
        columns.append(Column(
            FieldType(TypeKind.BIGINT, nullable=False),
            cnt.astype(np.int64)))
    return Chunk(columns)


# ==================== helpers ====================


def _narrow_stats(a: np.ndarray, bound) -> np.ndarray:
    """Stats-driven staging width for the big-scan tile path: columns
    whose value bounds fit int8/int16 stage at that width (an SF100
    lineitem needs ~7 columns resident in HBM — int64 staging would not
    fit). Kernels upcast to int32 at entry (`widen32`), so compute
    semantics are unchanged; XLA fuses the converts into the consumers."""
    if a.dtype.kind in "iu" and bound is not None:
        lo, hi = bound
        if -128 <= lo and hi <= 127:
            return a.astype(np.int8)
        if -32768 <= lo and hi <= 32767:
            return a.astype(np.int16)
    return _narrow(a)


def widen32(cols):
    """Upcast narrow staged tile columns to int32 for kernel compute."""
    out = []
    for d, v in cols:
        if d.dtype in (jnp.int8, jnp.int16):
            d = d.astype(jnp.int32)
        out.append((d, v))
    return out


def _pad(a: np.ndarray, b: int) -> np.ndarray:
    if len(a) == b:
        return a
    out = np.zeros(b, dtype=a.dtype)
    out[: len(a)] = a
    return out


def _pad_bool(a: np.ndarray, b: int) -> np.ndarray:
    out = np.zeros(b, dtype=bool)
    out[: len(a)] = a
    return out


def _lex_runs_ordered(snap, offsets) -> bool:
    """Lexicographic non-decreasing check over epoch columns (NULL-free):
    proves every distinct key tuple forms one contiguous storage run."""
    tie = None
    for off in offsets:
        v = snap.epoch.valids[off]
        if v is not None and not v.all():
            return False  # NULL codes sort above every value: order breaks
        d = snap.epoch.columns[off]
        if d.dtype.kind not in "iub":
            return False
        if len(d) < 2:
            continue
        a, b = d[:-1], d[1:]
        if tie is None:
            if np.any(a > b):
                return False
            tie = a == b
        else:
            if np.any(tie & (a > b)):
                return False
            tie = tie & (a == b)
    return True


def _dag_key(dag: CopDAG, prepared: dict[Any, Any]) -> str:
    # structural + constant identity, plus the resolved payload signature
    # (string codes, dict sizes, strategy/cards/offsets, schedule) collected
    # in deterministic walk order — append-only dictionaries mean
    # (code values, table lengths) fully capture staleness
    sig = tuple(prepared.get("__sig__", ()))
    return f"{dag.describe()}|{_expr_reprs(dag)}|{sig}"


def _expr_reprs(dag: CopDAG) -> str:
    parts = []
    if dag.selection:
        parts.append(repr(dag.selection.conditions))
    if dag.projections:
        parts.append(repr(dag.projections))
    if dag.agg:
        parts.append(repr(dag.agg.group_by))
        parts.append(repr(dag.agg.aggs))
    if dag.topn:
        parts.append(repr(dag.topn.items))
    return "|".join(parts)


def _subst_proj_cols(e: PlanExpr, projections: list[PlanExpr]) -> PlanExpr:
    """Rewrite Col refs (projection-output indices) to the projected exprs."""
    if isinstance(e, Col):
        return projections[e.idx]
    if isinstance(e, Call):
        return Call(e.op, [_subst_proj_cols(a, projections) for a in e.args],
                    e.ftype, e.extra)
    return e


def _like_to_regex(pattern: str) -> str:
    out = []
    i = 0
    while i < len(pattern):
        c = pattern[i]
        if c == "\\" and i + 1 < len(pattern):
            out.append(__import__("re").escape(pattern[i + 1]))
            i += 2
            continue
        if c == "%":
            out.append(".*")
        elif c == "_":
            out.append(".")
        else:
            out.append(__import__("re").escape(c))
        i += 1
    return "".join(out)
