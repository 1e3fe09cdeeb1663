"""Placement: where one epoch's rows live, and what follows from that.

The system has two placements, and this module is the one place that
knows what either means:

* `Single` (the `SINGLE` value): the rows sit on one device. Staged
  arrays are plain device arrays, a kernel body becomes a program by
  `named_jit` alone, nothing is exchanged.
* `Sharded`: the rows are split over the `shard` axis of a 1-D device
  mesh (scan fan-out as row sharding; reference: the coprocessor's
  region fan-out, store/tikv/coprocessor.go:248 buildCopTasks, and the
  MPP exchanges of planner/core/fragment.go). Scan columns and masks
  place `P('shard')` at creation; join build sides replicate
  (broadcast exchange) or, past a threshold, shard by key and have the
  probe rows routed to them (hash-partition exchange,
  parallel/exchange.py); a kernel body becomes a program under
  `shard_map`, where each device reduces its row shard to the same
  exact int32 limb partials the single-device body produces
  (sumexact.py) and psum / pmin / pmax over the axis merge them, so
  the host final stage is the same for both placements. Every sharded
  program also returns a per-shard (input rows, survivors) pair for the
  mesh flight recorder (copr/mesh.py).

A placement is a value: `CopClient.placement_scope(snap)` pins one per
thread and dispatch, chosen by `MeshPlane.placement_for(snap)`
(copr/mesh.py, the policy); the client, the fragment executor and
ANALYZE ask `cop.placement` for everything that differs. Both classes
answer the same questions; methods that need the client's caches or its
recorder take them as arguments. Imports point one way: copr/client ->
copr/placement -> parallel/exchange.
"""

from __future__ import annotations

import hashlib
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from ..parallel import exchange as EX
from .eval import eval_expr

AXIS = "shard"


def make_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1-D data mesh over the given (or all) devices."""
    devs = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devs), (AXIS,))


def named_jit(fn, name: str):
    """jax.jit(fn) under the program's own name, taken from the key it
    is cached under (and adding nothing to that key): the host event
    reads PjitFunction(<name>) and the XLA module jit_<name>, where
    every program used to be `kernel` or `body`."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _bucket(n: int) -> int:
    """Static shape bucket: smallest of {2^k, 1.5*2^k} >= max(n, 256)."""
    b = 256
    while b < n:
        if b + b // 2 >= n:
            return b + b // 2
        b *= 2
    return b


def _narrow(a: np.ndarray) -> np.ndarray:
    """64-bit host columns -> 32-bit device staging (the device is
    64-bit-free; see copr/client.py's docstring)."""
    if a.dtype == np.int64:
        return a.astype(np.int32)
    if a.dtype == np.float64:
        return a.astype(np.float32)
    return a


def epoch_nbytes(epoch) -> int:
    """Host bytes of one columnar epoch (columns + validity lanes)."""
    n = 0
    for data, valid in zip(epoch.columns, epoch.valids):
        n += int(data.nbytes)
        if valid is not None:
            n += int(valid.nbytes)
    return n


def _plan_digest(kind: str, identity) -> str:
    """Stable per-logical-kernel digest: the plan identity WITHOUT the
    shape bucket or placement — the same key the recompile-storm
    detector groups by (bucket/placement churn re-enters compile under
    ONE signature)."""
    return hashlib.sha256(
        (str(kind) + "|" + str(identity)).encode()).hexdigest()[:16]


# ==================== single ====================

class Single:
    """Rows on one device."""

    key = "single"   # kernel-cache namespace
    axis = None      # no mesh axis: nothing routes, nothing merges
    n_devices = 1    # also the candidate blocks of an hc output

    def engine(self, mode: Optional[str] = None) -> str:
        """EXPLAIN ANALYZE engine tag (`mode`: the fragment's)."""
        return "device" if mode is None else f"device[{mode}]"

    # ---- staging ----
    def bucket_size(self, n: int) -> int:
        return _bucket(n)

    def place_cols(self, data, valid, build: bool = False):
        return jnp.asarray(data), jnp.asarray(valid)

    def place_mask(self, mask, build: bool = False):
        return jnp.asarray(mask)

    def stage_key_suffix(self, build: bool = False) -> tuple:
        return ()

    def stage_build_table(self, cop, facade, snap):
        return cop._stage_inputs(facade, snap, overlay=False)

    def place_build_array(self, cop, arr, key):
        return arr

    def partition_build(self, cop, snap) -> bool:
        return False  # everything is local

    # ---- exchanges ----
    def hc_exchange_fn(self, frag, prepared):
        return None  # all groups are already local

    def join_exchange_fn(self, frag, prepared, spans):
        return None

    # ---- programs ----
    def agg_program(self, body, sched, identity, rec):
        return named_jit(body, "titpu_agg")

    def topn_program(self, body, survivors, identity, rec):
        return named_jit(body, "titpu_topn")

    def rows_program(self, body, survivors, identity, rec):
        return named_jit(body, "titpu_rowmask")

    def frag_program(self, kernel, mode: str, prepared, rec):
        return named_jit(kernel, f"titpu_frag_{mode}")


SINGLE = Single()


# ==================== sharded ====================

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


def _collective_merge(out: dict, sched) -> dict:
    """Merge per-shard agg partials over the mesh axis: pmin/pmax for
    min/max keys, psum for everything else (int32 limb partials and float
    block sums are both additive)."""
    minmax_kind = {f"m{ai}": s["kind"] for ai, s in enumerate(sched)
                   if s["kind"] in ("min", "max")}
    hll_keys = {f"h{ai}" for ai, s in enumerate(sched)
                if s["kind"] == "hll"}
    res = {}
    for key, val in out.items():
        kind = minmax_kind.get(key)
        if kind == "min":
            res[key] = jax.lax.pmin(val, AXIS)
        elif kind == "max" or key in hll_keys:
            # hll registers union across shards by elementwise max
            res[key] = jax.lax.pmax(val, AXIS)
        else:
            res[key] = jax.lax.psum(val, AXIS)
    return res


def _hc_out_specs(prepared) -> dict:
    """shard_map out_specs for the hc partial schema: per-device
    candidate blocks concatenate (disjoint group partitions after
    the exchange); overflow is psum-replicated."""
    specs: dict = {"picked": P(AXIS), "score": P(AXIS),
                   "overflow": P()}
    for gi in range(len(prepared["__hc_nulls__"])):
        specs[f"gk{gi}"] = P(AXIS)
    for ai, s in enumerate(prepared["__hc_sched__"]):
        specs[f"cnt{ai}"] = P(None, None, AXIS)
        if s["kind"] in ("min", "max"):
            # sorted-operand min/max: one encoded value per candidate
            specs[f"mm{ai}"] = P(AXIS)
        for ti in range(len(s.get("terms", ()))):
            specs[f"s{ai}_{ti}"] = P(None, None, AXIS)
    return specs


def _build_in_specs(prepared):
    """Per-build shard_map in_specs: broadcast builds replicate (P()),
    the partitioned build's key-ordered arrays shard by key range."""
    part_ji = prepared.get("__part_join__")
    n_joins = prepared.get("__n_joins__", 0)
    if part_ji is None:
        return P()
    return [
        {"bykey": P(AXIS), "present": P(AXIS)} if ji == part_ji else P()
        for ji in range(n_joins)
    ] + [P()] * prepared.get("__n_semis__", 0)  # replicated bitmaps


def _with_shard_stats(fn, kind: str, digest: str, rec):
    """Split a stats-augmented jitted kernel's (result, stats) pair: the
    result flows back to the placement-blind machinery; the tiny
    [n_devices, 2] per-shard stats arrays queue on the recorder's
    thread-local pending list and are fetched when the engine takes the
    node's mesh note — AFTER the statement's own device_get, so no
    extra sync lands inside the dispatch pipeline."""

    def kern(*args):
        out, stats = fn(*args)
        rec.note_pending(kind, digest, stats, op=obs.active_operator())
        return out

    return kern


class Sharded:
    """Rows over the `shard` axis of `mesh`. One value per mesh plane
    (`cfg` is the plane's MeshConfig: the build election reads its
    replicate_threshold_bytes)."""

    key = "shard"
    axis = AXIS

    def __init__(self, mesh: Mesh, cfg) -> None:
        self.mesh = mesh
        self.cfg = cfg
        self.n_devices = int(mesh.devices.size)

    def engine(self, mode: Optional[str] = None) -> str:
        return f"{SINGLE.engine(mode)}@mesh{self.n_devices}"

    # ---- staging: scan columns/masks shard on the rows axis at
    # CREATION time and the sharded arrays are what the client's caches
    # hold, so epochs stay device-resident across queries (re-placing
    # per dispatch would be a mesh-wide transfer per fragment run).
    # Build-table staging (`build=True`) places REPLICATED instead — the
    # broadcast-join side every device gathers from. The placed arrays
    # work for tiles too: each TILE_ROWS slice is scanned by all devices.
    def bucket_size(self, n: int) -> int:
        """Round the shape bucket so the rows axis shards evenly AND each
        shard is a multiple of 8 rows — per-shard jnp.packbits pads to
        byte boundaries, and concatenating padded shard masks would shift
        every later shard's rows (seen at 64+ devices where lcm(256, n)
        alone leaves 4-row shards)."""
        lcm = int(np.lcm(256, 8 * self.n_devices))
        return -(-_bucket(n) // lcm) * lcm

    def _sharding(self, build: bool) -> NamedSharding:
        return NamedSharding(self.mesh, P() if build else P(AXIS))

    def _note_broadcast(self, build: bool, *arrays) -> None:
        """Replicating build arrays copies them to every other device —
        the dominant reshard-traffic component; counted HERE because
        placement happens at creation (the later replicated() re-place
        is an identity and cannot see the broadcast)."""
        if build:
            n = sum(int(getattr(a, "nbytes", 0)) for a in arrays)
            obs.MESH_RESHARD_BYTES.inc(n * max(self.n_devices - 1, 1))

    def place_cols(self, data, valid, build: bool = False):
        sharding = self._sharding(build)
        with obs.stage("reshard" if build else "shard"):
            self._note_broadcast(build, data, valid)
            return (jax.device_put(data, sharding),
                    jax.device_put(valid, sharding))

    def place_mask(self, mask, build: bool = False):
        with obs.stage("reshard" if build else "shard"):
            self._note_broadcast(build, mask)
            return jax.device_put(mask, self._sharding(build))

    def stage_key_suffix(self, build: bool = False) -> tuple:
        # builds cache under a distinct placement namespace: one epoch
        # can be a sharded probe AND a replicated broadcast build, and
        # aliasing the two under one key would pin a full replica on
        # every device and re-shard it per dispatch
        return ("rep",) if build else ()

    def stage_build_table(self, cop, facade, snap):
        # build columns place REPLICATED at creation (broadcast-join
        # side) under "rep"-suffixed staging keys; the replicated()
        # re-placement below is then a no-copy identity, and the repc
        # keys keep the epoch-led eviction story
        cols, vis, host_cols, host_mask = cop._stage_inputs(
            facade, snap, overlay=False, build=True)
        b = vis.shape[0]
        eid = snap.epoch.epoch_id
        with cop._lock:
            cacheable = cop._live_epochs.get(
                facade.scan.table_id) == eid
        rep_cols = []
        for off, (d, v) in zip(facade.scan.col_offsets, cols):
            rep_cols.append((
                self.replicated(cop, (eid, "repc", off, b), d, cacheable),
                self.replicated(cop, (eid, "repv", off, b), v, cacheable)))
        vis = self.replicated(
            cop, (eid, "repvis", b, snap.mask_digest), vis,
            cacheable)
        cop._tls.build_cacheable = cacheable
        return rep_cols, vis, host_cols, host_mask

    def place_build_array(self, cop, arr, key):
        # perm arrays are cached device-resident per epoch; replicate once
        # under an epoch-led key so _evict_stale reclaims the broadcast
        return self.replicated(
            cop, key, arr, getattr(cop._tls, "build_cacheable", True))

    def replicated(self, cop, key, arr, cacheable: bool = True):
        """Broadcast once per epoch, then reuse: re-placing cached arrays
        every query would pay a full mesh transfer per fragment run. A
        snapshot on an already-superseded epoch must not seed entries the
        one-shot eviction transition will never reclaim."""
        with cop._lock:
            hit = cop._col_cache.get(key)
        if hit is not None:
            return hit
        with obs.stage("reshard"):
            placed = jax.device_put(arr, NamedSharding(self.mesh, P()))
        if getattr(arr, "sharding", None) != placed.sharding:
            # a real broadcast (not an identity re-place): every other
            # device receives a full copy over the mesh links
            obs.MESH_RESHARD_BYTES.inc(
                int(getattr(arr, "nbytes", 0))
                * max(self.n_devices - 1, 1))
        if cacheable:
            with cop._lock:
                cop._col_cache[key] = placed
        return placed

    # ---- join build election: a build too large to replicate — by row
    # count (cop.partition_join_threshold) or by bytes (the plane's
    # replicate-threshold-bytes) — shards by key and probe rows route
    # over ICI (hash-partition vs broadcast exchange, reference:
    # planner/core/fragment.go:45)
    def partition_build(self, cop, snap) -> bool:
        if snap.epoch.num_rows > cop.partition_join_threshold:
            return True
        return epoch_nbytes(snap.epoch) > \
            self.cfg.replicate_threshold_bytes

    def stage_partitioned_build(self, cop, t, snap, lo, span, j):
        """Key-interleaved build arrays sharded over the mesh: device d
        owns keys with (key-lo) % n_dev == d, laid out at local index
        (key-lo) // n_dev. Round-robin interleaving (not contiguous
        ranges) matters: probe tables are typically key-SORTED (TPC-H
        lineitem is orderkey-ordered), so range ownership would route a
        device's whole shard to one destination and overflow any bounded
        exchange capacity — interleaving spreads sorted probes uniformly.
        The perm indirection of the broadcast path disappears: after
        routing, a probe row gathers its build row by direct local
        key index."""
        n_dev = self.n_devices
        span_pad = -(-span // n_dev) * n_dev
        per_dev = span_pad // n_dev
        epoch = snap.epoch
        key_off = t.col_offsets[j.build_key_local]
        ck = (epoch.epoch_id, "partb", key_off, lo, span_pad,
              snap.mask_digest, tuple(t.col_offsets))
        with cop._lock:
            hit = cop._col_cache.get(ck)
            cacheable = cop._live_epochs.get(t.table.id) == epoch.epoch_id
        if hit is not None:
            return hit
        keys = epoch.columns[key_off]
        kvalid = epoch.valids[key_off]
        sel = snap.base_visible.copy()
        if kvalid is not None:
            sel &= kvalid
        idx = np.nonzero(sel)[0]
        k = keys[idx].astype(np.int64) - lo
        pos = (k % n_dev) * per_dev + k // n_dev  # interleave bijection
        present = np.zeros(span_pad, dtype=bool)
        present[pos] = True
        sharding = NamedSharding(self.mesh, P(AXIS))
        bykey = []
        with obs.stage("shard"):
            for off in t.col_offsets:
                data = np.zeros(span_pad, dtype=_narrow(
                    epoch.columns[off][:0]).dtype)
                data[pos] = _narrow(epoch.columns[off][idx])
                v = epoch.valids[off]
                valid = present.copy()
                if v is not None:
                    valid[pos] = v[idx]
                bykey.append((jax.device_put(data, sharding),
                              jax.device_put(valid, sharding)))
            build = {"bykey": bykey,
                     "present": jax.device_put(present, sharding)}
        if cacheable:
            with cop._lock:
                cop._col_cache[ck] = build
        return build

    # ---- exchanges (parallel/exchange.py routes; these pick the
    # destination device of every row) ----
    def join_exchange_fn(self, frag, prepared, spans):
        part_ji = prepared["__part_join__"]
        j = frag.joins[part_ji]
        lo, span = spans[part_ji]
        n_dev = self.n_devices

        def route(cols, mask):
            key_v, key_vl = eval_expr(j.probe_key, cols, prepared)
            k = key_v.astype(jnp.int32) - jnp.int32(lo)
            m = mask.shape[0]
            iota = jnp.arange(m, dtype=jnp.int32)
            live = mask & key_vl & (k >= 0) & (k < span)
            # interleaved build ownership: key k lives on device k % n.
            # Dead rows (padding / null / out-of-span keys) spread
            # round-robin so no bucket overflows on them.
            dest = jnp.where(live, k % jnp.int32(n_dev),
                             iota % jnp.int32(n_dev))
            return EX.route_cols(dest, cols, mask, AXIS, n_dev,
                                 EX.capacity_for(m, n_dev))

        return route

    def hc_exchange_fn(self, frag, prepared):
        """hc GROUP BY shards via the group-partition exchange: joined
        rows route by group-key hash (all_to_all) so each device owns
        whole groups, then runs the sorted-run candidate path on its
        partition."""
        n_dev = self.n_devices
        seg_keys = prepared["__hc_segkeys__"]
        nulls = prepared["__hc_nulls__"]
        group_by = frag.agg.group_by

        def route(cols, mask):
            # NULL-encoded segment keys (the same encoding _hc_body uses)
            # determine the destination: every row of a group shares them
            keys = []
            for gi in seg_keys:
                g = group_by[gi]
                v, vl = eval_expr(g, cols, prepared)
                if v.dtype == jnp.bool_:
                    v = v.astype(jnp.int32)
                keys.append(jnp.where(vl, v.astype(jnp.int32),
                                      jnp.int32(nulls[gi])))
            m = mask.shape[0]
            # dead rows (bucket padding / filtered) spread round-robin —
            # they'd otherwise hash to one bucket and overflow it
            iota = jnp.arange(m, dtype=jnp.int32)
            dest = jnp.where(
                mask,
                jnp.abs(EX.mix_hash(keys)) % jnp.int32(n_dev),
                iota % jnp.int32(n_dev))
            return EX.route_cols(dest, cols, mask, AXIS, n_dev,
                                 EX.capacity_for(m, n_dev))

        return route

    # ---- programs: the body under shard_map, with the flight
    # recorder's per-shard stats taken BEFORE any collective merge, so
    # they are the per-shard (not global) numbers ----
    def agg_program(self, body, sched, identity, rec):
        # input rows from the visibility mask, post-filter survivors
        # read off the 'rows' partial the kernel already computes
        def sharded(cols, row_mask):
            out = body(cols, row_mask)
            stats = _stat_pair(jnp.sum(row_mask.astype(jnp.int32)),
                               _rows_partial_total(out["rows"]))
            return _collective_merge(out, sched), stats

        # every output is replicated post-collective; a single P() acts
        # as a pytree prefix matching every leaf of the output dict
        mapped = shard_map(sharded, mesh=self.mesh,
                           in_specs=(P(AXIS), P(AXIS)),
                           out_specs=(P(), P(AXIS)))
        return _with_shard_stats(
            named_jit(mapped, "titpu_mesh_agg"), "agg",
            _plan_digest("agg", identity), rec)

    def topn_program(self, body, survivors, identity, rec):
        # local top-k per shard; `survivors` re-derives the selection
        # mask, which XLA CSEs with the identical graph inside the body
        def sharded(cols, row_mask):
            out = body(cols, row_mask)
            m = survivors(cols, row_mask)
            return out, _stat_pair(jnp.sum(row_mask.astype(jnp.int32)),
                                   jnp.sum(m.astype(jnp.int32)))

        mapped = shard_map(sharded, mesh=self.mesh,
                           in_specs=(P(AXIS), P(AXIS)),
                           # per-shard candidate columns concatenate
                           # along the k axis; the host PhysSort +
                           # PhysLimit above merge exactly
                           out_specs=(P(None, AXIS), P(AXIS)))
        return _with_shard_stats(
            named_jit(mapped, "titpu_mesh_topn"), "topn",
            _plan_digest("topn", identity), rec)

    def rows_program(self, body, survivors, identity, rec):
        def sharded(cols, row_mask):
            packed = body(cols, row_mask)
            m = survivors(cols, row_mask)
            return packed, _stat_pair(
                jnp.sum(row_mask.astype(jnp.int32)),
                jnp.sum(m.astype(jnp.int32)))

        mapped = shard_map(sharded, mesh=self.mesh,
                           in_specs=(P(AXIS), P(AXIS)),
                           out_specs=(P(AXIS), P(AXIS)))
        return _with_shard_stats(
            named_jit(mapped, "titpu_mesh_rows"), "rows",
            _plan_digest("rows", identity), rec)

    def frag_program(self, kernel, mode: str, prepared, rec):
        """shard_map the fragment body: probe rows sharded, builds
        replicated (or key-partitioned); agg partials merge with
        native-int32 collectives, row bitmasks concatenate along the
        rows axis."""
        routed = prepared.get("__part_join__") is not None or mode == "hc"
        kind = "frag-" + mode
        digest = _plan_digest(kind, tuple(prepared.get("__sig__", ())))
        in_specs = (P(AXIS), P(AXIS), _build_in_specs(prepared))
        if mode == "agg":
            sched = prepared["__agg_sched__"]

            def merged(pcols, pvis, builds):
                out = kernel(pcols, pvis, builds)
                stats = _stat_pair(jnp.sum(pvis.astype(jnp.int32)),
                                   _rows_partial_total(out["rows"]))
                return _collective_merge(out, sched), stats

            fn = named_jit(shard_map(
                merged, mesh=self.mesh, in_specs=in_specs,
                out_specs=(P(), P(AXIS))), "titpu_mesh_frag_agg")
        elif mode == "hc":
            # post-exchange survivors are not observable outside the
            # candidate path, so only input balance is recorded
            # (-1 = unknown survivors)
            def hc_body(pcols, pvis, builds):
                res = kernel(pcols, pvis, builds)
                stats = _stat_pair(jnp.sum(pvis.astype(jnp.int32)),
                                   jnp.int32(-1))
                return res, stats

            fn = named_jit(shard_map(
                hc_body, mesh=self.mesh, in_specs=in_specs,
                out_specs=(_hc_out_specs(prepared), P(AXIS))),
                "titpu_mesh_frag_hc")
        elif mode == "topn":
            # fused join+topn: per-shard top-n candidate rows concatenate
            # along the k axis (the host Sort/Limit above merge exactly);
            # survivors are not observable outside the candidate cut, so
            # only input balance is recorded
            def tp_body(pcols, pvis, builds):
                res = kernel(pcols, pvis, builds)
                stats = _stat_pair(jnp.sum(pvis.astype(jnp.int32)),
                                   jnp.int32(-1))
                return res, stats

            fn = named_jit(shard_map(
                tp_body, mesh=self.mesh, in_specs=in_specs,
                out_specs=(P(None, AXIS), P(AXIS))), "titpu_mesh_frag_topn")
        else:
            # rows mode: the per-shard packed bitmask is P(AXIS)-sharded
            # (shards are 256-multiples, so byte boundaries align and
            # concatenation is the global mask); each device's slice
            # popcounts to its survivors at collect time, so the kernel
            # needs no extra outputs. Rows fragments never route: the
            # partitioned-join election (fragment.py) is agg/hc-only —
            # routed rows would lose probe-row identity
            inner = named_jit(shard_map(
                kernel, mesh=self.mesh, in_specs=in_specs,
                out_specs=P(AXIS)), "titpu_mesh_frag_rows")

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
                nbytes = sum(int(a.nbytes) for a in
                             jax.tree_util.tree_leaves((pcols, pvis)))
                obs.MESH_RESHARD_BYTES.inc(nbytes)
            out, stats = fn(pcols, pvis, builds, *rest)
            rec.note_pending(kind, digest, stats, routed=nbytes,
                             op=obs.active_operator())
            return out

        return kern
