"""Distributed coprocessor execution over a TPU mesh.

The multi-chip tier of the design (SURVEY.md §7 step 10): where the
reference fans coprocessor tasks out to TiKV regions over gRPC and runs MPP
exchanges between TiFlash nodes (reference: store/tikv/coprocessor.go:248
buildCopTasks; store/tikv/mpp.go:372 DispatchMPPTasks; exchange operators
from planner/core/fragment.go), the TPU framework shards the column epoch
across devices and lets XLA collectives do the exchange:

* scan fan-out (P1)  -> rows axis sharding of the padded column arrays
* partial aggregation (P2 partial stage) -> per-shard exact limb partials
* final merge (P2 final / P9 exchange)   -> psum/pmin/pmax over the mesh
  axis (ICI), all in native int32 — the limb partials are exact under
  addition (sumexact.py), so the collective needs no 64-bit emulation.

The partial layout is identical to the single-chip path, so the host final
stage is unchanged — it just receives partials that were already reduced
across devices.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from ..copr.client import CopClient, named_jit

AXIS = "shard"


def make_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1-D data mesh over the given (or all) devices."""
    devs = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devs), (AXIS,))


class DistCopClient(CopClient):
    """CopClient whose aggregation kernels run sharded over a device mesh.

    Row batches are padded to shape buckets (multiples of 256, so any
    power-of-two mesh divides them); each device reduces its row shard into
    the full dense segment space, then collectives over the mesh axis yield
    the global partials on every device. Inputs are placed with row-sharded
    NamedShardings so jit consumes them without host round-trips.
    """

    def __init__(self, mesh: Mesh) -> None:
        super().__init__()
        self.mesh = mesh
        self._n = mesh.devices.size

    def _build_agg_kernel(self, dag, prepared, cards, segments):
        body = self._agg_kernel_body(dag, prepared, cards, segments)
        sched = prepared["__agg_sched__"]

        def sharded(cols, row_mask):
            return _collective_merge(body(cols, row_mask), sched)

        # every output is replicated post-collective; a single P() acts
        # as a pytree prefix matching every leaf of the output dict
        mapped = shard_map(
            sharded,
            mesh=self.mesh,
            in_specs=(P(AXIS), P(AXIS)),
            out_specs=P(),
        )
        return named_jit(mapped, "titpu_mesh_agg")

    def _bucket_size(self, n: int) -> int:
        """Round the shape bucket so the rows axis shards evenly AND each
        shard is a multiple of 8 rows — per-shard jnp.packbits pads to
        byte boundaries, and concatenating padded shard masks would shift
        every later shard's rows (seen at 64+ devices where lcm(256, n)
        alone leaves 4-row shards)."""
        b = super()._bucket_size(n)
        lcm = int(np.lcm(256, 8 * self._n))
        return -(-b // lcm) * lcm

    # staging placement: scan columns/masks shard on the rows axis at
    # CREATION time and the sharded arrays are what the caches hold, so
    # epochs stay device-resident across queries (re-placing per dispatch
    # was a mesh-wide transfer per fragment run). Build-table staging
    # (the TLS flag below) places REPLICATED instead — the broadcast-join
    # side every device gathers from. The placed arrays work for tiles
    # too: each TILE_ROWS slice is scanned by all devices.
    def _scan_sharding(self):
        if getattr(self._tls, "place_build", False):
            return NamedSharding(self.mesh, P())
        return NamedSharding(self.mesh, P(AXIS))

    def _note_broadcast(self, *arrays) -> None:
        """Replicating build arrays copies them to every other device —
        the dominant reshard-traffic component; counted HERE because
        placement happens at creation (the later _replicated() re-place
        is an identity and cannot see the broadcast)."""
        if getattr(self._tls, "place_build", False):
            n = sum(int(getattr(a, "nbytes", 0)) for a in arrays)
            obs.MESH_RESHARD_BYTES.inc(n * max(self._n - 1, 1))

    def _place_cols(self, data, valid):
        sharding = self._scan_sharding()
        build = getattr(self._tls, "place_build", False)
        with obs.stage("reshard" if build else "shard"):
            self._note_broadcast(data, valid)
            return (jax.device_put(data, sharding),
                    jax.device_put(valid, sharding))

    def _place_mask(self, mask):
        build = getattr(self._tls, "place_build", False)
        with obs.stage("reshard" if build else "shard"):
            self._note_broadcast(mask)
            return jax.device_put(mask, self._scan_sharding())

    # ---- fragment placement: probe shards, build tables replicate ------
    # (broadcast-join placement — the MPP broadcast exchange mode,
    # reference: planner/core/fragment.go broadcast vs hash partition)

    # hc GROUP BY shards via the group-partition exchange: joined rows
    # route by group-key hash (all_to_all) so each device owns whole
    # groups, then runs the sorted-run candidate path on its partition
    supports_hc = True

    @property
    def hc_exchange_blocks(self) -> int:
        return self._n

    frag_axis = AXIS
    # builds larger than this replicate no more: they shard by key range
    # and probe rows route over ICI (hash-partition vs broadcast exchange,
    # reference: planner/core/fragment.go:45). Tests shrink it to force
    # the partitioned path at toy scale.
    partition_join_threshold = 1 << 21

    def _stage_partitioned_build(self, t, snap, lo, span, j):
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
        from ..copr.client import _mask_digest, _narrow

        n_dev = self._n
        span_pad = -(-span // n_dev) * n_dev
        per_dev = span_pad // n_dev
        epoch = snap.epoch
        key_off = t.col_offsets[j.build_key_local]
        host_mask = snap.base_visible
        ck = (epoch.epoch_id, "partb", key_off, lo, span_pad,
              _mask_digest(host_mask), tuple(t.col_offsets))
        with self._lock:
            hit = self._col_cache.get(ck)
            cacheable = self._live_epochs.get(t.table.id) == epoch.epoch_id
        if hit is not None:
            return hit
        keys = epoch.columns[key_off]
        kvalid = epoch.valids[key_off]
        sel = host_mask.copy()
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
            with self._lock:
                self._col_cache[ck] = build
        return build

    def _join_exchange_fn(self, frag, prepared, spans):
        from ..copr.eval import eval_expr
        from . import exchange as EX

        part_ji = prepared["__part_join__"]
        j = frag.joins[part_ji]
        lo, span = spans[part_ji]
        n_dev = self._n

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

    def _hc_exchange_fn(self, frag, prepared):
        from ..copr.eval import eval_expr
        from . import exchange as EX

        n_dev = self._n
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

    def _stage_key_suffix(self):
        # builds cache under a distinct placement namespace: one epoch
        # can be a sharded probe AND a replicated broadcast build
        return ("rep",) if getattr(self._tls, "place_build", False) else ()

    def _stage_build_table(self, facade, snap):
        # build columns place REPLICATED at creation (broadcast-join
        # side) under "rep"-suffixed staging keys; the _replicated()
        # re-placement below is then a no-copy identity, and the repc
        # keys keep the epoch-led eviction story
        self._tls.place_build = True
        try:
            cols, vis, host_cols, host_mask = CopClient._stage_inputs(
                self, facade, snap, overlay=False)
        finally:
            self._tls.place_build = False
        b = vis.shape[0]
        eid = snap.epoch.epoch_id
        with self._lock:
            cacheable = self._live_epochs.get(
                facade.scan.table_id) == eid
        rep_cols = []
        for off, (d, v) in zip(facade.scan.col_offsets, cols):
            rep_cols.append((
                self._replicated((eid, "repc", off, b), d, cacheable),
                self._replicated((eid, "repv", off, b), v, cacheable)))
        from ..copr.client import _mask_digest
        vis = self._replicated(
            (eid, "repvis", b, _mask_digest(host_mask)), vis, cacheable)
        self._frag_cacheable = cacheable
        return rep_cols, vis, host_cols, host_mask

    def _place_build_array(self, arr, key=None):
        # perm arrays are cached device-resident per epoch; replicate once
        # under an epoch-led key so _evict_stale reclaims the broadcast
        if key is None:
            return jax.device_put(arr, NamedSharding(self.mesh, P()))
        return self._replicated(key, arr,
                                getattr(self, "_frag_cacheable", True))

    def _replicated(self, key, arr, cacheable: bool = True):
        """Broadcast once per epoch, then reuse: re-placing cached arrays
        every query would pay a full mesh transfer per fragment run. A
        snapshot on an already-superseded epoch must not seed entries the
        one-shot eviction transition will never reclaim."""
        with self._lock:
            hit = self._col_cache.get(key)
        if hit is not None:
            return hit
        with obs.stage("reshard"):
            placed = jax.device_put(arr, NamedSharding(self.mesh, P()))
        if getattr(arr, "sharding", None) != placed.sharding:
            # a real broadcast (not an identity re-place): every other
            # device receives a full copy over the mesh links
            obs.MESH_RESHARD_BYTES.inc(
                int(getattr(arr, "nbytes", 0)) * max(self._n - 1, 1))
        if cacheable:
            with self._lock:
                self._col_cache[key] = placed
        return placed

    def _frag_jit(self, kernel, mode, prepared):
        """shard_map the fragment body: probe rows sharded, builds
        replicated; agg partials merge with native-int32 collectives, row
        bitmasks concatenate along the rows axis."""
        build_specs = self._build_in_specs(prepared)
        if mode == "agg":
            sched = prepared["__agg_sched__"]

            def merged(pcols, pvis, builds):
                return _collective_merge(kernel(pcols, pvis, builds), sched)

            mapped = shard_map(
                merged, mesh=self.mesh,
                in_specs=(P(AXIS), P(AXIS), build_specs),
                out_specs=P())
            return named_jit(mapped, "titpu_mesh_frag_agg")
        if mode == "hc":
            mapped = shard_map(
                kernel, mesh=self.mesh,
                in_specs=(P(AXIS), P(AXIS), build_specs),
                out_specs=self._hc_out_specs(prepared))
            return named_jit(mapped, "titpu_mesh_frag_hc")
        if mode == "topn":
            # fused join+topn: each shard ships its own top-n candidate
            # rows, concatenated along the k axis (n·shards rows total);
            # the host Sort/Limit above merge exactly
            mapped = shard_map(
                kernel, mesh=self.mesh,
                in_specs=(P(AXIS), P(AXIS), build_specs),
                out_specs=P(None, AXIS))
            return named_jit(mapped, "titpu_mesh_frag_topn")
        # row mode: per-shard packed bitmask; shards are 256-multiples so
        # byte boundaries align and concatenation is the global mask
        mapped = shard_map(
            kernel, mesh=self.mesh,
            in_specs=(P(AXIS), P(AXIS), build_specs),
            out_specs=P(AXIS))
        return named_jit(mapped, "titpu_mesh_frag_rows")

    @staticmethod
    def _hc_out_specs(prepared) -> dict:
        """shard_map out_specs for the hc partial schema: per-device
        candidate blocks concatenate (disjoint group partitions after
        the exchange); overflow is psum-replicated. Shared with the
        mesh client so the spec dict cannot diverge from the schema."""
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

    def _build_in_specs(self, prepared):
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

    # ---- TopN: local top-k per shard, host merge ------------------------
    def _build_topn_kernel(self, dag, prepared, expr, desc, n):
        raw = self._topn_body(dag, prepared, expr, desc, n)
        mapped = shard_map(
            raw, mesh=self.mesh,
            in_specs=(P(AXIS), P(AXIS)),
            # per-shard candidate columns concatenate along the k axis;
            # the host PhysSort+PhysLimit above merge exactly
            out_specs=P(None, AXIS))
        return named_jit(mapped, "titpu_mesh_topn")

    def _build_rowmask_kernel(self, dag, prepared):
        raw = self._rowmask_body(dag, prepared)
        mapped = shard_map(
            raw, mesh=self.mesh,
            in_specs=(P(AXIS), P(AXIS)),
            out_specs=P(AXIS))
        return named_jit(mapped, "titpu_mesh_rows")


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
