"""Fragment executor: snowflake join trees as ONE fused device program.

The device half of plan/fragment.py. Where the reference dispatches plan
fragments to TiFlash nodes and exchanges rows between them (reference:
store/tikv/mpp.go:372, executor/mpp_gather.go:103,
store/mockstore/unistore/cophandler/mpp.go in-process equivalent), the TPU
executes the whole tree in one kernel:

* build (dimension) tables live on device as full column sets plus an
  int32 permutation table perm[key - lo] -> row index (-1 = absent),
  cached per epoch like scan columns — the unique-key eligibility from
  plan time makes every join a static-shape gather;
* the probe (fact) table streams through: key -> perm lookup -> column
  gathers, chaining joins (a build table's gathered column can be the
  next join's key, so snowflakes cost one gather each);
* build-side filters + MVCC visibility evaluate over the full build
  columns and gate matches via the gathered bitmap;
* post-join selection and dense-segment aggregation reuse the exact same
  kernel machinery as single-table pushdowns (client.agg_partials), and
  ALL outputs return in one jax.device_get — a whole multi-join
  aggregation query costs one dispatch and one host sync.

Runtime gates, each a typed `_Fallback(reason)` that hands the SAME
FragmentDAG to an equivalent host (numpy) interpreter — same results, same
partial layout, no replanning — counted under the reason in
`tidb_copr_fragment_fallbacks_total` and tagged `host(fragment:<reason>)`:

* `build-overlay`: uncommitted or unfolded rows on a build (or membership)
  table — the perm tables index folded epochs only;
* `int64-column`: a scanned int64 column whose epoch bounds leave int32;
* `filter-unsafe` / `selection-unsafe`: a predicate whose arithmetic can
  leave int32 under the columns' bounds;
* `key-width`: a join or membership key that is unbounded or leaves int32;
* `key-span`: a build key span above FRAG_SPAN_CAP (the perm table);
* `group-space`: a GROUP BY that neither the dense segment space (at most
  8192 slots, after the predicates have pinned what they can:
  bounds.implied_domains) nor the sorted-run candidate path can hold;
* `exchange-overflow`, `group-overflow`, `hc-boundary`, `fat-boundary`:
  decode-time proofs that failed (a mesh exchange bucket, the all-groups
  candidate buffer, a tie across the candidate cut);
* `compile`: a CompileError from lowering an expression the device does
  not take (a string ordering compare, LIKE over a computed string).

One device error degrades the same way, counted as `device-oom`: a
program the gates admitted did not fit HBM (device_refusal). Any other
error from the device compiler or runtime fails the statement
(DeviceError). A read the device answers is counted by the mode that
served it in `tidb_copr_fragment_reads_total{mode}` and by the rows it
brought back in `tidb_copr_fragment_fetched_rows_total`.

A fragment with run-statistics gates (plan/fragment.py FragRunGate, mode
suffix `+runstat`) reads them on the device from the probe's storage runs
only; a probe snapshot that cannot serve them (`runstat-overlay`: an MVCC
overlay breaks the runs; `runstat-mesh`; `runstat-unordered`: storage is
not ordered by the run key, or it holds a NULL; `runstat-long-run`: a run
longer than a tile; `runstat-sum-width`: a run's sum could leave int32)
takes the host interpreter like any other gate, which totals each gate
per key value.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..chunk.chunk import Chunk
from ..chunk.column import Column
from ..plan.expr import Col
from ..plan.fragment import FragmentDAG
from . import rowbits
from . import runstat as RS
from . import topnsel
from .bounds import expr_bounds, expr_device_safe, fits_int32
from .client import (
    CopClient,
    CopResult,
    agg_partials,
    decode_agg_partials,
    widen32,
)
from .eval import CompileError, DeviceError, eval_expr, selection_mask
from .npeval import NumpyEval

# widest admissible build-key span: perm table of 64M int32 = 256MB HBM
FRAG_SPAN_CAP = 1 << 26
# the sorted-run hc body (a GROUP BY whose keys storage order does not
# group) sorts and gathers every row it is given: over an epoch of this
# many rows or more it first packs the rows that pass the predicates into
# a buffer a HC_COMPACT_DIV-th as long, tile by tile with the columns it
# reads riding along (_compact_rows, copr/rowpack.py), and runs whole only
# where more pass than the buffer holds (Q10 at SF10: 1.9% of 60 M rows
# pass, packed in 16 ms where a sort and gathers took 395 ms)
HC_COMPACT_MIN_ROWS = 1 << 22
HC_COMPACT_DIV = 32


class _Fallback(Exception):
    """Raised by a device gate; carries the gate's reason so operators can
    see WHY a query left the device path (obs label + engine string)."""

    def __init__(self, reason: str = "gate") -> None:
        super().__init__(reason)
        self.reason = reason


def execute_fragment(cop: CopClient, frag: FragmentDAG, snaps: dict
                     ) -> CopResult:
    """snaps: table_id -> TableSnapshot for every fragment table."""
    # placement is decided by the PROBE (fact) epoch: a sharded probe
    # makes this a mesh fragment (builds replicate or key-partition),
    # a small probe keeps the whole tree on the single-device path
    with cop.placement_scope(snaps[frag.tables[0].table.id]):
        try:
            with obs.span("copr.fragment") as sp:
                r = _device_fragment(cop, frag, snaps)
                mode = r.engine.split("[", 1)[1].split("]", 1)[0]
                if sp:
                    build_rows = sum(
                        snaps[t.table.id].epoch.num_rows
                        for t in frag.tables[1:])
                    gates = "".join(
                        f", gate {g.kind}" for g in frag.runstats)
                    sp.note = (f"{len(frag.tables)} tables, mode {mode}"
                               f"{gates}, {build_rows} build rows")
            obs.COPR_REQUESTS.inc(engine="device-fragment")
            obs.FRAG_READS.inc(mode=mode)
            for g in frag.runstats:
                obs.RUNSTAT_GATES.inc(kind=g.kind)
            obs.FRAG_FETCHED_ROWS.inc(sum(c.num_rows for c in r.chunks))
            return r
        except jax.errors.JaxRuntimeError as e:
            reason = device_refusal(e)
        except (_Fallback, CompileError) as e:
            reason = getattr(e, "reason", None) or "compile"
        obs.COPR_REQUESTS.inc(engine="host-fragment")
        obs.FRAG_FALLBACKS.inc(reason=reason)
        # the host interpreter's time is join work (the probe/
        # gather/agg loop) — its stage under the join's label keeps
        # the fallback path visible in the per-operator plane, not
        # buried under "fragment"
        with obs.operator("join"), obs.stage("host_fallback"):
            r = _host_fragment(frag, snaps)
        r.engine = f"host(fragment:{reason})"
        return r


def device_refusal(e: BaseException) -> str:
    """The one device error that degrades: a program the gates admitted
    did not fit HBM. The statement is answered by the host interpreter
    under the counted, tagged reason `device-oom` (chip_smoke.py fails
    on it like on any host tag). Every other compiler or runtime error
    — Mosaic or XLA refusing a program — is the statement's error."""
    if "RESOURCE_EXHAUSTED" in str(e):
        return "device-oom"
    raise DeviceError.of(e) from e


# ==================== device path ====================

def _device_fragment(cop, frag, snaps) -> CopResult:
    probe = frag.tables[0]
    psnap = snaps[probe.table.id]

    # ---- eligibility over this snapshot ----
    tab_bounds = []
    tab_dicts = []
    for ti, t in enumerate(frag.tables):
        snap = snaps[t.table.id]
        if ti > 0 and len(snap.overlay_handles) > 0:
            raise _Fallback("build-overlay")  # uncommitted/unfolded build rows
        facade = _facade_dag(t)
        b = cop._scan_bounds(facade, snap)
        for ci, off in enumerate(t.col_offsets):
            if snap.epoch.columns[off].dtype == np.int64 and \
                    not fits_int32(b[ci]):
                raise _Fallback("int64-column")
        tab_bounds.append(b)
        tab_dicts.append([snap.dictionaries[off] for off in t.col_offsets])
        cop._evict_stale(t.table.id, snap.epoch.epoch_id)

    # combined spaces
    comb_bounds: list = []
    comb_dicts: list = []
    for b, d in zip(tab_bounds, tab_dicts):
        comb_bounds.extend(b)
        comb_dicts.extend(d)

    prepared: dict[Any, Any] = {"__sig__": [], "__col_bounds__": comb_bounds}

    # per-table filters resolve against their own dictionaries
    for ti, t in enumerate(frag.tables):
        for c in t.filters:
            cop._prepare_expr(c, tab_dicts[ti], prepared)
            if not expr_device_safe(c, tab_bounds[ti]):
                raise _Fallback("filter-unsafe")
    for c in frag.selection:
        cop._prepare_expr(c, comb_dicts, prepared)
        if not expr_device_safe(c, comb_bounds):
            raise _Fallback("selection-unsafe")
    if frag.agg is not None:
        # group keys and aggregate arguments can embed string predicates
        # (e.g. CASE WHEN priority = '1-URGENT'); resolve them to codes
        for g in frag.agg.group_by:
            cop._prepare_expr(g, comb_dicts, prepared)
        for d in frag.agg.aggs:
            if d.arg is not None:
                cop._prepare_expr(d.arg, comb_dicts, prepared)

    if frag.runstats:
        _prepare_runstats(cop, frag, psnap, prepared, comb_dicts,
                          comb_bounds)

    # join key spans
    spans = []
    for j in frag.joins:
        t = frag.tables[j.build]
        kb = tab_bounds[j.build][j.build_key_local]
        pb = expr_bounds(j.probe_key, comb_bounds)
        if kb is None or pb is None or not fits_int32(pb):
            raise _Fallback("key-width")
        lo, hi = kb
        span = hi - lo + 1
        if span > FRAG_SPAN_CAP:
            raise _Fallback("key-span")
        spans.append((lo, span))
        prepared["__sig__"].append(("join", j.build, lo, span))

    # semi/anti membership edges: probe key must compute on device; the
    # build side only needs a bounded integer key span (the bitmap is
    # built host-side, so build filters never face device gates)
    semi_spans = []
    for si, sm in enumerate(frag.semis):
        snap = snaps[sm.table.table.id]
        if len(snap.overlay_handles) > 0:
            raise _Fallback("build-overlay")
        cop._evict_stale(sm.table.table.id, snap.epoch.epoch_id)
        cop._prepare_expr(sm.probe_key, comb_dicts, prepared)
        if not expr_device_safe(sm.probe_key, comb_bounds):
            raise _Fallback("key-width")
        kb = cop._col_stats(
            snap, sm.table.col_offsets[sm.build_key_local])
        pb = expr_bounds(sm.probe_key, comb_bounds)
        if kb is None or pb is None or not fits_int32(pb):
            raise _Fallback("key-width")
        lo, span = kb[0], kb[1] - kb[0] + 1
        if span > FRAG_SPAN_CAP:
            raise _Fallback("key-span")
        semi_spans.append((lo, span))
    prepared["__semi_spans__"] = semi_spans
    prepared["__n_semis__"] = len(frag.semis)

    mode = "agg" if frag.agg is not None else "rows"

    if mode == "rows" and frag.topn is not None:
        # join+topn: pack the consumer's ORDER BY into one int32
        # composite so the fused program returns only the top-n rows per
        # batch/tile/shard. An unpackable key set degrades to the plain
        # row-bitmask mode (still fused joins), never to the host.
        from . import topnpack as TP
        try:
            for e, _ in frag.topn.items:
                cop._prepare_expr(e, comb_dicts, prepared)
            specs, _reason = TP.plan_pack(frag.topn.items, comb_bounds,
                                          comb_dicts)
        except CompileError:
            specs = None
        if specs is not None:
            TP.stage_rank_tables(specs, prepared)
            prepared["__topn_pack__"] = specs
            prepared["__sig__"].append(
                ("topnpack", frag.topn.n) + TP.pack_sig(specs))
            mode = "topn"

    # ---- partitioned (non-broadcast) join election ----
    # a build too large to replicate is sharded by key range; probe rows
    # route to the owning device before the gathers (the MPP hash-
    # partition exchange mode vs broadcast, planner/core/fragment.go:45).
    # One partitioned join per fragment; output must be merge-safe
    # partials (agg/hc), since routed rows lose probe-row identity.
    part_ji = None
    pl = cop.placement
    if frag.agg is not None and pl.axis is not None:
        n_probe_cols = len(frag.tables[0].col_offsets)

        def probe_prefix_only(e) -> bool:
            # the exchange routes BEFORE any gathers, so the routing key
            # must be computable from the probe table's own columns — a
            # key gathered from an earlier build cannot elect
            if isinstance(e, Col):
                return e.idx < n_probe_cols
            return all(probe_prefix_only(a) for a in getattr(e, "args", ()))

        # a build too large to replicate — by row count or by bytes
        # (the plane's replicate-threshold-bytes) — shards by key; the
        # placement decides (Sharded.partition_build)
        big = [(snaps[frag.tables[j.build].table.id].epoch.num_rows, ji)
               for ji, j in enumerate(frag.joins)
               if pl.partition_build(
                   cop, snaps[frag.tables[j.build].table.id])
               and probe_prefix_only(j.probe_key)]
        if big:
            part_ji = max(big)[1]
    prepared["__part_join__"] = part_ji
    prepared["__n_joins__"] = len(frag.joins)

    if frag.agg is not None:
        n_rows = psnap.epoch.num_rows + len(psnap.overlay_handles)
        facade = _agg_facade(frag)
        # every predicate a joined row has to pass, for the key space:
        # each table's own filters at its base, the selection at 0
        conds, base = [(frag.selection, 0)], 0
        for t in frag.tables:
            conds.append((t.filters, base))
            base += len(t.col_offsets)
        err = cop._prepare_agg(facade, comb_dicts, comb_bounds, prepared,
                               n_rows, conds=conds)
        if err is not None:
            # dense segment space rejected (or deliberately skipped:
            # the sparse-occupancy gate routes wide, mostly-empty
            # einsum spaces here); the sorted-run candidate machinery
            # (copr/hcagg.py) covers the rest: a TopN consumer takes
            # the top-k candidate path, a HAVING consumer the filtered
            # path, and ANY other consumer the all-groups "group" mode
            # — sort + segment-reduce with a cap-checked candidate
            # buffer, so an arbitrary multi-key GROUP BY stays on
            # device whenever its group count fits the buffer
            if len(psnap.overlay_handles) > 0 or \
                    not _prepare_hc(frag, comb_bounds, prepared, n_rows):
                if not err.startswith("sparse segment space") or \
                        cop._prepare_agg(facade, comb_dicts, comb_bounds,
                                         prepared, n_rows, sparse_gate=False,
                                         conds=conds) is not None:
                    raise _Fallback("group-space")
                # the sparse-occupancy preference could not take the
                # sorted-run path here (overlay rows / an hc gate):
                # the dense einsum still serves the query on device
            else:
                mode = "hc"
                if frag.hc is None and not frag.having:
                    prepared["__hc_all__"] = True
                    prepared["__sig__"].append(
                        ("hcall", FragmentDAG.HAVING_CAP))

    if mode == "hc":
        # run-ordered fast path: storage order already groups the segment
        # keys (fact tables are clustered by their join/PK key), so the
        # kernel skips the lexicographic sort — segment boundaries come
        # from raw key-change points and filtered-out rows contribute
        # zeros. Exchanges (group hash or partitioned join) re-order rows
        # across devices, so the path is single-device only.
        segcols = prepared.get("__hc_segcols__")
        has_mm = any(s["kind"] in ("min", "max")
                     for s in prepared["__hc_sched__"])
        if segcols is not None and part_ji is None and not has_mm and \
                pl.axis is None and cop._runs_ordered(psnap, segcols):
            prepared["__hc_runordered__"] = True
            prepared["__sig__"].append(("runord",))
            # streamseg (Pallas) eligibility: rank-space per-group sums
            # in one pass; K value arrays must fit the kernel's VMEM
            # window and per-key row counts its f32 exactness bound
            from . import streamseg as SS
            n_arrays = 1
            for s_ in prepared["__hc_sched__"]:
                n_arrays += 1 + sum(t[2] for t in s_.get("terms", ()))
            if n_arrays <= SS.MAX_ARRAYS:
                meta = cop._rank_meta(psnap, segcols)
                if meta is not None:
                    prepared["__rank_meta__"] = meta
                    prepared["__sig__"].append(SS.program_key(meta))
        # hc None (HAVING-filtered or all-groups "group" mode) runs in
        # rank space when the epoch is run-ordered, else through the
        # sorted-run body's gate-scored candidate buffer

    if mode == "hc" and frag.hc is not None and frag.hc.items:
        # join+agg+topn fused final cut: every ORDER BY item resolved to
        # a group key / SUM / COUNT (plan/fragment._resolve_hc_items), so
        # the kernel can sort the candidate buffer by the EXACT multi-key
        # order (limb-pair digits; dictionary ranks for string group
        # keys) and ship only k+1 rows per candidate block — the +1 row
        # proves the cut boundary is tie-free at decode time.
        from . import topnpack as TP
        fused = True
        for kind, idx, _desc in frag.hc.items:
            if kind == "agg":
                entry = prepared["__hc_sched__"][idx]
                if not TP.digits_fit(entry) or \
                        TP.count_pairs(entry) > TP.MAX_DIGIT_PAIRS:
                    fused = False
                    break
                d_ = frag.agg.aggs[idx]
                if d_.func == "avg":
                    # AVG compares as the host's ROUNDED decimal
                    # (arg scale + div_precincrement); the long
                    # division is int32-exact only under the count cap
                    at_ = d_.arg.ftype
                    ot_ = d_.ftype
                    src_sc = at_.scale if at_.is_decimal else 0
                    out_sc = ot_.scale if ot_.is_decimal else 0
                    if ot_.is_float or out_sc != src_sc + 4 or \
                            n_rows >= TP.AVG_CNT_CAP:
                        fused = False
                        break
            else:
                g = frag.agg.group_by[idx]
                if g.ftype.is_string and (
                        not isinstance(g, Col)
                        or comb_dicts[g.idx] is None):
                    fused = False
                    break
        if fused:
            prepared["__hc_fused__"] = True
            for kind, idx, _desc in frag.hc.items:
                if kind != "group":
                    continue
                g = frag.agg.group_by[idx]
                if not g.ftype.is_string:
                    continue
                d = comb_dicts[g.idx]
                TP.stage_rank_table(prepared, ("hc_rank", idx), d,
                                    g.ftype.is_ci)
                prepared["__sig__"].append(("hcrank", idx, len(d)))
            prepared["__sig__"].append(
                ("fat", frag.hc.k, tuple(frag.hc.items)))

    if mode == "hc" and not prepared.get("__hc_runordered__") and \
            pl.axis is None and part_ji is None and \
            n_rows >= HC_COMPACT_MIN_ROWS:
        # the sorted-run body over a big epoch packs the passing rows
        # first; a statement whose rows once overflowed the buffer is
        # remembered in cop._hc_dense and runs whole from then on
        dense_key = (_frag_key(frag), _sig(prepared))
        if dense_key in cop._hc_dense:
            prepared["__hc_pack__"] = "whole"
        else:
            nullable = _nullable_cols(frag, snaps)
            prepared["__hc_pack__"] = "packed"
            prepared["__hc_dense_key__"] = dense_key
            prepared["__hc_compact__"] = HC_COMPACT_DIV
            prepared["__hc_nullable__"] = nullable
            prepared["__sig__"].append(
                ("hccompact", HC_COMPACT_DIV, nullable))

    # ---- staging ----
    builds = []
    # build-side staging (dimension columns + perm tables) is join
    # work: the operator frame routes its stage time + transfer bytes
    # to "join" in the per-operator attribution plane
    with obs.operator("join"), \
            obs.stage("staging", span_name="copr.staging"):
        for ji, j in enumerate(frag.joins):
            t = frag.tables[j.build]
            snap = snaps[t.table.id]
            lo, span = spans[ji]
            if ji == part_ji:
                builds.append(pl.stage_partitioned_build(
                    cop, t, snap, lo, span, j))
                continue
            cols, vis, host_cols, host_mask = pl.stage_build_table(
                cop, _facade_dag(t), snap)
            key_off = t.col_offsets[j.build_key_local]
            perm = _perm_array(cop, snap, key_off, lo, span, host_mask)
            perm = pl.place_build_array(
                cop, perm, key=(snap.epoch.epoch_id, "perm-rep", key_off,
                                lo, span, snap.mask_digest))
            builds.append({"cols": cols, "vis": vis, "perm": perm})
        # membership bitmaps ride BEHIND the join builds in the same
        # kernel-argument list (replicated on the mesh); their host-side
        # (has_null, empty) facts bake into the kernel signature
        semi_flags = []
        for si, sm in enumerate(frag.semis):
            snap = snaps[sm.table.table.id]
            lo, span = semi_spans[si]
            entry = _stage_semi_bitmap(cop, sm, snap, lo, span)
            prepared["__sig__"].append(
                ("semi", si, sm.kind, lo, span,
                 entry["has_null"], entry["empty"]))
            semi_flags.append((entry["has_null"], entry["empty"]))
            builds.append({"bm": entry["bm"]})  # arrays only: jit args
        prepared["__semi_flags__"] = semi_flags

    chunks: list[Chunk] = []
    if psnap.epoch.num_rows > 0:
        chunks.extend(_run_frag_batch(cop, frag, snaps, prepared, spans,
                                      builds, overlay=False, mode=mode))
    if len(psnap.overlay_handles) > 0:
        # hc gated overlay out above: a group split across batches would
        # break the candidate-superset guarantee
        chunks.extend(_run_frag_batch(cop, frag, snaps, prepared, spans,
                                      builds, overlay=True, mode=mode))
    if not chunks:
        chunks = [_empty_chunk(frag, comb_dicts)]
    emode = "fat" if prepared.get("__hc_fused__") else (
        "group" if prepared.get("__hc_all__") else mode)
    if getattr(frag, "semis", None):
        emode = f"{emode}+semi"
    if frag.runstats:
        emode = f"{emode}+runstat"
    return CopResult(chunks, is_partial_agg=frag.agg is not None,
                     engine=pl.engine(emode))


def lift_group_dag(dag, snap) -> Optional[FragmentDAG]:
    """Degenerate one-table FragmentDAG for a pushed-down CopDAG agg
    whose dense segment space failed (client._try_group_fragment): same
    scan columns / filters / aggregation, partial layout unchanged, so
    the all-groups sorted-run path can serve it."""
    from ..plan.fragment import FragTable
    table = getattr(snap.store, "table", None)
    if table is None:
        return None
    by_off = {c.offset: c.ftype for c in table.columns}
    try:
        col_types = [by_off[off] for off in dag.scan.col_offsets]
    except KeyError:
        return None
    t = FragTable(table, list(dag.scan.col_offsets),
                  list(dag.selection.conditions) if dag.selection else [],
                  col_types)
    frag = FragmentDAG([t], [])
    frag.agg = dag.agg
    frag.output_types = list(dag.output_types)
    return frag


def _facade_dag(t):
    """Minimal CopDAG stand-in for CopClient staging/bounds helpers."""
    from ..plan.dag import CopDAG, DAGScan
    return CopDAG(scan=DAGScan(t.table.id, list(t.col_offsets)),
                  output_types=list(t.col_types))


def _agg_facade(frag):
    from ..plan.dag import CopDAG, DAGScan
    combined_offsets = []
    for t in frag.tables:
        combined_offsets.extend(t.col_offsets)
    return CopDAG(scan=DAGScan(frag.tables[0].table.id, combined_offsets),
                  agg=frag.agg, output_types=list(frag.output_types))


def _perm_array(cop, snap, key_off: int, lo: int, span: int,
                host_mask: np.ndarray):
    """key -> epoch row index (device int32, -1 absent), visible+valid rows
    only. Cached DEVICE-resident per (epoch, key column, visibility) —
    rebuilding and re-uploading a multi-MB lookup table per query would
    put a host pass and a host-to-device copy on every dispatch."""
    # epoch id LEADS the key so _evict_stale (which frees every cache
    # entry with k[0] == superseded epoch) reclaims perm tables too
    key = (snap.epoch.epoch_id, "perm", key_off, lo, span,
           snap.mask_digest)
    with cop._lock:
        hit = cop._col_cache.get(key)
        cacheable = cop._live_epochs.get(snap.store.table.id) \
            == snap.epoch.epoch_id
    if hit is not None:
        return hit
    keys = snap.epoch.columns[key_off]
    valid = snap.epoch.valids[key_off]
    sel = host_mask.copy()
    if valid is not None:
        sel &= valid
    idx = np.nonzero(sel)[0]
    perm = np.full(span, -1, dtype=np.int32)
    perm[keys[idx].astype(np.int64) - lo] = idx.astype(np.int32)
    dev = jnp.asarray(perm)
    if cacheable:
        with cop._lock:
            cop._col_cache[key] = dev
    return dev


def _semi_build_facts(bcols, dicts, t, key_local: int,
                      keep0: np.ndarray):
    """NULL-aware membership facts of a semi/anti BUILD side, shared by
    the device bitmap staging and the host interpreter (one definition
    of the set semantics, so the bit-identical guarantee can't drift):
    over the given (data, valid) column pairs and the initial row mask
    `keep0` (visibility on the device path, all-rows on the host path),
    returns (keep, has_null, key_data, ok) where `keep` marks
    filter-passing rows (the SET — NULL-keyed members included),
    `has_null` whether the set contains a NULL key, and `ok` the
    valid-key member rows."""
    n = len(keep0)
    keep = keep0.copy()
    if t.filters and n:
        ev = NumpyEval([(d, np.ones(n, bool) if v is None else v)
                        for d, v in bcols], dicts, n)
        for c in t.filters:
            fv, fvl = ev.eval(c)
            keep &= _truthy(np.asarray(fv)) & fvl
    kd, kv = bcols[key_local]
    has_null = bool(np.any(keep & ~kv)) if kv is not None else False
    ok = keep if kv is None else (keep & kv)
    return keep, has_null, kd, ok


def _stage_semi_bitmap(cop, sm, snap, lo: int, span: int) -> dict:
    """Device-resident membership bitmap for a semi/anti edge: bit
    [key - lo] set iff some visible, filter-passing build row carries
    that key. Built host-side (numpy — build filters never face device
    gates) and cached per (epoch, visibility, filter set) like perm
    tables; NULL-key facts for the NULL-aware NOT IN form ride along as
    host constants."""
    t = sm.table
    key_off = t.col_offsets[sm.build_key_local]
    fsig = repr(t.filters)
    ck = (snap.epoch.epoch_id, "semibm", key_off, lo, span,
          snap.mask_digest, hash(fsig))
    with cop._lock:
        hit = cop._col_cache.get(ck)
        cacheable = cop._live_epochs.get(t.table.id) \
            == snap.epoch.epoch_id
    if hit is not None:
        return hit
    bcols = [(snap.epoch.columns[off], snap.epoch.valids[off])
             for off in t.col_offsets]
    keep, has_null, kd, ok = _semi_build_facts(
        bcols, [snap.dictionaries[off] for off in t.col_offsets],
        t, sm.build_key_local, snap.base_visible)
    idx = np.nonzero(ok)[0]
    bm = np.zeros(span, dtype=bool)
    if len(idx):
        bm[kd[idx].astype(np.int64) - lo] = True
    dev = cop.placement.place_build_array(
        cop, jnp.asarray(bm),
        key=(snap.epoch.epoch_id, "semibm-rep", key_off, lo, span,
             snap.mask_digest, hash(fsig)))
    from .client import _note_transfer
    _note_transfer(dev)
    entry = {"bm": dev, "has_null": has_null,
             "empty": not bool(keep.any())}
    if cacheable:
        with cop._lock:
            cop._col_cache[ck] = entry
    return entry


def _prepare_runstats(cop, frag, psnap, prepared, comb_dicts,
                      comb_bounds) -> None:
    """Gates of the run-statistics mode over this probe snapshot, and its
    static parameters: the doubling steps that cover the epoch's longest
    run and the halo a tile borrows from each neighbour."""
    if len(psnap.overlay_handles) > 0:
        raise _Fallback("runstat-overlay")  # overlay rows sit apart
    if cop.placement.axis is not None:
        raise _Fallback("runstat-mesh")
    g0 = frag.runstats[0]
    key_off = g0.table.col_offsets[g0.key_local]
    if not cop._runs_ordered(psnap, [key_off]):
        raise _Fallback("runstat-unordered")
    longest = cop._longest_run(psnap, key_off)
    halo = max(longest - 1, 0)
    if psnap.epoch.num_rows > cop.TILE_ROWS and halo > cop.TILE_ROWS:
        raise _Fallback("runstat-long-run")
    for g in frag.runstats:
        dicts = [psnap.dictionaries[off] for off in g.table.col_offsets]
        bounds = cop._scan_bounds(_facade_dag(g.table), psnap)
        for ci, off in enumerate(g.table.col_offsets):
            if psnap.epoch.columns[off].dtype == np.int64 and \
                    not fits_int32(bounds[ci]):
                raise _Fallback("int64-column")
        for c in g.table.filters:
            cop._prepare_expr(c, dicts, prepared)
            if not expr_device_safe(c, bounds):
                raise _Fallback("filter-unsafe")
        for _func, arg, _op, _thr in g.having:
            if arg is None:
                continue
            cop._prepare_expr(arg, dicts, prepared)
            b = expr_bounds(arg, bounds)
            # every run total stays inside int32 with room for the
            # threshold's clamp (runstat.compare)
            if not expr_device_safe(arg, bounds) or b is None or \
                    max(abs(b[0]), abs(b[1])) * longest > RS.I32_MAX - 1:
                raise _Fallback("runstat-sum-width")
        if g.probe_val is not None:
            cop._prepare_expr(g.probe_val, comb_dicts, prepared)
            if not expr_device_safe(g.probe_val, comb_bounds):
                raise _Fallback("key-width")
    steps = RS.steps_for(longest)
    prepared["__runstat__"] = {"steps": steps, "halo": halo,
                               "tile": cop.TILE_ROWS}
    prepared["__sig__"].append(("runstat", steps, halo, cop.TILE_ROWS))


def _stage_runstat(cop, frag, psnap, tiles=None, ti=None) -> dict:
    """The gates' columns as the program reads them: {"g": one column
    tuple a gate, "vis"} over the whole epoch, or over tile `ti` of the
    per-gate `tiles` with its neighbours ("prev", "next": the same shape;
    "edge": int32[2], whether each is a real neighbour)."""
    if tiles is None:
        staged = [cop._stage_inputs(_facade_dag(g.table), psnap,
                                    overlay=False) for g in frag.runstats]
        return {"g": [tuple(st[0]) for st in staged], "vis": staged[0][1]}

    def at(i):
        return {"g": [tuple(t[i][0]) for t in tiles], "vis": tiles[0][i][1]}

    last = len(tiles[0]) - 1
    return {**at(ti), "prev": at(max(ti - 1, 0)), "next": at(min(ti + 1, last)),
            "edge": jnp.asarray([ti > 0, ti < last], dtype=jnp.int32)}


def _runstat_mask(frag, prepared, rs, cols):
    """bool[rows]: the probe rows that pass every run-statistics gate.
    rs: the gates' staged columns (_stage_runstat); cols: the combined
    columns of the same rows (the probe value a residual compares)."""
    cfg = prepared["__runstat__"]
    tile = cfg["tile"]
    vis, gcols = rs["vis"], [widen32(list(g)) for g in rs["g"]]
    halo = cfg["halo"] if "prev" in rs else 0
    if halo:
        # a tile's rows with its neighbours' edge rows around them; a
        # neighbour that does not exist (edge 0) contributes no row
        prev, nxt = rs["prev"], rs["next"]
        vis = RS.extend(vis, prev["vis"] & (rs["edge"][0] > 0),
                        nxt["vis"] & (rs["edge"][1] > 0), tile, halo)
        gcols = [widen32([
            (RS.extend(d, pd, nd, tile, halo), RS.extend(v, pv, nv, tile,
                                                           halo))
            for (d, v), (pd, pv), (nd, nv) in zip(cur, pg, ng)])
            for cur, pg, ng in zip(rs["g"], prev["g"], nxt["g"])]
    g0 = frag.runstats[0]
    key = gcols[0][g0.key_local][0]
    passed = None
    for g, gc in zip(frag.runstats, gcols):
        m = selection_mask(g.table.filters, gc, prepared, vis)
        if g.kind == "in_having":
            arrays = [(m.astype(jnp.int32), "sum")]
            for func, arg, _op, _thr in g.having:
                if arg is not None:
                    x, xv = eval_expr(arg, gc, prepared)
                    ok = m & xv
                    arrays.append((ok.astype(jnp.int32), "sum"))
                    if func == "sum":
                        arrays.append((jnp.where(ok, x.astype(jnp.int32),
                                                 0), "sum"))
            tot = RS.run_totals(key, arrays, cfg["steps"])
            ok = tot[0] > 0   # the run is one of the subquery's groups
            k = 1
            for func, arg, op, thr in g.having:
                if arg is None:
                    ok = ok & RS.compare(op, tot[0], thr)
                elif func == "count":
                    ok = ok & RS.compare(op, tot[k], thr)
                    k += 1
                else:   # a SUM of no value is NULL: no comparison holds
                    ok = ok & (tot[k] > 0) & RS.compare(op, tot[k + 1], thr)
                    k += 2
            ok = RS.unextend(ok, tile, halo)
        else:
            s_, sv = gc[g.cmp_local]
            live = m & sv
            cnt, lo, hi = (RS.unextend(t, tile, halo) for t in RS.run_totals(
                key, [(live.astype(jnp.int32), "sum"),
                      (jnp.where(live, s_, RS.I32_MAX), "min"),
                      (jnp.where(live, s_, RS.I32_MIN), "max")],
                cfg["steps"]))
            # some row of the run holds another value than the probe row's
            pv, pvl = eval_expr(g.probe_val, cols, prepared)
            pv = pv.astype(jnp.int32)
            ok = pvl & (cnt > 0) & ~((lo == pv) & (hi == pv))
            if g.kind == "not_exists":
                ok = ~ok
        passed = ok if passed is None else passed & ok
    return passed


def _mode_op(frag, mode: str) -> str:
    """The fused kernel's operator label for the attribution plane:
    one device program covers the whole tree, so the label names the
    fused composition (the tree's dominant consumers) — a join+agg
    kernel's milliseconds must not masquerade as plain scan time."""
    if mode == "hc":
        if frag.hc is None:  # HAVING-filtered candidate path
            return "join+agg" if frag.joins else "agg"
        return "join+agg+topn" if frag.joins else "agg+topn"
    if mode == "topn":
        return "join+topn" if frag.joins else "topn"
    if mode == "agg":
        return "join+agg" if frag.joins else "agg"
    return "join"


def _run_frag_batch(cop, frag, snaps, prepared, spans, builds, overlay,
                    mode=None):
    probe = frag.tables[0]
    psnap = snaps[probe.table.id]
    if mode is None:
        mode = "agg" if frag.agg is not None else "rows"
    # big epochs stream through TILES exactly like the single-table path:
    # one compiled kernel, per-tile partials merged host-side — an
    # untiled 60M-row fragment kernel plans ~16GB of HBM intermediates
    # and fails to compile. The rank-space hc kernel streams internally
    # (bounded VMEM window) and keeps whole-epoch staging.
    pl = cop.placement
    if mode in ("agg", "rows", "topn") and not overlay and \
            pl.axis is None and \
            prepared.get("__part_join__") is None and \
            psnap.epoch.num_rows > cop.TILE_ROWS:
        return _run_frag_tiled(cop, frag, snaps, prepared, spans, builds,
                               mode)
    # probe-side staging is scan work; aligned build staging is join
    # work — separate operator frames keep the attribution honest
    with obs.operator("scan"), \
            obs.stage("staging", span_name="copr.staging"):
        pcols, pvis, phost, phost_mask = cop._stage_inputs(
            _facade_dag(probe), psnap, overlay=overlay)
    # single-device epoch batches swap the in-kernel perm gathers
    # for epoch-cached ALIGNED build columns (see _stage_aligned):
    # the first query against an epoch pays the gathers once; every
    # later fragment query over the same epochs is pure elementwise
    # + MXU work
    jb, sb = builds[:len(frag.joins)], builds[len(frag.joins):]
    kern_builds = builds
    if jb and not overlay and pl.axis is None and \
            prepared.get("__part_join__") is None:
        with obs.operator("join"), \
                obs.stage("staging", span_name="copr.staging"):
            kern_builds = _stage_aligned(cop, frag, snaps, prepared,
                                         spans, jb, pcols) + sb
    if frag.runstats:
        with obs.operator("scan"), \
                obs.stage("staging", span_name="copr.staging"):
            kern_builds = kern_builds + [_stage_runstat(cop, frag, psnap)]

    aux = None
    if mode == "hc" and not overlay and \
            prepared.get("__rank_meta__") is not None:
        aux = _stage_rank_aux(cop, psnap, prepared)
    key = ("frag", _frag_key(frag), _sig(prepared), mode,
           pcols[0][0].shape[0] if pcols else 0,
           tuple(_build_shape(b) for b in kern_builds))
    taken = cop._select_taken(key, prepared) \
        if mode in ("topn", "hc") else None
    name = _prog_mode(frag, mode)
    kern = cop._kernel(key, lambda: pl.frag_program(
        _build_frag_kernel(frag, prepared, spans, mode, pl), name,
        prepared, cop.recorder))
    prog = f"titpu_frag_{name}"
    with obs.operator(_mode_op(frag, mode)):
        with obs.stage("kernel", span_name="device.dispatch", prog=prog):
            dev = kern(pcols, pvis, kern_builds) if aux is None \
                else kern(pcols, pvis, kern_builds, aux)
        if taken is not None:
            (obs.HC_SELECT if mode == "hc" else obs.TOPN_SELECT).inc(
                path=taken[0])
        with obs.stage("device_get", span_name="device.fetch",
                       clocked=True, prog=prog):
            out = jax.device_get(dev)

    # one count a read the packing was eligible for, whatever it did
    pack = prepared.pop("__hc_pack__", None)
    spilled = mode == "hc" and np.any(np.asarray(
        out.pop("compact_overflow", 0)) > 0)
    if pack is not None:
        obs.HC_PACK.inc(path="spilled" if spilled else pack)
    if spilled:
        # more rows passed than the packed buffer holds: the statement
        # runs whole, now and from now on
        prepared["__sig__"].remove(
            ("hccompact", prepared.pop("__hc_compact__"),
             prepared.pop("__hc_nullable__")))
        with cop._lock:
            cop._hc_dense.add(prepared["__hc_dense_key__"])
        return _run_frag_batch(cop, frag, snaps, prepared, spans, builds,
                               overlay, mode)
    with obs.stage("decode"):
        if mode == "hc":
            # candidate blocks = exchange partitions (1 on a single device)
            prepared["__hc_blocks__"] = pl.n_devices
            chunk = _decode_hc(frag, snaps, prepared, out)
            return [] if chunk is None else [chunk]
        if mode == "agg":
            return _decode_frag_agg(frag, snaps, prepared, out)
        if mode == "topn":
            chunk = _decode_frag_topn(frag, snaps, out)
            return [] if chunk is None else [chunk]

        # row mode: device returned a packed probe-row bitmask; host
        # replays the (cheap, vectorized) gathers for the passing rows only
        n_rows = phost[0][0].shape[0] if phost else 0
        idx = rowbits.decode([out], [n_rows], n_rows)
    return _host_rows_for(frag, snaps, idx, overlay)


def _run_frag_tiled(cop, frag, snaps, prepared, spans, builds, mode):
    """Stream the probe through shape-bucketed tiles: the same compiled
    fragment kernel serves every tile, aligned join columns are cached
    per (epoch pair, tile), and the per-tile agg partials merge exactly
    like the single-table tiled path (client._merge_tile_outs)."""
    from .client import _merge_tile_outs

    probe = frag.tables[0]
    psnap = snaps[probe.table.id]
    with obs.operator("scan"), \
            obs.stage("staging", span_name="copr.staging"):
        tiles = cop._stage_tiles(_facade_dag(probe), psnap)
        gate_tiles = [cop._stage_tiles(_facade_dag(g.table), psnap)
                      for g in frag.runstats]
    bucket = tiles[0][0][0][0].shape[0] if tiles and tiles[0][0] else 0
    kern = None
    devs = []
    kop = _mode_op(frag, mode)
    prog = f"titpu_frag_{_prog_mode(frag, mode)}"
    taken = None
    jb_t, sb_t = builds[:len(frag.joins)], builds[len(frag.joins):]
    for ti, (cols, vis, cnt) in enumerate(tiles):
        kb = builds
        if jb_t:
            with obs.operator("join"), \
                    obs.stage("staging", span_name="copr.staging"):
                kb = _stage_aligned(cop, frag, snaps, prepared, spans,
                                    jb_t, cols, tag=("tile", ti)) + sb_t
        if frag.runstats:
            kb = kb + [_stage_runstat(cop, frag, psnap, gate_tiles, ti)]
        if kern is None:
            key = ("frag", _frag_key(frag), _sig(prepared), mode, bucket,
                   tuple(_build_shape(b) for b in kb))
            if mode == "topn":
                taken = cop._select_taken(key, prepared)
            pl = cop.placement
            kern = cop._kernel(key, lambda: pl.frag_program(
                _build_frag_kernel(frag, prepared, spans, mode, pl),
                _prog_mode(frag, mode), prepared, cop.recorder))
        from ..util import interrupt
        interrupt.check()
        with obs.operator(kop), \
                obs.stage("kernel", span_name="device.dispatch", prog=prog):
            devs.append(kern(cols, vis, kb))
    with obs.operator(kop), \
            obs.stage("device_get", span_name="device.fetch", clocked=True,
                      prog=prog):
        outs = jax.device_get(devs)

    if mode == "agg":
        with obs.stage("merge"):
            out = _merge_tile_outs(outs, prepared["__agg_sched__"])
        with obs.stage("decode"):
            return _decode_frag_agg(frag, snaps, prepared, out)

    if mode == "topn":
        # per-tile candidate rows; the host Sort/Limit above merge them
        if taken:
            obs.TOPN_SELECT.inc(path=taken[0])
        with obs.stage("decode"):
            return [c for c in (_decode_frag_topn(frag, snaps, out)
                                for out in outs) if c is not None]

    # rows: per-tile packed bitmasks -> global epoch row indices
    with obs.stage("decode"):
        idx = rowbits.decode(outs, [cnt for _, _, cnt in tiles],
                             cop.TILE_ROWS)
    return _host_rows_for(frag, snaps, idx, overlay=False)


def _build_shape(b: dict):
    """What of one kernel-argument entry a program's cache key holds."""
    if "bykey" in b:
        return ("part", b["present"].shape[0])
    if "acols" in b:
        return ("al", b["found"].shape[0])
    if "bm" in b:
        return ("bm", b["bm"].shape[0])
    if "g" in b:
        return ("rs", b["vis"].shape[0], "prev" in b)
    return b["cols"][0][0].shape[0]


def _prog_mode(frag, mode: str) -> str:
    """The program's name after titpu_frag_: its mode, with `_runstat`
    where run-statistics gates ride in it."""
    return f"{mode}_runstat" if frag.runstats else mode


def _decode_frag_agg(frag, snaps, prepared, out) -> list[Chunk]:
    """Fetched dense-agg partials -> partial-layout chunks (shared by the
    whole-epoch and tiled executions)."""
    if np.any(np.asarray(out.pop("overflow", 0)) > 0):
        raise _Fallback("exchange-overflow")  # join bucket skew
    cards = prepared["__dense_cards__"]
    comb_dicts = []
    for t in frag.tables:
        snap = snaps[t.table.id]
        comb_dicts.extend(snap.dictionaries[off]
                          for off in t.col_offsets)
    group_dicts = [
        comb_dicts[g.idx]
        if g.ftype.is_string and isinstance(g, Col) else None
        for g in frag.agg.group_by
    ]
    chunk = decode_agg_partials(
        frag.agg, prepared, cards, out, group_dicts,
        frag.output_types[len(frag.agg.group_by):])
    return [] if chunk is None else [chunk]


def _decode_frag_topn(frag, snaps, out) -> Optional[Chunk]:
    """Fetched top-n candidate rows -> one tree-order chunk (mirrors
    client._topn_decode); the host Sort/Limit above merge the candidate
    chunks from batches/tiles/shards exactly. String columns come back
    as dictionary codes and decode here, after the cut."""
    ints = np.asarray(out["ints"])
    flts = out.get("flts")
    if flts is not None:
        flts = np.asarray(flts)
    picked = ints[1].astype(bool)
    if not picked.any():
        return None
    comb_dicts = []
    for t in frag.tables:
        snap = snaps[t.table.id]
        comb_dicts.extend(snap.dictionaries[off] for off in t.col_offsets)
    columns = []
    ii = fi = 0
    for pos, comb in enumerate(frag.out_map):
        ft = frag.output_types[pos]
        if ft.is_float:
            data = flts[fi][picked]
            valid = flts[fi + 1][picked] > 0
            fi += 2
        else:
            data = ints[2 + ii][picked]
            valid = ints[2 + ii + 1][picked].astype(bool)
            ii += 2
        columns.append(Column(
            ft, data.astype(ft.np_dtype),
            None if valid.all() else valid, comb_dicts[comb]))
    if not columns:
        return None
    return Chunk(columns)


def _stage_rank_aux(cop, snap, prepared):
    """Device-resident epoch arrays for the streamseg rank kernel: the
    in-block local ranks lr with the per-block rank counts cb, and
    first-row-per-rank r0 (cached per epoch)."""
    meta = prepared["__rank_meta__"]
    key = (snap.epoch.epoch_id, "rankaux", meta["n0"], meta["nd"])
    with cop._lock:
        hit = cop._col_cache.get(key)
        cacheable = cop._live_epochs.get(snap.store.table.id) \
            == snap.epoch.epoch_id
    if hit is None:
        from . import streamseg as SS
        hit = {**SS.rank_aux(meta), "r0": jnp.asarray(meta["r0"])}
        if cacheable:
            with cop._lock:
                cop._col_cache[key] = hit
    return hit


def _expr_cols(exprs, base: int = 0) -> set:
    """Combined-space columns the expressions read (`base`: where the
    expressions' own column space starts in the combined one)."""
    read: set = set()

    def walk(e) -> None:
        if isinstance(e, Col):
            read.add(base + e.idx)
        for a in getattr(e, "args", ()):
            walk(a)

    for e in exprs:
        walk(e)
    return read


def _agg_read_cols(frag) -> Optional[set]:
    """Combined-space columns an aggregating fragment's program reads:
    join and membership keys, the tables' own filters, the selection,
    group keys and aggregate arguments. None for the row modes, whose
    output is every column."""
    if frag.agg is None:
        return None
    used = _expr_cols(
        [j.probe_key for j in frag.joins]
        + [sm.probe_key for sm in frag.semis]
        + [g.probe_val for g in frag.runstats if g.probe_val is not None]
        + frag.selection + list(frag.agg.group_by)
        + [d.arg for d in frag.agg.aggs if d.arg is not None])
    base = 0
    for t in frag.tables:  # a table's filters are in its local space
        used |= _expr_cols(t.filters, base)
        base += len(t.col_offsets)
    return used


def _nullable_cols(frag, snaps) -> tuple:
    """Combined-space columns whose epoch holds a NULL. Every other
    column is valid at every row the program's mask passes: a probe
    column's validity is all true, and a build column's is its join's
    `found`, which the mask includes."""
    out = []
    base = 0
    for t in frag.tables:
        valids = snaps[t.table.id].epoch.valids
        out.extend(base + ci for ci, off in enumerate(t.col_offsets)
                   if valids[off] is not None)
        base += len(t.col_offsets)
    return tuple(out)


def _stage_aligned(cop, frag, snaps, prepared, spans, builds, pcols,
                   tag=None):
    """Materialize build columns ALIGNED to the padded probe rows as
    epoch-cached device arrays.

    The in-kernel join (perm lookup + per-row column gathers) is the same
    computation for every query over an epoch pair — only the filters and
    aggregates change. TPU random gather runs ~50M elem/s (orders of
    magnitude under the elementwise/MXU paths), so paying it per query
    dominated join fragments. Instead the gathers run ONCE per (probe
    epoch, build epoch) and the results — one probe-length column per
    referenced build column plus a 'found' bitmap — stay device-resident,
    like the reference caching a TiFlash co-located/denormalized layout
    rather than re-shipping rows per query (reference:
    store/tikv/batch_coprocessor.go keeps region data local to a store;
    executor/index_lookup_join.go re-probes per batch, which this design
    deliberately avoids).

    A probe-length column is as large as a fact column, so the cache is
    kept PER COLUMN and per join PATH (the chain of (key column, build
    epoch) hops from the probe), not per statement: two statements that
    reach ORDERS through l_orderkey share o_orderdate's aligned copy,
    and an aggregating program aligns only the columns it reads
    (_agg_read_cols) — a build's key column, needed by the join alone,
    is never gathered; its slot in the kernel's column list holds the
    'found' bitmap as a placeholder nothing reads. A NULL-free build
    column's validity IS the path's 'found' bitmap (one shared array).
    At SF10 that is what lets seven join programs' columns sit beside
    LINEITEM in one chip's HBM.

    Returns a per-join list: {'acols': ((data, valid), ...), 'found': m}
    for joins it could align (probe key is a plain Col over the probe
    prefix or an earlier aligned column), else the original builds entry
    (the kernel gathers those as before)."""
    probe = frag.tables[0]
    psnap = snaps[probe.table.id]
    pep = psnap.epoch.epoch_id
    bucket = pcols[0][0].shape[0] if pcols else 0
    used = _agg_read_cols(frag)
    # combined-index -> ((data, valid) device pair, build epochs hopped
    # through to reach it, identity of how it was reached), or None where
    # the slot was not materialized (a join the kernel gathers itself, or
    # a column nothing reads)
    combined: list = [(c, (), ("probe", off))
                      for c, off in zip(pcols, probe.col_offsets)]
    out = []
    for j, (lo, span), b in zip(frag.joins, spans, builds):
        t = frag.tables[j.build]
        width = len(t.col_offsets)
        base = len(combined)
        key_e = j.probe_key
        src = None
        if "cols" in b and isinstance(key_e, Col) and \
                key_e.idx < len(combined):
            src = combined[key_e.idx]
        if src is None:
            out.append(b)
            combined.extend([None] * width)
            continue
        (kd, kv), src_hops, src_id = src
        bsnap = snaps[t.table.id]
        bep = bsnap.epoch.epoch_id
        # every build epoch on the path sits at k[2] so that _evict_stale
        # frees the chain when ANY hop's epoch is replaced
        hops = src_hops + (bep,)
        path = (src_id, t.table.id, t.col_offsets[j.build_key_local], lo,
                span, bsnap.mask_digest)

        def ckey(what):
            return (pep, "aligned", hops, path, bucket, psnap.mask_digest,
                    tag, what)

        want = [ci for ci in range(width)
                if used is None or base + ci in used]
        with cop._lock:
            found = cop._col_cache.get(ckey("found"))
            got = {ci: cop._col_cache.get(ckey(t.col_offsets[ci]))
                   for ci in want}
            cacheable = (
                cop._live_epochs.get(probe.table.id) == pep
                and cop._live_epochs.get(t.table.id) == bep)
        missing = [ci for ci in want if got[ci] is None]
        if found is None or missing:
            k = kd.astype(jnp.int32) - jnp.int32(lo)
            inrange = (k >= 0) & (k < span)
            ridx = b["perm"][jnp.clip(k, 0, span - 1)]
            gidx = jnp.clip(ridx, 0)
            fresh = {}
            if found is None:
                found = inrange & (ridx >= 0) & kv & b["vis"][gidx]
                fresh[ckey("found")] = found
            for ci in missing:
                d, v = b["cols"][ci]
                off = t.col_offsets[ci]
                got[ci] = (d[gidx], found
                           if bsnap.epoch.valids[off] is None
                           else v[gidx] & found)
                fresh[ckey(off)] = got[ci]
            if cacheable:
                with cop._lock:
                    for ck, arr in fresh.items():
                        cop._col_cache[ck] = arr
        out.append({"acols": tuple(got.get(ci, (found, found))
                                   for ci in range(width)),
                    "found": found})
        combined.extend(
            (got[ci], hops, (path, t.col_offsets[ci])) if ci in got
            else None for ci in range(width))
    return out


def _prepare_hc(frag, comb_bounds, prepared, n_rows) -> bool:
    """Gates + schedule for the sorted-run candidate path. Group keys must
    be int32-encodable with a collision-free NULL code (bounds hi + 1);
    aggregates must be additive (count / int-decomposable sum / avg)."""
    from .bounds import decompose_terms, limbs_for
    from . import sumexact as _SE

    nulls: list[int] = []
    spans_ = []
    los: list[int] = []
    for g in frag.agg.group_by:
        if g.ftype.is_float:
            return False
        if not expr_device_safe(g, comb_bounds):
            return False
        b = expr_bounds(g, comb_bounds)
        if b is None or b[1] + 1 >= 2**31 - 1:
            return False
        nulls.append(b[1] + 1)
        spans_.append(b[1] - b[0])
        los.append(b[0])

    # ---- segment-key selection (functional dependencies) ----
    # XLA's variadic sort compile time grows steeply with operand count,
    # so sort only by group keys that DETERMINE the rest: a build table
    # reached through a unique join whose key is determined contributes
    # all its columns (e.g. Q3 groups by l_orderkey + o_orderdate +
    # o_shippriority — the orders columns are functions of l_orderkey)
    bases = []
    acc = 0
    for t in frag.tables:
        bases.append((acc, acc + len(t.col_offsets)))
        acc += len(t.col_offsets)

    # a table's PK handle column determines every other column of that
    # table (row identity) — without this rule Q10-style group lists
    # (c_custkey, c_name, c_acctbal, ...) would need one sort key per
    # column and overflow the seg-key budget
    pk_comb: dict[int, int] = {}
    for ti, t in enumerate(frag.tables):
        off = getattr(t.table, "pk_handle_offset", None)
        if off is not None and off in t.col_offsets:
            pk_comb[ti] = bases[ti][0] + t.col_offsets.index(off)

    def cols_of(e) -> set:
        out = set()

        def walk(x):
            if isinstance(x, Col):
                out.add(x.idx)
            elif hasattr(x, "args"):
                for a in x.args:
                    walk(a)
        walk(e)
        return out

    def closure(det: set) -> set:
        det = set(det)
        changed = True
        while changed:
            changed = False
            for j in frag.joins:
                rng = set(range(*bases[j.build]))
                if rng <= det:
                    continue
                if cols_of(j.probe_key) <= det:
                    det |= rng
                    changed = True
            for ti, pc in pk_comb.items():
                rng = set(range(*bases[ti]))
                if pc in det and not rng <= det:
                    det |= rng
                    changed = True
        return det

    order = sorted(range(len(frag.agg.group_by)),
                   key=lambda gi: -spans_[gi])
    all_needed: set = set()
    for g in frag.agg.group_by:
        all_needed |= cols_of(g)
    # one plain key that determines every group column (a PK or a join
    # chain root) sorts alone — the common OLAP shape
    seg_keys: list[int] = []
    for gi in order:
        g = frag.agg.group_by[gi]
        if isinstance(g, Col) and all_needed <= closure({g.idx}):
            seg_keys = [gi]
            break
    if not seg_keys:
        det: set = set()
        for gi in order:
            g = frag.agg.group_by[gi]
            need = cols_of(g)
            if need and not need <= closure(det):
                seg_keys.append(gi)
                # only a PLAIN column key determines its column: a
                # composite expression (a+b) being constant does not pin
                # its arguments
                if isinstance(g, Col):
                    det |= need
    if not seg_keys:
        seg_keys = [0]
    segpack = None
    if len(seg_keys) > 2:
        # group-key packing: fold several segment keys into one int32
        # sort operand when their (span+2) code-space products fit —
        # XLA's variadic sort keeps <= 2 key operands instead of the
        # whole query rejecting to the host. Packing is a bijection on
        # the key tuples, which is all segment_bounds needs (equal
        # tuples stay contiguous in the sorted order).
        groups: list[list[int]] = []
        cur: list[int] = []
        prod = 1
        for gi in seg_keys:
            card = spans_[gi] + 2
            if card > 2**31 - 2:
                return False
            if prod * card > 2**31 - 2 and cur:
                groups.append(cur)
                cur, prod = [], 1
            cur.append(gi)
            prod *= card
        groups.append(cur)
        if len(groups) > 2:
            return False
        segpack = [[(gi, los[gi], spans_[gi] + 2) for gi in g]
                   for g in groups]
    prepared["__hc_segpack__"] = segpack
    sched: list[dict] = []
    n_minmax = 0
    for d in frag.agg.aggs:
        if d.arg is None or d.func == "count":
            sched.append({"kind": "count"})
            continue
        if d.func in ("min", "max"):
            # min/max by the sort itself: the value rides as one extra
            # ascending sort operand (complemented for max) appended
            # after the segment keys, so each segment's FIRST row holds
            # its min/max — one such operand per sort, hence one
            # min/max aggregate per fragment
            n_minmax += 1
            if n_minmax > 1 or d.arg.ftype.is_float or \
                    not expr_device_safe(d.arg, comb_bounds):
                return False
            vb = expr_bounds(d.arg, comb_bounds)
            # I32_MAX is the NULL/dropped sentinel in the encoded
            # operand (for max the complement -1-v must also clear it)
            if vb is None or vb[0] <= -(2**31) + 2 or vb[1] >= 2**31 - 2:
                return False
            sched.append({"kind": d.func})
            continue
        if d.func not in ("sum", "avg") or d.arg.ftype.is_float:
            return False
        terms = decompose_terms(d.arg, comb_bounds)
        if terms is None:
            return False
        b = expr_bounds(d.arg, comb_bounds)
        if b is None:
            return False
        sched.append({
            "kind": "isum",
            # past the int64 bound the host recombines in arithmetic that
            # cannot wrap (sumexact.combine_terms), as the dense path does
            "wide": _SE.needs_wide(max(abs(b[0]), abs(b[1])), n_rows),
            "terms": [(t, s, limbs_for(expr_bounds(t, comb_bounds),
                                       _SE.LIMB_BITS))
                      for t, s in terms],
        })
    prepared["__hc_nulls__"] = nulls
    prepared["__hc_los__"] = los
    prepared["__hc_sched__"] = sched
    prepared["__hc_segkeys__"] = seg_keys
    # run-order eligibility: when every segment key resolves to a plain
    # PROBE column, the executor can test whether storage order already
    # groups them (clustered-PK aggregation — TPC-H lineitem is
    # orderkey-ordered) and skip the device sort entirely (the
    # StreamAgg-over-ordered-input choice; reference:
    # planner/core/exhaust_physical_plans.go getStreamAggs requires input
    # order, executor/aggregate.go StreamAgg). A group key that IS the
    # unique build key of a join (Q18's o_orderkey) substitutes to the
    # join's probe key: equal wherever the inner join matches, and
    # unmatched segments are gated out by the zero row count.
    n_probe = len(frag.tables[0].col_offsets)

    def probe_local_of(e) -> Optional[int]:
        if not isinstance(e, Col):
            return None
        if e.idx < n_probe:
            return e.idx
        for j in frag.joins:
            b0, _ = bases[j.build]
            if e.idx == b0 + j.build_key_local and \
                    isinstance(j.probe_key, Col) and \
                    j.probe_key.idx < n_probe:
                return j.probe_key.idx
        return None

    segcols = []
    segprobe = []
    for gi in seg_keys:
        local = probe_local_of(frag.agg.group_by[gi])
        if local is None:
            segcols = None
            break
        segprobe.append(local)
        segcols.append(frag.tables[0].col_offsets[local])
    prepared["__hc_segcols__"] = segcols
    prepared["__hc_segprobe__"] = segprobe if segcols else None
    prepared["__sig__"].append((
        "hc",
        (frag.hc.score, frag.hc.desc, frag.hc.cap) if frag.hc
        else ("having", tuple(frag.having or ())),
        tuple(nulls),
        tuple(los),  # the fused cut's sentinel-fold branches key on lo
        tuple(seg_keys),
        tuple(tuple(g) for g in segpack) if segpack else None,
        tuple((s["kind"],) + tuple((repr(t), sh, L)
                                   for t, sh, L in s.get("terms", ()))
              for s in sched)))
    return True


def _build_frag_kernel(frag, prepared, spans, mode, pl):
    """The fragment's (pcols, pvis, builds[, aux]) -> outputs body for
    placement `pl`, which makes it a program (pl.frag_program)."""
    sel = frag.selection
    agg = frag.agg
    if mode == "agg":
        cards = prepared["__dense_cards__"]
        segments = 1
        for c in cards:
            segments *= max(c, 1)
    # group-partition exchange: a sharded placement routes joined rows
    # by group-key hash so each device owns whole groups (the MPP
    # hash-partition exchange mode, planner/core/fragment.go:45)
    hc_exchange = None
    if mode == "hc":
        hc_exchange = pl.hc_exchange_fn(frag, prepared)
    # partitioned-join exchange: probe rows route by join-key range to the
    # device holding that slice of the key-ordered build shard
    part_ji = prepared.get("__part_join__")
    join_exchange = None
    if part_ji is not None:
        join_exchange = pl.join_exchange_fn(frag, prepared, spans)
        part_axis = pl.axis
        part_span = spans[part_ji][1]
        part_n_dev = pl.n_devices
        part_per_dev = -(-part_span // part_n_dev)
    semi_spans = prepared.get("__semi_spans__", ())
    semi_flags = prepared.get("__semi_flags__", ())
    hc_compact = prepared.get("__hc_compact__")

    def kernel(pcols, pvis, builds, aux=None):
        cols = widen32(list(pcols))
        mask = pvis
        if frag.tables[0].filters:
            # probe-side pushed-down filters (local space == combined
            # prefix) gate rows before any gather work
            mask = selection_mask(frag.tables[0].filters, cols, prepared,
                                  mask)
        overflow_j = None
        if join_exchange is not None:
            cols, mask, overflow_j = join_exchange(cols, mask)
        for ji, (j, (lo, span), b) in enumerate(
                zip(frag.joins, spans, builds)):
            if "acols" in b:
                # pre-aligned join: columns already sit in probe-row
                # order; only the query's build-side filters remain
                t = frag.tables[j.build]
                found = b["found"]
                acols = widen32(list(b["acols"]))
                if t.filters:
                    found = selection_mask(t.filters, acols, prepared,
                                           found)
                for (d, v) in acols:
                    cols.append((d, v & found))
                mask = mask & found
                continue
            key_v, key_vl = eval_expr(j.probe_key, cols, prepared)
            k = key_v.astype(jnp.int32) - jnp.int32(lo)
            t = frag.tables[j.build]
            if ji == part_ji:
                # rows were routed here by k % n_dev (interleaved build
                # ownership): gather against the LOCAL slice, whose index
                # for key k is k // n_dev
                dev = jax.lax.axis_index(part_axis).astype(jnp.int32)
                local = k // jnp.int32(part_n_dev)
                inrange = (k >= 0) & (k < span) & \
                    (k % jnp.int32(part_n_dev) == dev)
                gidx = jnp.clip(local, 0, part_per_dev - 1)
                bmask = b["present"]
                if t.filters:
                    bmask = selection_mask(t.filters, b["bykey"], prepared,
                                           bmask)
                found = inrange & key_vl & bmask[gidx]
                for (d, v) in b["bykey"]:
                    cols.append((d[gidx], v[gidx] & found))
                mask = mask & found
                continue
            inrange = (k >= 0) & (k < span)
            ksafe = jnp.clip(k, 0, span - 1)
            ridx = b["perm"][ksafe]
            found = inrange & (ridx >= 0) & key_vl
            gidx = jnp.clip(ridx, 0)
            # build-side validity: visibility + pushed-down filters over
            # the FULL build columns, gathered per probe row
            bcols = widen32(list(b["cols"]))
            bmask = b["vis"]
            if t.filters:
                bmask = selection_mask(t.filters, bcols, prepared,
                                       bmask)
            found = found & bmask[gidx]
            for (d, v) in bcols:
                cols.append((d[gidx], v[gidx] & found))
            mask = mask & found
        # semi/anti membership gates: bitmap lookups over the combined
        # columns (applied after every gather so keys from build tables
        # work), NULL-aware for the NOT IN (ANTI_NULL) form
        for si, sm in enumerate(frag.semis):
            b = builds[len(frag.joins) + si]
            lo_s, span_s = semi_spans[si]
            has_null, empty = semi_flags[si]
            if sm.kind == "ANTI_NULL" and empty:
                continue  # NOT IN (empty set) keeps every row
            if sm.kind == "ANTI_NULL" and has_null:
                # any NULL in the subquery side: no row qualifies
                mask = mask & jnp.zeros_like(mask)
                continue
            kv_s, kvl_s = eval_expr(sm.probe_key, cols, prepared)
            ks = kv_s.astype(jnp.int32) - jnp.int32(lo_s)
            inr = (ks >= 0) & (ks < span_s)
            hit = b["bm"][jnp.clip(ks, 0, span_s - 1)] & inr & kvl_s
            if sm.kind == "SEMI":
                mask = mask & hit
            elif sm.kind == "ANTI":
                mask = mask & ~hit  # NULL probe key never matches: kept
            else:  # ANTI_NULL, null-free set: NULL probe key filtered
                mask = mask & kvl_s & ~hit
        if frag.runstats:
            mask = mask & _runstat_mask(
                frag, prepared, builds[len(frag.joins) + len(frag.semis)],
                cols)
        if sel:
            mask = selection_mask(sel, cols, prepared, mask)
        if mode == "agg":
            out = agg_partials(agg, prepared, cards, segments, cols, mask)
            if overflow_j is not None:
                out["overflow"] = overflow_j
            return out
        if mode == "hc":
            if hc_exchange is not None:
                cols, mask, overflow = hc_exchange(cols, mask)
                res = _hc_body(frag, prepared, cols, mask)
                res["overflow"] = overflow if overflow_j is None \
                    else overflow + overflow_j
                return res
            res = _hc_body(frag, prepared, cols, mask, aux, hc_compact)
            if overflow_j is not None:
                res["overflow"] = overflow_j
            return res
        if mode == "topn":
            # fused multi-key TopN: ONE int32 composite ranks the joined
            # rows, and the n winners' output columns gather in-kernel —
            # the packed candidate rows are the only device->host bytes
            from . import topnpack as TP
            comp = TP.composite_score(prepared["__topn_pack__"], cols,
                                      prepared, eval_expr)
            score = jnp.where(mask, comp, jnp.iinfo(jnp.int32).min)
            idx = topnsel.select(score, min(frag.topn.n, score.shape[0]),
                                 prepared.get("__select_taken__"))
            int_rows = [idx.astype(jnp.int32),
                        mask[idx].astype(jnp.int32)]
            flt_rows = []
            for pos, comb in enumerate(frag.out_map):
                d, v = cols[comb]
                pvk = d[idx]
                pvlk = (v & mask)[idx]
                if frag.output_types[pos].is_float:
                    flt_rows.append(pvk.astype(jnp.float32))
                    flt_rows.append(pvlk.astype(jnp.float32))
                else:
                    int_rows.append(pvk.astype(jnp.int32))
                    int_rows.append(pvlk.astype(jnp.int32))
            res = {"ints": jnp.stack(int_rows)}
            if flt_rows:
                res["flts"] = jnp.stack(flt_rows)
            return res
        return jnp.packbits(mask)

    return kernel


def _maybe_fused_cut(frag, prepared, res):
    """Device-side exact final ordering for the fused join+agg+topn
    mode: sort the candidate buffer by the COMPLETE ORDER BY — exact
    limb-pair digit comparison for SUM/COUNT items (topnpack.pair_digits),
    rank/complement codes for group keys, MySQL NULL placement as a flag
    component, candidate order as the final tie-break — then truncate
    the heavy arrays to k+1 rows per candidate block, so only the
    winning groups (plus one boundary witness) leave HBM. `picked` and
    `score` stay cap-length in sorted order: the decode's per-block
    soundness check still needs the full buffer-exhaustion picture."""
    if not prepared.get("__hc_fused__"):
        return res
    from . import topnpack as TP

    sched = prepared["__hc_sched__"]
    nulls = prepared["__hc_nulls__"]
    los = prepared.get("__hc_los__", ())
    cap = res["picked"].shape[0]
    i32 = np.iinfo(np.int32)
    keys = [jnp.int32(1) - res["picked"]]  # picked candidates lead
    for kind, idx, desc in frag.hc.items:
        if kind == "group":
            enc = res[f"gk{idx}"]
            isnull = enc == jnp.int32(nulls[idx])
            table = prepared.get(("hc_rank", idx))
            val = table[jnp.clip(enc, 0, table.shape[0] - 1)] \
                if table is not None else enc
            # DESC reverses with ~val (= -1 - val): order-reversing and
            # wrap-free over the whole int32 range, unlike negation
            # (which wraps at INT32_MIN). NULL folds into the value
            # operand when the sentinel cannot collide with a real
            # (transformed) value: any lo > INT32_MIN leaves one code
            # free at each end; a key that can hold INT32_MIN itself
            # (fits_int32 admits it) keeps a separate flag operand.
            lo = los[idx] if idx < len(los) else None
            safe = table is not None or (lo is not None
                                         and lo > i32.min)
            if desc:  # NULL last; larger value first
                rev = jnp.int32(-1) - val
                if safe:
                    keys.append(jnp.where(isnull, jnp.int32(i32.max),
                                          rev))
                else:
                    keys.append(jnp.where(isnull, 1, 0))
                    keys.append(jnp.where(isnull, 0, rev))
            else:     # NULL first; smaller value first
                if safe:
                    keys.append(jnp.where(isnull, jnp.int32(i32.min),
                                          val))
                else:
                    keys.append(jnp.where(isnull, 0, 1))
                    keys.append(jnp.where(isnull, 0, val))
            continue
        s_ = sched[idx]
        if s_["kind"] == "count":
            contribs = [(0, res[f"cnt{idx}"])]
            isnull = None  # COUNT is never NULL
        else:
            contribs = [(sh, res[f"s{idx}_{ti}"])
                        for ti, (_t, sh, _L) in enumerate(s_["terms"])]
            cntp = res[f"cnt{idx}"]
            cnt = cntp[0, 0] * jnp.int32(4096) + cntp[0, 1]
            isnull = cnt == 0  # SUM/AVG over no valid rows is NULL
        if s_["kind"] != "count" and \
                frag.agg.aggs[idx].func == "avg":
            # exact rounded-decimal AVG ordering (gated on the count
            # cap + scale shape by the fused-eligibility check)
            keys.extend(TP.avg_sort_keys(
                TP.pair_digits(contribs), cnt, isnull, desc))
            continue
        dks = TP.digit_sort_keys(TP.pair_digits(contribs), desc)
        if isnull is not None:
            # the signed head is carry-bounded well inside int32, so the
            # NULL sentinel folds into it (first-ASC / last-DESC)
            sent = jnp.int32(i32.max if desc else i32.min)
            dks = [jnp.where(isnull, sent, dks[0])] + \
                [jnp.where(isnull, 0, dk) for dk in dks[1:]]
        keys.extend(dks)
    iota = jnp.arange(cap, dtype=jnp.int32)
    perm = jax.lax.sort(tuple(keys) + (iota,),
                        num_keys=len(keys) + 1)[-1]
    kcut = min(cap, frag.hc.k + 1)
    cut = {}
    for name, v in res.items():
        if name in ("picked", "score"):
            cut[name] = v[perm]
        else:
            cut[name] = v[..., perm[:kcut]]
    return cut


def _hc_rank_body(frag, prepared, cols, mask, aux):
    """Rank-space hc aggregation over run-ordered input (streamseg).

    The Pallas kernel turns per-row masked value arrays into exact
    per-GROUP sums indexed by rank (= position among distinct key runs);
    score, candidate top-k, and the decode layout all then work on the
    rank axis (~rows/4 long) with only O(cap)-sized device fetches. Group
    keys for candidates are gathered at each rank's first row (r0):
    within a run every group key is constant (functional dependency), so
    any row serves; fully-masked runs are gated by a zero row count."""
    from . import streamseg as SS
    from . import sumexact as _SE

    agg = frag.agg
    hc = frag.hc
    nulls = prepared["__hc_nulls__"]
    sched = prepared["__hc_sched__"]
    meta = prepared["__rank_meta__"]

    encs = []
    for gi, g in enumerate(agg.group_by):
        v, vl = eval_expr(g, cols, prepared)
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.int32)
        encs.append(jnp.where(vl, v.astype(jnp.int32),
                              jnp.int32(nulls[gi])))

    arrs = [mask.astype(jnp.float32)]
    cnt_ix: list[int] = []
    term_ix: list[list] = []
    for ai, (d, s_) in enumerate(zip(agg.aggs, sched)):
        if s_["kind"] == "count":
            if d.arg is not None:
                _, vl = eval_expr(d.arg, cols, prepared)
                arrs.append((mask & vl).astype(jnp.float32))
            else:
                arrs.append(mask.astype(jnp.float32))
            cnt_ix.append(len(arrs) - 1)
            term_ix.append([])
            continue
        _, vl = eval_expr(d.arg, cols, prepared)
        contrib = mask & vl
        arrs.append(contrib.astype(jnp.float32))
        cnt_ix.append(len(arrs) - 1)
        t_list = []
        for (t, shift, L) in s_["terms"]:
            tv, _ = eval_expr(t, cols, prepared)
            tv32 = jnp.where(contrib, tv.astype(jnp.int32), 0)
            limb_ids = []
            for li in _SE.limbs_of(tv32, L):
                arrs.append(li.astype(jnp.float32))
                limb_ids.append(len(arrs) - 1)
            t_list.append((shift, limb_ids))
        term_ix.append(t_list)

    tot = SS.rank_sums(jnp.stack(arrs), aux, meta)  # f32[K, nd_pad]
    gate = tot[0] > 0
    r0 = aux["r0"]

    def agg_f32(ai):
        """(approximate f32 value, count) of aggregate ai per rank."""
        cnt = tot[cnt_ix[ai]]
        if sched[ai]["kind"] == "count":
            return cnt, cnt
        sv = jnp.zeros_like(cnt)
        for shift, limb_ids in term_ix[ai]:
            t = jnp.zeros_like(cnt)
            for pos, ix in enumerate(limb_ids):
                t = t + tot[ix] * float(1 << (_SE.LIMB_BITS * pos))
            sv = sv + t * float(1 << shift)
        return sv, cnt

    if hc is None:
        # HAVING-filtered groups: the device passes a safely WIDENED
        # predicate (f32 relative error margin) — completeness is what
        # matters; the host Selection above re-applies it exactly
        pass_m = gate
        for (ai, op, thr) in (frag.having or ()):
            sv, _cnt = agg_f32(ai)
            eps = jnp.abs(sv) * jnp.float32(2.0 ** -18) + jnp.float32(2.0)
            thr_f = jnp.float32(thr)
            if op == "gt":
                ok = sv > thr_f - eps
            elif op == "ge":
                ok = sv >= thr_f - eps
            elif op == "lt":
                ok = sv < thr_f + eps
            else:
                ok = sv <= thr_f + eps
            pass_m = pass_m & ok
        score = jnp.where(pass_m, 1.0, -jnp.inf)
        cand = topnsel.candidates(score, FragmentDAG.HAVING_CAP,
                                  prepared.get("__select_taken__"))
        rows_of = r0[cand]
        res = {"picked": pass_m[cand].astype(jnp.int32),
               "score": score[cand]}
        for gi in range(len(agg.group_by)):
            res[f"gk{gi}"] = encs[gi][rows_of]
        _emit_pairs(res, sched, term_ix, cnt_ix, tot, cand)
        return res

    # ---- candidate selection by (approximate) primary sort score ----
    kind, idx = hc.score
    if kind == "group":
        enc_r = encs[idx][r0]
        sv = enc_r.astype(jnp.float32)
        score_null = enc_r == nulls[idx]
    else:
        d = agg.aggs[idx]
        sv, cnt = agg_f32(idx)
        if sched[idx]["kind"] == "count":
            score_null = jnp.zeros_like(gate)
        else:
            if d.func == "avg":
                sv = sv / jnp.maximum(cnt, 1.0)
            score_null = cnt == 0
    signed = sv if hc.desc else -sv
    signed = jnp.where(score_null,
                       jnp.float32(-1e38 if hc.desc else np.inf), signed)
    score = jnp.where(gate, signed, -jnp.inf)

    cand = topnsel.candidates(score, hc.cap,
                              prepared.get("__select_taken__"))
    rows_of = r0[cand]
    res = {"picked": gate[cand].astype(jnp.int32), "score": score[cand]}
    for gi in range(len(agg.group_by)):
        res[f"gk{gi}"] = encs[gi][rows_of]
    _emit_pairs(res, sched, term_ix, cnt_ix, tot, cand)
    return _maybe_fused_cut(frag, prepared, res)


def _emit_pairs(res, sched, term_ix, cnt_ix, tot, cand):
    """Candidate rank sums -> the decode's [limbs, 2, cap] pair layout
    (hi*4096 + lo == value; exact for the gated per-rank totals)."""
    from . import sumexact as _SE

    def pairs(v_f32):
        v = v_f32.astype(jnp.int32)
        return jnp.stack([v >> _SE.LIMB_BITS,
                          v & ((1 << _SE.LIMB_BITS) - 1)])

    for ai, s_ in enumerate(sched):
        res[f"cnt{ai}"] = pairs(tot[cnt_ix[ai]][cand])[None]
        for ti, (shift, limb_ids) in enumerate(term_ix[ai]):
            res[f"s{ai}_{ti}"] = jnp.stack(
                [pairs(tot[ix][cand]) for ix in limb_ids])


def _compact_rows(cols, mask, cap: int, read: set, nullable: tuple):
    """Pack the rows `mask` passes to the front of a `cap`-row buffer:
    (the columns `read` at the passing rows in storage order, the
    buffer's row mask, whether more passed than fit, the epoch row of
    every slot). The columns ride the packing itself (copr/rowpack.py:
    a shift network inside tiles of 65 536 rows, then the tiles placed
    in order), so nothing is sorted or gathered at the epoch's length:
    67 M rows with three columns pack in 15.7 ms on a v5e, where one sort
    of the row numbers and gathers of the data and validity at the packed
    rows take 395 ms (PERF.md, section 6). A slot that holds a row is
    valid in every column not in `nullable` (copr/fragment.py
    _nullable_cols), so only those carry their validity along."""
    from . import rowpack

    read = sorted(read)
    riders = [cols[i][0] for i in read] + [cols[i][1] for i in read
                                           if i in nullable]
    src, vals, total = rowpack.pack(mask, riders, cap)
    keep = jnp.arange(cap, dtype=jnp.int32) < total
    data = dict(zip(read, vals))
    valid = dict(zip([i for i in read if i in nullable], vals[len(read):]))
    # a column the body does not read keeps its slot with a stand-in
    packed = [(data[i], valid.get(i, keep)) if i in data else (keep, keep)
              for i in range(len(cols))]
    return packed, keep, total > cap, src


def _hc_body(frag, prepared, cols, mask, aux=None, compact=None):
    """Sorted-run candidate aggregation (copr/hcagg.py machinery).

    Sorts by the SEGMENT keys only (the functional-dependency analysis in
    _prepare_hc proved the other group keys constant within a segment) —
    XLA's variadic sort compile time is the binding constraint. Candidate
    selection is topnsel.candidates (exact by score) over a score
    recombined from the exact pair sums (elementwise, no global scan).
    Run-ordered epochs with rank metadata dispatch to the streamseg
    rank-space body instead."""
    if aux is not None and prepared.get("__rank_meta__") is not None:
        return _hc_rank_body(frag, prepared, cols, mask, aux)
    from . import hcagg as HC
    from . import sumexact as _SE

    agg = frag.agg
    # group keys read only at the candidate rows, from the epoch's own
    # columns (where the body is packed)
    late: set = set()
    if compact:
        # the sort and the sums read the segment keys, the score's key and
        # the aggregates' arguments at every packed row; a group key that
        # the segment keys determine is read at the candidates alone
        eager = set(prepared["__hc_segkeys__"])
        if frag.hc is not None and frag.hc.score[0] == "group":
            eager.add(frag.hc.score[1])
        late = set(range(len(agg.group_by))) - eager
        epoch_cols = cols
        cols, mask, spilled, src = _compact_rows(
            cols, mask, mask.shape[0] // compact, _expr_cols(
                [agg.group_by[gi] for gi in eager]
                + [d.arg for d in agg.aggs if d.arg is not None]),
            prepared["__hc_nullable__"])
    hc = frag.hc
    nulls = prepared["__hc_nulls__"]
    sched = prepared["__hc_sched__"]
    seg_keys = prepared["__hc_segkeys__"]
    runord = bool(prepared.get("__hc_runordered__"))
    n = mask.shape[0]

    def encode(gi, at):
        v, vl = eval_expr(agg.group_by[gi], at, prepared)
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.int32)
        return jnp.where(vl, v.astype(jnp.int32), jnp.int32(nulls[gi]))

    encs = [None if gi in late else encode(gi, cols)
            for gi in range(len(agg.group_by))]

    # min/max rides the sort: one extra ascending operand (complement
    # for max) after the segment keys, so each segment's first row holds
    # the aggregate; NULL/dropped rows take the I32_MAX sentinel and
    # sort last within their segment (gated by cnt at decode)
    mm_ai = next((ai for ai, s_ in enumerate(sched)
                  if s_["kind"] in ("min", "max")), None)
    mm_enc = None
    if mm_ai is not None:
        assert not runord  # _device_fragment forces the sort path
        d_mm = agg.aggs[mm_ai]
        mv, mvl = eval_expr(d_mm.arg, cols, prepared)
        mv32 = mv.astype(jnp.int32)
        if sched[mm_ai]["kind"] == "max":
            mv32 = jnp.int32(-1) - mv32  # order-reversing, wrap-free
        mm_enc = jnp.where(mask & mvl, mv32, HC._I32_MAX)
    if runord:
        # storage order already groups the segment keys: boundaries are
        # raw key-change points (of the PROBE columns — a substituted
        # build-key group enc would carry null codes at unmatched rows);
        # rows dropped by the filter mask stay in place and contribute
        # zero to every segment sum, and a segment whose rows were ALL
        # dropped is gated out after hc_rows below
        perm = None
        sk = [cols[i][0].astype(jnp.int32)
              for i in prepared["__hc_segprobe__"]]
        is_start, end_idx = HC.segment_bounds(sk, jnp.ones(n, bool))
        valid = None
    else:
        segpack = prepared.get("__hc_segpack__")
        if segpack is not None:
            # packed operands: Horner over the NULL-encoded shifted
            # codes — a bijection on the key tuples, so boundaries and
            # grouping are exactly the multi-operand sort's
            operands = []
            for grp in segpack:
                k = None
                for gi, lo, card in grp:
                    code = encs[gi] - jnp.int32(lo)
                    k = code if k is None else \
                        k * jnp.int32(card) + code
                operands.append(k)
        else:
            operands = [encs[gi] for gi in seg_keys]
        sort_keys = []
        for pos, k in enumerate(operands):
            if pos == 0:
                k = jnp.where(mask, k, HC._I32_MAX)
            sort_keys.append(k)
        n_seg_ops = len(sort_keys)
        if mm_enc is not None:
            sort_keys.append(mm_enc)
        sk, perm = HC.sort_by_keys(sort_keys)
        valid = sk[0] != HC._I32_MAX
        is_start, end_idx = HC.segment_bounds(sk[:n_seg_ops], valid)
    iota = jnp.arange(n, dtype=jnp.int32)

    def P(x):
        return x if perm is None else x[perm]

    def pair_stack(values_unsorted_i32, n_limbs):
        """-> int32[n_limbs, 2, n] per-row candidate pair sums."""
        v_sorted = P(values_unsorted_i32)
        outs = []
        for li in _SE.limbs_of(v_sorted, n_limbs):
            hi, lo = HC.seg_sum_pairs(li, end_idx)
            outs.append(jnp.stack([hi, lo]))
        return jnp.stack(outs)

    def pairs_to_f32(pairs):
        """[L, 2, n] pair sums -> approximate per-row f32 value."""
        total = jnp.zeros(n, jnp.float32)
        for li in range(pairs.shape[0]):
            v = pairs[li, 0].astype(jnp.float32) * 4096.0 + \
                pairs[li, 1].astype(jnp.float32)
            total = total + v * float(1 << (_SE.LIMB_BITS * li))
        return total

    ones = mask.astype(jnp.int32)
    out = {"hc_rows": pair_stack(ones, 1)}

    for ai, (d, s) in enumerate(zip(agg.aggs, sched)):
        if s["kind"] == "count":
            if d.arg is not None:
                _, vl = eval_expr(d.arg, cols, prepared)
                out[f"hc_cnt{ai}"] = pair_stack((mask & vl).astype(
                    jnp.int32), 1)
            else:
                out[f"hc_cnt{ai}"] = out["hc_rows"]
            continue
        _, vl = eval_expr(d.arg, cols, prepared)
        contrib = mask & vl
        out[f"hc_cnt{ai}"] = pair_stack(contrib.astype(jnp.int32), 1)
        if s["kind"] in ("min", "max"):
            continue  # value comes from the sorted mm operand below
        for ti, (t, shift, L) in enumerate(s["terms"]):
            tv, _ = eval_expr(t, cols, prepared)
            tv32 = jnp.where(contrib, tv.astype(jnp.int32), 0)
            out[f"hc_s{ai}_{ti}"] = pair_stack(tv32, L)

    # a raw segment whose rows were ALL filtered out is not a group at
    # all (run-ordered mode only; the sort path pushes dropped rows to
    # the end, so every surviving start is a real group)
    if runord:
        rp = out["hc_rows"]
        seg_rows = rp[0, 0].astype(jnp.float32) * 4096.0 + \
            rp[0, 1].astype(jnp.float32)  # exact: counts < 2^24
        gate = is_start & (seg_rows > 0)
    else:
        gate = is_start & valid

    # ---- candidate selection by (approximate) primary sort score ----
    if hc is None:
        # all-groups "group" mode / HAVING over an unordered epoch:
        # every surviving group is a candidate (score 1.0), HAVING
        # predicates filter with a safe f32 widening (completeness is
        # what matters — the host Selection above re-applies them
        # exactly), and the decode verifies the candidate buffer was
        # not exhausted so no group was silently dropped
        pass_m = gate
        for (ai, op, thr) in (frag.having or ()):
            if sched[ai]["kind"] == "count":
                sv_h = pairs_to_f32(out[f"hc_cnt{ai}"])
            else:
                sv_h = jnp.zeros(n, jnp.float32)
                for ti, (t, shift, L) in enumerate(sched[ai]["terms"]):
                    sv_h = sv_h + pairs_to_f32(out[f"hc_s{ai}_{ti}"]) * \
                        float(1 << shift)
            eps = jnp.abs(sv_h) * jnp.float32(2.0 ** -18) + jnp.float32(2.0)
            thr_f = jnp.float32(thr)
            if op == "gt":
                ok = sv_h > thr_f - eps
            elif op == "ge":
                ok = sv_h >= thr_f - eps
            elif op == "lt":
                ok = sv_h < thr_f + eps
            else:
                ok = sv_h <= thr_f + eps
            pass_m = pass_m & ok
        score = jnp.where(pass_m, 1.0, -jnp.inf)
        k_cap = FragmentDAG.HAVING_CAP
    else:
        kind, idx = hc.score
        if kind == "group":
            sv = P(encs[idx]).astype(jnp.float32)
            score_null = P(encs[idx]) == nulls[idx]
        else:
            d = agg.aggs[idx]
            if sched[idx]["kind"] == "count":
                sv = pairs_to_f32(out[f"hc_cnt{idx}"])
                score_null = jnp.zeros(n, bool)  # COUNT is never NULL
            else:
                sv = jnp.zeros(n, jnp.float32)
                for ti, (t, shift, L) in enumerate(sched[idx]["terms"]):
                    sv = sv + pairs_to_f32(out[f"hc_s{idx}_{ti}"]) * \
                        float(1 << shift)
                cnt = pairs_to_f32(out[f"hc_cnt{idx}"])
                if d.func == "avg":
                    sv = sv / jnp.maximum(cnt, 1.0)
                score_null = cnt == 0  # SUM/AVG over no valid rows is NULL
        signed = sv if hc.desc else -sv
        # MySQL NULL ordering: first in ASC, last in DESC. ASC -> +inf
        # makes the NULL group a guaranteed candidate. DESC uses a FINITE
        # floor (below any real sum, which is bounded by int64) so NULL
        # groups still outrank non-start rows (-inf): group starts then
        # always win the candidate slots, making "not all slots picked" a
        # sound proof that every group is a candidate. Ties among several
        # NULL groups at the floor are caught by the decode's strict-gap
        # boundary check.
        signed = jnp.where(score_null,
                           jnp.float32(-1e38 if hc.desc else np.inf),
                           signed)
        score = jnp.where(gate, signed, -jnp.inf)
        k_cap = hc.cap

    # EXACTLY by score: the candidate-superset guarantee the decode
    # relies on. How (blocks, or the whole array where they do not pay)
    # follows from (n, k_cap) alone: topnsel.candidates
    cand = topnsel.candidates(score, k_cap,
                              prepared.get("__select_taken__"))
    res = {"picked": (gate if hc is not None else
                      pass_m)[cand].astype(jnp.int32),
           "score": score[cand]}
    if late:
        # the candidates' epoch rows, and the epoch's columns there
        at = src[P(iota)[cand]]
        read = _expr_cols(agg.group_by[gi] for gi in late)
        at_cols = [(c[0][at], c[1][at]) if i in read else (at, at)
                   for i, c in enumerate(epoch_cols)]
    for gi in range(len(agg.group_by)):
        res[f"gk{gi}"] = encode(gi, at_cols) if gi in late \
            else P(encs[gi])[cand]
    for ai, s in enumerate(sched):
        res[f"cnt{ai}"] = out[f"hc_cnt{ai}"][:, :, cand]
        for ti in range(len(s.get("terms", ()))):
            res[f"s{ai}_{ti}"] = out[f"hc_s{ai}_{ti}"][:, :, cand]
    if mm_ai is not None:
        res[f"mm{mm_ai}"] = sk[-1][cand]
    res = _maybe_fused_cut(frag, prepared, res)
    if compact:
        res["compact_overflow"] = spilled.astype(jnp.int32)
    return res


def _decode_hc(frag, snaps, prepared, out) -> Optional[Chunk]:
    """Candidate partials -> partial-layout chunk (subset of groups; the
    host HashAgg(final) + Sort + Limit above do the exact final ranking)."""
    if np.any(np.asarray(out.pop("overflow", 0)) > 0):
        raise _Fallback("exchange-overflow")  # adversarial skew
    picked = out["picked"].astype(bool)
    if not picked.any():
        return None
    if frag.hc is None:
        # HAVING / all-groups mode: sound iff no candidate BLOCK was
        # exhausted (every group — or margined-passing group — of that
        # exchange partition fit its buffer); blocks are per-device on
        # the mesh, one on a single device
        blocks = max(1, int(prepared.get("__hc_blocks__", 1)))
        kb = len(picked) // blocks
        for b in range(blocks):
            if picked[b * kb:(b + 1) * kb].all():
                raise _Fallback("group-overflow")
        return _decode_hc_rows(frag, snaps, prepared, out, picked)
    # candidate blocks are per-exchange-partition (group spaces disjoint);
    # each partition's buffer must be verified independently
    from . import hcagg as HC
    if not HC.candidate_blocks_sound(
            picked, out["score"], frag.hc.k,
            prepared.get("__hc_blocks__", 1)):
        raise _Fallback("hc-boundary")
    if prepared.get("__hc_fused__"):
        return _decode_fat(frag, snaps, prepared, out)
    return _decode_hc_rows(frag, snaps, prepared, out, picked)


def _decode_fat(frag, snaps, prepared, out) -> Optional[Chunk]:
    """Fused-cut candidates -> the final k groups per candidate block.

    The kernel shipped each block's candidates in EXACT final order with
    the heavy arrays truncated to k+1 rows; take the first
    min(picked, k) rows per block and verify the cut boundary is
    tie-free on every ORDER BY item (row k-1 must differ from row k) —
    an all-key tie is ambiguous against the host's stable sort and falls
    back to the exact host interpreter."""
    from . import sumexact as _SE

    k = frag.hc.k
    blocks = max(1, int(prepared.get("__hc_blocks__", 1)))
    picked_full = np.asarray(out["picked"]).astype(bool)
    cap = len(picked_full) // blocks
    probe = out.get("gk0")
    if probe is None:
        probe = out.get("cnt0")
    kcut = np.asarray(probe).shape[-1] // blocks

    def row_key(block: int, pos: int) -> tuple:
        p = block * kcut + pos
        vals: list = []
        for kind, idx, _desc in frag.hc.items:
            if kind == "group":
                vals.append(int(np.asarray(out[f"gk{idx}"])[p]))
                continue
            s_ = prepared["__hc_sched__"][idx]
            cnt = int(_SE.combine_partials(
                np.asarray(out[f"cnt{idx}"])[:, :, p:p + 1])[0])
            if s_["kind"] == "count":
                vals.append(cnt)
                continue
            v = 0
            for ti, (_t, sh, _L) in enumerate(s_["terms"]):
                v += int(_SE.combine_partials(
                    np.asarray(out[f"s{idx}_{ti}"])[:, :, p:p + 1])[0]) \
                    << sh
            if frag.agg.aggs[idx].func == "avg":
                # the item compares as the host's rounded decimal —
                # the tie check must use the SAME value
                if cnt == 0:
                    vals.append((True, 0))
                    continue
                from ..types.value import Decimal as _Dec
                at_ = frag.agg.aggs[idx].arg.ftype
                sc = at_.scale if at_.is_decimal else 0
                q = _Dec(v, sc).div(_Dec.from_int(cnt))
                vals.append((False, q.unscaled))
                continue
            vals.append((cnt == 0, v))  # NULL flag + exact value
        return tuple(vals)

    sel = np.zeros(blocks * kcut, dtype=bool)
    for b in range(blocks):
        npicked = int(picked_full[b * cap:(b + 1) * cap].sum())
        take = min(npicked, k, kcut)
        if npicked > k and kcut > k and \
                row_key(b, k - 1) == row_key(b, k):
            raise _Fallback("fat-boundary")
        sel[b * kcut: b * kcut + take] = True
    if not sel.any():
        return None
    heavy = {name: v for name, v in out.items()
             if name not in ("picked", "score")}
    return _decode_hc_rows(frag, snaps, prepared, heavy, sel)


def _decode_hc_rows(frag, snaps, prepared, out, picked) -> Chunk:
    """Materialize the picked candidates as a partial-layout chunk."""
    from . import sumexact as _SE
    from ..types.field_type import FieldType, TypeKind

    agg = frag.agg
    sched = prepared["__hc_sched__"]
    nulls = prepared["__hc_nulls__"]
    sel = np.nonzero(picked)[0]

    comb_dicts = []
    for t in frag.tables:
        snap = snaps[t.table.id]
        comb_dicts.extend(snap.dictionaries[off] for off in t.col_offsets)

    columns = []
    for gi, g in enumerate(agg.group_by):
        raw = out[f"gk{gi}"][sel]
        is_null = raw == nulls[gi]
        data = raw.astype(g.ftype.np_dtype)
        dictionary = comb_dicts[g.idx] \
            if g.ftype.is_string and isinstance(g, Col) else None
        columns.append(Column(
            g.ftype, data, None if not is_null.any() else ~is_null,
            dictionary))
    for ai, (d, s) in enumerate(zip(agg.aggs, sched)):
        # pair layout matches sumexact partials: value = hi*4096 + lo
        cnt = _SE.combine_partials(out[f"cnt{ai}"])[sel]
        val_t = frag.output_types[len(agg.group_by) + 2 * ai]
        if s["kind"] == "count":
            vcol = Column(val_t, cnt.astype(np.int64))
        elif s["kind"] in ("min", "max"):
            enc = np.asarray(out[f"mm{ai}"])[sel].astype(np.int64)
            val = enc if s["kind"] == "min" else -1 - enc
            val = np.where(cnt > 0, val, 0)  # sentinel-filled when empty
            vcol = Column(val_t, val.astype(val_t.np_dtype),
                          None if (cnt > 0).all() else (cnt > 0))
        else:
            val = _SE.combine_terms(
                [out[f"s{ai}_{ti}"] for ti in range(len(s["terms"]))],
                [shift for _, shift, _ in s["terms"]],
                wide=s["wide"], sel=sel)
            vcol = Column(val_t, val.astype(val_t.np_dtype),
                          None if (cnt > 0).all() else (cnt > 0))
        columns.append(vcol)
        columns.append(Column(FieldType(TypeKind.BIGINT, nullable=False),
                              cnt.astype(np.int64)))
    return Chunk(columns)


def _sig(prepared) -> tuple:
    return tuple(prepared.get("__sig__", ()))


def _frag_key(frag: FragmentDAG) -> str:
    """Structural + full-expression identity (filters and selections of
    different queries can share shapes — describe() alone collides)."""
    parts = [frag.describe()]
    for t in frag.tables:
        parts.append(repr(t.filters))
    for sm in frag.semis:
        parts.append(f"{sm.kind}|{repr(sm.table.filters)}")
    for g in frag.runstats:
        parts.append(f"{g.kind}|{g.table.col_offsets}|{g.table.filters!r}|"
                     f"{g.cmp_local}|{g.probe_val!r}|{g.having!r}")
    parts.append(repr(frag.selection))
    if frag.agg is not None:
        parts.append(repr(frag.agg.group_by))
        parts.append(repr(frag.agg.aggs))
    if frag.out_map is not None:
        parts.append(repr(frag.out_map))
    if frag.topn is not None:
        parts.append(f"topn{frag.topn.n}|{frag.topn.items!r}")
    if frag.hc is not None:
        parts.append(f"hc{frag.hc.k}|{frag.hc.items!r}")
    return "|".join(parts)


def _host_rows_for(frag, snaps, probe_idx, overlay) -> list[Chunk]:
    """Materialize joined output rows (tree order) for given probe rows:
    the read's `gather` stage."""
    with obs.stage("gather"):
        cols, valid, dicts = _host_join(frag, snaps, probe_idx,
                                        overlay=overlay,
                                        epoch_only_probe=True)
        if cols is None:
            return []
        return _rows_chunk(frag, cols, valid, dicts)


def _rows_chunk(frag, cols, valids, dicts) -> list[Chunk]:
    columns = []
    for pos, comb in enumerate(frag.out_map):
        ft = frag.output_types[pos]
        v = valids[comb]
        columns.append(Column(
            ft, cols[comb].astype(ft.np_dtype),
            None if v is None or v.all() else v, dicts[comb]))
    if not columns:
        return []
    return [Chunk(columns)]


# ==================== host fallback interpreter ====================

def _host_fragment(frag: FragmentDAG, snaps: dict) -> CopResult:
    """Numpy interpreter of the same FragmentDAG — used when the snapshot
    fails a device gate. Produces identical chunks (partial agg layout or
    tree-order rows)."""
    cols, valid, dicts = _host_join(frag, snaps, None, overlay=None,
                                    epoch_only_probe=False)
    if cols is None:
        if frag.agg is not None:
            return CopResult([], is_partial_agg=True)
        return CopResult([], is_partial_agg=False)
    if frag.agg is None:
        return CopResult(_rows_chunk(frag, cols, valid, dicts),
                         is_partial_agg=False)
    chunk = _host_agg(frag, cols, valid, dicts)
    return CopResult([] if chunk is None else [chunk], is_partial_agg=True)


def _full_host_cols(snap, col_offsets):
    """(data, valid) per column over visible epoch rows + overlay rows."""
    vis = snap.base_visible
    n_o = len(snap.overlay_handles)
    out = []
    for off in col_offsets:
        d = snap.epoch.columns[off][vis]
        v = snap.epoch.valids[off]
        v = None if v is None else v[vis]
        if n_o:
            od = snap.overlay_columns[off]
            ov = snap.overlay_valids[off]
            d = np.concatenate([d, od])
            if v is None and ov is None:
                v = None
            else:
                va = np.ones(len(d) - n_o, bool) if v is None else v
                vb = np.ones(n_o, bool) if ov is None else ov
                v = np.concatenate([va, vb])
        out.append((d, v))
    return out


def _host_join(frag, snaps, probe_idx, overlay, epoch_only_probe):
    """Vectorized host join. Returns (cols, valids, dicts) in combined
    order for the surviving row set, or (None, None, None) if empty.

    probe_idx + epoch_only_probe: device row mode hands back the passing
    probe row indices of one batch (epoch or overlay) — replay gathers for
    exactly those rows, with NO further filtering (the device already
    applied every filter)."""
    probe = frag.tables[0]
    psnap = snaps[probe.table.id]

    if epoch_only_probe:
        base = []
        for off in probe.col_offsets:
            if overlay:
                d, v = psnap.overlay_columns[off], psnap.overlay_valids[off]
            else:
                d, v = psnap.epoch.columns[off], psnap.epoch.valids[off]
            base.append((d[probe_idx],
                         None if v is None else v[probe_idx]))
        filtered = False
    else:
        base = _full_host_cols(psnap, probe.col_offsets)
        filtered = True

    cols = [d for d, _ in base]
    valids = [np.ones(len(cols[0]), bool) if v is None else v.copy()
              for d, v in base] if cols else []
    dicts = [psnap.dictionaries[off] for off in probe.col_offsets]
    nrows = len(cols[0]) if cols else 0
    keep = np.ones(nrows, bool)

    if filtered and probe.filters:
        ev = NumpyEval([(c, v) for c, v in zip(cols, valids)],
                       dicts, nrows)
        for c in probe.filters:
            fv, fvl = ev.eval(c)
            keep &= _truthy(np.asarray(fv)) & fvl

    for j in frag.joins:
        t = frag.tables[j.build]
        snap = snaps[t.table.id]
        bcols = _full_host_cols(snap, t.col_offsets)
        bn = len(bcols[0][0]) if bcols else 0
        bkeep = np.ones(bn, bool)
        bdicts = [snap.dictionaries[off] for off in t.col_offsets]
        if filtered and t.filters:
            bev = NumpyEval(
                [(d, np.ones(bn, bool) if v is None else v)
                 for d, v in bcols], bdicts, bn)
            for c in t.filters:
                fv, fvl = bev.eval(c)
                bkeep &= _truthy(np.asarray(fv)) & fvl
        # unique-key mapping via sorted search
        kd, kv = bcols[j.build_key_local]
        ok = bkeep.copy()
        if kv is not None:
            ok &= kv
        bidx = np.nonzero(ok)[0]
        bkeys = kd[bidx].astype(np.int64)
        order = np.argsort(bkeys, kind="stable")
        skeys = bkeys[order]
        srows = bidx[order]

        ev = NumpyEval([(c, v) for c, v in zip(cols, valids)], dicts,
                       nrows)
        pk, pkv = ev.eval(j.probe_key)
        pk = np.asarray(pk).astype(np.int64)
        pos = np.searchsorted(skeys, pk)
        pos_safe = np.clip(pos, 0, max(len(skeys) - 1, 0))
        found = np.zeros(nrows, bool) if len(skeys) == 0 else (
            (pos < len(skeys)) & (skeys[pos_safe] == pk))
        found &= np.asarray(pkv)
        rows = srows[pos_safe] if len(skeys) else np.zeros(nrows, np.int64)
        keep &= found
        safe_rows = np.where(found, rows, 0)
        for (d, v) in bcols:
            cols.append(d[safe_rows])
            valids.append((np.ones(nrows, bool) if v is None
                           else v[safe_rows]) & found)
        dicts.extend(bdicts)

    if filtered and nrows:
        # semi/anti membership gates (device twin: the bitmap lookups in
        # _build_frag_kernel); device row-mode replay skips them — the
        # kernel already applied every gate
        for sm in frag.semis:
            snap = snaps[sm.table.table.id]
            bcols = _full_host_cols(snap, sm.table.col_offsets)
            bn = len(bcols[0][0]) if bcols else 0
            bkeep, has_null, kd, ok = _semi_build_facts(
                bcols, [snap.dictionaries[off]
                        for off in sm.table.col_offsets],
                sm.table, sm.build_key_local, np.ones(bn, bool))
            skeys = np.unique(kd[ok].astype(np.int64))
            ev = NumpyEval([(c, v) for c, v in zip(cols, valids)],
                           dicts, nrows)
            pk, pkv = ev.eval(sm.probe_key)
            pkv = np.asarray(pkv)
            found = np.isin(np.asarray(pk).astype(np.int64), skeys) & pkv
            if sm.kind == "SEMI":
                keep &= found
            elif sm.kind == "ANTI":
                keep &= ~found
            else:  # ANTI_NULL: NULL-aware NOT IN
                if not bkeep.any():
                    pass  # NOT IN (empty set) keeps every row
                elif has_null:
                    keep &= False
                else:
                    keep &= pkv & ~found

    if filtered and nrows:
        for g in frag.runstats:
            keep &= _host_runstat_gate(g, snaps, probe, cols, valids, dicts)

    if filtered and frag.selection and nrows:
        ev = NumpyEval([(c, v) for c, v in zip(cols, valids)], dicts,
                       nrows)
        for c in frag.selection:
            fv, fvl = ev.eval(c)
            keep &= _truthy(np.asarray(fv)) & fvl

    if filtered:
        idx = np.nonzero(keep)[0]
        if len(idx) == 0:
            return None, None, None
        cols = [c[idx] for c in cols]
        valids = [v[idx] for v in valids]
    elif nrows == 0:
        return None, None, None
    return cols, valids, dicts


def _host_runstat_gate(g, snaps, probe, cols, valids, dicts) -> np.ndarray:
    """bool[rows]: the joined rows a run-statistics gate passes, over every
    visible row of the gate's table (overlay included), each row's group
    found by its key value; the device twin is _runstat_mask, which reads
    the same totals off the storage runs."""
    snap = snaps[g.table.table.id]
    gv = [(d, np.ones(len(d), bool) if v is None else v)
          for d, v in _full_host_cols(snap, g.table.col_offsets)]
    gn = len(gv[0][0])
    gev = NumpyEval(gv, [snap.dictionaries[off]
                         for off in g.table.col_offsets], gn)
    live = gv[g.key_local][1].copy()
    for c in g.table.filters:
        fv, fvl = gev.eval(c)
        live &= _truthy(np.asarray(fv)) & fvl
    keys, inv = np.unique(gv[g.key_local][0][live].astype(np.int64),
                          return_inverse=True)
    inv = inv.reshape(-1)
    # the joined row's own key: the probe column the gate is keyed by
    pk = probe.col_offsets.index(g.table.col_offsets[g.key_local])
    rk = cols[pk].astype(np.int64)
    at = np.minimum(np.searchsorted(keys, rk), max(len(keys) - 1, 0))
    found = valids[pk] & (keys[at] == rk) if len(keys) else \
        np.zeros(len(rk), bool)

    def total(vals, red=np.add, ident=0):
        """Each joined row's total of `vals` (one a live row) over its
        key's group."""
        out = np.full(len(keys), ident, np.int64)
        red.at(out, inv, vals)
        return out[at] if len(keys) else out[:0]

    if g.kind == "in_having":
        ok = found
        for func, arg, op, thr in g.having:
            if arg is None:
                v = total(np.ones(int(live.sum()), np.int64))
            else:
                av, avl = gev.eval(arg)
                avl = np.asarray(avl)[live]
                v = n = total(avl.astype(np.int64))
                if func == "sum":   # a SUM of no value is NULL: no match
                    ok = ok & (n > 0)
                    v = total(np.where(avl, np.asarray(av)[live].astype(
                        np.int64), 0))
            ok = ok & {"gt": v > thr, "ge": v >= thr, "lt": v < thr,
                       "le": v <= thr}[op]
        return ok
    cd, cv = gv[g.cmp_local]
    has = cv[live]
    c = cd[live].astype(np.int64)
    top = np.iinfo(np.int64)
    cnt = total(has.astype(np.int64))
    lo = total(np.where(has, c, top.max), np.minimum, top.max)
    hi = total(np.where(has, c, top.min), np.maximum, top.min)
    pv, pvl = NumpyEval([(d, v) for d, v in zip(cols, valids)], dicts,
                        len(rk)).eval(g.probe_val)
    pv = np.asarray(pv).astype(np.int64)
    # some row of the key's group holds another value than the row's own
    ok = found & np.asarray(pvl) & (cnt > 0) & ~((lo == pv) & (hi == pv))
    return ~ok if g.kind == "not_exists" else ok


def _host_agg(frag, cols, valids, dicts) -> Optional[Chunk]:
    """Partial-layout aggregation over joined host rows (numpy)."""
    agg = frag.agg
    n = len(cols[0]) if cols else 0
    if n == 0:
        return None
    ev = NumpyEval([(c, v) for c, v in zip(cols, valids)], dicts, n)
    keys = []
    for g in agg.group_by:
        gv, gvl = ev.eval(g)
        gv = np.asarray(gv)
        enc = gv.astype(np.float64).view(np.int64) \
            if np.issubdtype(gv.dtype, np.floating) else gv.astype(np.int64)
        keys.append((np.where(gvl, enc, np.int64(-(2**62))), gv, gvl))
    if keys:
        stacked = np.stack([k[0] for k in keys], axis=1)
        _, first, inv = np.unique(stacked, axis=0, return_index=True,
                                  return_inverse=True)
        inv = inv.reshape(-1)
    else:
        first = np.zeros(1, np.int64)
        inv = np.zeros(n, np.int64)
    n_seg = len(first)

    columns: list[Column] = []
    for gi, g in enumerate(agg.group_by):
        _, gv, gvl = keys[gi]
        data = gv[first]
        vl = gvl[first]
        dictionary = dicts[g.idx] \
            if g.ftype.is_string and isinstance(g, Col) else None
        columns.append(Column(g.ftype, data.astype(g.ftype.np_dtype),
                              None if vl.all() else vl, dictionary))
    from ..types.field_type import FieldType, TypeKind
    for ai, d in enumerate(agg.aggs):
        val_t = frag.output_types[len(agg.group_by) + 2 * ai]
        if d.arg is None:
            cnt = np.bincount(inv, minlength=n_seg).astype(np.int64)
            val = cnt
            vcol = Column(val_t, val)
        else:
            av, avl = ev.eval(d.arg)
            av = np.asarray(av)
            avl = np.asarray(avl)
            cnt = np.bincount(inv, weights=avl.astype(np.float64),
                              minlength=n_seg).astype(np.int64)
            if d.func == "count":
                vcol = Column(val_t, cnt)
            elif d.func in ("sum", "avg"):
                if np.issubdtype(av.dtype, np.floating):
                    s = np.bincount(inv, weights=np.where(avl, av, 0.0),
                                    minlength=n_seg)
                else:
                    s = np.zeros(n_seg, np.int64)
                    np.add.at(s, inv, np.where(avl, av.astype(np.int64), 0))
                vcol = Column(val_t, s.astype(val_t.np_dtype),
                              None if (cnt > 0).all() else (cnt > 0))
            elif d.func in ("min", "max"):
                if np.issubdtype(av.dtype, np.floating):
                    sent = np.inf if d.func == "min" else -np.inf
                    vv = np.where(avl, av, sent)
                else:
                    sent = np.int64(2**62) if d.func == "min" \
                        else np.int64(-(2**62))
                    vv = np.where(avl, av.astype(np.int64), sent)
                s = np.full(n_seg, sent, dtype=vv.dtype)
                red = np.minimum if d.func == "min" else np.maximum
                red.at(s, inv, vv)
                s = np.where(cnt > 0, s, 0)
                vcol = Column(val_t, s.astype(val_t.np_dtype),
                              None if (cnt > 0).all() else (cnt > 0))
            else:
                raise CompileError(f"host fragment agg {d.func}")
        columns.append(vcol)
        columns.append(Column(FieldType(TypeKind.BIGINT, nullable=False),
                              cnt.astype(np.int64)))
    return Chunk(columns)


def _empty_chunk(frag: FragmentDAG, comb_dicts) -> Chunk:
    columns = []
    if frag.agg is not None:
        from ..types.field_type import FieldType, TypeKind
        for g in frag.agg.group_by:
            dictionary = comb_dicts[g.idx] \
                if g.ftype.is_string and isinstance(g, Col) else None
            columns.append(Column(g.ftype, np.empty(0, g.ftype.np_dtype),
                                  None, dictionary))
        for ai, d in enumerate(frag.agg.aggs):
            vt = frag.output_types[len(frag.agg.group_by) + 2 * ai]
            columns.append(Column(vt, np.empty(0, vt.np_dtype)))
            columns.append(Column(
                FieldType(TypeKind.BIGINT, nullable=False),
                np.empty(0, np.int64)))
        return Chunk(columns)
    for pos, comb in enumerate(frag.out_map):
        ft = frag.output_types[pos]
        columns.append(Column(ft, np.empty(0, ft.np_dtype), None,
                              comb_dicts[comb]))
    return Chunk(columns)


def _truthy(v: np.ndarray) -> np.ndarray:
    if v.dtype == np.bool_:
        return v
    return v != 0
