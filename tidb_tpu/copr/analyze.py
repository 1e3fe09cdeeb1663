"""ANALYZE pushdown: full-column statistics as device reduction kernels.

The reference pushes ANALYZE to the store as sample collectors + FM
sketches per region (reference: executor/analyze.go,
statistics/fmsketch.go, distsql/distsql.go:137 Analyze); only histogram
assembly happens centrally. The TPU analog (SURVEY §2.3 P13): one fused
reduction kernel per column batch over the SAME shape-bucketed tiles the
query path stages (cached device columns are reused), producing

  * non-null row count,
  * min / max,
  * 256 HLL-style registers from a 32-bit splitmix hash (the device is
    64-bit-free) — the NDV estimator that replaces a host np.unique over
    the full column.

Histograms and CM sketches still build host-side from a bounded SAMPLE
(statistics/builder.go builds histograms from samples in the reference
too); the device pass removes the full-column host scans that dominate
ANALYZE wall time at SF10+.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

N_REG = 256         # HLL registers (2^8: ~6.5% standard error)
_REG_BITS = 8

# splitmix32-style avalanche (device-side; uint32 lanes)
_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)


def _hash32(x):
    h = x.astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_M1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_M2)
    h = h ^ (h >> 16)
    return h


def hash32_host(x: np.ndarray) -> np.ndarray:
    """Host twin of the device hash (sketches built on either side must
    agree)."""
    with np.errstate(over="ignore"):
        h = x.astype(np.uint32)
        h ^= h >> 16
        h *= _M1
        h ^= h >> 13
        h *= _M2
        h ^= h >> 16
    return h


def hll_bucket_rank(v32):
    """Device (bucket, rank) per lane for HLL register updates: bucket =
    low 8 hash bits, rank = 1 + trailing zeros of the remaining bits
    (isolated low bit is a power of two -> exact f32 log2). Shared by
    ANALYZE NDV and the APPROX_COUNT_DISTINCT aggregate so their sketches
    merge."""
    h = _hash32(v32)
    bucket = (h & jnp.uint32(N_REG - 1)).astype(jnp.int32)
    rest = (h >> _REG_BITS) | jnp.uint32(1 << (32 - _REG_BITS))
    low = rest & (~rest + jnp.uint32(1))
    rank = jnp.log2(low.astype(jnp.float32)).astype(jnp.int32) + 1
    return bucket, rank


def hll_bucket_rank_host(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Host twin of hll_bucket_rank (bit-identical registers)."""
    h = hash32_host(x)
    bucket = (h & np.uint32(N_REG - 1)).astype(np.int32)
    rest = (h >> np.uint32(_REG_BITS)) | np.uint32(1 << (32 - _REG_BITS))
    low = rest & (~rest + np.uint32(1))
    rank = np.log2(low.astype(np.float64)).astype(np.int32) + 1
    return bucket, rank


def hll_hash_src_int(v: np.ndarray) -> np.ndarray:
    """uint32 hash input for integer values. The choice is PER ELEMENT:
    int32-range values use their low 32 bits (bit-identical to the device
    sketch), wider values fold their high 32 bits in (plain truncation
    would collide every pair differing only above bit 31). A per-batch
    choice would hash the same in-range value differently across partial
    producers (partitions/overlay), double-counting it in the register
    merge."""
    v = np.asarray(v).astype(np.int64)
    u = v.view(np.uint64)
    low = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    in_range = (v >= -(2 ** 31)) & (v < 2 ** 31)
    if in_range.all():
        return low
    folded = ((u ^ (u >> np.uint64(32))) &
              np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return np.where(in_range, low, folded)


def float_bits_key(x: np.ndarray) -> np.ndarray:
    """Canonical int64 bit-key for float64 values: -0.0 normalizes to
    0.0 so the two zero encodings compare equal. Shared by distinct
    aggregation, the host HLL hash, and ADMIN CHECK unique scans — one
    canonicalization, three consumers."""
    norm = np.where(x == 0, 0.0, np.asarray(x, np.float64))
    return norm.view(np.int64)


def hll_group_registers_host(av: np.ndarray, avl: np.ndarray,
                             inv: np.ndarray, n_seg: int) -> np.ndarray:
    """Per-group HLL registers host-side: (n_seg, N_REG) int32 max-rank,
    bit-identical to the device scatter (copr/client.agg_partials hll
    branch) so host-fallback partials merge with device partials."""
    regs = np.zeros((n_seg, N_REG), np.int32)
    rows = np.nonzero(avl)[0]
    if len(rows):
        bucket, rank = hll_bucket_rank_host(av[rows])
        np.maximum.at(regs, (inv[rows], bucket), rank)
    return regs


def hll_pack_words(regs: np.ndarray) -> np.ndarray:
    """(n, N_REG) int32 registers -> (n, N_REG // 8) int64 byte-packed."""
    regs = regs.astype(np.int64)
    words = np.zeros((regs.shape[0], N_REG // 8), np.int64)
    for w in range(N_REG // 8):
        for b in range(8):
            words[:, w] |= regs[:, w * 8 + b] << (8 * b)
    return words


def hll_unpack_words(words: np.ndarray) -> np.ndarray:
    """(n, N_REG // 8) int64 byte-packed -> (n, N_REG) int32 registers."""
    out = np.zeros((words.shape[0], N_REG), np.int32)
    for w in range(words.shape[1]):
        for b in range(8):
            out[:, w * 8 + b] = (words[:, w] >> (8 * b)) & 0xFF
    return out


def _column_partials(data, valid):
    """Reduction body for one staged column (int32/f32 + validity)."""
    v32 = data.astype(jnp.int32) if data.dtype in (
        jnp.int8, jnp.int16, jnp.int32) else data
    cnt = jnp.sum(valid.astype(jnp.int32))
    if v32.dtype == jnp.float32:
        big = jnp.float32(np.inf)
        mn = jnp.min(jnp.where(valid, v32, big))
        mx = jnp.max(jnp.where(valid, v32, -big))
    else:
        big = jnp.int32(2**31 - 1)
        mn = jnp.min(jnp.where(valid, v32, big))
        mx = jnp.max(jnp.where(valid, v32, -big - 1))
    # HLL registers over a 32-bit hash: bucket = low _REG_BITS bits, rank =
    # trailing zeros of the remaining bits + 1 (isolated low bit is a
    # power of two -> exact f32 log2)
    hsrc = jax.lax.bitcast_convert_type(v32, jnp.int32) \
        if v32.dtype == jnp.float32 else v32
    bucket, rank = hll_bucket_rank(hsrc)
    rank = jnp.where(valid, rank, 0)
    regs = jnp.zeros(N_REG, jnp.int32).at[bucket].max(rank)
    return {"cnt": cnt, "mn": mn, "mx": mx, "regs": regs}


def _merge(parts: list[dict]) -> dict:
    out = dict(parts[0])
    for p in parts[1:]:
        out["cnt"] = out["cnt"] + p["cnt"]
        out["mn"] = np.minimum(out["mn"], p["mn"])
        out["mx"] = np.maximum(out["mx"], p["mx"])
        out["regs"] = np.maximum(out["regs"], p["regs"])
    return out


def hll_ndv(regs: np.ndarray, nonnull: float) -> int:
    """Standard HLL estimate with small-range correction."""
    m = float(N_REG)
    alpha = {16: 0.673, 32: 0.697, 64: 0.709}.get(
        N_REG, 0.7213 / (1 + 1.079 / m))
    regs = np.asarray(regs, dtype=np.float64)
    est = alpha * m * m / np.sum(np.exp2(-regs))
    zeros = float((regs == 0).sum())
    if est <= 2.5 * m and zeros > 0:
        est = m * np.log(m / zeros)
    return max(1, min(int(round(est)), int(nonnull)))


def device_column_stats(cop, snap, offsets: list[int]):
    """off -> (nonnull_count, min, max, ndv) via one kernel per tile,
    reusing the query path's cached tile staging. Columns whose staged
    width cannot represent the values (host int64 beyond int32) are
    skipped — the caller falls back to host stats for those."""
    from ..plan.dag import CopDAG, DAGScan

    usable = []
    for off in offsets:
        d = snap.epoch.columns[off]
        if d.dtype == np.int64:
            b = cop._col_stats(snap, off)
            if b is None or b[0] < -(2**31) or b[1] >= 2**31:
                continue
        usable.append(off)
    if not usable:
        return {}
    dag = CopDAG(scan=DAGScan(snap.store.table.id, usable))
    # placement must match the query path's: an ANALYZE staging outside
    # the scope would seed the SHARED client's epoch cache with
    # single-device arrays under the keys sharded queries hit, silently
    # defeating the persistent sharded residency
    with cop.placement_scope(snap):
        tiles = cop._stage_tiles(dag, snap)
        bucket = tiles[0][0][0][0].shape[0] if tiles and tiles[0][0] else 0

        def build():
            def kernel(d, v, vis):
                from .client import widen32
                (d, v), = widen32([(d, v)])
                return _column_partials(d, v & vis)
            from .placement import named_jit
            return named_jit(kernel, "titpu_analyze")

        # one kernel per (dtype, bucket) — shared across all columns of
        # that width, so the first ANALYZE compiles a handful of tiny
        # programs
        devs = []
        for ci in range(len(usable)):
            dt = str(tiles[0][0][ci][0].dtype)
            kern = cop._kernel(("analyze", dt, bucket), build)
            devs.append([kern(cols[ci][0], cols[ci][1], vis)
                         for cols, vis, _ in tiles])
        outs = jax.device_get(devs)
    result = {}
    for ci, off in enumerate(usable):
        p = _merge(list(outs[ci]))
        nonnull = float(p["cnt"])
        result[off] = (nonnull, p["mn"], p["mx"],
                       hll_ndv(p["regs"], nonnull) if nonnull else 0)
    return result
