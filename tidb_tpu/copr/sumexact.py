"""Exact integer segment sums on TPU without 64-bit device arithmetic.

TPUs have no native int64/float64; JAX's x64 mode emulates them (pairs of
u32 + X64Combine), which doubles transfer sizes and parameter counts.
This module provides the x64-free primitive the aggregation kernels are built on:

    per-row int32 values -> int32[limbs, 2, segments] partials
    (every partial is exactly representable; the host recombines to int64)

Scheme (SURVEY.md §7 hard-part 1, "scaled int32-pair kernels"):

* the value is split into signed 12-bit limbs (arithmetic-shift top limb
  keeps the sign);
* each limb is summed per segment in float32 over blocks of <= 4096 rows,
  so every block partial is an integer < 2^24 — exactly representable in
  f32 (this is where the MXU einsum path gets its exactness too);
* block partials (exact f32 integers < 2^24) convert to int32 and are
  split at 2^12; the hi/lo halves sum in native int32 over the block axis
  — exact for up to 2^19 blocks (2^31 rows), so tile size never limits
  exactness;
* the [limbs, 2(hi/lo), segments] int32 partials stay well under int32
  range for any realistic tile (hi/lo sums <= n_rows), so a cross-device
  psum over the mesh is exact in native int32 — no float, no int64 in the
  collective.

The host combines with int64 Horner:  p = hi*4096 + lo per limb, then
value = sum_i p_i << (12*i), while the planner's interval analysis proves
that no total can pass int64 (largest value x rows < 2**62). Past that
bound `combine_terms(wide=True)` recombines the same partials in Python
integers, which cannot wrap, and a
total that does not fit the int64 the result column carries is the
statement's out-of-range error (`SumOutOfRange`), never a wrapped number.
What still bounds the device side is above: hi/lo sums exact to 2**31
rows a dispatch.

Reference analog: the partial/final two-stage hash aggregation
(reference: executor/aggregate.go:146) — partials here are limb sums
instead of per-worker hash tables.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..errno import ER_DATA_OUT_OF_RANGE, CodedError

LIMB_BITS = 12
_LIMB_MASK = (1 << LIMB_BITS) - 1
_L2 = 1 << LIMB_BITS  # second-level split base
BLOCK = 4096  # rows per exact f32 block: 4096 * (2^12-1) < 2^24
EINSUM_BLOCK = 2048  # rows per one-hot einsum block (MXU path)


def limbs_of(v: jnp.ndarray, n_limbs: int) -> list[jnp.ndarray]:
    """Signed 12-bit limb decomposition of an int32 array.

    v == sum_i limbs[i] << (12*i); limbs 0..n-2 in [0, 4096), the top limb
    signed (arithmetic shift). All int32 ops.
    """
    out = []
    x = v
    for i in range(n_limbs):
        if i < n_limbs - 1:
            out.append(x & _LIMB_MASK)
            x = x >> LIMB_BITS
        else:
            out.append(x)
    return out


def _two_level(part: jnp.ndarray) -> jnp.ndarray:
    """f32[blocks, segments] exact-int partials -> int32[2, segments].

    Converts the exact f32 partials to int32 (all < 2^24) and sums the
    2^12-split halves in native int32 over the block axis.
    """
    p = part.astype(jnp.int32)
    return jnp.stack([(p >> LIMB_BITS).sum(axis=0),
                      (p & _LIMB_MASK).sum(axis=0)])


def seg_sum_partials(
    v: jnp.ndarray,
    seg: jnp.ndarray,
    segments: int,
    n_limbs: int,
    one_hot: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Exact per-segment sums of int32 v -> int32[n_limbs, 2, segments].

    seg: int32 segment id per row, -1 = excluded (masked/padded rows).
    For small segment counts the masked-reduction ("loop") form is used —
    XLA fuses it into one pass; larger spaces use the one-hot f32 einsum
    on the MXU (pass the shared `one_hot` to amortize it across values).
    """
    n = v.shape[0]
    limbs = limbs_of(v, n_limbs)
    outs = []
    if one_hot is None:
        # loop strategy: per-segment masked block sums
        nblk = -(-n // BLOCK)
        pad = nblk * BLOCK - n

        def blk(x):
            return jnp.pad(x, (0, pad)).reshape(nblk, BLOCK)

        seg_b = jnp.pad(seg, (0, pad), constant_values=-1).reshape(nblk, BLOCK)
        for li in limbs:
            lb = blk(li.astype(jnp.float32))
            per_seg = []
            for k in range(segments):
                m = seg_b == k
                part = jnp.where(m, lb, 0.0).sum(axis=1)  # f32[nblk] exact
                per_seg.append(_two_level(part[:, None])[:, 0])
            outs.append(jnp.stack(per_seg, axis=-1))  # [2, segments]
    else:
        # einsum strategy: one_hot is f32[blocks, EINSUM_BLOCK, segments]
        for li in limbs:
            nblk = one_hot.shape[0]
            pad = nblk * EINSUM_BLOCK - n
            lb = jnp.pad(li.astype(jnp.float32), (0, pad)).reshape(
                nblk, EINSUM_BLOCK)
            # f32 MXU pass; HIGHEST stops bf16 rounding of 12-bit limbs
            part = jnp.einsum("cb,cbk->ck", lb, one_hot,
                              precision=jax.lax.Precision.HIGHEST)
            outs.append(_two_level(part))
    return jnp.stack(outs)  # int32[n_limbs, 2, segments]


def make_one_hot(seg: jnp.ndarray, segments: int) -> jnp.ndarray:
    """Shared f32 one-hot for the einsum path; -1 rows vanish (all-zero)."""
    n = seg.shape[0]
    nblk = -(-n // EINSUM_BLOCK)
    pad = nblk * EINSUM_BLOCK - n
    seg2 = jnp.pad(seg, (0, pad), constant_values=-1).reshape(
        nblk, EINSUM_BLOCK)
    return jax.nn.one_hot(seg2, segments, dtype=jnp.float32)


def merge_additive(vals) -> np.ndarray:
    """Sum per-tile / per-shard additive partials host-side in int64.

    Limb partials are exact under addition but hi/lo sums can exceed
    int32 once many tiles (or mesh shards fetched without a device psum)
    merge — so the host merge widens first. Shared by the tiled single-
    table path and the mesh plane's host-side partial merge."""
    return np.sum(np.stack([np.asarray(v).astype(np.int64) for v in vals]),
                  axis=0)


def combine_partials(p: np.ndarray, dtype=np.int64) -> np.ndarray:
    """int32[n_limbs, 2, segments] -> int64[segments], exact.

    Horner over limbs of (hi*4096 + lo); intermediates stay within int64
    because the true total does. `dtype=object` does the same in Python
    integers, for a total that may not (combine_terms).
    """
    p = np.asarray(p).astype(dtype)
    n_limbs = p.shape[0]
    total = np.zeros(p.shape[2], dtype=dtype)
    for i in range(n_limbs - 1, -1, -1):
        total = total * (1 << LIMB_BITS) + (p[i, 0] * _L2 + p[i, 1])
    return total


# largest |value| x rows under which the int64 Horner cannot wrap
INT64_SAFE = 2**62
_I64_MIN, _I64_MAX = -(2**63), 2**63 - 1
# the counter's label tuples, kept: one per aggregate on the decode path
_WIDTH_KEY = {False: (("width", "int64"),), True: (("width", "wide"),)}


class SumOutOfRange(CodedError):
    """An exact SUM whose total does not fit the int64 its result column
    carries (MySQL: ER_DATA_OUT_OF_RANGE, 'BIGINT value is out of
    range'). The statement's error: no wrapped number, no host re-run."""

    errno = ER_DATA_OUT_OF_RANGE
    sqlstate = "22003"


def needs_wide(magnitude: int, n_rows: int) -> bool:
    """True where largest |value| x rows cannot prove that the total fits
    the int64 Horner: the aggregate's schedule entry is marked `wide`."""
    return magnitude * max(n_rows, 1) >= INT64_SAFE


def check_int64(total: np.ndarray) -> np.ndarray:
    """object[...] of Python integers -> int64[...], or SumOutOfRange."""
    if total.size and (total.max() > _I64_MAX or total.min() < _I64_MIN):
        raise SumOutOfRange("BIGINT value is out of range in 'sum'")
    return total.astype(np.int64)


def combine_terms(parts, shifts, wide: bool = False,
                  sel=None) -> np.ndarray:
    """sum_i combine(parts[i]) << shifts[i] -> int64[segments], exact.

    parts: one int32[n_limbs, 2, segments] array per term of a decomposed
    aggregate argument (bounds.decompose_terms), each already merged over
    tiles and shards; `sel` keeps only those segments. Under the int64
    bound (`wide` false) the Horner runs in int64. Past it the same
    partials recombine in Python integers, and a total outside int64
    raises SumOutOfRange."""
    obs.SUM_RECOMBINE.inc_key(_WIDTH_KEY[bool(wide)], 1.0)
    dtype = object if wide else np.int64
    total = 0
    for p, shift in zip(parts, shifts):
        if sel is not None:
            p = np.asarray(p)[:, :, sel]
        total = total + (combine_partials(p, dtype) << shift)
    return check_int64(total) if wide else total


def float_seg_sums(
    v: jnp.ndarray,
    seg: jnp.ndarray,
    segments: int,
    n_blocks: int = 32,
) -> jnp.ndarray:
    """Blocked f32 per-segment sums -> f32[n_blocks, segments].

    The host sums the block partials in float64, so rounding error is
    confined within blocks of n/n_blocks rows (near-f64 accuracy without
    any f64 on device).
    """
    n = v.shape[0]
    per = -(-n // n_blocks)
    pad = per * n_blocks - n
    vb = jnp.pad(v.astype(jnp.float32), (0, pad)).reshape(n_blocks, per)
    sb = jnp.pad(seg, (0, pad), constant_values=-1).reshape(n_blocks, per)
    outs = []
    for k in range(segments):
        outs.append(jnp.where(sb == k, vb, 0.0).sum(axis=1))
    return jnp.stack(outs, axis=1)  # [n_blocks, segments]


def combine_float(p: np.ndarray) -> np.ndarray:
    """f32[n_blocks, segments] -> f64[segments] (host f64 accumulate)."""
    return np.asarray(p, dtype=np.float64).sum(axis=0)
