"""High-cardinality group-by on device: sorted runs + fused TopN.

The dense-segment aggregation (client.agg_partials) caps at 8192 segments
— far below GROUP BY l_orderkey (millions of groups). This module covers
the high-cardinality shape that matters in practice: aggregation whose
consumer is ORDER BY ... LIMIT k (TPC-H Q3/Q10/Q18-style), where only the
top-k groups survive. The reference handles this with a hash aggregate
feeding a TopN heap (executor/aggregate.go:146 + executor/sort.go); the
TPU formulation is sort-based and fully static-shape:

1. rows sort lexicographically by the group keys (jax.lax.sort, multiple
   key operands — no radix combination, so key spaces beyond int32 work);
2. segment starts are key-change positions; each start's segment END is
   recovered with a suffix-min scan over start indices (static shapes, no
   dynamic group count anywhere);
3. per-aggregate sums use the same 12-bit-limb exactness scheme as
   sumexact.py, but as PREFIX sums: per limb, an exact-f32 in-block
   inclusive cumsum (< 2^24) plus int32 hi/lo cumsums of block totals;
   a segment's limb sum is the prefix difference between its end and
   start-1, returned as an (hi, lo+inblock) int32 pair the host combines
   exactly into int64;
4. an f32 score (the primary ORDER BY item, recombined from the exact
   pair sums) feeds topnsel.candidates (exact selection by score: block
   maxima and two small top-ks, or approx_max_k with recall_target=1.0
   where the buffer is too large for blocks to pay) and a 4x candidate
   buffer; the host re-ranks candidates exactly, and the decode verifies
   the score boundary (k-th strictly beats the buffer's worst — f32
   rounding is monotone, so a strict f32 gap proves no non-candidate can
   reach the top-k) falling back to the host interpreter on ambiguity.

Outputs are k-capped regardless of group count: a million-group TopN
query still fetches a few KB in the single device_get.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import sumexact as SE

_I32_MAX = np.int32(2**31 - 1)

PREFIX_BLOCK = 4096  # in-block f32 cumsum stays < 2^24 for 12-bit limbs


def _blocked_prefix(limb: jnp.ndarray):
    """Exact global inclusive prefix of a 12-bit-limb int32 array,
    represented as (hi int32, lo_plus_inblock int32) with
    prefix = hi * 4096 + lo. hi <= n/4096, lo < 2^25."""
    n = limb.shape[0]
    nblk = -(-n // PREFIX_BLOCK)
    pad = nblk * PREFIX_BLOCK - n
    lb = jnp.pad(limb, (0, pad)).reshape(nblk, PREFIX_BLOCK)
    inblk = jnp.cumsum(lb.astype(jnp.float32), axis=1)  # exact (< 2^24)
    totals = inblk[:, -1].astype(jnp.int32)
    # exclusive block prefixes, split at 2^12 to stay int32-exact
    ex_hi = jnp.cumsum(totals >> SE.LIMB_BITS) - (totals >> SE.LIMB_BITS)
    ex_lo = jnp.cumsum(totals & ((1 << SE.LIMB_BITS) - 1)) - (
        totals & ((1 << SE.LIMB_BITS) - 1))
    hi = jnp.repeat(ex_hi, PREFIX_BLOCK)[:n]
    lo = jnp.repeat(ex_lo, PREFIX_BLOCK)[:n] + \
        inblk.reshape(-1)[:n].astype(jnp.int32)
    return hi, lo


def seg_sum_pairs(limb_sorted: jnp.ndarray, ends: jnp.ndarray):
    """For every row i of the sorted order, the exact limb sum over rows
    i..ends[i] as an int32 pair (hi_diff, lo_diff); value = hi*4096 + lo.
    The prefix before row i is the prefix at i - 1: a shift, where the
    prefix at the segment's end is a gather (a v5e gathers 115 M elements
    a second and shifts at memory speed: my chip run, PR 35)."""
    hi, lo = _blocked_prefix(limb_sorted)

    def before(p):
        return jnp.concatenate([jnp.zeros(1, p.dtype), p[:-1]])

    return hi[ends] - before(hi), lo[ends] - before(lo)


def sort_by_keys(keys: list[jnp.ndarray]):
    """Lexicographic sort; returns (sorted key arrays, permutation)."""
    iota = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    out = jax.lax.sort(tuple(keys) + (iota,), num_keys=len(keys))
    return list(out[:-1]), out[-1]


def _suffix_min(s: jnp.ndarray) -> jnp.ndarray:
    """Inclusive suffix minimum via log-doubling shifts.

    XLA's associative_scan / cummin lowerings compile pathologically at
    multi-million element sizes on TPU (minutes); ~21 shifted elementwise
    minimums compile in ~1s and run in microseconds."""
    d = 1
    n = s.shape[0]
    while d < n:
        shifted = jnp.concatenate(
            [s[d:], jnp.full(d, _I32_MAX, jnp.int32)])
        s = jnp.minimum(s, shifted)
        d *= 2
    return s


def candidate_blocks_sound(picked: np.ndarray, score: np.ndarray,
                           k: int, blocks: int) -> bool:
    """Soundness check for fetched candidate buffers, per exchange
    partition.

    Candidate blocks are per-device (the mesh group-partition exchange
    gives every device a disjoint slice of the group space; a single
    device is one block). A block whose buffer is NOT exhausted proves
    every group of its partition is a candidate. An exhausted block is
    sound only if the k-th best score strictly beats the buffer's worst
    — f32 scores order-embed the exact primary values, so a strict gap
    proves no non-candidate can reach the top-k; a tie at the boundary
    is ambiguous and the caller must fall back to the exact host path."""
    blocks = max(1, int(blocks))
    kb = len(picked) // blocks
    for b in range(blocks):
        pb = picked[b * kb:(b + 1) * kb]
        if not pb.all():
            continue
        sb = score[b * kb:(b + 1) * kb]
        if k >= kb or not (sb[k - 1] > sb[-1]):
            return False
    return True


def segment_bounds(sorted_keys: list[jnp.ndarray], valid_row: jnp.ndarray):
    """(is_start, end_idx) for the sorted order. valid_row marks rows that
    belong to some group (dropped rows sorted to the end are False)."""
    n = sorted_keys[0].shape[0]
    changed = jnp.zeros(n, bool).at[0].set(True)
    for k in sorted_keys:
        changed = changed | jnp.concatenate(
            [jnp.ones(1, bool), k[1:] != k[:-1]])
    is_start = changed & valid_row
    iota = jnp.arange(n, dtype=jnp.int32)
    # end of segment starting at i = (next start after i) - 1, where a
    # dropped row also terminates the last real segment
    boundary = is_start | ~valid_row
    s_idx = jnp.where(boundary, iota, n)
    shifted = jnp.concatenate([s_idx[1:], jnp.full(1, n, jnp.int32)])
    nxt = _suffix_min(shifted)
    end_idx = jnp.minimum(nxt - 1, n - 1)
    return is_start, end_idx

