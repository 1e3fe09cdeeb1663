"""The k winners of one TopN tile: an exact block-select top-k.

`jax.lax.top_k(score, k)` over a whole tile is a sort of the tile on the
TPU (83.9 ms a statement for ten rows of 60 M, against 1.4 ms this way:
PERF.md section 6, PR 27). `select` returns the same row numbers in the
same order from one max-reduce pass and two small top-ks:

1. view the tile as [G, L] blocks of L consecutive rows and reduce each
   block to its maximum (in two steps: the maxima of 128-row runs, a view
   that costs no copy of the tile, then of L / 128 of those);
2. `top_k` of the G maxima picks the k best blocks (ties to the lower
   block: it is index-stable); their numbers are put in ascending order;
3. `top_k` over the k*L rows of those blocks, flattened in that order,
   picks the winners; each maps back to its row in the tile.

Exact, ties included. The order is (score descending, row ascending).
Were a winner `e` in a block `B` that step 2 left out, k blocks would
rank before `B`: each has a larger maximum, or an equal one and a lower
number, so each holds a row that scores at least `e` and, where equal,
precedes it: k rows beat `e`. With the chosen blocks ascending the
flattened candidates keep row order among equal scores, so step 3's
index-stable `top_k` returns what the whole-tile one returns.

Float scores are ranked through their IEEE total-order int32 image
(-NaN < -inf < ... < -0.0 < +0.0 < ... < +inf < +NaN), the order
`jax.lax.top_k` ranks floats in, so the block maxima and both top-ks
agree on every bit pattern: the sentinels (+-inf, -finfo.max) are
ordinary values, and a NaN key (the engine stores none: MySQL has none)
would rank first exactly as it does in `jax.lax.top_k`.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

MIN_BLOCK = 128  # one lane row: a block never splits a vector register
PATHS = ("block", "full")  # the labels of tidb_copr_topn_select_total


def block_len(b: int, k: int) -> Optional[int]:
    """Rows a block for a tile of `b` rows and `k` winners, or None where
    the whole-tile `top_k` stays: a function of the static shapes only.

    L is the power of two nearest sqrt(b / k) on a log scale (it balances
    the G = b / L maxima against the k * L candidates), at least
    MIN_BLOCK. The block path needs L to divide b, and is pointless
    unless the two small top-ks together see at most a quarter of the
    tile: that leaves out k close to G and every tile of a few blocks
    (k = 10: under 8 192 rows; small tables, the MVCC overlay batch)."""
    if k < 1:
        return None
    L = max(MIN_BLOCK, 1 << (b // k).bit_length() // 2)
    if b % L or 4 * (b // L + k * L) > b:
        return None
    return L


def _total_order(score):
    """Floats as int32 in IEEE total order; integers as they are."""
    if not jnp.issubdtype(score.dtype, jnp.floating):
        return score
    bits = jax.lax.bitcast_convert_type(score.astype(jnp.float32), jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def select(score, k: int, taken: Optional[list] = None):
    """Row numbers of the k largest of `score` (1-D, static length), ties
    to the lower row: `jax.lax.top_k(score, k)[1]`, index for index.
    `taken`, a one-slot list, receives the one of PATHS this trace takes
    for the shape it sees (a shard's, under `shard_map`)."""
    b = score.shape[0]
    L = block_len(b, k)
    if taken is not None:
        taken[:] = ["full" if L is None else "block"]
    if L is None:
        return jax.lax.top_k(score, k)[1]
    # [b / 128, 128] is the 1-D tile's own memory order on the TPU (a
    # bitcast); a [G, L] view with L > 128 is a relayout copy of the tile
    per = L // MIN_BLOCK
    rows = _total_order(score).reshape(b // MIN_BLOCK, MIN_BLOCK)
    maxima = jnp.max(rows, axis=1).reshape(b // L, per).max(axis=1)
    _, best = jax.lax.top_k(maxima, k)
    best = jnp.sort(best)
    pick = (best[:, None] * per + jnp.arange(per, dtype=best.dtype)).ravel()
    _, cand = jax.lax.top_k(rows[pick].ravel(), k)
    return pick[cand // MIN_BLOCK] * MIN_BLOCK + cand % MIN_BLOCK
