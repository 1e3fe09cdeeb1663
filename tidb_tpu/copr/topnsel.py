"""The k best of a long score array: an exact block-select top-k.

Two callers: the TopN's k winners of one tile (`select`: copr/client.py,
the fragment's TopN mode) and the candidate buffer of the
high-cardinality GROUP BY bodies (`candidates`: copr/fragment.py
`_hc_rank_body` / `_hc_body`, HCTopN.cap or HAVING_CAP entries of the
per-group scores).

`jax.lax.top_k(score, k)` over a whole tile is a sort of the tile on the
TPU (83.9 ms a statement for ten rows of 60 M, against 1.4 ms this way:
PERF.md section 6, PR 27), and so is `jax.lax.approx_max_k` at
`recall_target=1.0` (30.2 ms for 74 of 15 M group scores: PERF.md
section 6, PR 33). The block path returns the same row numbers in the
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
HC_PATHS = ("block", "approx")  # ... of tidb_copr_hc_select_total


def block_len(b: int, k: int, ragged: bool = False) -> Optional[int]:
    """Rows a block for `b` scores and `k` winners, or None where the
    caller's whole-array selection stays: a function of the static shapes
    only.

    L is the power of two nearest sqrt(b / k) on a log scale (it balances
    the G = b / L maxima against the k * L candidates), at least
    MIN_BLOCK. The block path needs L to divide b unless the caller pads
    a `ragged` last block, and is pointless unless the two small top-ks
    together see at most a quarter of the array: that leaves out k close
    to G (65 536 candidates of a few million groups) and every array of a
    few blocks (k = 10: under 8 192 rows; small tables, the MVCC overlay
    batch)."""
    if k < 1:
        return None
    L = max(MIN_BLOCK, 1 << (b // k).bit_length() // 2)
    if (b % L and not ragged) or 4 * (-(-b // L) + k * L) > b:
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
    L = block_len(score.shape[0], k)
    if taken is not None:
        taken[:] = ["full" if L is None else "block"]
    if L is None:
        return jax.lax.top_k(score, k)[1]
    return _block_select(score, k, L)


def candidates(score, k: int, taken: Optional[list] = None):
    """`select` for the hc bodies' candidate buffer: the indices of the
    `k` (at most all) largest of the f32 `score`, exactly by score. Any
    length takes the block path (ties to the lower index, as `select`):
    a last block that the scores do not fill is padded with -inf, what a
    rank that is no group scores. It ties with or loses to every score
    (the engine makes no NaN) and has a higher index than all
    `k <= len(score)` of them, so a padded index is never returned. Where
    no block length pays, the whole-array selection is
    `jax.lax.approx_max_k` at `recall_target=1.0` (exact by score, ties
    as the implementation leaves them), never `jax.lax.top_k`: at a buffer
    of 65 536 of a few million scores both sort them all, and top_k takes
    twice as long to compile (~20 s against ~10 s). `taken` receives the
    one of HC_PATHS this trace takes."""
    n = score.shape[0]
    k = min(k, n)
    L = block_len(n, k, ragged=True)
    if taken is not None:
        taken[:] = ["approx" if L is None else "block"]
    if L is None:
        return jax.lax.approx_max_k(score, k, recall_target=1.0)[1]
    if n % L:
        score = jnp.pad(score, (0, -n % L), constant_values=-jnp.inf)
    return _block_select(score, k, L)


def _block_select(score, k: int, L: int):
    """Steps 1-3 of the module docstring over a `score` whose length L
    divides. Floats are ranked through their total-order image where they
    are read (the max-reduce pass, the gathered candidates): no array as
    long as the scores is written."""
    b = score.shape[0]
    # [b / 128, 128] is the 1-D tile's own memory order on the TPU (a
    # bitcast); a [G, L] view with L > 128 is a relayout copy of the tile
    per = L // MIN_BLOCK
    rows = score.reshape(b // MIN_BLOCK, MIN_BLOCK)
    maxima = jnp.max(_total_order(rows), axis=1).reshape(
        b // L, per).max(axis=1)
    _, best = jax.lax.top_k(maxima, k)
    best = jnp.sort(best)
    pick = (best[:, None] * per + jnp.arange(per, dtype=best.dtype)).ravel()
    _, cand = jax.lax.top_k(_total_order(rows[pick]).ravel(), k)
    return pick[cand // MIN_BLOCK] * MIN_BLOCK + cand % MIN_BLOCK
