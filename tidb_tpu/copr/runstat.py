"""Run statistics: per-run totals over a run-ordered column, broadcast back
to every row of the run, inside the fragment's program.

A fact table stored clustered by a key (TPC-H LINEITEM by l_orderkey) holds
each key value as ONE contiguous run of rows. A subquery correlated on that
key over the same table — Q18's IN (GROUP BY l_orderkey HAVING ...), Q21's
EXISTS / NOT EXISTS on l_orderkey with a residual on l_suppkey — asks, for
every probe row, a question about the row's own run: a sum, a count, the
least and greatest value of a column. `run_totals` answers it for every row
at once with segmented doubling scans (Hillis-Steele): step s combines each
row with the row 2^s away in each direction where their keys are equal.
Rows between two rows of equal key belong to the same run (contiguity), so
after ceil(log2 L) steps, L the longest run of the epoch, the forward scan
holds the run's total from its first row to the row and the backward scan
from the row to its last; together, the whole run. No sort, no gather, no
scatter: shifted elementwise work that XLA fuses, exact in int32 for the
bounds the caller proves.

A tiled read (copr/fragment.py _run_frag_tiled) gives each tile a halo: the
previous tile's last H rows and the next tile's first H rows, H >= L - 1,
so that a run split by a tile edge is totalled across it (`extend`).
"""

from __future__ import annotations

import jax.numpy as jnp

I32_MAX = 2**31 - 1
I32_MIN = -(2**31)
IDENTITY = {"sum": 0, "min": I32_MAX, "max": I32_MIN}
KINDS = ("exists", "not_exists", "in_having")   # a gate's kinds
# the aggregating bodies a gated read can take (its mode: `<body>+runstat`)
BODIES = ("agg", "group", "hc", "fat")


def steps_for(longest: int) -> int:
    """Doubling steps that cover a run of `longest` rows."""
    return max(0, (longest - 1).bit_length())


def _shift(x, d: int, fill):
    """y[i] = x[i - d] (d > 0) or x[i + |d|] (d < 0); `fill` where that
    row is outside the array."""
    n = x.shape[0]
    pad = jnp.full((min(abs(d), n),), fill, x.dtype)
    if d > 0:
        return jnp.concatenate([pad, x[:n - d]])[:n]
    return jnp.concatenate([x[-d:], pad])[:n]


def _combine(op: str, a, b):
    if op == "sum":
        return a + b
    return jnp.minimum(a, b) if op == "min" else jnp.maximum(a, b)


def run_totals(key, arrays, steps: int):
    """key: int32[n] run key in storage order (runs contiguous, at most
    2^steps rows). arrays: [(int32[n], op)] with op in sum / min / max and
    every row that must not count already at the op's identity.
    -> [int32[n]]: each row's whole-run total of each array."""
    n = key.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    fwd = [v for v, _ in arrays]
    bwd = list(fwd)
    for s in range(steps):
        d = 1 << s
        if d >= n:
            break
        same_f = (key == _shift(key, d, 0)) & (idx >= d)
        same_b = (key == _shift(key, -d, 0)) & (idx < n - d)
        for i, (_, op) in enumerate(arrays):
            ident = IDENTITY[op]
            fwd[i] = _combine(op, fwd[i], jnp.where(
                same_f, _shift(fwd[i], d, ident), ident))
            bwd[i] = _combine(op, bwd[i], jnp.where(
                same_b, _shift(bwd[i], -d, ident), ident))
    return [f + b - v if op == "sum" else _combine(op, f, b)
            for (v, op), f, b in zip(arrays, fwd, bwd)]


def extend(cur, prev, nxt, tile: int, halo: int):
    """A tile's array with its halo: the previous tile's last `halo` real
    rows, the tile's `tile` real-row slots, the next tile's first `halo`
    rows, then the tile's bucket padding (so that no padding sits between
    a tile's last row and the next tile's first)."""
    if halo == 0:
        return cur
    return jnp.concatenate([prev[tile - halo:tile], cur[:tile],
                            nxt[:halo], cur[tile:]])


def unextend(x, tile: int, halo: int):
    """The tile's own rows of an `extend`ed array, in its bucket order."""
    if halo == 0:
        return x
    return jnp.concatenate([x[halo:halo + tile], x[2 * halo + tile:]])


def compare(op: str, v, thr: int):
    """v <op> thr for int32 v, |v| < 2^31 - 1, and any integer thr: a
    threshold outside int32 clamps to a value with the same answer."""
    t = jnp.int32(min(max(thr, I32_MIN), I32_MAX))
    return {"gt": v > t, "ge": v >= t, "lt": v < t, "le": v <= t}[op]
