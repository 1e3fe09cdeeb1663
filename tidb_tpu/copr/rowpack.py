"""Pack the rows a mask passes to the front of a buffer, tile by tile.

`pack(mask, arrays, cap)` returns each array's values at the passing rows
in storage order, and the row number of each, in buffers `cap` long: what
`jnp.flatnonzero(mask)[:cap]` and a gather of every array there give,
without a sort or a gather over the whole length.

Two steps, neither of which moves an element more than a tile:

1. `titpu_pack_rows`, one Pallas kernel a tile of `L` rows (a [L / 128,
   128] block in row-major order): every passing row moves towards the
   front of its tile by the number of failing rows before it, one bit of
   that count at a time, lowest first. No two rows ever meet (the shift of
   a later row exceeds an earlier one's by at most the rows between them),
   so after log2(L) steps each tile's passing rows lead it, in order, and
   every array rides the same moves. The count before a row comes from two
   small matrix products (a row's prefix inside its 128 lanes, then the
   totals of the rows above it): exact, since every operand is 0, 1 or a
   count of at most 128 and the sums are f32 under 2**24.
2. The tiles in order, each written whole at the number of rows that
   passed in the tiles before it (`dynamic_update_slice` in a loop): the
   next tile overwrites the failing tail of the one before, and an offset
   past `cap` lands in the buffer's slack.

Off the TPU the same kernel runs in Pallas's interpreter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

KERNEL_NAME = "titpu_pack_rows"
# rows a tile: of 4 096-65 536, 65 536 packs fastest on a v5e (for 67 M
# rows and three riders the kernel takes 9.4 ms and the placement of its
# 1 024 tiles 7.1 ms; PERF.md, section 6, has the whole probe)
TILE = 1 << 16
_NONE = np.iinfo(np.int32).max


def _kernel(m_ref, *refs, L):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BR = L // 128
    K = (len(refs) - 1) // 2       # arrays in; the row numbers + them out
    ins, src_ref, outs = refs[:K], refs[K], refs[K + 1:]
    mm = m_ref[...]
    # failing rows before each row of the tile, from two matrix products
    lane_r = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
    lane_c = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
    incl = jnp.dot(mm.astype(jnp.bfloat16),
                   (lane_r <= lane_c).astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    row_r = jax.lax.broadcasted_iota(jnp.int32, (BR, BR), 0)
    row_c = jax.lax.broadcasted_iota(jnp.int32, (BR, BR), 1)
    above = jnp.dot((row_c < row_r).astype(jnp.bfloat16),
                    jnp.broadcast_to(incl[:, 127:128], (BR, 128)).astype(
                        jnp.bfloat16),
                    preferred_element_type=jnp.float32)
    r = jax.lax.broadcasted_iota(jnp.int32, (BR, 128), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (BR, 128), 1)
    flat = r * 128 + c
    # the shift still to make; -1 where the slot holds no passing row
    z = jnp.where(mm > 0, flat - ((incl + above).astype(jnp.int32) - mm), -1)
    vals = [ref[...] for ref in ins]
    for k in range(L.bit_length() - 1):
        s = 1 << k
        if s < 128:
            def ahead(x, s=s):      # x at flat + s
                a = pltpu.roll(x, 128 - s, 1)
                return jnp.where(c < 128 - s, a, pltpu.roll(a, BR - 1, 0))
            past_end = (r == BR - 1) & (c >= 128 - s)
        else:
            def ahead(x, q=s // 128):
                return pltpu.roll(x, BR - q, 0)
            past_end = r >= BR - s // 128
        zi = jnp.where(past_end, -1, ahead(z))
        arrives = (zi >= 0) & (((zi >> k) & 1) == 1)
        leaves = (z >= 0) & (((z >> k) & 1) == 1)
        z = jnp.where(arrives, zi, jnp.where(leaves, -1, z))
        vals = [jnp.where(arrives, ahead(v), v) for v in vals]
    src_ref[...] = jnp.where(z >= 0, pl.program_id(0) * L + flat + z, _NONE)
    for o_ref, v in zip(outs, vals):
        o_ref[...] = v


def _pack_tiles(mask, arrays, L):
    """[n] mask and 32-bit arrays (n a multiple of L) -> (row numbers and
    arrays as [n / 128, 128], each tile's passing rows at its front; the
    count of passing rows a tile)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = mask.shape[0]
    R, T, BR = n // 128, n // L, L // 128
    spec = pl.BlockSpec((BR, 128), lambda i: (i, 0))
    m = mask.astype(jnp.int32)
    out = pl.pallas_call(
        lambda *refs: _kernel(*refs, L=L),
        name=KERNEL_NAME,
        grid=(T,),
        in_specs=[spec] * (1 + len(arrays)),
        out_specs=[spec] * (1 + len(arrays)),
        out_shape=[jax.ShapeDtypeStruct((R, 128), jnp.int32)] + [
            jax.ShapeDtypeStruct((R, 128), a.dtype) for a in arrays],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=jax.default_backend() != "tpu",
    )(m.reshape(R, 128), *[a.reshape(R, 128) for a in arrays])
    return out, jnp.sum(m.reshape(T, L), axis=1)


def _place(tiles, counts, cap, L):
    """Each tile's first counts[t] rows at the rows passed before it."""
    BR = L // 128
    at = jnp.minimum(jnp.cumsum(counts) - counts, cap)

    def put(t, bufs):
        return tuple(
            jax.lax.dynamic_update_slice(
                b, jax.lax.dynamic_slice(a, (t * BR, 0), (BR, 128)).reshape(L),
                (at[t],))
            for b, a in zip(bufs, tiles))
    bufs = tuple(jnp.zeros(cap + L, a.dtype) for a in tiles)
    return [b[:cap] for b in jax.lax.fori_loop(0, counts.shape[0], put, bufs)]


def pack(mask, arrays, cap: int):
    """-> (row numbers int32[cap], [each array's values there], rows that
    passed). Slots from min(passed, cap) on hold no row: their row
    numbers are clamped into [0, n) and their values are any."""
    n = mask.shape[0]
    L = max(128, min(TILE, 1 << (n - 1).bit_length()))
    pad = -n % L
    wide = [a.astype(jnp.int32) if a.dtype.itemsize != 4 else a
            for a in arrays]
    if pad:
        mask = jnp.pad(mask, (0, pad))
        wide = [jnp.pad(a, (0, pad)) for a in wide]
    tiles, counts = _pack_tiles(mask, wide, L)
    placed = _place(tiles, counts, cap, L)
    src = jnp.minimum(placed[0], n - 1)
    vals = [p.astype(a.dtype) for p, a in zip(placed[1:], arrays)]
    return src, vals, jnp.sum(counts)
