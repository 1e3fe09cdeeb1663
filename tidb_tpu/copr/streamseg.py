"""Run-ordered segmented sums as a Pallas TPU kernel.

The high-cardinality aggregation path needs per-GROUP sums when the group
key has millions of distinct values (GROUP BY l_orderkey). When storage
order already groups the key (fact tables are clustered by PK — the
StreamAgg eligibility, reference: planner/core/exhaust_physical_plans.go
getStreamAggs, executor/aggregate.go StreamAgg), every group is one
contiguous run and the whole aggregation is a *rank-space* reduction:

    rank(row)   = number of key changes up to the row   (host-precomputed)
    out[k, r]   = sum of vals[k, row] over rows with rank(row) == r

XLA offers no fast lowering for this on TPU: sorts are unnecessary,
scatter-adds serialize, and per-row prefix+gather schemes cost random
gathers per value array. This kernel streams the rows once:

  * 1-D sequential grid; each step consumes `nb` inner blocks of `blk`
    rows (ROWS_PER_STEP rows a step), UNROLL blocks a loop trip;
  * everything that depends on the key column only is computed once per
    epoch by `rank_meta` and staged: the in-block local rank of every row
    (`lr`, int32[n0]) and the rank of every block's first row (`cb`, one
    scalar a block, read from SMEM). The kernel runs no scan and carries
    no count from block to block;
  * per inner block: the K value rows are split into two bf16-exact
    pieces each (below), a TRANSPOSED one-hot ohT[ohw, blk] is built
    (iota along sublanes == lr broadcast along sublanes: no
    lane-to-sublane relayout) and ONE bf16 MXU pass contracts the last
    axis of both, pieces[2K, blk] . ohT^T -> f32[2K, ohw] (the q.kT
    form). The one-hot is only as wide as the widest block of the epoch
    needs (`ohw` = maxd rounded up to 128): the product is rotated by
    (rank offset % 128) lanes into the 128-aligned slice of the VMEM
    window it is added to, so no lanes are spent on absorbing the offset;
  * the sliding VMEM window flushes fixed-size 128-aligned chunks to the
    HBM output (pieces recombined, async copy, static roll) whenever
    enough ranks are final; ranks are written exactly once.

Exactness. Callers pass integer-valued f32 arrays: 0/1 masks and 12-bit
limbs (the signed top limb in [-2048, 2048)). The kernel splits every
value v into lo = v & 63 in [0, 64) and hi = v >> 6 (arithmetic) in
[-32, 64): both below 2^8 in magnitude, which bfloat16 (8 significant
bits) holds exactly, as it holds the one-hot's 0 and 1. Every product is
such an integer times 0 or 1, the MXU accumulates in f32, and a per-rank
total of either piece stays below MAX_ROWS_PER_KEY * 2^6 = 2^18 < 2^24, so
no sum rounds. At a flush the pieces are recombined as hi * 64 + lo: a
power-of-two scaling plus an addition whose result is the original
per-rank total, an integer below 2^24 by the MAX_ROWS_PER_KEY gate
(4096 * 4095 < 2^24), hence exact in f32 as the f32 HIGHEST product was.

Geometry. Work per row is proportional to the one-hot width, a block has
a fixed cost, and the widest block of the epoch decides the width, so the
rows per inner block are chosen per epoch (`_choose_block`) from the key
column's own change flags by the cost ohw + BLOCK_COST / blk a row. On
the v5e (tidb_tpu/bench/rank_sums_probe.py --blk all; PERF.md section 6,
PR 25) 1-7 rows a key take 256 rows x 128 lanes, 1-2 rows a key and
unique keys 128 x 128, runs of 16-48 rows 1024 x 128: the measured
optimum in each case. The kernel's static parameters derive from
(blk, ohw) alone, not from the raw maxd, so epochs of the same shape
share one compiled program (`program_key`).

What the chip showed (same probe, K = 4, 60 M rows of 1-7 a key, ms a
call of `rank_sums_pallas`): the f32 HIGHEST kernel this replaces 141.3;
bf16 pieces alone 100.2; transposed one-hot 79.3; staged ranks (no scan)
41.7-49.1; rotation instead of 128 lanes of slack 38.8; 4-16 blocks a
loop trip 29.9-21.9 (a lone block is latency-bound: 65.0 at one block a
trip); split and recombination inside the kernel, K rows streamed
instead of 8: 14.3, of which the custom call is 9.9 (0.165 ns a row).

On non-TPU backends `rank_sums` lowers to jax.ops.segment_sum — the
semantic spec of the kernel — so the test suite exercises the same path
shape on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCKS = (128, 256, 512, 1024)   # candidate rows per inner block
ROWS_PER_STEP = 16384            # rows per grid step (nb = this // blk)
# Fixed cost of one inner block in units of one one-hot element (a row x a
# lane): the cost of a geometry per row is ohw + BLOCK_COST / blk. Read off
# the v5e with rank_sums_probe --blk all (PERF.md section 6, PR 25): 15 ns
# a block against 0.00065 ns a row and lane, over five key shapes.
BLOCK_COST = 24000
PIECE_BITS = 6   # lo = v & 63, hi = v >> 6: both exact in bfloat16
MAX_ROWS_PER_KEY = 4096   # f32 exactness: rows_per_key * (2^12-1) < 2^24
MAX_ARRAYS = 8   # K cap: 2 * 8 pieces = one bf16 sublane tile (16 rows)
KERNEL_NAME = "titpu_rank_sums"   # the Mosaic custom call's name in HLO
OPERAND_DTYPE = jnp.bfloat16
UNROLL = 16      # inner blocks per loop trip


def _r128(x: int) -> int:
    return (-(-x // 128)) * 128


def _choose_block(f: np.ndarray, blk: int | None = None):
    """(blk, maxd) of the cheapest candidate block size for the change
    flags f (or of the one asked for). maxd is the widest span of ranks
    inside one block: its key changes after its first row, plus one."""
    fb = np.zeros(-(-len(f) // BLOCKS[-1]) * BLOCKS[-1], dtype=f.dtype)
    fb[:len(f)] = f
    s128 = fb.reshape(-1, 128).sum(axis=1, dtype=np.int32)
    best = None
    for b in BLOCKS if blk is None else (blk,):
        d = s128.reshape(-1, b // 128).sum(axis=1) - fb[::b]
        maxd = int(d.max()) + 1
        cost = _r128(maxd) + BLOCK_COST / b
        if best is None or cost <= best[0]:   # a tie goes to fewer blocks
            best = (cost, b, maxd)
    return best[1:]


def rank_meta(key_cols: list[np.ndarray], blk: int | None = None):
    """Host-side per-epoch metadata from the raw (lexicographically
    run-ordered) key column(s). Pad rows added by staging land on local
    rank 0 of their block; their values are query-masked to zero.

    `blk` overrides the choice of rows per inner block (the probe sweeps
    it to read BLOCK_COST off the chip; product code never passes it).

    Returns None when a gate fails (too many rows in one key)."""
    n0 = len(key_cols[0])
    if n0 == 0:
        return None
    chg = np.zeros(n0, dtype=bool)
    for k in key_cols:
        chg[1:] |= k[1:] != k[:-1]
    r0 = np.flatnonzero(np.concatenate([[True], chg[1:n0]])).astype(
        np.int32)
    nd = len(r0)
    seg_rows = np.diff(np.concatenate([r0, [n0]]))
    if len(seg_rows) and seg_rows.max() > MAX_ROWS_PER_KEY:
        return None
    f = chg.view(np.int8)
    blk, maxd = _choose_block(f, blk)
    # cb[b]: rank of block b's first row; lr: rank of a row minus its
    # block's cb, in [0, maxd)
    lr = f.astype(np.int32)
    np.cumsum(lr, out=lr)
    cb = lr[::blk].copy()
    lr -= np.repeat(cb, blk)[:n0]
    nb = ROWS_PER_STEP // blk
    ohw = _r128(maxd)                     # one-hot width
    F = nb * ohw + 128                    # fixed flush chunk
    # window: under F unflushed ranks at step start + one step's growth
    # (<= nb*maxd < F) + the rotated one-hot extent of the last block
    wstep = 2 * F + ohw + 256
    nd_pad = max(_r128(nd), 128)
    out_pad = nd_pad + wstep + F          # final flush slack
    r0_pad = np.zeros(nd_pad, dtype=np.int32)
    r0_pad[:nd] = r0
    return {
        "n0": n0, "nd": nd,
        "nd_pad": nd_pad, "out_pad": out_pad, "maxd": maxd,
        "blk": blk, "nb": nb, "ohw": ohw,
        "flush": F, "wstep": wstep, "lr": lr, "cb": cb, "r0": r0_pad,
        "identity": nd == n0,
    }


def program_key(meta) -> tuple:
    """What of an epoch's metadata a compiled rank-path program depends
    on: the derived geometry, not the raw maxd."""
    return ("rankseg", meta["nd"], meta["n0"], meta["blk"], meta["ohw"],
            meta["identity"])


def _kernel(cb_ref, vals_ref, lr_ref, out_hbm, acc, comb, sem, st, *, BLK,
            NB, OHW, F, WS, steps):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    ci = i % 8       # this step's row of the SMEM block of block scalars
    AW = OHW + 128   # accumulate extent: the one-hot rotated by < 128
    KR = vals_ref.shape[0]   # value arrays; acc holds lo rows, then hi

    @pl.when(i == 0)
    def _init():
        acc[:, :] = jnp.zeros_like(acc)
        st[0] = 0   # completed flushes (window base = st[0] * F)

    def emit(width):
        """hi * 64 + lo of the window's first `width` ranks -> HBM."""
        comb[:, 0:width] = (acc[KR:, 0:width] * float(1 << PIECE_BITS)
                            + acc[:KR, 0:width])
        cp = pltpu.make_async_copy(
            comb.at[:, 0:width], out_hbm.at[:, pl.ds(st[0] * F, width)], sem)
        cp.start()
        cp.wait()

    # flush a fixed 128-aligned chunk once the window holds F final ranks
    # (ranks below that of this step's first row are final; that one may
    # still grow — never flush past it)
    @pl.when((i > 0) & (cb_ref[ci, 0] - st[0] * F >= F))
    def _flush():
        emit(F)
        rolled = pltpu.roll(acc[:, :], WS - F, axis=1)
        ll = jax.lax.broadcasted_iota(jnp.int32, (1, WS), 1)
        acc[:, :] = jnp.where(ll < WS - F, rolled, 0.0)
        st[0] = st[0] + 1

    base = st[0] * F
    rk = jax.lax.broadcasted_iota(jnp.int32, (OHW, BLK), 0)
    zpad = jnp.zeros((2 * KR, 128), jnp.float32)

    def inner(j):
        off = pl.multiple_of(j * BLK, BLK)
        # two bf16-exact pieces per array (module docstring, Exactness)
        vi = vals_ref[:, pl.ds(off, BLK)].astype(jnp.int32)
        v = jnp.concatenate([
            (vi & ((1 << PIECE_BITS) - 1)).astype(jnp.float32),
            (vi >> PIECE_BITS).astype(jnp.float32),
        ]).astype(OPERAND_DTYPE)                    # [2 KR, BLK]
        lr = lr_ref[:, pl.ds(off, BLK)]
        oht = (rk == lr).astype(OPERAND_DTYPE)      # [OHW, BLK]
        S = jax.lax.dot_general(
            v, oht, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # [2 KR, OHW]
        o = cb_ref[ci, j] - base             # window-relative rank offset
        o128 = pl.multiple_of(o // 128 * 128, 128)
        Sw = pltpu.roll(jnp.concatenate([S, zpad], axis=1), o - o128,
                        axis=1)
        acc[:, pl.ds(o128, AW)] = acc[:, pl.ds(o128, AW)] + Sw

    U = min(UNROLL, NB)

    def group(g, carry):
        # U blocks a trip: a block's chain (scalar read, one-hot, MXU,
        # rotation, window update) is latency-bound alone
        for u in range(U):
            inner(g * U + u)
        return carry

    jax.lax.fori_loop(0, NB // U, group, 0)

    @pl.when(i == steps - 1)
    def _final():
        emit(WS)


def rank_aux(meta) -> dict:
    """The epoch arrays the kernel reads besides the values, as device
    arrays (callers cache them per epoch)."""
    lr = meta["lr"]
    lr = np.pad(lr, (0, -len(lr) % ROWS_PER_STEP))   # a whole last step
    return {"lr": jnp.asarray(lr), "cb": jnp.asarray(meta["cb"])}


def _ranks(aux, meta, n: int):
    """int32[n] global rank per row from the staged (lr, cb); rows past
    n0 get rank 0 (their values are zero)."""
    n0 = meta["n0"]
    rank = aux["lr"][:n0] + jnp.repeat(aux["cb"], meta["blk"])[:n0]
    if n > n0:
        rank = jnp.pad(rank, (0, n - n0))
    return rank[:n]


def rank_sums_pallas(vals, aux, meta):
    """The kernel proper: vals f32[K, n] (integers, |v| < 2^12), aux the
    staged `rank_aux` -> f32[K, nd_pad] per-rank sums; entries at ranks
    >= nd are unwritten HBM. Lowers through Mosaic on a TPU; under
    pltpu.force_tpu_interpret_mode() the same body runs on any
    backend."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K, n = vals.shape
    blk, nb = meta["blk"], meta["nb"]
    steps = -(-n // (nb * blk))
    npad2 = steps * nb * blk
    KR = 4 if K <= 4 else MAX_ARRAYS   # rows streamed: half a tile or one
    vals = jnp.pad(vals, ((0, KR - K), (0, npad2 - n)))
    lr = aux["lr"]
    lr = jnp.pad(lr, (0, npad2 - lr.shape[0])).reshape(1, -1)
    # a step's nb block scalars are one row of an (8, 128) SMEM block
    cb = aux["cb"]
    cb = jnp.pad(cb, (0, steps * nb - cb.shape[0]), mode="edge")
    cb = jnp.pad(cb.reshape(steps, nb), ((0, -steps % 8), (0, 128 - nb)))
    kern = functools.partial(
        _kernel, BLK=blk, NB=nb, OHW=meta["ohw"], F=meta["flush"],
        WS=meta["wstep"], steps=steps)
    out = pl.pallas_call(
        kern,
        name=KERNEL_NAME,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((8, 128), lambda i: (i // 8, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((KR, nb * blk), lambda i: (0, i)),
            pl.BlockSpec((1, nb * blk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2 * KR, meta["wstep"]), jnp.float32),
            pltpu.VMEM((KR, meta["wstep"]), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SMEM((1,), jnp.int32),
        ],
        out_shape=jax.ShapeDtypeStruct((KR, meta["out_pad"]), jnp.float32),
    )(cb, vals, lr)
    return out[:K, :meta["nd_pad"]]


def rank_sums(vals, aux, meta):
    """vals: f32[K, n_pad] query-masked integer-valued arrays, aux the
    staged `rank_aux`.
    -> f32[K, nd_pad] per-rank sums (exact integers; entries at ranks
    >= nd are zeroed).

    TPU: the Pallas kernel above; otherwise jax.ops.segment_sum."""
    nd, nd_pad = meta["nd"], meta["nd_pad"]
    if meta["identity"]:
        flat = vals[:, :nd_pad]
        if flat.shape[1] < nd_pad:
            flat = jnp.pad(flat, ((0, 0), (0, nd_pad - flat.shape[1])))
    elif jax.default_backend() != "tpu":
        rank = _ranks(aux, meta, vals.shape[1])
        flat = jax.vmap(
            lambda v: jax.ops.segment_sum(v, rank, num_segments=nd_pad)
        )(vals)
    else:
        flat = rank_sums_pallas(vals, aux, meta)
    # ranks beyond nd carry garbage (unwritten HBM) on the kernel path
    live = jnp.arange(nd_pad, dtype=jnp.int32) < nd
    return jnp.where(live[None, :], flat, 0.0)
