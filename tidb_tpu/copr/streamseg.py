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
scatter-adds serialize, and per-row prefix+gather schemes cost 4 random
gathers per value array (~50M elem/s). This kernel streams the rows once:

  * 1-D sequential grid; each step consumes B inner blocks of BLK rows
    (the fori_loop amortizes the ~15us grid-step overhead);
  * per inner block: local ranks = running count + in-block cumsum of the
    host-precomputed change flags (log-doubling rolls — Mosaic has no
    cumsum primitive);
  * per-rank sums via ONE one-hot f32 matmul on the MXU
    ([K, BLK] x [BLK, OHW]) — exact, because every addend is an integer
    limb < 2^12 and every per-rank total is < 2^24 (gated on max rows per
    key). The one-hot target absorbs the sub-128 part of the rank offset,
    so the accumulate into the VMEM window is 128-lane-aligned;
  * the sliding VMEM window flushes fixed-size 128-aligned chunks to the
    HBM output (async copy + static roll) whenever enough ranks are
    final; ranks are written exactly once.

Host metadata (change flags, block stats) is computed once per epoch from
the key column(s) and cached; per query the kernel reads only the masked
value arrays.

On non-TPU backends `rank_sums` lowers to jax.ops.segment_sum — the
semantic spec of the kernel — so the test suite exercises the same path
shape on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLK = 1024     # rows per inner block (one-hot sublane extent)
B = 16         # inner blocks per grid step
MAX_ROWS_PER_KEY = 4096   # f32 exactness: rows_per_key * (2^12-1) < 2^24
MAX_ARRAYS = 8  # K cap
KERNEL_NAME = "titpu_rank_sums"   # the Mosaic custom call's name in HLO


def _r128(x: int) -> int:
    return (-(-x // 128)) * 128


def rank_meta(key_cols: list[np.ndarray]):
    """Host-side per-epoch metadata from the raw (lexicographically
    run-ordered) key column(s). Pad rows added by staging keep the last
    rank; their values are query-masked to zero.

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
    f = np.zeros(n0, dtype=np.int32)
    f[1:] = chg[1:]
    # widest per-inner-block rank count (drives the one-hot width)
    nblk0 = -(-n0 // BLK)
    fb = np.zeros(nblk0 * BLK, dtype=np.int64)
    fb[:n0] = f
    maxd = int(fb.reshape(nblk0, BLK).sum(axis=1).max()) + 1
    ohw = _r128(maxd + 2) + 128           # +128: absorbs offset % 128
    F = _r128(B * maxd + 2)               # fixed flush chunk
    # window: up to F unflushed ranks at step start + one step's growth
    # (<= B*maxd <= F) + the one-hot extent of the last block
    wstep = 2 * F + ohw + 256
    nd_pad = max(_r128(nd), 128)
    out_pad = nd_pad + wstep + F          # final flush slack
    r0_pad = np.zeros(nd_pad, dtype=np.int32)
    r0_pad[:nd] = r0
    return {
        "n0": n0, "nd": nd,
        "nd_pad": nd_pad, "out_pad": out_pad, "maxd": maxd, "ohw": ohw,
        "flush": F, "wstep": wstep, "f": f, "r0": r0_pad,
        "identity": nd == n0,
    }


def _kernel(vals_ref, f_ref, out_hbm, acc, sem, st, *, K, OHW, F, WS,
            steps):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc[:, :] = jnp.zeros_like(acc)
        st[0] = 0   # rank count so far (global, inclusive of last rank)
        st[1] = 0   # completed flushes (window base = st[1] * F)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, BLK), 1)
    ohl = jax.lax.broadcasted_iota(jnp.int32, (BLK, OHW), 1)

    def inner(j, cur):
        v = vals_ref[:, pl.ds(j * BLK, BLK)]
        fl = f_ref[0, pl.ds(j * BLK, BLK)].reshape(1, BLK)
        blr = fl
        d = 1
        while d < BLK:
            blr = blr + jnp.where(lane >= d, pltpu.roll(blr, d, axis=1),
                                  0)
            d *= 2
        o = cur - st[1] * F               # window-relative rank offset
        o128 = o // 128 * 128
        w = (o - o128) + blr              # per-row one-hot target
        oh = (ohl == w.reshape(BLK, 1)).astype(jnp.float32)
        S = jax.lax.dot_general(
            v, oh, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        cur_win = acc[:, pl.ds(o128, OHW)]
        acc[:, pl.ds(o128, OHW)] = cur_win + S
        return cur + jnp.sum(fl)

    cur = jax.lax.fori_loop(0, B, inner, st[0])
    st[0] = cur

    # flush a fixed 128-aligned chunk once the window holds F final ranks
    # (the last active rank may still grow — never flush past it)
    @pl.when((cur - 1 - st[1] * F >= F) & (i < steps - 1))
    def _flush():
        cp = pltpu.make_async_copy(
            acc.at[:, 0:F], out_hbm.at[:, pl.ds(st[1] * F, F)], sem)
        cp.start()
        cp.wait()
        rolled = pltpu.roll(acc[:, :], WS - F, axis=1)
        ll = jax.lax.broadcasted_iota(jnp.int32, (1, WS), 1)
        acc[:, :] = jnp.where(ll < WS - F, rolled, 0.0)
        st[1] = st[1] + 1

    @pl.when(i == steps - 1)
    def _final():
        cp = pltpu.make_async_copy(
            acc.at[:, :], out_hbm.at[:, pl.ds(st[1] * F, WS)], sem)
        cp.start()
        cp.wait()


def rank_sums_pallas(vals, f_dev, meta):
    """The kernel proper: vals f32[K, n], f_dev int32[n0] change flags
    -> f32[K, nd_pad] per-rank sums; entries at ranks >= nd are
    unwritten HBM. Lowers through Mosaic on a TPU; under
    pltpu.force_tpu_interpret_mode() the same body runs on any
    backend."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K = vals.shape[0]
    steps = -(-vals.shape[1] // (B * BLK))
    npad2 = steps * B * BLK
    K8 = -(-K // 8) * 8   # DMA slices must be sublane(8)-aligned
    pad_rows = ((0, K8 - K), (0, max(0, npad2 - vals.shape[1])))
    if pad_rows != ((0, 0), (0, 0)):
        vals = jnp.pad(vals, pad_rows)
    kern = functools.partial(
        _kernel, K=K8, OHW=meta["ohw"], F=meta["flush"],
        WS=meta["wstep"], steps=steps)
    out = pl.pallas_call(
        kern,
        name=KERNEL_NAME,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((K8, B * BLK), lambda i: (0, i)),
            pl.BlockSpec((1, B * BLK), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((K8, meta["wstep"]), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SMEM((2,), jnp.int32),
        ],
        out_shape=jax.ShapeDtypeStruct((K8, meta["out_pad"]),
                                       jnp.float32),
    )(vals, jnp.pad(f_dev, (0, npad2 - f_dev.shape[0])
                    ).reshape(1, -1))
    return out[:K, :meta["nd_pad"]]


def rank_sums(vals, f_dev, meta):
    """vals: f32[K, n_pad] query-masked integer-valued arrays.
    -> f32[K, nd_pad] per-rank sums (exact integers; entries at ranks
    >= nd are zeroed).

    TPU: the Pallas kernel above; otherwise jax.ops.segment_sum."""
    nd, nd_pad = meta["nd"], meta["nd_pad"]
    if meta["identity"]:
        flat = vals[:, :nd_pad]
        if flat.shape[1] < nd_pad:
            flat = jnp.pad(flat, ((0, 0), (0, nd_pad - flat.shape[1])))
    elif jax.default_backend() != "tpu":
        f = f_dev
        if f.shape[0] < vals.shape[1]:
            f = jnp.pad(f, (0, vals.shape[1] - f.shape[0]))
        rank = jnp.cumsum(f[: vals.shape[1]])
        flat = jax.vmap(
            lambda v: jax.ops.segment_sum(v, rank, num_segments=nd_pad)
        )(vals)
    else:
        flat = rank_sums_pallas(vals, f_dev, meta)
    # ranks beyond nd carry garbage (unwritten HBM) on the kernel path
    live = jnp.arange(nd_pad, dtype=jnp.int32) < nd
    return jnp.where(live[None, :], flat, 0.0)
