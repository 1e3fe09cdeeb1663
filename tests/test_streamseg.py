"""The streamseg Pallas kernel body, interpreted on CPU.

Off the TPU `rank_sums` lowers to jax.ops.segment_sum, so without these
tests tier-1 never executes the kernel body. TPU interpret mode runs the
same `pallas_call` (grid, VMEM window, flush DMAs, SMEM state) on any
backend; the reference is the segment_sum spec on the same inputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from tidb_tpu.copr import streamseg as SS


def _keys(n_keys: int, max_rep: int, seed: int) -> np.ndarray:
    reps = np.random.default_rng(seed).integers(1, max_rep + 1, n_keys)
    return np.repeat(np.arange(n_keys, dtype=np.int64), reps)


def _spec(vals: np.ndarray, meta) -> np.ndarray:
    rank = np.cumsum(meta["f"])
    return np.stack([np.bincount(rank, weights=v[:meta["n0"]],
                                 minlength=meta["nd"])[:meta["nd"]]
                     for v in vals]).astype(np.float32)


@pytest.mark.parametrize("n_keys,max_rep,K,pad", [
    (2000, 7, 3, 0),       # multi-row keys, one grid step, K % 8 != 0
    (9000, 7, 3, 0),       # three grid steps: the window flushes mid-run
    (40000, 2, 5, 1000),   # wide one-hot (maxd ~700) + staging pad rows
    (700, 1, 8, 0),        # identity keys never reach the kernel via
                           # rank_sums; the body must still be right
])
def test_kernel_body_matches_segment_sum(n_keys, max_rep, K, pad):
    key = _keys(n_keys, max_rep, seed=n_keys)
    meta = SS.rank_meta([key])
    n0 = meta["n0"]
    steps = -(-(n0 + pad) // (SS.B * SS.BLK))
    if n_keys == 9000:
        assert steps >= 3 and meta["nd"] > meta["flush"]  # flush crossed
    vals = np.random.default_rng(1).integers(
        0, 1 << 12, (K, n0 + pad)).astype(np.float32)
    vals[:, n0:] = 0  # staging pad rows are query-masked to zero
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(SS.rank_sums_pallas(
            jnp.asarray(vals), jnp.asarray(meta["f"]), meta))
    assert got.shape == (K, meta["nd_pad"])
    np.testing.assert_array_equal(got[:, :meta["nd"]], _spec(vals, meta))


def test_served_group_by_reaches_the_kernel(monkeypatch):
    """GROUP BY over a run-ordered key takes the rank path end to end:
    with the backend gate reading `tpu` the statement's program traces
    the Pallas call (interpreted here) and answers like the segment_sum
    lowering the CPU normally takes."""
    from tidb_tpu.bench.tpch import load_lineitem
    from tidb_tpu.copr.client import CopClient
    from tidb_tpu.session import Session

    sql = ("select l_orderkey, sum(l_quantity) from lineitem "
           "group by l_orderkey order by 2 desc, 1 limit 10")
    s = Session()
    load_lineitem(s, 20000)
    want = s.query(sql)
    traced = []
    inner = SS.rank_sums_pallas

    def spy(vals, f_dev, meta):
        traced.append(vals.shape)
        return inner(vals, f_dev, meta)

    monkeypatch.setattr(SS, "rank_sums_pallas", spy)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fresh = Session(s.storage, cop=CopClient())  # own (empty) jit cache
    with pltpu.force_tpu_interpret_mode():
        got = fresh.query(sql)
    assert traced, "the rank path did not reach the Pallas kernel"
    assert got == want
