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


def _spec(vals: np.ndarray, key: np.ndarray) -> np.ndarray:
    """Per-key sums straight from the key column (numpy int64), not from
    anything rank_meta computed."""
    rank = np.cumsum(np.concatenate([[0], key[1:] != key[:-1]]))
    return np.stack([np.bincount(rank, weights=v[:len(key)])
                     for v in vals.astype(np.int64)]).astype(np.float32)


def _run_body(vals: np.ndarray, meta) -> np.ndarray:
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(SS.rank_sums_pallas(
            jnp.asarray(vals), SS.rank_aux(meta), meta))
    assert got.shape == (len(vals), meta["nd_pad"])
    return got[:, :meta["nd"]]


@pytest.mark.parametrize("n_keys,max_rep,K,pad", [
    (2000, 7, 3, 0),       # multi-row keys, one grid step, K % 8 != 0
    (9000, 7, 3, 0),       # three grid steps
    (40000, 2, 5, 1000),   # 1-2 rows a key: the table whose one-hot
                           # is widest a row (128-row blocks) + pad rows
    (700, 1, 8, 0),        # identity keys never reach the kernel via
                           # rank_sums; the body must still be right
    (20000, 7, 4, 0),      # the cell's key shape (1-7 rows, K = 4) over
                           # five grid steps: the window flushes mid-run
    (20000, 7, 8, 777),    # K = 8: all 16 bf16 rows live, + pad rows
    (30000, 2, 4, 0),      # 1-2 rows a key past a flush
])
def test_kernel_body_matches_segment_sum(n_keys, max_rep, K, pad):
    key = _keys(n_keys, max_rep, seed=n_keys)
    meta = SS.rank_meta([key])
    n0 = meta["n0"]
    steps = -(-(n0 + pad) // (meta["nb"] * meta["blk"]))
    if n_keys >= 9000:
        assert steps >= 3
    if n_keys >= 20000:   # the window flushes before the last step
        assert meta["cb"][(steps - 1) * meta["nb"]] >= meta["flush"]
    vals = np.random.default_rng(1).integers(
        0, 1 << 12, (K, n0 + pad)).astype(np.float32)
    vals[:, n0:] = 0  # staging pad rows are query-masked to zero
    np.testing.assert_array_equal(_run_body(vals, meta), _spec(vals, key))


@pytest.mark.parametrize("blk", SS.BLOCKS)
def test_kernel_body_every_block_size(blk):
    """Each candidate geometry is a right one: the choice is about cost
    only (rank_meta's override is what the probe sweeps on the chip)."""
    key = _keys(6000, 7, seed=blk)
    meta = SS.rank_meta([key], blk=blk)
    assert meta["blk"] == blk and meta["nb"] * blk == SS.ROWS_PER_STEP
    vals = np.random.default_rng(2).integers(
        0, 1 << 12, (4, meta["n0"])).astype(np.float32)
    np.testing.assert_array_equal(_run_body(vals, meta), _spec(vals, key))


def test_kernel_body_signed_top_limb():
    """The signed top limb keeps its sign in the high piece: values over
    the whole signed 12-bit range [-2048, 2048) beside unsigned limbs."""
    key = _keys(5000, 7, seed=5)
    meta = SS.rank_meta([key])
    rng = np.random.default_rng(3)
    vals = np.stack([
        rng.integers(0, 2, meta["n0"]),            # a 0/1 mask
        rng.integers(0, 1 << 12, meta["n0"]),      # an unsigned limb
        rng.integers(-2048, 2048, meta["n0"]),     # the signed top limb
        np.full(meta["n0"], -2048),                # its lower edge
    ]).astype(np.float32)
    got = _run_body(vals, meta)
    assert (got[2] < 0).any() and (got[3] < 0).all()
    np.testing.assert_array_equal(got, _spec(vals, key))


def test_kernel_body_at_the_exactness_bound():
    """Every addend 4095 and one key of MAX_ROWS_PER_KEY rows: its total
    4096 * 4095 = 16 773 120 < 2^24 is the bound, reached. One more row
    and rank_meta refuses the epoch."""
    small = _keys(3000, 7, seed=7)
    big = np.full(SS.MAX_ROWS_PER_KEY, 10 ** 6, dtype=np.int64)
    key = np.concatenate([small[:7001], big, small[7001:] + 2 * 10 ** 6])
    meta = SS.rank_meta([key])
    vals = np.full((2, meta["n0"]), 4095, dtype=np.float32)
    got = _run_body(vals, meta)
    assert got.max() == SS.MAX_ROWS_PER_KEY * 4095 == 16_773_120
    np.testing.assert_array_equal(got, _spec(vals, key))
    over = np.concatenate([key[:7001], [10 ** 6], key[7001:]])
    assert SS.rank_meta([over]) is None


def test_geometry_follows_the_key_column():
    """rank_meta alone (no kernel): three key shapes get three block
    sizes, reported in meta; and the program key holds the
    derived geometry, so another seed of the same shape (another maxd)
    finds the compiled program."""
    cell = SS.rank_meta([_keys(60000, 7, seed=1)])     # 1-7 rows a key
    pairs = SS.rank_meta([_keys(120000, 2, seed=2)])   # 1-2 rows a key
    runs = SS.rank_meta([np.repeat(np.arange(8000), 32)])   # long runs
    uniq = SS.rank_meta([np.arange(100000) * 3])
    # narrow blocks where nearly every row opens a key, wide ones where
    # a block holds few keys (what the chip's constants decide)
    assert (pairs["blk"], cell["blk"], runs["blk"]) == (128, 256, 1024)
    assert pairs["ohw"] == cell["ohw"] == runs["ohw"] == 128
    for m in (cell, pairs, runs, uniq):
        assert m["blk"] in SS.BLOCKS and m["blk"] * m["nb"] == \
            SS.ROWS_PER_STEP
        assert m["maxd"] <= m["ohw"] == -(-m["maxd"] // 128) * 128
        assert m["flush"] >= m["nb"] * m["maxd"] and m["flush"] % 128 == 0
        assert m["wstep"] >= 2 * m["flush"] + m["ohw"] + 128
        # lr/cb are the ranks: every row's rank is its block's + its own
        rank = m["lr"] + np.repeat(m["cb"], m["blk"])[:m["n0"]]
        assert rank[0] == 0 and rank[-1] == m["nd"] - 1
        assert 0 <= m["lr"].min() and m["lr"].max() == m["maxd"] - 1
    assert uniq["identity"] and not cell["identity"]

    def same_multiset(seed):   # the cell's seeds: one multiset of order
        reps = np.repeat(np.arange(1, 8), 60000 // 7)  # sizes, shuffled
        np.random.default_rng(seed).shuffle(reps)
        return SS.rank_meta([np.repeat(np.arange(len(reps)), reps)])
    a, b = same_multiset(11), same_multiset(12)
    assert a["maxd"] != b["maxd"]       # the raw width differs by seed
    assert SS.program_key(a) == SS.program_key(b)
    assert SS.program_key(a)[0] == "rankseg"
    assert SS.program_key(a) != SS.program_key(pairs)


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

    def spy(vals, aux, meta):
        traced.append(vals.shape)
        return inner(vals, aux, meta)

    monkeypatch.setattr(SS, "rank_sums_pallas", spy)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fresh = Session(s.storage, cop=CopClient())  # own (empty) jit cache
    with pltpu.force_tpu_interpret_mode():
        got = fresh.query(sql)
    assert traced, "the rank path did not reach the Pallas kernel"
    assert got == want
