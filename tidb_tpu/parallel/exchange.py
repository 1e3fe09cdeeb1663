"""Hash/range-partition exchange over the mesh: the all_to_all data plane.

The reference's MPP tier has two exchange modes — broadcast and hash
partition (reference: planner/core/fragment.go:45 ExchangeSender types,
store/tikv/mpp.go:372 dispatch; TiFlash moves rows node->node over gRPC).
The TPU translation routes rows between devices with ONE XLA collective:
each device buckets its rows by destination, lays them out as a
[n_dev, capacity] send buffer, and `jax.lax.all_to_all` transposes the
device/bucket axes over ICI. Static shapes throughout: capacity is fixed
at trace time, and skew beyond it sets an overflow flag (psum'd to every
device) that the host turns into a fallback — never silent truncation.

Used by the sharded placement (copr/placement.py) for:
* high-cardinality GROUP BY: route rows by group-key hash so every group
  lands wholly on one device, then run the per-device sorted-run
  candidate aggregation (copr/hcagg.py) on disjoint group partitions;
* partitioned (non-broadcast) joins: route probe rows by join-key range
  to the device owning that build shard.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def mix_hash(keys: list[jnp.ndarray]) -> jnp.ndarray:
    """Deterministic int32 mix of one or more int32 key arrays (same key
    tuple -> same value on every device; wrapping int32 mul is fine)."""
    h = jnp.zeros_like(keys[0])
    for k in keys:
        h = h * jnp.int32(-1640531527) + k  # 0x9E3779B9 golden ratio
        h = h ^ (h >> 15)
    h = h * jnp.int32(-2048144789)  # 0x85EBCA6B murmur mix
    h = h ^ (h >> 13)
    return h


def capacity_for(m: int, n_dev: int, slack: float = 2.0) -> int:
    """Per-(device,dest) send capacity: expected m/n_dev rows with slack.
    Overflow under adversarial skew is detected, not truncated."""
    c = int(m * slack) // n_dev + 1
    return max(64, min(c, m))


def route_cols(dest, cols, mask, axis: str, n_dev: int, capacity: int):
    """route_rows over a fragment column list: packs [(data, valid), ...]
    plus the row mask, routes, and unpacks. Shared by the group-partition
    (hc) and join-partition exchanges."""
    payload: list = [mask]
    for d, v in cols:
        payload.append(d)
        payload.append(v)
    recv, recv_valid, overflow = route_rows(dest, payload, axis, n_dev,
                                            capacity)
    new_mask = recv[0] & recv_valid
    new_cols = [(recv[1 + 2 * i], recv[2 + 2 * i]) for i in range(len(cols))]
    return new_cols, new_mask, overflow


def route_rows(
    dest: jnp.ndarray,
    payload: list[jnp.ndarray],
    axis: str,
    n_dev: int,
    capacity: int,
):
    """Send row i of every payload array to device dest[i].

    Per-device view (inside shard_map): dest int32[m] in [0, n_dev);
    payload arrays shaped [m]. Returns (recv_payload, recv_valid,
    overflow) where recv arrays are [n_dev * capacity] (concatenated by
    source device), recv_valid marks real rows vs padding, and overflow
    is a replicated int32 >0 if ANY device overflowed a bucket.

    The layout pass is gather-only (sort + searchsorted + takes) — no
    scatter, so it maps cleanly onto the TPU's vector units.
    """
    m = dest.shape[0]
    iota = jnp.arange(m, dtype=jnp.int32)
    # stable sort by destination; perm brings payloads into dest order
    sd, perm = jax.lax.sort((dest, iota), num_keys=1, is_stable=True)
    start = jnp.searchsorted(sd, jnp.arange(n_dev, dtype=jnp.int32),
                             side="left").astype(jnp.int32)
    ends = jnp.append(start[1:], jnp.int32(m))
    counts = ends - start
    overflow = jnp.any(counts > capacity)

    slots = jnp.arange(n_dev * capacity, dtype=jnp.int32)
    d_idx = slots // capacity
    c_idx = slots % capacity
    src = jnp.clip(start[d_idx] + c_idx, 0, max(m - 1, 0))
    slot_valid = c_idx < counts[d_idx]

    def transpose(send):
        """[n_dev*capacity, ...] slot-space buffer -> received buffer."""
        send = send.reshape((n_dev, capacity) + send.shape[1:])
        recv = jax.lax.all_to_all(send, axis, 0, 0)
        return recv.reshape((n_dev * capacity,) + recv.shape[2:])

    def xch(x):
        return transpose(x[perm][src])  # row space -> slot space -> send

    recv_payload = [xch(x) for x in payload]
    # slot_valid is ALREADY slot-space: no row-permutation gather
    recv_valid = transpose(slot_valid)
    total_overflow = jax.lax.psum(overflow.astype(jnp.int32), axis)
    return recv_payload, recv_valid, total_overflow
