"""Fetched packed row bitmasks -> epoch row numbers, on the host.

A row-returning coprocessor read (copr/client.py `_run_rows`, the row
mode of copr/fragment.py) fetches one `jnp.packbits` bitmask a tile:
big-endian bit order, one bit a row of the tile's bucket, on a mesh the
shards' masks concatenated along rows (copr/placement.py `bucket_size`
keeps every shard a multiple of 8 rows, so the concatenation is the mask
of the whole tile). Tile `ti` covers epoch rows `ti * tile_rows` on;
bits at `cnt` and past it are the bucket's padding.

`decode` turns them into the ascending row numbers that
`np.nonzero(np.concatenate([np.unpackbits(p)[:cnt] ...]))` gives. It
views the bytes as 64-bit words, `flatnonzero`s the words and unpacks
only the 8 bytes of each word that holds a set bit: one pass over the
words plus 64 bits a nonzero word, and nothing row-sized where few rows
pass. A filtered scan that passes 1 row in 23 000 (the benchmark's row
scans: about 2 600 of 60 M) sets about 1 word in 360.

Measured on one thread of an Intel Xeon CPU (numpy 2.0; 15 tiles of
2**22 flags, 60 M rows, set bits spread uniformly; best of five, ms a
decode, this one / a full unpack: one `unpackbits` a tile,
`astype(bool)`, `concatenate`, `nonzero`): one set bit in 1 word of 360
2.4 / 58.9; in 1 word of 16 6.3 / 57.8; in every word 51.0 / 64.0; 16
set bits in every word (13 M rows) 237 / 175; every bit set (38 M rows)
525 / 265. Where that many rows pass, the host gather and the result
rows of the read cost seconds, so one route serves every density.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def decode(packs: Sequence[np.ndarray], counts: Sequence[int],
           tile_rows: int, limit: Optional[int] = None) -> np.ndarray:
    """Epoch row numbers (int64, ascending) of the set bits of the
    fetched per-tile masks `packs`; tile `ti` holds `counts[ti]` rows
    from row `ti * tile_rows`. With `limit`, the first `limit` of them:
    tiles past the one that reaches it are not decoded."""
    parts: list[np.ndarray] = []
    got = 0
    for ti, (packed, cnt) in enumerate(zip(packs, counts)):
        if limit is not None and got >= limit:
            break
        local = _tile(np.ascontiguousarray(packed), cnt)
        if local.size:
            local += ti * tile_rows
            parts.append(local)
            got += local.size
    idx = np.concatenate(parts) if parts else np.zeros(0, np.int64)
    return idx if limit is None else idx[:limit]


def _tile(packed: np.ndarray, cnt: int) -> np.ndarray:
    """Positions below `cnt` (ascending) of the set bits of one tile's
    mask."""
    n8 = packed.size & ~7
    nz = np.flatnonzero(packed[:n8].view(np.uint64))
    bits = np.unpackbits(packed[:n8].reshape(-1, 8)[nz].reshape(-1))
    f = np.flatnonzero(bits.view(bool))
    local = nz[f >> 6]
    local <<= 6
    f &= 63
    local |= f
    if n8 < packed.size:
        # the bytes past the last whole word
        tail = np.flatnonzero(np.unpackbits(packed[n8:]).view(bool))
        local = np.concatenate([local, tail + n8 * 8])
    return local[:np.searchsorted(local, cnt)]
