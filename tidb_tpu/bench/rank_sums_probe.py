"""Time the `titpu_rank_sums` Pallas kernel alone on the chip.

    chiprun -- python3 -m tidb_tpu.bench.rank_sums_probe \\
        [--k 4] [--keys 15000000] [--rows-a-key 1-7] [--blk 256|all] \\
        [--reps 5] [--seed 7] [--trace]

Builds one run-ordered key column (`--keys` keys of `--rows-a-key` rows,
drawn uniformly), K integer value arrays of the kind the rank path passes
(0/1 masks, unsigned 12-bit limbs, the signed top limb), times
`streamseg.rank_sums_pallas` with `block_until_ready`, and checks every
per-key sum against numpy int64 (`np.add.reduceat`, the segment_sum
spec). Prints one JSON line a geometry: ms a call, ns a row, the geometry
chosen (or forced with `--blk`; `all` sweeps streamseg.BLOCKS, which is
how BLOCK_COST is read off the chip) and the operand dtype. With
`--trace` it also prints the device time of the custom call itself and of
the XLA ops around it (the split into pieces, the recombination).

The cell's shape is the default (K = 4: gate, count, two limbs of
l_quantity; 60 M rows of 1-7 a key); Q3's is `--k 8 --keys 1500000`.
Not run by any benchmark cell. Exits 2 off the TPU: a CPU time is no
device time.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

import numpy as np


def _values(k: int, n: int, seed: int):
    """f32[k, n] on the device: row 0-1 masks, the last row the signed
    top limb, the rest unsigned limbs."""
    import jax
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.key(seed), k)
    rows = []
    for i in range(k):
        lo, hi = (0, 2) if i < 2 else (0, 4096)
        if i == k - 1 and k > 2:
            lo, hi = -2048, 2048
        rows.append(jax.random.randint(keys[i], (n,), lo, hi, jnp.int32))
    return jnp.stack(rows).astype(jnp.float32)


def _device_ops(trace_dir: str, top: int = 6) -> dict:
    """ms per device op of the newest trace under trace_dir."""
    import jax
    path = max(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    ops: dict[str, float] = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:   # the name is the whole HLO line
                op = ev.name.split(" = ")[0].lstrip("%")
                ops[op] = ops.get(op, 0.0) + ev.duration_ns / 1e6
    return dict(sorted(ops.items(), key=lambda kv: -kv[1])[:top])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--keys", type=int, default=15_000_000)
    ap.add_argument("--rows-a-key", default="1-7")
    ap.add_argument("--blk", default=None)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--trace", action="store_true")
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"rank_sums_probe: backend is {jax.default_backend()!r}, "
              "not a TPU: nothing to time", file=sys.stderr)
        return 2
    from tidb_tpu.copr import streamseg as SS

    lo, _, hi = args.rows_a_key.partition("-")
    reps = np.random.default_rng(args.seed).integers(
        int(lo), int(hi or lo) + 1, args.keys)
    key = np.repeat(np.arange(args.keys, dtype=np.int64), reps)
    vals = _values(args.k, len(key), args.seed)
    r0 = np.flatnonzero(np.concatenate([[True], key[1:] != key[:-1]]))
    spec = np.stack([np.add.reduceat(v.astype(np.int64), r0)
                     for v in np.asarray(vals)])
    dev = jax.devices()[0]
    if args.blk == "all":
        blks = list(SS.BLOCKS)
    else:
        blks = [int(args.blk) if args.blk else None]
    bad = 0
    for blk in blks:
        meta = SS.rank_meta([key], blk=blk)
        aux = SS.rank_aux(meta)
        fn = jax.jit(lambda v, a: SS.rank_sums_pallas(v, a, meta))
        t0 = time.perf_counter()
        out = fn(vals, aux).block_until_ready()
        first = time.perf_counter() - t0
        ts = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            fn(vals, aux).block_until_ready()
            ts.append(time.perf_counter() - t0)
        line = {
            "kernel": SS.KERNEL_NAME, "device_kind": dev.device_kind,
            "K": args.k, "rows": len(key), "keys": args.keys,
            "rows_a_key": args.rows_a_key, "forced_blk": blk,
            "geometry": {g: meta[g] for g in (
                "blk", "nb", "ohw", "maxd", "flush", "wstep")},
            "operand_dtype": np.dtype(SS.OPERAND_DTYPE).name,
            "ms_a_call": min(ts) * 1e3,
            "ms_a_call_median": float(np.median(ts)) * 1e3,
            "ns_a_row": min(ts) * 1e9 / len(key),
            "first_call_s": first,
            "correct": bool((np.asarray(out)[:, :meta["nd"]].astype(
                np.int64) == spec).all()),
        }
        if args.trace:
            tdir = os.path.join("chiprun_out", "rank_sums_probe_trace")
            with jax.profiler.trace(tdir):
                fn(vals, aux).block_until_ready()
            line["device_ops_ms"] = _device_ops(tdir)
        bad += not line["correct"]
        print(json.dumps(line), flush=True)
        del aux, out
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
