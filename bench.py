"""Benchmark board: TPC-H (SF10 + SF100), SSB, ClickBench-style configs.

Prints the headline JSON line {"metric", "value", "unit", "vs_baseline"}
to stdout — INCREMENTALLY: once after every completed flight (latest line
supersedes earlier ones), so a later flight's failure can never erase the
board. The headline is TPC-H Q6 at the north-star SF100 scale
(BASELINE.json metric: "TPC-H rows/sec/chip; Q1+Q6 p50 latency at SF100").

Isolation: each flight runs in its OWN SUBPROCESS. The parent holds only
numpy and a few MB; a flight that exhausts RAM is the biggest process on
the box, so the OOM killer takes the flight, not the board (round 4
lesson: one in-process SSB SF100 flight OOM-killed the whole board,
BENCH_r04.json rc=137). Flights auto-scale their dataset to MemAvailable.

Comparison basis (BASELINE.md): the reference publishes no absolute
numbers in-repo and its Go toolchain isn't present here, so the floor is
a COMPILED (C++ -O3) row-at-a-time Q6 loop over row-major storage — the
execution model of the reference's mocktikv interpreter (reference:
store/mockstore/mocktikv/cop_handler_dag.go:150, row loop over MVCC
pairs) without its per-row decode overhead, i.e. a conservative floor
(native/baseline.cpp). The old Python row-loop baseline is still measured
and reported for series continuity with BENCH_r01..r04. BOTH sides of
the headline ratio are SINGLE-STREAM.

Configs (BASELINE.json configs[0..4] + the r04 join target):
  q6_sf10 / q1_sf10     — scan flight at SF10 (series continuity)
  q6_sf100 / q1_sf100   — the north star (BENCH_SF_BIG, default 100)
  q3_sf10 / q5_sf10     — snowflake join fragments at SF10
  ssb q1.1-1.3          — SSB flight at BENCH_SSB_SF (default 100)
  cb_*                  — ClickBench-style wide scan/TopN at
                          BENCH_CB_ROWS (default 100M)
  multichip             — mesh data plane: sharded-vs-single-device
                          rows/s + per-device placement (shard spec,
                          bytes per device) at BENCH_MESH_ROWS rows
                          over BENCH_MESH_DEVICES devices

Every timed query passes an exact digest check against a numpy oracle
first. Each timed query's per-operator/per-stage attribution (the Top
SQL plane's session-side read: stages_ms / operators_ms / operator
transfer bytes) is logged as an `attribution <name>: {...}` line and
stored under the flight result's "attribution" key, and every datagen/
load phase emits a heartbeat (rows, rows/s, RSS) every 5s — so an OOM
or timeout kill leaves a diagnosable trail. On any flight failure the
child persists an inspection snapshot (res["inspection"]: the
obs_inspect rules over every live store + event-ring tails) into the
result JSON, and a partial snapshot is re-dumped every 30s so even a
SIGKILL'd flight (rc=137/rc=124) leaves a diagnosis. The SF100
north-star flight (tpch_big) runs FIRST.

Devices: each flight child is the one process that touches JAX (this
parent never imports it). A flight FAILS when JAX finds no accelerator;
XLA's CPU backend serves only when BENCH_PLATFORM=cpu names it. Every
board line ends with the platform, device_kind and device count it was
taken on, follower/replica children are pinned to JAX_PLATFORMS=cpu and
labelled so, and the board exits non-zero if any flight failed.

Environment knobs:
BENCH_SF (10), BENCH_JOIN_SF (10),
BENCH_SSB_SF (100), BENCH_CB_ROWS (1e8), BENCH_SF_BIG (100),
BENCH_MESH_ROWS (4e6), BENCH_MESH_DEVICES (8),
BENCH_REPEAT (5), BENCH_CLIENTS (8), BENCH_PLATFORM,
BENCH_FLIGHT_TIMEOUT (5400s), BENCH_RAM_FRACTION (0.75),
BENCH_FLIGHTS (comma list to run a subset).
"""

from __future__ import annotations

import ctypes
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

ROWS_PER_SF = 6_001_215

# lineitem physical column order (matches bench.tpch.LINEITEM_DDL)
_LI_COLS = [
    "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
    "l_extendedprice", "l_discount", "l_tax", "l_returnflag",
    "l_linestatus", "l_shipdate", "l_commitdate", "l_receiptdate",
]


def _meminfo_gb(field: str) -> float:
    try:
        with open("/proc/meminfo") as f:
            for ln in f:
                if ln.startswith(field):
                    return int(ln.split()[1]) / 1e6
    except OSError:
        pass
    return 0.0


def _rss_gb() -> float:
    try:
        with open("/proc/self/status") as f:
            for ln in f:
                if ln.startswith("VmRSS"):
                    return int(ln.split()[1]) / 1e6
    except OSError:
        pass
    return 0.0


def log(msg: str) -> None:
    print(f"# [rss={_rss_gb():.1f}G] {msg}", file=sys.stderr, flush=True)


class _Heartbeat:
    """Datagen/load heartbeat: a daemon thread logs progress (rows so
    far, rows/s, process RSS) every few seconds, so the next SF100
    OOM kill or timeout (BENCH_r04 rc=137 at gen, BENCH_r05 rc=124 at
    504s/45.9G RSS) leaves a diagnosable trail in the board output
    instead of a silent death. Flights bump `.rows` as they generate;
    phases that cannot count rows still get elapsed + RSS."""

    def __init__(self, label: str, interval_s: float = 5.0) -> None:
        self.label = label
        self.interval_s = interval_s
        self.rows = 0
        self.t0 = time.perf_counter()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="bench-heartbeat")

    def _line(self, tag: str) -> None:
        el = time.perf_counter() - self.t0
        rate = self.rows / el if el > 0 else 0.0
        log(f"heartbeat {self.label} {tag}: rows={self.rows} "
            f"({rate / 1e6:.2f}M rows/s, {el:.0f}s elapsed)")

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            self._line("tick")

    def __enter__(self) -> "_Heartbeat":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)
        self._line("done" if exc[0] is None else "ABORTED")


GEN_VERSION = 1  # bump to invalidate on-disk datagen caches


def _cache_dir() -> str:
    return os.environ.get(
        "BENCH_CACHE_DIR",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".bench_cache"))


def generate_lineitem_chunked(n: int, hb: _Heartbeat,
                              chunk: int = 16_000_000):
    """Chunked lineitem generation, streamed through an on-disk columnar
    cache (one .npy per column under BENCH_CACHE_DIR) reused across
    runs.

    The SF100 flights died in datagen two rounds running (BENCH_r04
    rc=137 OOM, r05 rc=124 timeout at 504s/45.9G RSS, all 600M rows
    held in memory): generation now writes each chunk straight into
    np.lib.format memmaps — transient RSS is ONE chunk of generator
    temporaries, the kernel flushes column pages behind the writer —
    and a later run finds the `_COMPLETE` marker and memory-maps the
    columns read-only in O(seconds) with page-cache-evictable RSS.
    Chunks are seeded independently — self-consistent data; the oracles
    read the same (mapped) arrays. Falls back to in-memory generation
    when the cache dir is unwritable."""
    from tidb_tpu.bench.tpch import generate_lineitem_arrays

    if n <= chunk:
        out = generate_lineitem_arrays(n)
        hb.rows = n
        return out
    # chunk is part of the identity: chunks are seeded independently, so
    # the concrete rows are a function of the chunk size
    tag = os.path.join(_cache_dir(),
                       f"lineitem_n{n}_c{chunk}_seed42_v{GEN_VERSION}")
    marker = os.path.join(tag, "_COMPLETE")
    if os.path.exists(marker):
        out = {c: np.load(os.path.join(tag, c + ".npy"), mmap_mode="r")
               for c in _LI_COLS}
        hb.rows = n
        log(f"datagen cache HIT: {tag} ({n} rows mapped)")
        return out
    first = generate_lineitem_arrays(chunk, seed=42)
    try:
        os.makedirs(tag, exist_ok=True)
        out = {k: np.lib.format.open_memmap(
            os.path.join(tag, k + ".npy"), mode="w+", dtype=v.dtype,
            shape=(n,)) for k, v in first.items()}
        cached = True
    except OSError as e:
        log(f"datagen cache unavailable ({e}); generating in memory")
        out = {k: np.empty(n, dtype=v.dtype) for k, v in first.items()}
        cached = False
    lo = 0
    i = 0
    while lo < n:
        hi = min(lo + chunk, n)
        part = first if lo == 0 else \
            generate_lineitem_arrays(hi - lo, seed=42 + i)
        for k in part:
            out[k][lo:hi] = part[k]
        part = None
        if i == 0:
            first = None
        hb.rows = hi
        lo = hi
        i += 1
    if cached:
        for v in out.values():
            v.flush()
        with open(marker, "w") as f:
            f.write(f"{n}\n")
        log(f"datagen cache WRITTEN: {tag}")
        # reopen read-only: the loaded epochs then share the page cache
        # and a crashed later phase cannot corrupt the cache
        out = {c: np.load(os.path.join(tag, c + ".npy"), mmap_mode="r")
               for c in _LI_COLS}
    return out


def _attribution(session) -> dict:
    """The last timed run's per-stage/per-operator attribution (the
    session-side read of the Top SQL plane) — persisted per query into
    the flight result + board tail so BENCH_*.json explains where the
    milliseconds went, not only how many there were. `engines` is the
    device/host path decision per coprocessor read, with the fragment
    mode and any gate reason embedded ("device[fat]@mesh8",
    "host(fragment:key-span)") — a regression off the device path now
    names itself on the board."""
    return {
        "stages_ms": {k: round(v * 1e3, 3)
                      for k, v in session.last_stages.items()},
        "operators_ms": {k: round(v * 1e3, 3)
                         for k, v in session.last_op_wall.items()},
        "operator_stages_ms": {
            op: {k: round(v * 1e3, 3) for k, v in d.items()}
            for op, d in session.last_op_stages.items()},
        "operator_bytes": dict(session.last_op_bytes),
        "engines": list(getattr(session, "last_engines", ()) or ()),
    }


def note_attribution(res: dict, name: str, session) -> None:
    att = _attribution(session)
    res.setdefault("attribution", {})[name] = att
    log(f"attribution {name}: " + json.dumps(att, sort_keys=True))
    paths = sorted(set(att["engines"]))
    host = [e for e in paths if e.startswith("host")]
    res["lines"].append(
        f"path {name}: {','.join(paths) or '(none)'}"
        + (" <- HOST FALLBACK" if host else ""))


# ---------------------------------------------------------------------------
# Baselines (parent-side: numpy + ctypes only, no jax import)
# ---------------------------------------------------------------------------

def _load_baseline_lib():
    so = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "native", "libbaseline.so")
    try:  # no-op when fresh; rebuilds after baseline.cpp edits
        subprocess.run(["make", "-C", os.path.dirname(so), "libbaseline.so"],
                       check=True, capture_output=True, timeout=120)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            OSError):
        if not os.path.exists(so):
            raise
    lib = ctypes.CDLL(so)
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    lib.q6_kv_rowloop.restype = ctypes.c_double
    lib.q6_kv_rowloop.argtypes = [
        i64p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
        ctypes.c_int64, ctypes.POINTER(ctypes.c_int64)]
    lib.q6_columnar_rowloop.restype = ctypes.c_double
    lib.q6_columnar_rowloop.argtypes = [
        i64p, i64p, i64p, i64p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.POINTER(ctypes.c_int64)]
    lib.q1_kv_rowloop.restype = ctypes.c_double
    lib.q1_kv_rowloop.argtypes = [
        i64p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")]
    return lib


def compiled_baselines(arrays, sample: int = 6_000_000):
    """(q6_kv_rps, q6_columnar_rps, q1_kv_rps) from native/baseline.cpp,
    median of 3 runs each over a `sample`-row prefix. The q6 kv variant
    is the vs_baseline denominator: a compiled row-loop over row-major
    rows, the mocktikv execution model (cop_handler_dag.go:150) minus
    its decode cost — i.e. a floor that flatters the reference."""
    from tidb_tpu.types.value import parse_date

    lib = _load_baseline_lib()
    n = min(sample, len(arrays["l_shipdate"]))
    rows = np.empty((n, len(_LI_COLS)), dtype=np.int64)
    for i, c in enumerate(_LI_COLS):
        rows[:, i] = arrays[c][:n]
    ship, disc = _LI_COLS.index("l_shipdate"), _LI_COLS.index("l_discount")
    qty, price = _LI_COLS.index("l_quantity"), _LI_COLS.index(
        "l_extendedprice")
    d1, d2 = parse_date("1994-01-01"), parse_date("1995-01-01")
    out = ctypes.c_int64()
    want = q6_oracle({k: arrays[k][:n] for k in (
        "l_shipdate", "l_discount", "l_quantity", "l_extendedprice")})
    kv = sorted(lib.q6_kv_rowloop(rows, n, len(_LI_COLS), ship, disc, qty,
                                  price, d1, d2, ctypes.byref(out))
                for _ in range(3))[1]
    assert out.value == want, "compiled kv baseline digest"
    # generator columns may be narrowed (int8/int16/int32 staging); the
    # C loop's ABI is int64 pointers
    cship = np.ascontiguousarray(arrays["l_shipdate"][:n], dtype=np.int64)
    cdisc = np.ascontiguousarray(arrays["l_discount"][:n], dtype=np.int64)
    cqty = np.ascontiguousarray(arrays["l_quantity"][:n], dtype=np.int64)
    cprice = np.ascontiguousarray(
        arrays["l_extendedprice"][:n], dtype=np.int64)
    col = sorted(lib.q6_columnar_rowloop(cship, cdisc, cqty, cprice, n,
                                         d1, d2, ctypes.byref(out))
                 for _ in range(3))[1]
    assert out.value == want, "compiled columnar baseline digest"
    cutoff = parse_date("1998-12-01") - 90
    acc = np.zeros(30, dtype=np.int64)
    q1 = sorted(lib.q1_kv_rowloop(
        rows, n, len(_LI_COLS), ship, _LI_COLS.index("l_returnflag"),
        _LI_COLS.index("l_linestatus"), qty, price, disc,
        _LI_COLS.index("l_tax"), cutoff, acc) for _ in range(3))[1]
    w1 = q1_oracle({k: arrays[k][:n] for k in (
        "l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
        "l_extendedprice", "l_discount", "l_tax")})
    got1 = {(k // 2, k % 2): tuple(int(v) for v in acc[k * 5:k * 5 + 5])
            for k in range(6) if acc[k * 5 + 4]}
    assert got1 == w1, "compiled q1 baseline digest"
    return n / kv, n / col, n / q1


def interpreted_q6_baseline(arrays, sample: int = 200_000) -> float:
    """Row-at-a-time *Python* interpreted Q6 rows/sec (median of 3) —
    the BENCH_r01..r04 denominator, kept for series continuity."""
    from tidb_tpu.types.value import parse_date

    n = min(sample, len(arrays["l_shipdate"]))
    ship = arrays["l_shipdate"][:n].tolist()
    disc = arrays["l_discount"][:n].tolist()
    qty = arrays["l_quantity"][:n].tolist()
    price = arrays["l_extendedprice"][:n].tolist()
    d1, d2 = parse_date("1994-01-01"), parse_date("1995-01-01")
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        acc = 0
        for i in range(n):
            s = ship[i]
            if s >= d1 and s < d2:
                d = disc[i]
                if 5 <= d <= 7 and qty[i] < 2400:
                    acc += price[i] * d
        rates.append(n / (time.perf_counter() - t0))
    return sorted(rates)[1]


# ---------------------------------------------------------------------------
# Oracles (chunked: SF100 masks of 600M rows must not clone the table)
# ---------------------------------------------------------------------------

def q6_oracle(arrays) -> int:
    from tidb_tpu.types.value import parse_date

    d1, d2 = parse_date("1994-01-01"), parse_date("1995-01-01")
    total, n = 0, len(arrays["l_shipdate"])
    for lo in range(0, n, 50_000_000):
        sl = slice(lo, min(lo + 50_000_000, n))
        ship = arrays["l_shipdate"][sl]
        m = ((ship >= d1) & (ship < d2)
             & (arrays["l_discount"][sl] >= 5)
             & (arrays["l_discount"][sl] <= 7)
             & (arrays["l_quantity"][sl] < 2400))
        total += int((arrays["l_extendedprice"][sl][m].astype(np.int64)
                      * arrays["l_discount"][sl][m]).sum())
    return total


def q1_oracle(arrays):
    """Exact int64 aggregates per (returnflag, linestatus) group,
    computed in 50M-row chunks (SF100: a 98%-selective mask must not
    materialise masked copies of the whole table)."""
    from tidb_tpu.types.value import parse_date

    cutoff = parse_date("1998-12-01") - 90
    n = len(arrays["l_shipdate"])
    acc = {name: np.zeros(6, dtype=np.int64)
           for name in ("qty", "base", "disc_price", "charge", "count")}
    for lo in range(0, n, 50_000_000):
        sl = slice(lo, min(lo + 50_000_000, n))
        m = arrays["l_shipdate"][sl] <= cutoff
        key = arrays["l_returnflag"][sl][m] * 2 + \
            arrays["l_linestatus"][sl][m]
        qty = arrays["l_quantity"][sl][m].astype(np.int64)
        ext = arrays["l_extendedprice"][sl][m].astype(np.int64)
        disc = arrays["l_discount"][sl][m].astype(np.int64)
        tax = arrays["l_tax"][sl][m].astype(np.int64)
        for name, vals in (("qty", qty), ("base", ext),
                           ("disc_price", ext * (100 - disc)),
                           ("charge", ext * (100 - disc) * (100 + tax)),
                           ("count", np.ones(len(key), np.int64))):
            np.add.at(acc[name], key, vals)
    res = {}
    for k in range(6):
        if acc["count"][k]:
            res[(k // 2, k % 2)] = tuple(int(acc[nm][k]) for nm in (
                "qty", "base", "disc_price", "charge", "count"))
    return res


def check_q1(rows, arrays) -> None:
    want = q1_oracle(arrays)
    flag_code = {"A": 0, "R": 1, "N": 2}
    status_code = {"F": 0, "O": 1}
    assert len(rows) == len(want), (len(rows), len(want))
    for r in rows:
        key = (flag_code[r[0]], status_code[r[1]])
        w = want[key]
        got = (r[2].unscaled, r[3].unscaled, r[4].unscaled, r[5].unscaled,
               r[9])
        assert got == w, f"Q1 digest mismatch {r[0]}/{r[1]}: {got} vs {w}"


def q3_oracle(jdata):
    """Exact top-10 (orderkey, revenue_unscaled) for TPC-H Q3."""
    from tidb_tpu.types.value import parse_date

    cutoff = parse_date("1995-03-15")
    segs, ccodes = jdata["customer"]["c_mktsegment"]
    bld = list(segs).index("BUILDING")
    cust = jdata["customer"]["c_custkey"]
    cust_ok = np.zeros(int(cust.max()) + 1, bool)
    cust_ok[cust[np.asarray(ccodes) == bld]] = True
    o = jdata["orders"]
    o_ok = (o["o_orderdate"] < cutoff) & cust_ok[o["o_custkey"]]
    span = int(o["o_orderkey"].max()) + 1
    ok_arr = np.zeros(span, bool)
    ok_arr[o["o_orderkey"][o_ok]] = True
    odate = np.zeros(span, np.int64)
    odate[o["o_orderkey"][o_ok]] = o["o_orderdate"][o_ok]
    li = jdata["lineitem"]
    lm = (li["l_shipdate"] > cutoff) & ok_arr[li["l_orderkey"]]
    rev = np.zeros(span, np.int64)
    np.add.at(rev, li["l_orderkey"][lm],
              li["l_extendedprice"][lm] * (100 - li["l_discount"][lm]))
    nz = np.nonzero(rev)[0]
    top = nz[np.lexsort((nz, odate[nz], -rev[nz]))[:10]]
    return [(int(k), int(rev[k])) for k in top]


def q10_oracle(jdata):
    """Exact (custkey, revenue_unscaled) top-20 set for TPC-H Q10."""
    from tidb_tpu.types.value import parse_date

    d1, d2 = parse_date("1993-10-01"), parse_date("1994-01-01")
    o = jdata["orders"]
    o_ok = (o["o_orderdate"] >= d1) & (o["o_orderdate"] < d2)
    ospan = int(o["o_orderkey"].max()) + 1
    o_cust = np.full(ospan, -1, np.int64)
    o_cust[o["o_orderkey"][o_ok]] = o["o_custkey"][o_ok]
    li = jdata["lineitem"]
    rvocab, rcodes = li["l_returnflag"]
    r_code = list(rvocab).index("R")
    cust = o_cust[li["l_orderkey"]]
    m = (np.asarray(rcodes) == r_code) & (cust >= 0)
    cspan = int(jdata["customer"]["c_custkey"].max()) + 1
    rev = np.zeros(cspan, np.int64)
    np.add.at(rev, cust[m],
              li["l_extendedprice"][m] * (100 - li["l_discount"][m]))
    nz = np.nonzero(rev)[0]
    top = nz[np.lexsort((nz, -rev[nz]))[:20]]
    # revenue-only ORDER BY: ties leave the tail unordered, so digests
    # compare the (custkey, revenue) SET
    return {(int(k), int(rev[k])) for k in top}


def time_q10(res: dict, session, jdata, label: str, repeat: int):
    """Digest-check + time TPC-H Q10 (the fused join+agg+topn shape) on
    an already-loaded session; returns rows/s."""
    from tidb_tpu.bench.tpch_queries import TPCH_QUERIES

    want = q10_oracle(jdata)
    got = {(int(r[0]), r[2].unscaled)
           for r in session.query(TPCH_QUERIES["q10"])}
    assert got == want, f"q10 digest: {sorted(got)[:3]} vs " \
                        f"{sorted(want)[:3]}"
    ts = times(lambda: session.query(TPCH_QUERIES["q10"]), repeat)
    note_attribution(res, label, session)
    line, rps = report(label, ts, len(jdata["lineitem"]["l_orderkey"]))
    res["lines"].append(line)
    return rps


def _years_of(days: np.ndarray) -> np.ndarray:
    return days.astype("datetime64[D]").astype(
        "datetime64[Y]").astype(np.int64) + 1970


def _keymap(keys: np.ndarray, vals: np.ndarray) -> np.ndarray:
    out = np.full(int(keys.max()) + 1, -1, np.int64)
    out[keys] = vals
    return out


def q7_oracle(jdata):
    """Exact (supp_nation, cust_nation, year, revenue_unscaled) rows for
    TPC-H Q7 (FRANCE/GERMANY, 1995-1996)."""
    from tidb_tpu.types.value import parse_date

    nvocab, ncodes = jdata["nation"]["n_name"]
    name_of = _keymap(jdata["nation"]["n_nationkey"], np.asarray(ncodes))
    fr, ge = list(nvocab).index("FRANCE"), list(nvocab).index("GERMANY")
    s_nat = _keymap(jdata["supplier"]["s_suppkey"],
                    jdata["supplier"]["s_nationkey"])
    c_nat = _keymap(jdata["customer"]["c_custkey"],
                    jdata["customer"]["c_nationkey"])
    o_cust = _keymap(jdata["orders"]["o_orderkey"],
                     jdata["orders"]["o_custkey"])
    li = jdata["lineitem"]
    d1, d2 = parse_date("1995-01-01"), parse_date("1996-12-31")
    ship = li["l_shipdate"]
    sn = name_of[s_nat[li["l_suppkey"]]]
    cn = name_of[c_nat[o_cust[li["l_orderkey"]]]]
    m = (ship >= d1) & (ship <= d2) & \
        (((sn == fr) & (cn == ge)) | ((sn == ge) & (cn == fr)))
    year = _years_of(ship[m])
    vol = li["l_extendedprice"][m] * (100 - li["l_discount"][m])
    key = (sn[m] * 2 + (cn[m] == fr)) * 8192 + year
    uniq, inv = np.unique(key, return_inverse=True)
    rev = np.zeros(len(uniq), np.int64)
    np.add.at(rev, inv, vol)
    out = set()
    for k, r in zip(uniq, rev):
        year = int(k % 8192)
        sc = int(k // 8192) // 2
        cc = fr if (k // 8192) % 2 else ge
        out.add((nvocab[sc], nvocab[cc], year, int(r)))
    return out


def time_q7(res: dict, session, jdata, label: str, repeat: int):
    """Digest-check + time TPC-H Q7 (the EXTRACT-year grouped
    aggregation newly device-resident in round 14b); returns rows/s."""
    from tidb_tpu.bench.tpch_queries import TPCH_QUERIES

    want = q7_oracle(jdata)
    got = {(r[0], r[1], int(r[2]), r[3].unscaled)
           for r in session.query(TPCH_QUERIES["q7"])}
    assert got == want, f"q7 digest: {sorted(got)[:2]} vs " \
                        f"{sorted(want)[:2]}"
    ts = times(lambda: session.query(TPCH_QUERIES["q7"]), repeat)
    note_attribution(res, label, session)
    line, rps = report(label, ts, len(jdata["lineitem"]["l_orderkey"]))
    res["lines"].append(line)
    return rps


def q8_oracle(jdata):
    """Exact (o_year, mkt_share_unscaled) rows for TPC-H Q8 (AMERICA /
    BRAZIL / ECONOMY ANODIZED STEEL), mkt_share via the engine's own
    decimal division semantics (scale + div_precincrement)."""
    from tidb_tpu.types.value import Decimal, parse_date

    rvocab, rcodes = jdata["region"]["r_name"]
    am = list(rvocab).index("AMERICA")
    reg_ok = np.zeros(int(jdata["region"]["r_regionkey"].max()) + 1, bool)
    reg_ok[jdata["region"]["r_regionkey"][np.asarray(rcodes) == am]] = True
    nat = jdata["nation"]
    nvocab, ncodes = nat["n_name"]
    br = list(nvocab).index("BRAZIL")
    nat_in_am = _keymap(nat["n_nationkey"],
                        reg_ok[nat["n_regionkey"]].astype(np.int64))
    name_of = _keymap(nat["n_nationkey"], np.asarray(ncodes))
    pvocab, pcodes = jdata["part"]["p_type"]
    steel = list(pvocab).index("ECONOMY ANODIZED STEEL")
    p_ok = _keymap(jdata["part"]["p_partkey"],
                   (np.asarray(pcodes) == steel).astype(np.int64))
    s_nat = _keymap(jdata["supplier"]["s_suppkey"],
                    jdata["supplier"]["s_nationkey"])
    c_nat = _keymap(jdata["customer"]["c_custkey"],
                    jdata["customer"]["c_nationkey"])
    o = jdata["orders"]
    d1, d2 = parse_date("1995-01-01"), parse_date("1996-12-31")
    o_ok = (o["o_orderdate"] >= d1) & (o["o_orderdate"] <= d2)
    o_cust = _keymap(o["o_orderkey"],
                     np.where(o_ok, o["o_custkey"], -1))
    o_year = _keymap(o["o_orderkey"], _years_of(o["o_orderdate"]))
    li = jdata["lineitem"]
    cust = o_cust[li["l_orderkey"]]
    m = (p_ok[li["l_partkey"]] == 1) & (cust >= 0) & \
        (nat_in_am[c_nat[np.maximum(cust, 0)]] == 1)
    vol = li["l_extendedprice"][m] * (100 - li["l_discount"][m])
    year = o_year[li["l_orderkey"]][m]
    brazil = name_of[s_nat[li["l_suppkey"]]][m] == br
    out = set()
    for y in np.unique(year):
        ym = year == y
        den = int(vol[ym].sum())
        num = int(vol[ym & brazil].sum())
        # the engine's exact decimal `/` (npeval op "div"): scale 4
        # operands -> scale 8 result, half away from zero
        q, r = divmod(abs(num) * 10 ** 8, abs(den))
        q += 2 * r >= abs(den)
        out.add((int(y), -q if (num < 0) != (den < 0) else q))
    return out


def time_q8(res: dict, session, jdata, label: str, repeat: int):
    """Digest-check + time TPC-H Q8; returns rows/s."""
    from tidb_tpu.bench.tpch_queries import TPCH_QUERIES

    want = q8_oracle(jdata)
    got = {(int(r[0]), r[1].unscaled)
           for r in session.query(TPCH_QUERIES["q8"])}
    assert got == want, f"q8 digest: {sorted(got)[:2]} vs " \
                        f"{sorted(want)[:2]}"
    ts = times(lambda: session.query(TPCH_QUERIES["q8"]), repeat)
    note_attribution(res, label, session)
    line, rps = report(label, ts, len(jdata["lineitem"]["l_orderkey"]))
    res["lines"].append(line)
    return rps


def q5_oracle(jdata):
    """Exact (nation, revenue_unscaled) rows for TPC-H Q5 (ASIA/1994)."""
    from tidb_tpu.types.value import parse_date

    d1, d2 = parse_date("1994-01-01"), parse_date("1995-01-01")
    rnames, rcodes = jdata["region"]["r_name"]
    asia = list(rnames).index("ASIA")
    r_ok = np.asarray(rcodes) == asia
    reg_ok = np.zeros(int(jdata["region"]["r_regionkey"].max()) + 1, bool)
    reg_ok[jdata["region"]["r_regionkey"][r_ok]] = True
    nat = jdata["nation"]
    n_ok = reg_ok[nat["n_regionkey"]]
    nspan = int(nat["n_nationkey"].max()) + 1
    nat_ok = np.zeros(nspan, bool)
    nat_ok[nat["n_nationkey"][n_ok]] = True
    cust = jdata["customer"]
    cspan = int(cust["c_custkey"].max()) + 1
    c_nat = np.full(cspan, -1, np.int64)
    c_nat[cust["c_custkey"]] = cust["c_nationkey"]
    supp = jdata["supplier"]
    sspan = int(supp["s_suppkey"].max()) + 1
    s_nat = np.full(sspan, -1, np.int64)
    s_nat[supp["s_suppkey"]] = supp["s_nationkey"]
    o = jdata["orders"]
    o_ok = (o["o_orderdate"] >= d1) & (o["o_orderdate"] < d2)
    ospan = int(o["o_orderkey"].max()) + 1
    o_cnat = np.full(ospan, -1, np.int64)
    o_cnat[o["o_orderkey"][o_ok]] = c_nat[o["o_custkey"][o_ok]]
    li = jdata["lineitem"]
    lnat = s_nat[li["l_suppkey"]]
    onat = o_cnat[li["l_orderkey"]]
    m = (lnat >= 0) & (lnat == onat) & nat_ok[np.clip(lnat, 0, None)]
    rev = np.zeros(nspan, np.int64)
    np.add.at(rev, lnat[m],
              li["l_extendedprice"][m] * (100 - li["l_discount"][m]))
    return {int(k): int(rev[k]) for k in np.nonzero(rev)[0]}


# ---------------------------------------------------------------------------
# Flight harness
# ---------------------------------------------------------------------------

def times(run, repeat) -> list[float]:
    run()  # warm
    ts = []
    for _ in range(repeat):
        t = time.perf_counter()
        run()
        ts.append(time.perf_counter() - t)
    ts.sort()
    return ts


def report(name, ts, rows) -> tuple[str, float]:
    p50 = ts[len(ts) // 2]
    line = (f"{name}: p50={p50 * 1e3:.1f}ms max={ts[-1] * 1e3:.1f}ms "
            f"(of {len(ts)}) {rows / p50 / 1e6:.1f}M rows/s single-stream")
    return line, rows / p50


def _scale_to_ram(requested_rows: int, bytes_per_row: float,
                  label: str, lines: list[str]) -> int:
    """Cap a dataset to MemAvailable * BENCH_RAM_FRACTION."""
    frac = float(os.environ.get("BENCH_RAM_FRACTION", 0.75))
    avail = _meminfo_gb("MemAvailable") * 1e9
    cap = int(avail * frac / bytes_per_row)
    if cap <= 0:  # /proc/meminfo unreadable: unknown, keep requested
        return requested_rows
    if requested_rows > cap:
        lines.append(
            f"{label}: auto-scaled {requested_rows} -> {cap} rows "
            f"(MemAvailable={avail / 1e9:.0f}GB x {frac} / "
            f"{bytes_per_row:.0f}B/row)")
        return cap
    return requested_rows


def _session_env(cpu_devices: int = 0) -> dict:
    """Flight-local engine setup: quiet the slow log (it drowned the
    r04 board's output tail), place the compile cache, and claim the
    flight's device. A flight needs an accelerator: XLA's CPU backend
    serves only when BENCH_PLATFORM=cpu names it (then with
    `cpu_devices` virtual devices for the mesh flight), and every board
    line carries the device (run_flight_child). This process is the one
    that touches the chip — the parent board never imports jax."""
    import logging

    import jax
    from tidb_tpu import device

    logging.getLogger("tidb_tpu.slowlog").setLevel(logging.ERROR)
    platform = os.environ.get("BENCH_PLATFORM")
    if platform:
        jax.config.update("jax_platforms", platform)
        if platform == "cpu" and cpu_devices:
            jax.config.update("jax_num_cpu_devices", cpu_devices)
    device.configure_compile_cache()
    info = device.describe()
    if info["platform"] == "cpu" and platform != "cpu":
        raise RuntimeError(
            f"no accelerator: JAX is on {device.line(info)}; a CPU run "
            f"has to be named (BENCH_PLATFORM=cpu) and is never reported "
            f"as a device")
    return info


# peak HBM bytes/s per chip by jax device_kind. Source: Google Cloud
# documentation, "TPU v5e" system architecture (819 GB/s per chip).
HBM_PEAK_BYTES_PER_S = {"TPU v5 lite": 819e9}


def _hbm_line(name: str, p50: float, n: int, col_bytes: float) -> str:
    """Estimated device bytes touched per pass vs nominal HBM bandwidth.
    col_bytes = per-row data bytes at staged (narrowed) widths; each
    staged column also carries a 1-byte validity lane + one shared
    visibility lane. An accelerator missing from HBM_PEAK_BYTES_PER_S
    is an error, not a default; a named CPU run has no HBM to compare."""
    from tidb_tpu import device

    info = device.describe()
    touched = n * col_bytes
    line = (f"{name}: ~{touched / p50 / 1e9:.0f} GB/s scan "
            f"({touched / 1e9:.1f}GB staged bytes / {p50 * 1e3:.1f}ms)")
    if info["platform"] == "cpu":
        return line
    bw = HBM_PEAK_BYTES_PER_S.get(info["device_kind"])
    if bw is None:
        raise RuntimeError(
            f"no HBM peak recorded for device_kind "
            f"{info['device_kind']!r}; add it to HBM_PEAK_BYTES_PER_S "
            f"with its source")
    return line + f" = {touched / p50 / bw * 100:.0f}% of nominal HBM bw"


# ---------------------------------------------------------------------------
# Flights (each runs in its own subprocess)
# ---------------------------------------------------------------------------

def flight_tpch(res: dict, big: bool) -> None:
    from tidb_tpu.bench.tpch import TPCH_Q1, TPCH_Q6, load_lineitem
    from tidb_tpu.session import Session

    _session_env()
    lines = res["lines"]
    sf = float(os.environ.get("BENCH_SF_BIG", 100)) if big else \
        float(os.environ.get("BENCH_SF", 10))
    repeat = int(os.environ.get("BENCH_REPEAT", 5))
    # 8 int64 + 3 int32 + 2 int8 columns adopted zero-copy by bulk_load
    # + remap/transient headroom
    n = _scale_to_ram(int(ROWS_PER_SF * sf), 115.0, f"tpch sf{sf:g}",
                      lines)
    sf_label = f"sf{sf:g}" if n == int(ROWS_PER_SF * sf) else \
        f"sf{n / ROWS_PER_SF:.0f}"
    log(f"tpch {sf_label}: generating {n} rows "
        f"(MemAvailable={_meminfo_gb('MemAvailable'):.0f}GB)")
    t0 = time.perf_counter()
    with _Heartbeat(f"tpch-{sf_label}-gen") as hb:
        arrays = generate_lineitem_chunked(n, hb)
    gen_s = time.perf_counter() - t0
    log(f"tpch {sf_label}: gen={gen_s:.0f}s; loading")
    session = Session()
    t0 = time.perf_counter()
    with _Heartbeat(f"tpch-{sf_label}-load") as hb:
        hb.rows = n
        load_lineitem(session, n, arrays=arrays)
    log(f"tpch {sf_label}: gen={gen_s:.0f}s "
        f"load={time.perf_counter() - t0:.0f}s ({n} rows)")
    if not big:
        res["values"]["py_baseline"] = interpreted_q6_baseline(arrays)
    got = session.query(TPCH_Q6)[0][0]
    log("q6 ran")
    assert got is not None and got.unscaled == q6_oracle(arrays), "q6"
    log("q6 digest OK")
    check_q1(session.query(TPCH_Q1), arrays)
    log("digests OK; timing")
    q6_ts = times(lambda: session.query(TPCH_Q6), repeat)
    note_attribution(res, f"q6_{sf_label}", session)
    q1_ts = times(lambda: session.query(TPCH_Q1), repeat)
    note_attribution(res, f"q1_{sf_label}", session)
    l6, q6_rps = report(f"q6_{sf_label}", q6_ts, n)
    l1, q1_rps = report(f"q1_{sf_label}", q1_ts, n)
    lines += [l6, l1]
    res["values"][f"q6_{'big' if big else 'small'}"] = q6_rps
    res["values"][f"q1_{'big' if big else 'small'}"] = q1_rps
    res["values"]["rows_" + ("big" if big else "small")] = n
    if big:
        # staged widths (client._narrow_stats): shipdate int16, discount
        # int8, quantity int16, extendedprice int32 (+1B valid lane each,
        # +1B shared visibility)
        lines.append(_hbm_line(f"q6_{sf_label}", q6_ts[len(q6_ts) // 2],
                               n, (2 + 1 + 2 + 4) + 4 + 1))
        # q1 staged widths: shipdate/quantity int16, extendedprice int32,
        # returnflag/linestatus/discount/tax int8 (+7 valid lanes +vis)
        lines.append(_hbm_line(f"q1_{sf_label}", q1_ts[len(q1_ts) // 2],
                               n, (2 + 2 + 4 + 4) + 7 + 1))
        return

    # concurrent throughput (separate, labeled)
    n_clients = int(os.environ.get("BENCH_CLIENTS", 8))

    def throughput(sql, per=2) -> float:
        import threading

        sessions = [Session(session.storage, cop=session.cop)
                    for _ in range(n_clients)]
        for s in sessions:
            s.query(sql)
        errs: list[BaseException] = []

        def run(s):
            try:
                for _ in range(per):
                    s.query(sql)
            except BaseException as e:
                errs.append(e)

        best = 0.0
        for _ in range(2):
            threads = [threading.Thread(target=run, args=(s,))
                       for s in sessions]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errs:
                raise errs[0]
            best = max(best, n_clients * per * n /
                       (time.perf_counter() - t0))
        return best

    tput = throughput(TPCH_Q6)
    res["values"]["q6_concurrent"] = tput
    lines.append(f"q6 concurrent throughput ({n_clients} clients): "
                 f"{tput / 1e6:.1f}M rows/s")

    # Q10 — the fused join+agg+topn shape (device multi-key TopN over a
    # snowflake join) tracked every round at a small join-corpus scale
    from tidb_tpu.bench.tpch_data import generate_tpch, load_table
    q10_sf = float(os.environ.get("BENCH_Q10_SF", 1))
    t0 = time.perf_counter()
    with _Heartbeat(f"tpch-q10-sf{q10_sf:g}-gen+load") as hb:
        jdata = generate_tpch(q10_sf, 17)
        jdata.pop("partsupp", None)  # unused by q7/q8/q10: free first
        hb.rows = len(jdata["lineitem"]["l_orderkey"])
        js = Session()
        for t in ("customer", "orders", "lineitem", "nation", "part",
                  "supplier", "region"):
            load_table(js, t, jdata[t])
    log(f"q10 corpus sf{q10_sf:g}: gen+load="
        f"{time.perf_counter() - t0:.0f}s")
    res["values"]["q10_small"] = time_q10(
        res, js, jdata, f"q10_sf{q10_sf:g}", repeat)
    # Q7/Q8 — the EXTRACT-year grouped aggregations newly
    # device-resident in round 14b (ISSUE 14), on the same join corpus
    res["values"]["q7_small"] = time_q7(
        res, js, jdata, f"q7_sf{q10_sf:g}", repeat)
    res["values"]["q8_small"] = time_q8(
        res, js, jdata, f"q8_sf{q10_sf:g}", repeat)


def flight_joins(res: dict) -> None:
    from tidb_tpu.bench.tpch_data import generate_tpch, load_table
    from tidb_tpu.bench.tpch_queries import TPCH_QUERIES
    from tidb_tpu.session import Session

    _session_env()
    lines = res["lines"]
    join_sf = float(os.environ.get("BENCH_JOIN_SF", 10))
    repeat = int(os.environ.get("BENCH_REPEAT", 5))
    t0 = time.perf_counter()
    with _Heartbeat(f"tpch-join-sf{join_sf:g}-gen+load") as hb:
        jdata = generate_tpch(join_sf, 11)
        hb.rows = len(jdata["lineitem"]["l_orderkey"])
        js = Session()
        for t in jdata:
            load_table(js, t, jdata[t])
    jrows = len(jdata["lineitem"]["l_orderkey"])
    log(f"tpch join corpus sf{join_sf:g}: gen+load="
        f"{time.perf_counter() - t0:.0f}s ({jrows} lineitem rows)")
    want3 = q3_oracle(jdata)
    got3 = [(int(r[0]), r[1].unscaled) for r in js.query(
        TPCH_QUERIES["q3"])]
    assert got3 == want3, f"q3 digest: {got3[:3]} vs {want3[:3]}"
    want5 = q5_oracle(jdata)
    got5 = {r[0]: r[1].unscaled for r in js.query(TPCH_QUERIES["q5"])}
    nnames, _ = jdata["nation"]["n_name"]
    nat_by_name = {nm: int(k) for nm, k in zip(
        nnames, jdata["nation"]["n_nationkey"])}
    got5 = {nat_by_name[name]: v for name, v in got5.items()}
    assert got5 == want5, f"q5 digest: {got5} vs {want5}"
    log("join digests OK; timing q3/q5")
    q3_ts = times(lambda: js.query(TPCH_QUERIES["q3"]), repeat)
    note_attribution(res, f"q3_sf{join_sf:g}", js)
    q5_ts = times(lambda: js.query(TPCH_QUERIES["q5"]), repeat)
    note_attribution(res, f"q5_sf{join_sf:g}", js)
    l3, q3_rps = report(f"q3_sf{join_sf:g}", q3_ts, jrows)
    l5, q5_rps = report(f"q5_sf{join_sf:g}", q5_ts, jrows)
    lines += [l3, l5]
    res["values"]["q3"] = q3_rps
    res["values"]["q5"] = q5_rps


def flight_ssb(res: dict) -> None:
    from tidb_tpu.bench import ssb
    from tidb_tpu.session import Session

    _session_env()
    lines = res["lines"]
    ssb_sf = float(os.environ.get("BENCH_SSB_SF", 100))
    repeat = int(os.environ.get("BENCH_REPEAT", 5))
    # 14 distinct int64 column buffers (commitdate shares orderdate's)
    # adopted zero-copy + 2 int8 code arrays + int32 dict columns +
    # generator transients
    n = _scale_to_ram(int(ssb.ROWS_PER_SF * ssb_sf), 155.0, "ssb", lines)
    sf = n / ssb.ROWS_PER_SF
    t0 = time.perf_counter()
    with _Heartbeat(f"ssb-sf{sf:g}-gen+load") as hb:
        lo = ssb.generate_lineorder(sf)
        hb.rows = len(lo["lo_orderdate"]) if "lo_orderdate" in lo else 0
        ss = Session()
        nrows_ssb = ssb.load_ssb(ss, sf, lineorder=lo)
        hb.rows = nrows_ssb
    log(f"ssb sf{sf:g}: gen+load={time.perf_counter() - t0:.0f}s "
        f"({nrows_ssb} lineorder rows)")
    for q in ("q1.1", "q1.2", "q1.3"):
        got = ss.query(ssb.SSB_QUERIES[q])[0][0]
        assert got is not None and int(got) == ssb.q1_oracle(lo, q), q
        ts = times(lambda sql=ssb.SSB_QUERIES[q]: ss.query(sql), repeat)
        note_attribution(res, f"ssb_{q}_sf{sf:g}", ss)
        line, rps = report(f"ssb_{q}_sf{sf:g}", ts, nrows_ssb)
        lines.append(line)
        res["values"][f"ssb_{q}"] = rps


def flight_cb(res: dict) -> None:
    from tidb_tpu.bench import clickbench as cbench
    from tidb_tpu.session import Session

    _session_env()
    lines = res["lines"]
    cb_rows = int(float(os.environ.get("BENCH_CB_ROWS", 1e8)))
    repeat = int(os.environ.get("BENCH_REPEAT", 5))
    cb_rows = _scale_to_ram(cb_rows, 110.0, "clickbench", lines)
    t0 = time.perf_counter()
    with _Heartbeat("clickbench-gen+load") as hb:
        hits = cbench.generate_hits(cb_rows)
        hb.rows = cb_rows
        cs = Session()
        cbench.load_hits(cs, cb_rows, hits=hits)
    log(f"clickbench hits_{cb_rows // 1_000_000}m: gen+load="
        f"{time.perf_counter() - t0:.0f}s")
    for q, sql in cbench.CB_QUERIES.items():
        got = cs.query(sql)
        want = cbench.cb_oracle(hits, q)
        if q in ("cb_scan", "cb_sum"):
            ok = int(got[0][0]) == want
        elif q == "cb_agg":
            ok = (int(got[0][0]), int(got[0][1])) == want
        else:
            ok = [(int(a), int(b)) for a, b in got] == want
        assert ok, f"{q} digest"
        ts = times(lambda s2=sql: cs.query(s2), repeat)
        note_attribution(res, q, cs)
        line, rps = report(q, ts, cb_rows)
        lines.append(line)
        res["values"][q] = rps


def flight_multichip(res: dict) -> None:
    """Mesh data plane: Q1/Q6-class scan+agg over epochs sharded across
    the device mesh vs the single-device path — per-query rows/s for
    both, plus per-device placement (shard spec + bytes per device from
    `arr.sharding` / `addressable_shards`). Runs on the host's real
    chips and fails on a single-device backend; only BENCH_PLATFORM=cpu
    gives it BENCH_MESH_DEVICES virtual CPU devices (a control-flow
    run, labelled as such on every line)."""
    import jax

    dev = _session_env(
        cpu_devices=int(os.environ.get("BENCH_MESH_DEVICES", 8)))
    if dev["count"] < 2:
        raise RuntimeError(
            f"multichip flight needs a multi-device backend, have "
            f"{dev['count']} device (BENCH_PLATFORM=cpu runs it on "
            f"BENCH_MESH_DEVICES virtual CPU devices)")
    from tidb_tpu.bench.tpch import TPCH_Q1, TPCH_Q6, load_lineitem
    from tidb_tpu.copr import mesh as M
    from tidb_tpu.copr.client import CopClient
    from tidb_tpu.session import Session

    lines = res["lines"]
    n_dev = len(jax.devices())
    repeat = int(os.environ.get("BENCH_REPEAT", 5))
    n = _scale_to_ram(int(float(os.environ.get("BENCH_MESH_ROWS", 4e6))),
                      115.0, "multichip", lines)
    log(f"multichip: {n_dev} devices, {n} rows")
    with _Heartbeat("multichip-gen") as hb:
        arrays = generate_lineitem_chunked(n, hb)
    single = Session(cop=CopClient())
    with _Heartbeat("multichip-load") as hb:
        hb.rows = n
        load_lineitem(single, n, arrays=arrays)
    plane = M.MeshPlane(M.MeshConfig(
        enabled=True, shard_threshold_rows=min(1 << 20, max(n // 2, 1))))
    mesh = Session(single.storage, cop=plane.client_for(single.storage))
    res["values"]["mesh_devices"] = n_dev
    lines.append(f"multichip: {n_dev} devices "
                 f"(active={plane.active}), {n} rows")

    want6 = q6_oracle(arrays)
    got = mesh.query(TPCH_Q6)[0][0]
    assert got is not None and got.unscaled == want6, "mesh q6 digest"
    assert single.query(TPCH_Q6)[0][0].unscaled == want6
    check_q1(mesh.query(TPCH_Q1), arrays)
    log("multichip digests OK (mesh == single == oracle); timing")

    # flight-recorder snapshot rides the result: per-query skew +
    # per-operator max-shard share, per-device bytes, exchange totals —
    # MULTICHIP_r06+ records placement QUALITY, not just rows/s
    from tidb_tpu import obs as _obs
    mesh_info: dict = {"devices": n_dev, "queries": {}}
    for name, sql in (("q6", TPCH_Q6), ("q1", TPCH_Q1)):
        ts_s = times(lambda s=sql: single.query(s), repeat)
        ts_m = times(lambda s=sql: mesh.query(s), repeat)
        note_attribution(res, f"multichip_{name}_mesh", mesh)
        _, rps_s = report(f"{name}_single", ts_s, n)
        _, rps_m = report(f"{name}_mesh", ts_m, n)
        res["values"][f"{name}_single_1dev"] = rps_s
        res["values"][f"{name}_mesh_{n_dev}dev"] = rps_m
        om = mesh.last_op_mesh
        skew = max((v[1] for v in om.values()), default=0.0)
        mesh_info["queries"][name] = {
            "skew": round(skew, 3),
            "op_shares": {k: round(v[0], 4) for k, v in om.items()},
        }
        lines.append(
            f"multichip {name}: single-device "
            f"{rps_s / 1e6:.1f}M rows/s vs {n_dev}-device mesh "
            f"{rps_m / 1e6:.1f}M rows/s ({rps_m / rps_s:.2f}x), "
            f"skew={skew:.2f}")

    rep = M.placement_report(mesh.cop)
    lines.append(
        f"multichip placement: {rep['sharded_arrays']} sharded + "
        f"{rep['replicated_arrays']} replicated arrays, "
        f"spec={rep['shard_spec']}")
    for dev in sorted(rep["device_bytes"]):
        lines.append(f"multichip placement {dev}: "
                     f"{rep['device_bytes'][dev]} bytes")
    res["values"]["mesh_device_bytes"] = rep["device_bytes"]
    res["values"]["mesh_sharded_arrays"] = rep["sharded_arrays"]
    mesh_info["device_bytes"] = rep["device_bytes"]
    mesh_info["device_peak_bytes"] = plane.device_peak_bytes()
    mesh_info["reshard_bytes_total"] = _obs.MESH_RESHARD_BYTES.get()
    res["mesh"] = mesh_info
    lines.append(
        f"multichip exchange: "
        f"{int(mesh_info['reshard_bytes_total'])} reshard bytes total")

    # Q10 over the mesh: the fused join+agg+topn shape executing
    # partition-wise (sharded probe, candidate blocks per device) vs the
    # single-device path — both digest-checked against the oracle. Runs
    # AFTER the placement report above: its corpus REPLACES the flight's
    # lineitem table (load_table drops + recreates), and the placement/
    # device-bytes record must keep describing the main workload.
    from tidb_tpu.bench.tpch_data import generate_tpch, load_table
    q10_sf = max(0.1, min(float(os.environ.get(
        "BENCH_MESH_Q10_SF", n / ROWS_PER_SF)), 10.0))
    with _Heartbeat(f"multichip-q10-sf{q10_sf:g}-gen+load") as hb:
        jdata = generate_tpch(q10_sf, 17)
        jdata.pop("partsupp", None)  # unused by q7/q8/q10: free first
        hb.rows = len(jdata["lineitem"]["l_orderkey"])
        for t in ("customer", "orders", "lineitem", "nation", "part",
                  "supplier", "region"):
            load_table(single, t, jdata[t])
    jrows = len(jdata["lineitem"]["l_orderkey"])
    rps_s10 = time_q10(res, single, jdata, "multichip_q10_single", repeat)
    rps_m10 = time_q10(res, mesh, jdata, "multichip_q10_mesh", repeat)
    res["values"]["q10_single_1dev"] = rps_s10
    res["values"][f"q10_mesh_{n_dev}dev"] = rps_m10
    om = mesh.last_op_mesh
    mesh_info["queries"]["q10"] = {
        "skew": round(max((v[1] for v in om.values()), default=0.0), 3),
        "op_shares": {k: round(v[0], 4) for k, v in om.items()},
    }
    lines.append(
        f"multichip q10 ({jrows} lineitem rows): single-device "
        f"{rps_s10 / 1e6:.1f}M rows/s vs {n_dev}-device mesh "
        f"{rps_m10 / 1e6:.1f}M rows/s ({rps_m10 / max(rps_s10, 1):.2f}x)")
    # Q7/Q8 — the round-14b grouped-aggregation conversions, sharded vs
    # single on the same corpus (ISSUE 14's missing number)
    for qname, timer in (("q7", time_q7), ("q8", time_q8)):
        rps_s = timer(res, single, jdata,
                      f"multichip_{qname}_single", repeat)
        rps_m = timer(res, mesh, jdata,
                      f"multichip_{qname}_mesh", repeat)
        res["values"][f"{qname}_single_1dev"] = rps_s
        res["values"][f"{qname}_mesh_{n_dev}dev"] = rps_m
        om = mesh.last_op_mesh
        mesh_info["queries"][qname] = {
            "skew": round(max((v[1] for v in om.values()),
                              default=0.0), 3),
            "op_shares": {k: round(v[0], 4) for k, v in om.items()},
        }
        lines.append(
            f"multichip {qname} ({jrows} lineitem rows): single-device "
            f"{rps_s / 1e6:.1f}M rows/s vs {n_dev}-device mesh "
            f"{rps_m / 1e6:.1f}M rows/s ({rps_m / max(rps_s, 1):.2f}x)")
    # dispatch ring taken LAST so the q10 dispatches are in the record
    mesh_info["dispatches"] = mesh.cop.recorder.snapshot()["dispatches"]


def flight_replica_read(res: dict) -> None:
    """Follower read tier: read QPS against ONE leader vs the same
    leader with serving follower REPLICA PROCESSES (real processes, so
    the offloaded compute actually leaves the router's CPU), p50/p99
    per mode and the routed fraction. The scaling claim of ROADMAP
    item 2 — read throughput grows with node count — gets a recorded
    number."""
    import shutil
    import signal as _signal

    _session_env()
    from tidb_tpu.session import Session
    from tidb_tpu.store.storage import Storage

    lines = res["lines"]
    n = int(float(os.environ.get("BENCH_REPLICA_ROWS", 1e5)))
    n_followers = int(os.environ.get("BENCH_REPLICA_FOLLOWERS", 2))
    workers = int(os.environ.get("BENCH_REPLICA_WORKERS", 8))
    seconds = float(os.environ.get("BENCH_REPLICA_SECONDS", 8))
    tmp = tempfile.mkdtemp(prefix="bench-replica-")
    procs: list[subprocess.Popen] = []
    leader = None
    try:
        leader = Storage(os.path.join(tmp, "leader"), shared=True,
                         rpc_listen="127.0.0.1:0")
        sess = Session(leader)
        sess.execute("create table rr (id bigint primary key, "
                     "grp bigint, v bigint)")
        rng = np.random.default_rng(7)
        vals = rng.integers(0, 1000, size=n)
        with _Heartbeat("replica-load") as hb:
            batch = 2000
            for lo in range(0, n, batch):
                hi = min(lo + batch, n)
                rows = ",".join(
                    f"({i},{i % 97},{int(vals[i])})"
                    for i in range(lo, hi))
                sess.execute(f"insert into rr values {rows}")
                hb.rows = hi
        addr = f"127.0.0.1:{leader.rpc_server.port}"
        code = (
            "import sys\n"
            "from tidb_tpu.store.storage import Storage\n"
            "import time\n"
            "s = Storage(sys.argv[1], remote=sys.argv[2])\n"
            "print('follower ready', flush=True)\n"
            "time.sleep(1e9)\n")
        # one process per chip: this flight process holds it, so the
        # follower replicas are pinned to XLA's CPU backend — their
        # scans are host work, and the board line below says so
        env = dict(os.environ, TIDB_TPU_REPLICA_APPLY_MS="100",
                   JAX_PLATFORMS="cpu")
        for i in range(n_followers):
            procs.append(subprocess.Popen(
                [sys.executable, "-c", code,
                 os.path.join(tmp, f"f{i}"), addr],
                stdout=sys.stderr, stderr=sys.stderr, env=env,
                cwd=os.path.dirname(os.path.abspath(__file__))))
        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline:
            serving = [m for m in leader.rpc_server.members()
                       if m["role"] == "follower" and m.get("serving")]
            if len(serving) >= n_followers:
                break
            time.sleep(0.25)
        else:
            raise RuntimeError(
                f"followers never started serving: "
                f"{leader.rpc_server.members()}")
        log(f"replica_read: {n_followers} serving followers up, "
            f"{n} rows, {workers} workers x {seconds:.0f}s per mode")

        queries = [f"select sum(v), count(*) from rr where grp = {g}"
                   for g in range(97)]

        def run_mode(mode: str) -> dict:
            lat: list[list[float]] = [[] for _ in range(workers)]
            stop = threading.Event()

            def work(wi: int) -> None:
                s = Session(leader)
                s.execute(f"set tidb_replica_read = '{mode}'")
                k = wi
                while not stop.is_set():
                    t0 = time.perf_counter()
                    s.query(queries[k % len(queries)])
                    lat[wi].append(time.perf_counter() - t0)
                    k += 1

            # warm both paths (compile) before the timed window; the
            # routed-fraction baseline snapshots AFTER the warm query
            warm = Session(leader)
            warm.execute(f"set tidb_replica_read = '{mode}'")
            warm.query(queries[0])
            served0 = leader.obs.replica_reads.get(outcome="served")
            threads = [threading.Thread(target=work, args=(i,),
                                        daemon=True)
                       for i in range(workers)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            time.sleep(seconds)
            stop.set()
            for t in threads:
                t.join(timeout=30.0)
            wall = time.perf_counter() - t0
            alls = sorted(x for ws in lat for x in ws)
            total = len(alls)
            served = leader.obs.replica_reads.get(
                outcome="served") - served0
            return {
                "qps": total / wall,
                "p50_ms": alls[total // 2] * 1e3 if alls else 0.0,
                "p99_ms": alls[min(total - 1, int(total * 0.99))] * 1e3
                if alls else 0.0,
                "routed_fraction": served / total if total else 0.0,
            }

        base = run_mode("leader")
        routed = run_mode("follower")
        res["values"]["replica_read_qps_leader"] = round(base["qps"], 1)
        res["values"]["replica_read_qps_routed"] = \
            round(routed["qps"], 1)
        res["values"]["replica_read_routed_fraction"] = \
            round(routed["routed_fraction"], 3)
        res["values"]["replica_read_followers"] = n_followers
        for mode, r in (("leader-only", base),
                        (f"leader+{n_followers}f", routed)):
            lines.append(
                f"replica_read {mode}: {r['qps']:.0f} QPS "
                f"p50={r['p50_ms']:.1f}ms p99={r['p99_ms']:.1f}ms "
                f"routed={r['routed_fraction']:.0%}")
        lines.append(
            f"replica_read scaling: {routed['qps'] / max(base['qps'], 1e-9):.2f}x "
            f"QPS with {n_followers} serving followers "
            f"({workers} workers, {n} rows; followers are child "
            f"processes pinned to JAX_PLATFORMS=cpu — routed reads are "
            f"host work, only the leader is on the flight's device)")

        # ranged phase: the same routed read with the range plane
        # armed as a 4-range leader fleet and the range-aware covering
        # gate on — every SELECT must be covered by the min published
        # closed_ts over the ranges its span touches, so the board
        # carries the gate's real cost: QPS under the gate plus the
        # fraction of worker busy-time spent in the covered_ts wait
        from tidb_tpu import obs as _obs
        from tidb_tpu.kv import tablecodec as _tc
        tid = leader.catalog.table("test", "rr").id
        splits = [_tc.record_key(int(tid), h)
                  for h in (n // 4, n // 2, 3 * n // 4)]
        leader.arm_ranges(enabled=True, split_points=splits,
                          lease_ms=150)
        leader.replica_read.range_aware = True
        nr = len(leader.ranges.server.specs)
        log(f"replica_read: range plane armed ({nr} ranges), "
            "range-aware covering gate on")
        wait0 = _obs.WAIT_SECONDS_TOTAL.get(state="covered_ts")
        ranged = run_mode("follower")
        waited = _obs.WAIT_SECONDS_TOTAL.get(
            state="covered_ts") - wait0
        busy = workers * seconds
        res["values"]["replica_read_qps_ranged"] = \
            round(ranged["qps"], 1)
        res["values"]["replica_read_covered_wait_fraction"] = \
            round(waited / busy, 4)
        res["values"]["replica_read_ranges"] = nr
        lines.append(
            f"replica_read ranged ({nr} ranges, gate on): "
            f"{ranged['qps']:.0f} QPS p50={ranged['p50_ms']:.1f}ms "
            f"p99={ranged['p99_ms']:.1f}ms "
            f"routed={ranged['routed_fraction']:.0%} "
            f"covered-ts wait {waited / busy:.1%} of busy time")
        lines.append(
            f"replica_read gate cost: "
            f"{ranged['qps'] / max(routed['qps'], 1e-9):.2f}x QPS vs "
            f"ungated routed read (fresh read_ts waits for the next "
            f"closed-ts heartbeat)")
    finally:
        for p in procs:
            try:
                p.send_signal(_signal.SIGTERM)
            except OSError:
                pass
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=10)
        if leader is not None:
            leader.close()
        shutil.rmtree(tmp, ignore_errors=True)


def _mini_client_module():
    """tests/mysql_client.py loaded by path (the wire flights reuse the
    independent protocol encoding the server tests are pinned by)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_mysql_client",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "tests", "mysql_client.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def flight_htap_mixed(res: dict) -> None:
    """The HTAP promise, measured: concurrent wire-path point
    get/update streams against Q1/Q6 analytical scans on ONE durable
    (sync-log=commit) server — the first recorded mixed workload.

    Board numbers: point p50/p99 (alone and under scan pressure),
    durable write QPS at 1/8/32 writers (cross-commit group fsync —
    amortization read from tidb_group_commit_batch_size), concurrent
    Q1/Q6 rows/s, and Top SQL attribution across the whole mix. The
    point ops run over the WIRE and must take the fast-path bypass
    (asserted via the `point` engine tag before anything is timed)."""
    import shutil

    _session_env()
    from tidb_tpu.bench.tpch import TPCH_Q1, TPCH_Q6, load_lineitem
    from tidb_tpu.server.server import Server
    from tidb_tpu.session import Session
    from tidb_tpu.store.storage import Storage

    mc = _mini_client_module()
    lines = res["lines"]
    point_rows = int(float(os.environ.get("BENCH_HTAP_POINT_ROWS", 1e5)))
    scan_rows = _scale_to_ram(
        int(float(os.environ.get("BENCH_HTAP_SCAN_ROWS",
                                 2_000_000))), 115.0, "htap scan", lines)
    seconds = float(os.environ.get("BENCH_HTAP_SECONDS", 6))
    readers = int(os.environ.get("BENCH_HTAP_READERS", 4))
    tmp = tempfile.mkdtemp(prefix="bench-htap-")
    server = None
    storage = None
    try:
        storage = Storage(os.path.join(tmp, "db"), sync_log="commit")
        storage.obs.topsql.configure(enabled=True, window_s=600)
        sess = Session(storage)
        sess.execute("create table sbtest (id bigint primary key, "
                     "k bigint, c varchar(64))")
        with _Heartbeat("htap-point-load") as hb:
            for lo in range(0, point_rows, 2000):
                hi = min(lo + 2000, point_rows)
                sess.execute("insert into sbtest values " + ",".join(
                    f"({i},{i % 1000},'c{i:020d}')"
                    for i in range(lo, hi)))
                hb.rows = hi
        with _Heartbeat("htap-lineitem-gen") as hb:
            arrays = generate_lineitem_chunked(scan_rows, hb)
        with _Heartbeat("htap-lineitem-load") as hb:
            hb.rows = scan_rows
            load_lineitem(sess, scan_rows, arrays=arrays)
        server = Server(storage, port=0, max_connections=256)
        server.start()
        addr = ("127.0.0.1", server.port)

        # the bypass gate BEFORE timing anything: wire-path point ops
        # must show the `point` engine (EXPLAIN ANALYZE surfaces it)
        probe = mc.MiniClient(*addr)
        ea = probe.query(
            "explain analyze select id, k from sbtest where id = 5")
        assert ea and ea[0][3] == "point", f"point bypass lost: {ea}"
        lines.append(f"htap point path: {ea[0][0]} engine={ea[0][3]} "
                     f"[{ea[0][4]}]")
        probe.close()

        def run_phase(n_read: int, n_write: int, n_scan: int,
                      secs: float) -> dict:
            stop = threading.Event()
            read_lat: list[list[float]] = [[] for _ in range(n_read)]
            write_lat: list[list[float]] = [[] for _ in range(n_write)]
            scan_counts = {"q1": [], "q6": []}
            errs: list[BaseException] = []

            def points(wi: int, lat: list, write: bool) -> None:
                try:
                    cl = mc.MiniClient(*addr)
                    rng = np.random.default_rng(1000 * wi + int(write))
                    ids = rng.integers(0, point_rows, size=1 << 14)
                    j = 0
                    while not stop.is_set():
                        i = int(ids[j & 0x3FFF])
                        j += 1
                        t0 = time.perf_counter()
                        if write:
                            cl.execute("update sbtest set k = k + 1 "
                                       f"where id = {i}")
                        else:
                            cl.query("select id, k, c from sbtest "
                                     f"where id = {i}")
                        lat.append(time.perf_counter() - t0)
                    cl.close()
                except BaseException as e:  # noqa: BLE001
                    errs.append(e)

            def scans() -> None:
                try:
                    cl = mc.MiniClient(*addr)
                    while not stop.is_set():
                        for name, sql in (("q6", TPCH_Q6),
                                          ("q1", TPCH_Q1)):
                            t0 = time.perf_counter()
                            cl.query(sql)
                            scan_counts[name].append(
                                time.perf_counter() - t0)
                            if stop.is_set():
                                break
                    cl.close()
                except BaseException as e:  # noqa: BLE001
                    errs.append(e)

            threads = (
                [threading.Thread(target=points, args=(i, read_lat[i],
                                                       False))
                 for i in range(n_read)]
                + [threading.Thread(target=points, args=(i, write_lat[i],
                                                         True))
                   for i in range(n_write)]
                + [threading.Thread(target=scans)
                   for _ in range(n_scan)])
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            time.sleep(secs)
            stop.set()
            for t in threads:
                t.join(timeout=60.0)
            wall = time.perf_counter() - t0
            if errs:
                raise errs[0]
            reads = sorted(x for ws in read_lat for x in ws)
            writes = sorted(x for ws in write_lat for x in ws)

            def pct(v: list, q: float) -> float:
                return v[min(len(v) - 1, int(len(v) * q))] * 1e3 \
                    if v else 0.0

            return {
                "wall": wall,
                "read_qps": len(reads) / wall,
                "write_qps": len(writes) / wall,
                "read_p50": pct(reads, 0.5), "read_p99": pct(reads, 0.99),
                "write_p50": pct(writes, 0.5),
                "write_p99": pct(writes, 0.99),
                "scans": {k: list(v) for k, v in scan_counts.items()},
            }

        # ---- durable write QPS by concurrency (group-fsync scaling) ----
        hist = storage.obs.group_commit_batch
        for conc in (1, 8, 32):
            _, sum0, n0 = hist.snapshot()
            ph = run_phase(0, conc, 0, seconds)
            _, sum1, n1 = hist.snapshot()
            batches = n1 - n0
            avg_batch = (sum1 - sum0) / batches if batches else 1.0
            res["values"][f"htap_write_qps_{conc}"] = \
                round(ph["write_qps"], 1)
            res["values"][f"htap_group_batch_{conc}"] = \
                round(avg_batch, 2)
            lines.append(
                f"htap_mixed write x{conc}: {ph['write_qps']:.0f} "
                f"durable QPS p50={ph['write_p50']:.2f}ms "
                f"p99={ph['write_p99']:.2f}ms "
                f"(group fsync avg batch {avg_batch:.1f} over "
                f"{batches} fsyncs)")
        q1 = res["values"].get("htap_write_qps_1", 0) or 1
        res["values"]["htap_write_scaling_32x"] = round(
            res["values"].get("htap_write_qps_32", 0) / q1, 2)
        lines.append(
            f"htap_mixed write scaling: "
            f"{res['values']['htap_write_scaling_32x']:.1f}x QPS at 32 "
            "writers vs 1 under sync-log=commit")

        # ---- wait-profile zero-overhead check: the x8 write phase
        # again with performance.wait-profile-enabled on (per-statement
        # typed ledger + windowed digest attribution) ----
        storage.obs.waitprofile.configure(enabled=True)
        try:
            wp_ph = run_phase(0, 8, 0, seconds)
        finally:
            storage.obs.waitprofile.configure(enabled=False)
        base = res["values"].get("htap_write_qps_8", 0) or 1
        res["values"]["htap_write_qps_8_wp"] = round(wp_ph["write_qps"], 1)
        res["values"]["htap_wp_ratio"] = round(
            wp_ph["write_qps"] / base, 3)
        lines.append(
            f"htap_mixed write x8 +wait-profile: "
            f"{wp_ph['write_qps']:.0f} durable QPS "
            f"({res['values']['htap_wp_ratio']:.3f}x of ledger-off)")
        wrows = storage.obs.waitprofile.table_rows()
        upd = [r for r in wrows if "update" in (r[2] or "")][:3]
        for r in upd:
            lines.append(
                f"htap_mixed waitprofile: {r[6]} {r[7]:.1f}ms "
                f"({r[8]:.0%} of wall) — {r[2][:60]}")

        # ---- point reads alone (baseline), then the full HTAP mix ----
        warm = mc.MiniClient(*addr)
        warm.query(TPCH_Q6)
        warm.query(TPCH_Q1)  # compile outside the timed window
        warm.close()
        alone = run_phase(readers, 0, 0, seconds)
        mixed = run_phase(readers, 8, 1, max(seconds, 8.0))
        res["values"]["htap_point_qps"] = round(mixed["read_qps"], 1)
        res["values"]["htap_point_p50_ms"] = round(mixed["read_p50"], 3)
        res["values"]["htap_point_p99_ms"] = round(mixed["read_p99"], 3)
        res["values"]["htap_point_alone_p99_ms"] = \
            round(alone["read_p99"], 3)
        lines.append(
            f"htap_mixed point alone x{readers}: "
            f"{alone['read_qps']:.0f} QPS p50={alone['read_p50']:.2f}ms "
            f"p99={alone['read_p99']:.2f}ms")
        for name in ("q6", "q1"):
            ts = mixed["scans"][name]
            if ts:
                p50 = sorted(ts)[len(ts) // 2]
                rps = scan_rows / p50
                res["values"][f"htap_scan_{name}_rows_s"] = round(rps)
                lines.append(
                    f"htap_mixed {name} under mix: {rps / 1e6:.1f}M "
                    f"rows/s ({len(ts)} scans, p50={p50 * 1e3:.0f}ms)")
        lines.append(
            f"htap_mixed point under mix x{readers} (+8 writers, "
            f"+Q1/Q6 stream): {mixed['read_qps']:.0f} QPS "
            f"p50={mixed['read_p50']:.2f}ms p99={mixed['read_p99']:.2f}ms")

        # ---- Top SQL attribution for the whole mix ----
        digests: dict[str, dict] = {}
        for b in storage.obs.topsql.snapshot():
            ents = list(b["digests"].values())
            if b["other"] is not None:
                ents.append(b["other"])
            for e in ents:
                d = digests.setdefault(e["digest"], {
                    "text": e["digest_text"], "execs": 0, "wall_ms": 0.0})
                d["execs"] += e["exec_count"]
                d["wall_ms"] += e["sum_wall_s"] * 1e3
        top = sorted(digests.values(), key=lambda d: -d["wall_ms"])[:5]
        for d in top:
            lines.append(
                f"htap_mixed topsql: {d['wall_ms']:.0f}ms over "
                f"{d['execs']} execs — {d['text'][:72]}")
        res["topsql"] = top
    finally:
        if server is not None:
            server.close()
        if storage is not None:
            storage.close()
        shutil.rmtree(tmp, ignore_errors=True)


def flight_range_write(res: dict) -> None:
    """Range-sharded write leadership: DURABLE (sync-log=commit,
    percolator 2PC through the range RPC tier) write QPS against ONE
    range leader vs N — the write-scaling claim of the range plane.
    With one range every commit serializes behind one WAL stream; with
    N ranges the same workload fans out over N independently-fsynced
    engines, so durable QPS should grow until the disk saturates."""
    import shutil

    _session_env()
    from tidb_tpu.kv.mvcc import OP_PUT, Mutation
    from tidb_tpu.kv.rangeclient import RangeRouter
    from tidb_tpu.kv.rangemeta import split_keyspace
    from tidb_tpu.kv.tso import TimestampOracle
    from tidb_tpu.kv.twopc import TwoPhaseCommitter
    from tidb_tpu.rpc.ranged import RangeServer

    from tidb_tpu import obs as _obs

    lines = res["lines"]
    n_leaders = int(os.environ.get("BENCH_RANGE_LEADERS", 4))
    workers = int(os.environ.get("BENCH_RANGE_WORKERS", 8))
    seconds = float(os.environ.get("BENCH_RANGE_SECONDS", 6))
    # third phase: the wait-profile zero-overhead check — the same
    # n_leaders workload with a fresh per-txn WaitLedger installed
    # (what performance.wait-profile-enabled costs this path)
    from tidb_tpu.obs_heat import RangeHeatRecorder

    qps: dict[tuple[int, bool], float] = {}
    heat_board: dict = {}
    for count, with_ledger in ((1, False), (n_leaders, False),
                               (n_leaders, True)):
        tmp = tempfile.mkdtemp(prefix=f"bench-range-{count}-")
        srv = None
        routers: list = []
        # the n-leader phase runs with the keyspace heat plane armed:
        # the flight result carries the observed per-range traffic
        # split (the keyspace-balance trail of the scaling claim)
        heat = None
        if count == n_leaders and not with_ledger:
            heat = RangeHeatRecorder()
            heat.configure(enabled=True, bucket_seconds=1,
                           sustained_buckets=1)
            heat.set_specs(split_keyspace(count))
        try:
            srv = RangeServer(tmp, lease_ms=60_000,
                              specs=split_keyspace(count),
                              sync_log="commit", heat=heat)
            tso = TimestampOracle()
            stop = threading.Event()
            counts = [0] * workers
            # uniform single-key txns spread across the keyspace: the
            # SAME workload both phases, only the range count changes
            def worker(w: int) -> None:
                router = RangeRouter(root=tmp)
                routers.append(router)
                committer = TwoPhaseCommitter(router, tso,
                                              lock_ttl=3000)
                i = 0
                while not stop.is_set():
                    if with_ledger:
                        # per-statement semantics: a fresh ledger per
                        # txn, like Session._execute_observed installs
                        _obs.install_wait_ledger(_obs.WaitLedger())
                    key = bytes([(w * 37 + i * 11) % 256]) + \
                        b"k%d.%d" % (w, i)
                    committer.commit(
                        [Mutation(OP_PUT, key, b"v%d" % i)], tso.ts())
                    counts[w] += 1
                    i += 1
                if with_ledger:
                    _obs.install_wait_ledger(None)
            threads = [threading.Thread(target=worker, args=(w,),
                                        name=f"bench-range-w{w}",
                                        daemon=True)
                       for w in range(workers)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            time.sleep(seconds)
            stop.set()
            for t in threads:
                t.join(timeout=30)
            wall = time.perf_counter() - t0
            qps[(count, with_ledger)] = sum(counts) / wall
            tag = " +wait-profile" if with_ledger else ""
            lines.append(
                f"range_write x{count} leader{'s' if count > 1 else ''}"
                f"{tag}: {qps[(count, with_ledger)]:.0f} durable txn/s "
                f"({workers} workers, sync-log=commit, "
                f"{sum(counts)} commits / {wall:.1f}s)")
            if heat is not None:
                payload = heat.debug_payload()
                heat_board = {
                    "ranges": payload.get("totals", {}),
                    "findings": payload.get("findings", []),
                    "heatmap": payload.get("heatmap", []),
                }
                writes = {rid: t[2] for rid, t
                          in heat_board["ranges"].items()}
                total_w = sum(writes.values()) or 1
                split = ", ".join(
                    f"r{rid}={w * 100.0 / total_w:.0f}%"
                    for rid, w in sorted(writes.items()))
                lines.append(f"range_write heat split: {split}")
                for hl in heat_board["heatmap"]:
                    lines.append(f"  {hl}")
                for f in heat_board["findings"]:
                    lines.append(
                        f"range_write heat finding: {f['rule']} "
                        f"{f['item']} {f['value']}")
        finally:
            for router in routers:
                router.close()
            if srv is not None:
                srv.close()
            shutil.rmtree(tmp, ignore_errors=True)
    res["values"]["range_write_qps_1"] = round(qps[(1, False)], 1)
    res["values"][f"range_write_qps_{n_leaders}"] = \
        round(qps[(n_leaders, False)], 1)
    res["values"]["range_write_scaling"] = round(
        qps[(n_leaders, False)] / max(qps[(1, False)], 1e-9), 2)
    res["values"]["range_write_leaders"] = n_leaders
    lines.append(
        f"range_write scaling: "
        f"{res['values']['range_write_scaling']:.2f}x durable write "
        f"QPS at {n_leaders} range leaders vs 1")
    res["heatmap"] = heat_board
    res["values"]["range_write_qps_wp"] = round(qps[(n_leaders, True)], 1)
    res["values"]["range_write_wp_ratio"] = round(
        qps[(n_leaders, True)] / max(qps[(n_leaders, False)], 1e-9), 3)

    # fourth phase: the acting loop under load — a skewed hot band on
    # ONE range with the auto-split actuator armed. The heat plane
    # advises a weighted-median split, the actuator executes it online
    # (writers keep committing through the epoch bump), and durable
    # QPS is sampled before/after the split lands.
    tmp = tempfile.mkdtemp(prefix="bench-range-autosplit-")
    srv = None
    routers = []
    heat = RangeHeatRecorder()
    heat.configure(enabled=True, bucket_seconds=1,
                   sustained_buckets=1, hot_ratio=1.5)
    heat.set_specs(split_keyspace(2))
    events = _obs.EventLog()
    try:
        srv = RangeServer(tmp, lease_ms=250, specs=split_keyspace(2),
                          sync_log="commit", heat=heat, events=events,
                          auto_split=True, split_cooldown_ms=0)
        tso = TimestampOracle()
        stop = threading.Event()
        counts = [0] * workers

        def hot_worker(w: int) -> None:
            router = RangeRouter(root=tmp)
            routers.append(router)
            committer = TwoPhaseCommitter(router, tso, lock_ttl=3000)
            i = 0
            while not stop.is_set():
                # every key inside one narrow band of range 1: the
                # classic hot-range shape the advisory targets
                key = b"\x10hot%04d" % ((w * 193 + i) % 512)
                committer.commit(
                    [Mutation(OP_PUT, key, b"v%d" % i)], tso.ts())
                counts[w] += 1
                i += 1

        threads = [threading.Thread(target=hot_worker, args=(w,),
                                    name=f"bench-autosplit-w{w}",
                                    daemon=True)
                   for w in range(workers)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        t_split = None
        pre_commits = 0
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            if t_split is None \
                    and len(srv.directory.load_specs()) >= 3:
                t_split = time.perf_counter()
                pre_commits = sum(counts)
            time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        wall = time.perf_counter() - t0
        total = sum(counts)
        res["values"]["range_write_auto_splits"] = srv._auto_splits
        if t_split is not None:
            pre_qps = pre_commits / max(t_split - t0, 1e-9)
            post_qps = (total - pre_commits) / max(wall -
                                                   (t_split - t0), 1e-9)
            res["values"]["range_write_qps_hot_pre"] = round(pre_qps, 1)
            res["values"]["range_write_qps_hot_post"] = round(post_qps, 1)
            lines.append(
                f"range_write auto-split: hot band split after "
                f"{t_split - t0:.1f}s — {pre_qps:.0f} txn/s on the "
                f"single hot range, {post_qps:.0f} txn/s once the "
                f"actuator partitioned it")
            for e in events.snapshot():
                if e["kind"] == "range_split":
                    lines.append(f"range_write auto-split event: "
                                 f"{e['detail']}")
        else:
            # an all-identical-keys or too-short run legitimately
            # yields no advisory — report, don't fail the flight
            lines.append(
                f"range_write auto-split: actuator did not fire in "
                f"{wall:.1f}s ({total} hot commits)")
    finally:
        for router in routers:
            router.close()
        if srv is not None:
            srv.close()
        shutil.rmtree(tmp, ignore_errors=True)
    lines.append(
        f"range_write wait-profile cost: "
        f"{res['values']['range_write_wp_ratio']:.3f}x QPS with the "
        "typed wait ledger on (fresh ledger per txn) vs off")


FLIGHTS = {
    "tpch_small": lambda res: flight_tpch(res, big=False),
    "tpch_big": lambda res: flight_tpch(res, big=True),
    "joins": flight_joins,
    "ssb": flight_ssb,
    "cb": flight_cb,
    "multichip": flight_multichip,
    "replica_read": flight_replica_read,
    "htap_mixed": flight_htap_mixed,
    "range_write": flight_range_write,
}


def _inspection_snapshot() -> list:
    """One inspection pass over every live Storage the flight built
    (the obs_inspect weak registry): rule findings + the event-ring
    tail. Best effort — a post-mortem must never raise."""
    try:
        from tidb_tpu import obs_inspect
        return obs_inspect.inspect_all()
    except BaseException as e:  # noqa: BLE001 — diagnosis is optional
        return [{"error": f"{type(e).__name__}: {str(e)[:200]}"}]


def run_flight_child(name: str, out_path: str) -> None:
    res = {"ok": False, "lines": [], "values": {}}

    # periodic partial dump (atomic tmp+rename): a flight the parent
    # SIGKILLs at the timeout — or the OOM killer takes — leaves its
    # latest inspection snapshot in the result file, so rc=124/rc=137
    # rounds carry a diagnosis instead of just a heartbeat tail. The
    # lock + stop re-check keep a mid-cycle dump from clobbering the
    # FINAL result if its join below times out.
    stop = threading.Event()
    out_lock = threading.Lock()

    def _dump_partial() -> None:
        import copy

        while not stop.wait(30.0):
            try:
                # deep copy with a retry: the flight thread mutates
                # res["values"]/res["lines"] concurrently, and a
                # mid-iteration mutation raises RuntimeError — exactly
                # during the active phases this snapshot exists for
                for _ in range(3):
                    try:
                        snap = copy.deepcopy(res)
                        break
                    except RuntimeError:
                        continue
                else:
                    continue  # busy dict; catch it next cycle
                snap["ok"] = False
                snap["partial"] = True
                snap["inspection"] = _inspection_snapshot()
                tmp = out_path + ".part.tmp"
                with open(tmp, "w") as f:
                    json.dump(snap, f, default=str)
                with out_lock:
                    if stop.is_set():
                        os.unlink(tmp)
                        return  # the final result owns the file now
                    os.replace(tmp, out_path)
            except BaseException:  # noqa: BLE001 — keep flying
                pass

    dumper = threading.Thread(target=_dump_partial, daemon=True,
                              name="bench-inspection-dump")
    dumper.start()
    try:
        FLIGHTS[name](res)
        res["ok"] = True
    except BaseException as e:  # noqa: BLE001 - report, parent decides
        res["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        res["inspection"] = _inspection_snapshot()
    finally:
        stop.set()
        dumper.join(timeout=2.0)
    # every board line names the device it was taken on
    from tidb_tpu import device
    dev = device.described()
    res["device"] = dev
    tag = f" [{device.line(dev)}]" if dev else " [no jax backend]"
    res["lines"] = [str(ln) + tag for ln in res["lines"]]
    with out_lock:
        # atomic like the periodic dumps: a kill landing mid-final-write
        # must not truncate away the last good partial snapshot
        tmp = out_path + ".final.tmp"
        with open(tmp, "w") as f:
            json.dump(res, f, default=str)
        os.replace(tmp, out_path)
    if not res["ok"]:
        log(f"flight {name} FAILED: {res.get('error')}")
        sys.exit(1)


# ---------------------------------------------------------------------------
# Round trajectory (ISSUE 15): compare this round against the previous
# committed BENCH_r*/MULTICHIP_r* record so a bench round produces a
# machine-read comparison, not just a JSON file nobody diffs.
# ---------------------------------------------------------------------------

_P50_RE = None  # compiled lazily


def parse_query_p50s(text: str) -> dict[str, float]:
    """Per-query p50 milliseconds from board text: every timed query
    reports through report() as '<name>: p50=NN.Nms ...', and the
    LEGACY round wrappers (r01..r06) carry the same lines in their
    stderr `tail` — one parser reads both eras."""
    import re
    global _P50_RE
    if _P50_RE is None:
        _P50_RE = re.compile(
            r"(?:^|\s)([A-Za-z_][\w.]*): p50=([0-9.]+)ms ")
    out: dict[str, float] = {}
    for m in _P50_RE.finditer(text):
        out[m.group(1)] = float(m.group(2))
    return out


def load_prev_round(prefix: str) -> tuple[int, Optional[dict]]:
    """Newest committed {prefix}_rNN.json next to this file ->
    (round_no, data); (0, None) when no round has ever landed."""
    import re
    here = os.path.dirname(os.path.abspath(__file__))
    best, data = 0, None
    for fn in sorted(os.listdir(here)):
        m = re.match(rf"{re.escape(prefix)}_r(\d+)\.json$", fn)
        if not m or int(m.group(1)) <= best:
            continue
        try:
            with open(os.path.join(here, fn)) as f:
                d = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        best, data = int(m.group(1)), d
    return best, data


def prev_round_p50s(data: Optional[dict]) -> dict[str, float]:
    """A previous round's per-query p50s: the structured `queries` map
    when the round wrote one (r07+), else parsed out of its board
    lines / stderr tail (the legacy wrapper format)."""
    if not isinstance(data, dict):
        return {}
    q = data.get("queries")
    if isinstance(q, dict):
        out = {}
        for k, v in q.items():
            try:
                out[str(k)] = float(v)
            except (TypeError, ValueError):
                continue
        return out
    text = "\n".join(str(ln) for ln in data.get("lines", []) or [])
    return parse_query_p50s(text + "\n" + str(data.get("tail", "")))


def compare_rounds(prev_no: int, prev_p50s: dict[str, float],
                   cur_p50s: dict[str, float],
                   ratio: float) -> dict:
    """The trajectory section: per-query prev/cur p50 + speedup, with
    regressions flagged by the SAME ratio knob the history plane's
    plan-regression rule uses (history.regression-ratio; env
    BENCH_REGRESSION_RATIO here — one threshold, two ends of the
    telemetry loop)."""
    deltas: dict[str, dict] = {}
    regressions: list[str] = []
    for name in sorted(set(cur_p50s) | set(prev_p50s)):
        cur = cur_p50s.get(name)
        prev = prev_p50s.get(name)
        if cur is None:
            # the worst regression of all: the query stopped producing
            # a number (flight died/timed out) — flag it, don't let it
            # vanish from the comparison
            regressions.append(name)
            deltas[name] = {"cur_ms": None, "prev_ms": prev,
                            "speedup": None, "regression": True}
            continue
        if prev is None or prev <= 0 or cur <= 0:
            deltas[name] = {"cur_ms": cur, "prev_ms": prev,
                            "speedup": None, "regression": False}
            continue
        speedup = prev / cur
        regressed = cur >= ratio * prev
        if regressed:
            regressions.append(name)
        deltas[name] = {"cur_ms": cur, "prev_ms": prev,
                        "speedup": round(speedup, 2),
                        "regression": regressed}
    return {"vs_round": prev_no, "regression_ratio": ratio,
            "deltas": deltas, "regressions": regressions}


def trajectory_lines(label: str, traj: dict) -> list[str]:
    """Board lines for one trajectory section, regressions loudest."""
    out = []
    if not traj["deltas"]:
        return [f"trajectory {label}: no comparable previous round"]
    for name, d in traj["deltas"].items():
        if d["cur_ms"] is None:
            out.append(
                f"trajectory {label} {name}: "
                f"{d['prev_ms']:.1f}ms -> MISSING (no result this "
                f"round) <- REGRESSION")
            continue
        if d["speedup"] is None:
            out.append(f"trajectory {label} {name}: {d['cur_ms']:.1f}ms "
                       "(new query, no r"
                       f"{traj['vs_round']:02d} point)")
            continue
        tag = " <- REGRESSION" if d["regression"] else ""
        out.append(
            f"trajectory {label} {name}: {d['prev_ms']:.1f}ms -> "
            f"{d['cur_ms']:.1f}ms ({d['speedup']:.2f}x vs "
            f"r{traj['vs_round']:02d}){tag}")
    if traj["regressions"]:
        out.append(
            f"trajectory {label}: {len(traj['regressions'])} "
            f"regression(s) >= {traj['regression_ratio']:g}x: "
            + ",".join(traj["regressions"]))
    return out


def _persist_round(path: str, payload: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    os.replace(tmp, path)
    log(f"round record written: {path}")


# ---------------------------------------------------------------------------
# Parent board
# ---------------------------------------------------------------------------

def _headline(values: dict, baseline_rps: float, lines_done: int) -> str:
    big = bool(values.get("q6_big"))
    rps = values.get("q6_big") or values.get("q6_small") or 0.0
    rows = values.get("rows_big" if big else "rows_small", 0)
    return json.dumps({
        "metric": "tpch_q6_rows_per_sec",
        "value": round(rps),
        "unit": "rows/s",
        "vs_baseline": round(rps / baseline_rps, 2) if baseline_rps else
        None,
        "scale": f"sf{round(rows / ROWS_PER_SF, 2):g}" if rows else
        "unknown",
        "baseline": "compiled C++ row-loop (native/baseline.cpp), "
                    "single-stream",
        "device": values.get("device"),
        "flights_done": lines_done,
    })


def main() -> None:
    if len(sys.argv) >= 4 and sys.argv[1] == "--flight":
        run_flight_child(sys.argv[2], sys.argv[4] if sys.argv[3] == "--out"
                         else sys.argv[3])
        return

    # ---- parent: measure the compiled baseline first (numpy-only) ----
    # A baseline failure must never cost the round its headline (the
    # round-4 lesson, generalized): flights still run, vs_baseline is
    # null, and the error is on the board.
    kv_rps = col_rps = q1_rps = 0.0
    baseline_err = None
    t0 = time.perf_counter()
    try:
        from tidb_tpu.bench.tpch import generate_lineitem_arrays

        sample = generate_lineitem_arrays(6_000_000)
        kv_rps, col_rps, q1_rps = compiled_baselines(sample)
        del sample
        log(f"compiled baselines ({time.perf_counter() - t0:.0f}s): "
            f"q6-kv-rowloop={kv_rps / 1e6:.0f}M rows/s, "
            f"q6-columnar-rowloop={col_rps / 1e6:.0f}M rows/s, "
            f"q1-kv-rowloop={q1_rps / 1e6:.0f}M rows/s (C++ -O3, "
            f"single-stream, native/baseline.cpp)")
    except Exception as e:  # noqa: BLE001 - headline must survive
        # (Exception, not BaseException: Ctrl-C/SystemExit still exit)
        baseline_err = f"{type(e).__name__}: {str(e)[:200]}"
        log(f"compiled baseline FAILED: {baseline_err}")

    # tpch_big FIRST: the SF100 north-star flight gets the freshest
    # machine (PR 9's datagen cache bounds its RSS) instead of paying
    # for everything that ran before it — two rounds died before the
    # big flight ever started (r04 rc=137, r05 rc=124)
    flight_names = os.environ.get(
        "BENCH_FLIGHTS",
        "tpch_big,tpch_small,joins,ssb,cb,multichip,replica_read,"
        "htap_mixed,range_write"
    ).split(",")
    timeout = float(os.environ.get("BENCH_FLIGHT_TIMEOUT", 5400))
    values: dict = {}
    flight_results: dict[str, dict] = {}
    all_lines: list[str] = [
        f"baseline_c_q6_kv_rowloop: {kv_rps / 1e6:.0f}M rows/s",
        f"baseline_c_q6_columnar_rowloop: {col_rps / 1e6:.0f}M rows/s",
        f"baseline_c_q1_kv_rowloop: {q1_rps / 1e6:.0f}M rows/s",
    ] if baseline_err is None else [f"compiled baseline FAILED: "
                                    f"{baseline_err}"]
    done = 0
    for name in flight_names:
        name = name.strip()
        if name not in FLIGHTS:
            log(f"unknown flight {name!r}; skipping")
            continue
        out = tempfile.NamedTemporaryFile(
            suffix=f".{name}.json", delete=False)
        out.close()
        log(f"=== flight {name} ===")
        t0 = time.perf_counter()
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--flight",
                 name, "--out", out.name],
                stdout=sys.stderr, stderr=sys.stderr, timeout=timeout)
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            rc = -1
            all_lines.append(f"flight {name} TIMED OUT after {timeout}s")
        try:
            with open(out.name) as f:
                res = json.load(f)
        except (OSError, json.JSONDecodeError):
            res = {"ok": False, "lines": [],
                   "error": f"no result file (rc={rc}"
                            f"{', likely OOM-killed' if rc == -9 else ''})"}
        os.unlink(out.name)
        flight_results[name] = res
        all_lines += res.get("lines", [])
        if res.get("ok"):
            values.update(res.get("values", {}))
            if res.get("values", {}).get("q6_big") or \
                    res.get("values", {}).get("q6_small"):
                values["device"] = res.get("device")  # the headline's
            done += 1
        else:
            all_lines.append(
                f"flight {name} FAILED: {res.get('error', f'rc={rc}')}")
            # the child's (possibly partial) inspection snapshot: the
            # diagnosis rides the board, not just the result JSON
            for snap in res.get("inspection", []) or []:
                findings = snap.get("findings") or []
                if snap.get("error"):
                    all_lines.append(
                        f"flight {name} inspection: {snap['error']}")
                for fnd in findings[:8]:
                    all_lines.append(
                        f"flight {name} inspection: {fnd.get('rule')}"
                        f"[{fnd.get('severity')}] {fnd.get('item')} "
                        f"{fnd.get('value', '')} — "
                        f"{str(fnd.get('details', ''))[:160]}")
        log(f"flight {name}: {'ok' if res.get('ok') else 'FAILED'} "
            f"in {time.perf_counter() - t0:.0f}s")
        # incremental headline: supersedes earlier lines, survives any
        # later flight's death
        if values.get("q6_big") or values.get("q6_small"):
            print(_headline(values, kv_rps, done), flush=True)

    if values.get("py_baseline"):
        all_lines.append(
            f"baseline_py_rowloop: {values['py_baseline'] / 1e3:.0f}K "
            f"rows/s (r01-r04 series denominator; r04 headline would be "
            f"{(values.get('q6_big') or values.get('q6_small', 0)) / values['py_baseline']:.1f}x against it)")

    # ---- round trajectory: this round vs the previous committed one ----
    ratio = float(os.environ.get("BENCH_REGRESSION_RATIO", 1.5))
    cur_p50s = parse_query_p50s("\n".join(all_lines))
    prev_no, prev_data = load_prev_round("BENCH")
    traj = compare_rounds(prev_no, prev_round_p50s(prev_data),
                          cur_p50s, ratio)
    all_lines += trajectory_lines("bench", traj)
    mc_res = flight_results.get("multichip")
    mc_traj = None
    if mc_res is not None:
        mc_p50s = parse_query_p50s(
            "\n".join(str(ln) for ln in mc_res.get("lines", [])))
        mc_no, mc_prev = load_prev_round("MULTICHIP")
        mc_prev_p50s = prev_round_p50s(mc_prev)
        if not mc_prev_p50s:
            # legacy MULTICHIP wrappers carried no query lines of
            # their own; the paired BENCH round's board has them
            mc_no = prev_no
            mc_prev_p50s = {
                k: v for k, v in prev_round_p50s(prev_data).items()
                if k.startswith("multichip_")}
        mc_traj = compare_rounds(mc_no, mc_prev_p50s, mc_p50s, ratio)
        all_lines += trajectory_lines("multichip", mc_traj)

    for ln in all_lines:
        log(ln)
    headline_ok = bool(values.get("q6_big") or values.get("q6_small"))
    if headline_ok:
        print(_headline(values, kv_rps, done), flush=True)
    else:
        print(json.dumps({
            "metric": "tpch_q6_rows_per_sec",
            "error": "no flight produced a headline"}), flush=True)

    # ---- round record (BENCH_ROUND=N): structured, comparator-ready ----
    # BENCH_r{N}.json + MULTICHIP_r{N}.json next to this file, written
    # atomically; the `queries`/`trajectory` sections are what the NEXT
    # round's comparator (and ROADMAP item 5's strategy learner) read,
    # so landing a round finally produces a machine-read comparison.
    round_no = os.environ.get("BENCH_ROUND")
    if round_no:
        here = os.path.dirname(os.path.abspath(__file__))
        n = int(round_no)
        cmd = " ".join(f"{k}={v}" for k, v in sorted(os.environ.items())
                       if k.startswith("BENCH_")) + " python bench.py"
        _persist_round(os.path.join(here, f"BENCH_r{n:02d}.json"), {
            "round": n, "cmd": cmd,
            "ok": headline_ok, "flights_done": done,
            "headline": json.loads(_headline(values, kv_rps, done)),
            "values": {k: (round(v, 3) if isinstance(v, float) else v)
                       for k, v in sorted(values.items())},
            "queries": cur_p50s,
            "trajectory": traj,
            "lines": all_lines,
        })
        if mc_res is not None:
            _persist_round(
                os.path.join(here, f"MULTICHIP_r{n:02d}.json"), {
                    "round": n,
                    "ok": bool(mc_res.get("ok")),
                    "n_devices": int(os.environ.get(
                        "BENCH_MESH_DEVICES", 8)),
                    "values": mc_res.get("values", {}),
                    "queries": parse_query_p50s(
                        "\n".join(str(ln)
                                  for ln in mc_res.get("lines", []))),
                    "trajectory": mc_traj,
                    "mesh": mc_res.get("mesh"),
                    "attribution": mc_res.get("attribution"),
                    "lines": mc_res.get("lines", []),
                    "error": mc_res.get("error"),
                })
    failed = sorted(n for n, r in flight_results.items()
                    if not r.get("ok"))
    if failed or not headline_ok:
        log(f"FAILED flights: {failed or 'none'}; headline "
            f"{'ok' if headline_ok else 'missing'}")
        sys.exit(1)


if __name__ == "__main__":
    main()
