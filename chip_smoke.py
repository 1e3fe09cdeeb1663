"""chip_smoke.py — does the served SQL path start and answer right on the chip?

Drives the system's main path once, through the entry points a user
calls: this ONE process builds a Storage, bulk-imports TPC-H `lineitem`
at SF10 (60 012 150 rows) and the eight-table join set at SF1, starts
`Server(storage, port=0)` exactly as `python -m tidb_tpu.server` does,
and drives it from four `tests/mysql_client.py` MiniClient connections on
threads: Q6, Q1, the GROUP BY l_orderkey top-10 (run-ordered rank path ->
the Pallas kernel), a filtered row scan, a single-key TopN, Q3 and Q5,
then an OLTP round (point SELECT, UPDATE, read the acknowledged write
back from another connection, INSERT into lineitem and Q6 again so the
MVCC overlay batch runs on the device too).

Every answer is compared exactly with the in-tree numpy oracles, every
EXPLAIN ANALYZE engine tag must be `device...` (`point` for the point
ops), the host-fallback counters must stay at zero, and the Pallas body
must have been traced into the served program and lowered by Mosaic.
Walls, compile stages and memory figures are printed as information;
nothing here is a benchmark.

    python chip_smoke.py              one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4    four-chip host, mesh plane active
    python chip_smoke.py --rehearse-cpu   tiny-SF control-flow rehearsal on
                                          XLA's CPU backend; every line says
                                          REHEARSAL and no result is printed

Exit code 0 and a last stdout line
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
only if every check held.
"""

from __future__ import annotations

import argparse
import decimal
import json
import logging
import os
import statistics
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
N_CONNS = 4
LINEITEM_SF = 10.0
JOIN_SF = 1.0
REHEARSAL_SF = 0.01

GROUP_TOP10 = ("select l_orderkey, sum(l_quantity) from lineitem "
               "group by l_orderkey order by 2 desc, 1 limit 10")
ROW_SCAN = ("select l_orderkey, l_linenumber, l_quantity, l_extendedprice "
            "from lineitem where l_shipdate between date '1995-03-01' "
            "and date '1995-03-31' and l_discount = 0.10 and l_quantity < 3 "
            "order by l_orderkey, l_linenumber")
TOPN = ("select l_orderkey, l_linenumber, l_extendedprice from lineitem "
        "order by l_extendedprice desc limit 10")
# rows the OLTP round adds to lineitem; all three pass Q6's predicate
Q6_INSERTS = [(1, 5, 10_00, 1000_00, 6), (2, 5, 12_00, 2500_50, 5),
              (3, 5, 23_00, 99_99, 7)]
ACCT_ROWS = 1000


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def unscaled(text: str, scale: int) -> int:
    """Exact unscaled integer of a wire DECIMAL at `scale` digits."""
    v = decimal.Decimal(text).scaleb(scale)
    check(v == v.to_integral_value(), f"{text!r} has more than {scale} "
          f"fractional digits")
    return int(v)


def printer(tag: str):
    """Result lines; a rehearsal stamps every one of them with `tag`."""
    def out(msg: str) -> None:
        print(f"{tag}{msg}", flush=True)
    return out


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--sf", type=float, default=None,
                   help="cut lineitem below SF10 (printed as a cut)")
    p.add_argument("--join-sf", type=float, default=None,
                   help="cut the join set below SF1 (printed as a cut)")
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny-SF rehearsal on XLA's CPU backend; never a "
                        "device result")
    return p.parse_args(argv)


# ---------------------------------------------------------------------------
# oracles for the statements bench.py has none for (numpy, from the arrays)
# ---------------------------------------------------------------------------

def group_top10_oracle(li):
    import numpy as np
    # bincount accumulates float64: exact while every sum < 2^53
    sums = np.bincount(li["l_orderkey"],
                       weights=li["l_quantity"]).astype(np.int64)
    keys = np.flatnonzero(np.bincount(li["l_orderkey"]))
    order = np.lexsort((keys, -sums[keys]))[:10]
    return [(int(keys[i]), int(sums[keys[i]])) for i in order]


def row_scan_oracle(li):
    import numpy as np
    from tidb_tpu.types.value import parse_date
    m = ((li["l_shipdate"] >= parse_date("1995-03-01"))
         & (li["l_shipdate"] <= parse_date("1995-03-31"))
         & (li["l_discount"] == 10) & (li["l_quantity"] < 300))
    idx = np.flatnonzero(m)
    idx = idx[np.lexsort((li["l_linenumber"][idx], li["l_orderkey"][idx]))]
    return [(int(li["l_orderkey"][i]), int(li["l_linenumber"][i]),
             int(li["l_quantity"][i]), int(li["l_extendedprice"][i]))
            for i in idx]


def topn_oracle(li):
    """(the ten largest l_extendedprice values, descending; every
    (orderkey, linenumber, price) row that may legitimately appear)."""
    import numpy as np
    ext = li["l_extendedprice"]
    top = sorted((int(v) for v in ext[np.argpartition(ext, -10)[-10:]]),
                 reverse=True)
    cand = np.flatnonzero(ext >= top[-1])
    return top, {(int(li["l_orderkey"][i]), int(li["l_linenumber"][i]),
                  int(ext[i])) for i in cand}


# ---------------------------------------------------------------------------
# the statements: (name, db, sql, checker(rows))
# ---------------------------------------------------------------------------

def build_statements(bench, li, jdata):
    from tidb_tpu.bench.tpch import TPCH_Q1, TPCH_Q6
    from tidb_tpu.bench.tpch_queries import TPCH_QUERIES

    want_q6 = bench.q6_oracle(li)
    want_q1 = bench.q1_oracle(li)
    want_grp = group_top10_oracle(li)
    want_scan = row_scan_oracle(li)
    want_topn, topn_rows = topn_oracle(li)
    want_q3 = bench.q3_oracle(jdata)
    want_q5 = bench.q5_oracle(jdata)
    nnames, _ = jdata["nation"]["n_name"]
    nat_by_name = {nm: int(k) for nm, k in zip(
        nnames, jdata["nation"]["n_nationkey"])}

    def q6(rows, extra=0):
        check(len(rows) == 1, f"q6: {len(rows)} rows")
        got = unscaled(rows[0][0], 4)
        check(got == want_q6 + extra, f"q6: {got} != {want_q6 + extra}")

    def q1(rows):
        flag = {"A": 0, "R": 1, "N": 2}
        status = {"F": 0, "O": 1}
        check(len(rows) == len(want_q1), f"q1: {len(rows)} groups")
        for r in rows:
            w = want_q1[(flag[r[0]], status[r[1]])]
            got = (unscaled(r[2], 2), unscaled(r[3], 2), unscaled(r[4], 4),
                   unscaled(r[5], 6), int(r[9]))
            check(got == w, f"q1 {r[0]}/{r[1]}: {got} != {w}")
            # AVG = SUM / COUNT at the returned scale, MySQL half-up
            for col, total, scale in ((6, w[0], 2), (7, w[1], 2)):
                q = decimal.Decimal(r[col])
                exact = (decimal.Decimal(total).scaleb(-scale)
                         / decimal.Decimal(w[4])).quantize(
                             q, rounding=decimal.ROUND_HALF_UP)
                check(q == exact, f"q1 avg col {col}: {q} != {exact}")

    def grp(rows):
        got = [(int(r[0]), unscaled(r[1], 2)) for r in rows]
        check(got == want_grp, f"group top10: {got} != {want_grp}")

    def scan(rows):
        got = [(int(r[0]), int(r[1]), unscaled(r[2], 2), unscaled(r[3], 2))
               for r in rows]
        check(len(want_scan) > 0, "row scan oracle selects nothing")
        check(got == want_scan,
              f"row scan: {len(got)} rows vs {len(want_scan)}; "
              f"first {got[:2]} vs {want_scan[:2]}")

    def topn(rows):
        got = [unscaled(r[2], 2) for r in rows]
        check(got == want_topn, f"topn values: {got} != {want_topn}")
        for r in rows:  # ties at the cut may differ; the rows must exist
            check((int(r[0]), int(r[1]), unscaled(r[2], 2)) in topn_rows,
                  f"topn row {r} is not a lineitem row")

    def q3(rows):
        got = [(int(r[0]), unscaled(r[1], 4)) for r in rows]
        check(got == want_q3, f"q3: {got[:3]} != {want_q3[:3]}")

    def q5(rows):
        got = {nat_by_name[r[0]]: unscaled(r[1], 4) for r in rows}
        check(got == want_q5, f"q5: {got} != {want_q5}")
        revs = [unscaled(r[1], 4) for r in rows]
        check(revs == sorted(revs, reverse=True), "q5 not revenue-ordered")

    return [("q6", "sf10", TPCH_Q6, q6), ("q1", "sf10", TPCH_Q1, q1),
            ("group_top10", "sf10", GROUP_TOP10, grp),
            ("row_scan", "sf10", ROW_SCAN, scan),
            ("topn", "sf10", TOPN, topn),
            ("q3", "joins", TPCH_QUERIES["q3"], q3),
            ("q5", "joins", TPCH_QUERIES["q5"], q5)]


def engines_of(rows, columns) -> tuple[list[str], str]:
    """(non-empty engine tags, the stage split) of an EXPLAIN ANALYZE."""
    ei, si = columns.index("engine"), columns.index("stages")
    return [r[ei] for r in rows if r[ei]], max((r[si] for r in rows), key=len)


def stage_ms(stages: str, name: str) -> float:
    total = 0.0
    for part in stages.split():
        k, _, v = part.partition(":")
        if k == name and v.endswith("ms"):
            total += float(v[:-2])
    return total


def check_engines(name, engines, ok_prefixes=("device",), mesh=0) -> None:
    check(bool(engines), f"{name}: EXPLAIN ANALYZE shows no engine")
    for e in engines:
        check(e.startswith(ok_prefixes),
              f"{name}: engine {e!r} is not one of {ok_prefixes}")
    if mesh:
        check(any(f"@mesh{mesh}" in e for e in engines),
              f"{name}: no @mesh{mesh} engine in {engines}")


# ---------------------------------------------------------------------------
# /metrics and memory
# ---------------------------------------------------------------------------

def scrape(status_port: int) -> dict[str, float]:
    """{metric{labels}: value} off the server's /metrics."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{status_port}/metrics", timeout=60) as r:
        text = r.read().decode()
    out = {}
    for ln in text.splitlines():
        if ln and not ln.startswith("#"):
            k, _, v = ln.rpartition(" ")
            out[k] = float(v)
    return out


def check_no_host_fallback(metrics: dict[str, float]) -> None:
    for k, v in metrics.items():
        if k.startswith("tidb_copr_fragment_fallbacks_total") or (
                k.startswith("tidb_copr_requests_total")
                and 'engine="host' in k):
            check(v == 0, f"host fallback counted: {k} = {v}")


def memory_line() -> str:
    import jax
    ms = jax.devices()[0].memory_stats() or {}
    return (f"memory_stats bytes_in_use={ms.get('bytes_in_use')} "
            f"peak_bytes_in_use={ms.get('peak_bytes_in_use')} "
            f"bytes_limit={ms.get('bytes_limit')}")


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# ---------------------------------------------------------------------------
# proof that the Pallas body ran
# ---------------------------------------------------------------------------

class PallasProbe:
    """Counts the traces of streamseg.rank_sums_pallas into served
    programs and keeps the argument shapes of the last one, so the same
    kernel can be lowered again and its compiled HLO inspected."""

    def __init__(self) -> None:
        from tidb_tpu.copr import streamseg
        self.mod = streamseg
        self.inner = streamseg.rank_sums_pallas
        self.traces: list = []
        streamseg.rank_sums_pallas = self

    def __call__(self, vals, aux, meta):
        self.traces.append((vals.shape, meta))
        return self.inner(vals, aux, meta)

    def mosaic_line(self) -> str:
        """Lower the served kernel once more and look for Mosaic's
        custom call under its own name in the compiled module."""
        import jax
        import jax.numpy as jnp
        check(bool(self.traces), "the Pallas kernel was never traced into "
              "a served program (group_top10 took another path)")
        vshape, meta = max(self.traces, key=lambda t: t[0][1])
        aux = {k: jax.ShapeDtypeStruct(meta[k].shape, jnp.int32)
               for k in ("lr", "cb")}
        text = jax.jit(lambda v, a: self.inner(v, a, meta)).lower(
            jax.ShapeDtypeStruct(vshape, jnp.float32),
            aux).compile().as_text()
        check("tpu_custom_call" in text and self.mod.KERNEL_NAME in text,
              "compiled rank_sums module has no Mosaic custom call")
        return (f"pallas: {len(self.traces)} trace(s) into served "
                f"programs {[list(t[0]) for t in self.traces]}; "
                f"{self.mod.KERNEL_NAME} vals{list(vshape)} maxd="
                f"{meta['maxd']} geometry blk={meta['blk']} x nb="
                f"{meta['nb']} ohw={meta['ohw']} operands "
                f"{jnp.dtype(self.mod.OPERAND_DTYPE).name} compiles to a "
                "tpu_custom_call (Mosaic)")


class CollectiveProbe:
    """Counts traces of the two mesh collectives (--chips 4)."""

    def __init__(self) -> None:
        import jax
        self.counts = {"all_to_all": 0, "psum": 0}
        for name in self.counts:
            setattr(jax.lax, name, self._wrap(name, getattr(jax.lax, name)))

    def _wrap(self, name, fn):
        def counted(*a, **kw):
            self.counts[name] += 1
            return fn(*a, **kw)
        return counted


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def load_data(out, session, seed: int, sf: float, join_sf: float):
    from tidb_tpu.bench.tpch import (ROWS_PER_SF, generate_lineitem_arrays,
                                     load_lineitem)
    from tidb_tpu.bench.tpch_data import generate_tpch, load_table

    n = int(ROWS_PER_SF * sf)
    t0 = time.perf_counter()
    li = generate_lineitem_arrays(n, seed)
    t1 = time.perf_counter()
    session.execute("create database sf10")
    session.execute("use sf10")
    load_lineitem(session, n, arrays=li)
    t2 = time.perf_counter()
    out(f"lineitem SF{sf:g}: {n} rows, host "
        f"{sum(a.nbytes for a in li.values())} bytes, gen={t1 - t0:.1f}s "
        f"import={t2 - t1:.1f}s")
    session.execute(
        "create table acct (id bigint primary key, bal bigint not null, "
        "note varchar(32))")
    session.execute("insert into acct values " + ",".join(
        f"({i}, {1000 + i}, 'n{i}')" for i in range(ACCT_ROWS)))
    t0 = time.perf_counter()
    jdata = generate_tpch(join_sf, seed + 1)
    session.execute("create database joins")
    session.execute("use joins")
    for t in jdata:
        load_table(session, t, jdata[t])
    jl, jo = (len(jdata["lineitem"]["l_orderkey"]),
              len(jdata["orders"]["o_orderkey"]))
    out(f"join set SF{join_sf:g}: lineitem {jl} rows (spans "
        f"{-(-jl // (1 << 22))} 4M-row tile(s)), orders {jo} rows, "
        f"gen+import={time.perf_counter() - t0:.1f}s")
    return li, jdata


def connect(mc, port: int) -> dict:
    """One wire connection per database, generous enough for a cold
    compile behind the first statement."""
    return {db: mc.MiniClient("127.0.0.1", port, db=db, timeout=1100)
            for db in ("sf10", "joins")}


def cold_pass(out, mc, port, stmts, mesh: int):
    """Connection 1: EXPLAIN ANALYZE each statement cold (engine tags,
    stage split, compile ms), then the statement itself, checked."""
    compile_total = 0.0
    conns = connect(mc, port)
    try:
        for name, db, sql, checker in stmts:
            c = conns[db]
            t0 = time.perf_counter()
            plan = c.query("explain analyze " + sql)
            cold = time.perf_counter() - t0
            engines, stages = engines_of(plan, c.columns)
            check_engines(name, engines,
                          mesh=mesh if db == "sf10" else 0)
            t0 = time.perf_counter()
            rows = c.query(sql)
            warm = time.perf_counter() - t0
            checker(rows)
            comp = stage_ms(stages, "compile")
            compile_total += comp
            out(f"{name}: PASS first-connection cold={cold:.2f}s "
                f"(compile stage {comp:.0f}ms) next={warm:.3f}s "
                f"engines={sorted(set(engines))} stages[{stages}]")
    finally:
        for c in conns.values():
            c.close()
    return compile_total


def concurrent_pass(out, mc, port, stmts, mesh: int):
    """N_CONNS wire connections at once, each running every statement:
    answer checked, then EXPLAIN ANALYZE for the engine tags."""
    walls: dict[str, list[float]] = {name: [] for name, *_ in stmts}
    splits: dict[str, str] = {}
    compiles: list[float] = []
    errors: list[BaseException] = []
    lock = threading.Lock()

    def run() -> None:
        conns = {}
        try:
            conns = connect(mc, port)
            for name, db, sql, checker in stmts:
                c = conns[db]
                t0 = time.perf_counter()
                rows = c.query(sql)
                wall = time.perf_counter() - t0
                checker(rows)
                plan = c.query("explain analyze " + sql)
                engines, stages = engines_of(plan, c.columns)
                check_engines(name, engines,
                              mesh=mesh if db == "sf10" else 0)
                with lock:
                    walls[name].append(wall)
                    splits.setdefault(name, stages)
                    compiles.append(stage_ms(stages, "compile"))
        except BaseException as e:  # re-raised by the caller
            with lock:
                errors.append(e)
        finally:
            for c in conns.values():
                c.close()

    threads = [threading.Thread(target=run, name=f"smoke-conn-{i}")
               for i in range(N_CONNS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    for name, ws in walls.items():
        check(len(ws) == N_CONNS, f"{name}: {len(ws)} of {N_CONNS} answers")
        out(f"{name}: PASS on {N_CONNS} concurrent connections, warm wall "
            f"median={statistics.median(ws):.3f}s max={max(ws):.3f}s "
            f"stages[{splits[name]}]")
    return sum(compiles)


def oltp_round(out, mc, port, q6_stmt):
    """Point SELECT / UPDATE / read-back on the keyed table, then INSERT
    into lineitem and Q6 again over the MVCC overlay."""
    _, db, q6_sql, check_q6 = q6_stmt
    errors: list[BaseException] = []
    barrier = threading.Barrier(N_CONNS)
    extra = sum(e * d for _, _, _, e, d in Q6_INSERTS)

    def run(i: int) -> None:
        c = None
        try:
            c = mc.MiniClient("127.0.0.1", port, db=db, timeout=1100)
            key, peer = 10 + i, 10 + (i + 1) % N_CONNS
            plan = c.query(f"explain analyze select bal from acct "
                           f"where id = {key}")
            engines, _ = engines_of(plan, c.columns)
            check_engines("point_select", engines, ok_prefixes=("point",))
            check(c.query(f"select bal, note from acct where id = {key}")
                  == [(str(1000 + key), f"n{key}")], "point select")
            check(c.execute(f"update acct set bal = bal + {7 + i} "
                            f"where id = {key}") == 1, "update acked 1 row")
            barrier.wait(timeout=600)
            # the acknowledged write of ANOTHER connection is visible
            got = c.query(f"select bal from acct where id = {peer}")
            want = 1000 + peer + 7 + (i + 1) % N_CONNS
            check(got == [(str(want),)], f"read-back of {peer}: {got}")
            if i == 0:
                for okey, lnum, qty, ext, disc in Q6_INSERTS:
                    check(c.execute(
                        "insert into lineitem values "
                        f"({okey}, 1, 1, {lnum}, {qty / 100}, {ext / 100}, "
                        f"{disc / 100}, 0.01, 'N', 'O', '1994-06-01', "
                        "'1994-06-02', '1994-06-03')") == 1, "insert acked")
            barrier.wait(timeout=600)
            check_q6(c.query(q6_sql), extra)
            plan = c.query("explain analyze " + q6_sql)
            engines, _ = engines_of(plan, c.columns)
            check_engines("q6_overlay", engines)
            if i == 0:
                spans = " ".join(str(r) for r in c.query("trace " + q6_sql))
                check("device.batch(overlay)" in spans,
                      "q6 after insert ran no device overlay batch")
        except BaseException as e:  # re-raised by the caller
            errors.append(e)
            barrier.abort()
        finally:
            if c is not None:
                c.close()

    threads = [threading.Thread(target=run, args=(i,),
                                name=f"smoke-oltp-{i}")
               for i in range(N_CONNS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    out(f"oltp: PASS point select (engine point), update, peer read-back "
        f"of the acknowledged write, {len(Q6_INSERTS)} inserts into "
        f"lineitem, then q6 = oracle + {extra} on {N_CONNS} connections "
        f"(engine device, device.batch(overlay) span present)")


def mesh_checks(out, storage, stmts, probe: CollectiveProbe, chips: int,
                metrics: dict[str, float]) -> None:
    """--chips 4: placement on every device, bit-identity with a
    single-device client in this process, both collectives traced."""
    from tidb_tpu.copr import mesh as M
    from tidb_tpu.copr.client import CopClient
    from tidb_tpu.session import Session

    client = M.client_of(storage)
    check(client is not None, "no mesh client for the storage")
    rep = M.placement_report(client)
    check(len(rep["device_bytes"]) == chips
          and all(b > 0 for b in rep["device_bytes"].values()),
          f"placement leaves a device empty: {rep['device_bytes']}")
    check(rep["sharded_arrays"] > 0 and "shard" in str(rep["shard_spec"]),
          f"no P('shard') arrays: {rep}")
    out(f"mesh placement: {rep['sharded_arrays']} sharded + "
        f"{rep['replicated_arrays']} replicated + {rep['single_arrays']} "
        f"single arrays, spec={rep['shard_spec']}, bytes/device="
        f"{rep['device_bytes']}")
    single = CopClient()
    for name, db, sql, _ in stmts:
        a = Session(storage, cop=client)
        b = Session(storage, cop=single)
        a.execute(f"use {db}")
        b.execute(f"use {db}")
        check(a.query(sql) == b.query(sql),
              f"{name}: mesh answer differs from the single-device client")
    out(f"mesh: all {len(stmts)} answers bit-identical to a single-device "
        f"CopClient in this process")
    routed = sum(v for k, v in metrics.items()
                 if k.startswith("tidb_mesh_reshard_bytes_total"))
    check(probe.counts["all_to_all"] > 0 and routed > 0,
          f"hash-partition exchange never ran: {probe.counts}, "
          f"routed bytes {routed}")
    check(probe.counts["psum"] > 0, f"psum merge never ran: {probe.counts}")
    with client._lock:
        partitioned = any("partb" in str(k) for k in client._col_cache)
    out(f"mesh collectives traced into served programs: {probe.counts}; "
        f"{routed:.0f} bytes routed over all_to_all; key-range "
        f"partitioned join build staged: {partitioned}")


def result_line(info: dict) -> str:
    """The driver's contract for the last stdout line: exactly the keys
    ok / device{platform, kind, count}, the device as JAX reports it."""
    return json.dumps({"ok": True, "device": {
        "platform": info["platform"], "kind": info["device_kind"],
        "count": info["count"]}})


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse_cpu:
        # must precede the first jax import: the rehearsal is on XLA's
        # CPU backend by name, wherever it is started
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, HERE)
    import jax
    import jaxlib

    import bench
    from tidb_tpu import device
    from tidb_tpu.server.server import Server
    from tidb_tpu.session import Session
    from tidb_tpu.store.storage import Storage

    rehearsal = args.rehearse_cpu
    if rehearsal:
        jax.config.update("jax_num_cpu_devices", args.chips)
    # the statements' own lines carry the walls; the slow log would
    # only bury a real failure's traceback on stderr
    logging.getLogger("tidb_tpu.slowlog").setLevel(logging.ERROR)
    cache_dir = device.configure_compile_cache()
    info = device.describe()
    out = printer(
        f"platform={info['platform']} REHEARSAL " if rehearsal else "")
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — informational only
        libtpu = "absent"
    out(f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu} "
        f"python={sys.version.split()[0]}")
    out(f"device: {device.line(info)}")
    entries0 = cache_entries(cache_dir)
    out(f"compile cache: {cache_dir} ({entries0} entries at start; "
        f"JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    if rehearsal:
        check(info["platform"] == "cpu", "rehearsal is CPU-only")
    elif info["platform"] != "tpu":
        print(f"FAIL: no TPU: JAX reports {device.line(info)}; "
              f"chip_smoke.py needs the chip (a CPU rehearsal is "
              f"--rehearse-cpu and is not a result)", file=sys.stderr)
        return 2
    if info["count"] != args.chips:
        print(f"FAIL: --chips {args.chips} but JAX sees {info['count']} "
              f"device(s)", file=sys.stderr)
        return 2
    mesh = args.chips if args.chips > 1 else 0

    sf = args.sf if args.sf is not None else (
        REHEARSAL_SF if rehearsal else LINEITEM_SF)
    join_sf = args.join_sf if args.join_sf is not None else (
        REHEARSAL_SF if rehearsal else JOIN_SF)
    if sf != LINEITEM_SF or join_sf != JOIN_SF:
        out(f"CUT: lineitem SF{sf:g} (full: SF{LINEITEM_SF:g}), join set "
            f"SF{join_sf:g} (full: SF{JOIN_SF:g})")

    pallas = PallasProbe()
    collectives = CollectiveProbe() if mesh else None
    mc = bench._mini_client_module()
    t_start = time.perf_counter()
    storage = Storage(None)
    out(f"kv engine: {storage.kv_engine}")
    srv = None
    try:
        li, jdata = load_data(out, Session(storage), args.seed, sf, join_sf)
        t0 = time.perf_counter()
        stmts = build_statements(bench, li, jdata)
        out(f"oracles: {time.perf_counter() - t0:.1f}s (numpy, untimed "
            f"against the server)")
        srv = Server(storage, port=0, status_port=0)
        srv.start()
        out(f"server: 127.0.0.1:{srv.port} status :{srv.status_port}")

        cold_compile = cold_pass(out, mc, srv.port, stmts, mesh)
        m1 = scrape(srv.status_port)
        buf1 = m1.get("tidb_device_buffer_bytes", 0.0)
        jit1 = m1.get("tidb_jit_cache_entries", 0.0)
        out(f"after the first connection: tidb_device_buffer_bytes="
            f"{buf1:.0f} tidb_jit_cache_entries={jit1:.0f} "
            f"cold_compile_total_ms={cold_compile:.0f}; {memory_line()}")

        warm_compile = concurrent_pass(out, mc, srv.port, stmts, mesh)
        m4 = scrape(srv.status_port)
        buf4 = m4.get("tidb_device_buffer_bytes", 0.0)
        jit4 = m4.get("tidb_jit_cache_entries", 0.0)
        out(f"after {N_CONNS} connections: tidb_device_buffer_bytes="
            f"{buf4:.0f} ({buf4 / max(buf1, 1):.2f}x the first) "
            f"tidb_jit_cache_entries={jit4:.0f} compile stage in this "
            f"pass={warm_compile:.0f}ms; {memory_line()}")
        check(buf4 == buf1 and jit4 == jit1 and warm_compile == 0,
              f"connections do not share staged data and kernels: buffer "
              f"bytes {buf1:.0f} -> {buf4:.0f}, jit entries {jit1:.0f} -> "
              f"{jit4:.0f}, compile ms {warm_compile:.0f}")

        if mesh:
            mesh_checks(out, storage, stmts, collectives, args.chips, m4)
        oltp_round(out, mc, srv.port, stmts[0])
        metrics = scrape(srv.status_port)
        check_no_host_fallback(metrics)
        out("host fallbacks: none (tidb_copr_requests_total{engine=host*} "
            "and tidb_copr_fragment_fallbacks_total all zero); requests: "
            + " ".join(f"{k[len('tidb_copr_requests_total'):]}={v:.0f}"
                       for k, v in sorted(metrics.items())
                       if k.startswith("tidb_copr_requests_total")))
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.status_port}/status",
                timeout=60) as r:
            status_dev = json.load(r).get("device")
        check(status_dev == info, f"/status device {status_dev} != {info}")

        if mesh:
            out("pallas: not exercised — the run-ordered rank path is "
                "single-device only (exchanges re-order rows)")
        elif info["platform"] == "tpu":
            out(pallas.mosaic_line())
        else:
            out("pallas: not exercised — off the TPU rank_sums lowers to "
                "segment_sum (tests/test_streamseg.py interprets the body)")
    finally:
        if srv is not None:
            srv.close()
        storage.close()
    out(f"compile cache at end: {cache_entries(cache_dir)} entries "
        f"({entries0} at start); cold_compile_total_ms={cold_compile:.0f}; "
        f"total wall {time.perf_counter() - t_start:.0f}s")
    if rehearsal:
        out("passed — a rehearsal of the control flow, not a device result")
        return 0
    print(result_line(info), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
