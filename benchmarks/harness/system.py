"""The system under test, and nothing else of the program: one process
builds a Storage, bulk-imports the configuration's data through the
program's own loaders, and serves it with `Server` over the MySQL wire, as
`python -m tidb_tpu.server` does. This is the only module of the benchmark
that imports tidb_tpu.
"""

from __future__ import annotations

import os
import threading
import time
import urllib.request

from ..datagen import sysbench, tpch


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, as the backend reports."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def configure_compile_cache() -> str:
    """JAX_COMPILATION_CACHE_DIR where set, else the program's fixed path in
    the checkout. Programs that compile in under a second are cached too:
    every run is a new process, and what the cache misses is paid again in
    every run's set-up."""
    import jax
    from tidb_tpu import device

    path = device.configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class System:
    """Storage + Server for one run. `data` keeps the seed's arrays for
    the references (numpy, host side)."""

    def __init__(self, config: dict, seed: int, scale: float, workdir: str,
                 log) -> None:
        from tidb_tpu.server.server import Server
        from tidb_tpu.session import Session
        from tidb_tpu.store.storage import Storage

        self.config = config
        self.data: dict = {}
        self.rows: dict[str, int] = {}
        st = config["storage"]
        path = os.path.join(workdir, "db") if st["durable"] else None
        self.storage = Storage(path, sync_log=st["sync_log"])
        log(f"kv engine: {self.storage.kv_engine}; storage "
            f"{'durable at ' + path if path else 'in memory'}, "
            f"sync_log={st['sync_log']}")
        self.server = None
        try:
            self._load(Session(self.storage), seed, scale, log)
            self.server = Server(self.storage, port=0, status_port=0)
            self.server.start()
        except BaseException:
            self.close()
            raise
        self.port = self.server.port
        self.status_port = self.server.status_port

    # ---- data -------------------------------------------------------------
    def _load(self, session, seed: int, scale: float, log) -> None:
        from tidb_tpu.bench.tpch_data import load_table

        cfg = self.config
        join_box: dict = {}
        join_thread = None
        if "joinset_scale_factor" in cfg:
            # generated beside lineitem: numpy releases the GIL for most of it
            def gen_joins() -> None:
                t0 = time.perf_counter()
                join_box["data"] = tpch.generate_tpch(
                    cfg["joinset_scale_factor"] * scale, seed + 1)
                join_box["gen_s"] = time.perf_counter() - t0
            join_thread = threading.Thread(target=gen_joins,
                                           name="bench-joinset-gen")
            join_thread.start()
        t0 = time.perf_counter()
        gen = tpch.generate_lineitem(cfg["lineitem_scale_factor"] * scale,
                                     seed)
        li, vocab = gen["columns"], gen["vocab"]
        n = len(li["l_orderkey"])
        t1 = time.perf_counter()
        session.execute("create database sf10")
        session.execute("use sf10")
        load_table(session, "lineitem",
                   {c: (vocab[c], a) if c in vocab else a
                    for c, a in li.items()})
        t2 = time.perf_counter()
        self.data["lineitem"] = li
        self.data["lineitem_vocab"] = vocab
        self.rows["sf10.lineitem"] = n
        log(f"sf10.lineitem: {n} rows x {len(li)} columns, max key "
            f"{int(li['l_orderkey'][-1])}, gen={t1 - t0:.1f}s "
            f"import={t2 - t1:.1f}s")
        if join_thread is not None:
            join_thread.join()
            jdata = join_box["data"]
            t0 = time.perf_counter()
            session.execute("create database joins")
            session.execute("use joins")
            for t in jdata:
                load_table(session, t, jdata[t])
                first = next(iter(jdata[t].values()))
                self.rows[f"joins.{t}"] = len(
                    first[1] if isinstance(first, tuple) else first)
            self.data["joins"] = jdata
            log(f"joins.*: lineitem {self.rows['joins.lineitem']} rows, "
                f"orders {self.rows['joins.orders']} rows, "
                f"gen={join_box['gen_s']:.1f}s (beside lineitem) "
                f"import={time.perf_counter() - t0:.1f}s")
        if "sbtest_table_size" in cfg:
            size = max(100, int(cfg["sbtest_table_size"] * scale))
            t0 = time.perf_counter()
            sb = sysbench.generate(size, seed + 2)
            session.execute("create database sbtest")
            session.execute("use sbtest")
            session.execute(sysbench.DDL)
            for sql in sysbench.insert_statements(sb):
                session.execute(sql)
            self.data["sbtest"] = sb
            self.rows["sbtest.sbtest1"] = size
            log(f"sbtest.sbtest1: {size} rows by multi-row INSERT, "
                f"gen+load={time.perf_counter() - t0:.1f}s")
        # statistics, as after any bulk import: without them the program's
        # auto-analyze fires at a session's 64th statement, inside the
        # window, and the plans (and so the compiled programs) change there
        t0 = time.perf_counter()
        took = {}
        for table in self.rows:
            db, name = table.split(".")
            t1 = time.perf_counter()
            session.execute(f"use {db}")
            session.execute(f"analyze table {name}")
            took[table] = time.perf_counter() - t1
        log(f"analyze table x{len(self.rows)}: "
            f"{time.perf_counter() - t0:.1f}s (" + ", ".join(
                f"{t} {s:.1f}s" for t, s in took.items() if s >= 1.0) + ")")

    # ---- counters ---------------------------------------------------------
    def scrape(self) -> dict[str, float]:
        """{sample{labels}: value} off the server's /metrics."""
        with urllib.request.urlopen(
                f"http://127.0.0.1:{self.status_port}/metrics",
                timeout=60) as r:
            text = r.read().decode()
        out = {}
        for ln in text.splitlines():
            if ln and not ln.startswith("#"):
                k, _, v = ln.rpartition(" ")
                try:
                    out[k] = float(v)
                except ValueError:
                    pass
        return out

    def close(self) -> None:
        if self.server is not None:
            self.server.close()
            self.server = None
        if self.storage is not None:
            self.storage.close()
            self.storage = None
