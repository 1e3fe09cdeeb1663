"""One run of one cell of TiTPU's benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The process holds the chip: it builds the configuration's Storage from the
seed, serves it with `tidb_tpu.server.server.Server` over the MySQL wire and
starts ONE child, the load generator (benchmarks/harness/loadgen.py, which
imports neither jax nor tidb_tpu), that drives the cell's traffic from the
client's side of the wire. Set-up (data, import, compile or cache load,
warm-up, lead-in) is `setup_s`; then the window, `--seconds` long; then,
with the window closed and the memory peak read, the references are built
and every answer the window returned is compared with them.

--trace 0 prints the cell's end-to-end metrics. --trace 1 runs the cell's
traffic for a few seconds under the profiler (idle share, breakdown), then
each class alone (device time per statement, rooflines) and prints the
per-layer metrics. The last line of stdout is the result; what was compared
is its last key and the last lines of stderr. No TPU, or another number of
chips than the cell's, is exit code 2 and no result. --rehearse-cpu runs the
control flow at a tiny scale on XLA's CPU backend: every line says REHEARSAL
and no result line is printed.
"""

from __future__ import annotations

import argparse
import gc
import hashlib
import importlib
import json
import os
import shutil
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
T0_ENV = "TITPU_BENCH_T0"
SERVER_CORES = 3        # this process's cores, where the host has >= 8: with
#                         all 11 the light cell's runs spread three times wider
GENERATOR_CORES = 2     # the child's own cores
KEY_STREAM = 1 << 16    # keys per keyed connection, reused in a cycle
RF1_ORDERS = 400        # inserts made ready; the pacer uses what it needs
READBACK_CHUNK = 500
JIT_MISS = 'tidb_copr_jit_cache_total{result="miss"}'
FSYNCED_COMMITS = "tidb_group_commit_commits_total"


class NoChip(RuntimeError):
    pass


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true")
    p.add_argument("--keep", action="store_true",
                   help="keep the traces and the statement log under "
                        ".bench_out/: tools/noise_study.py cuts a long "
                        "window's log into shorter windows")
    return p.parse_args(argv)


def pin_cores() -> tuple[list[int], list[int]]:
    """(cores of this process, cores of the generator child): disjoint,
    so that neither takes turns from the other, and few: a one-chip machine
    shares its host, and the server's threads wandering over every core it
    may use was the largest source of run-to-run spread (PERF.md, noise
    study). Called before any thread exists; threads started later inherit
    the mask."""
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) < 8:
        return cores, cores
    mine, child = cores[:SERVER_CORES], cores[-GENERATOR_CORES:]
    os.sched_setaffinity(0, mine)
    return mine, child


class Child:
    """The generator process and its line protocol."""

    def __init__(self, cores: list[int], log) -> None:
        """Started before this process imports jax: nothing of the chip's
        runtime is forked, and the child pins itself to its own cores."""
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        self.log = log
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "harness", "loadgen.py"),
             ",".join(map(str, cores))],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env, cwd=ROOT)
        self.wait_for("ready")

    def send(self, **cmd) -> None:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def wait_for(self, event: str) -> dict:
        while True:
            line = self.proc.stdout.readline()
            if not line:
                raise RuntimeError(
                    f"generator ended (code {self.proc.poll()}) before "
                    f"{event!r}")
            msg = json.loads(line)
            if msg.get("event") == event:
                return msg
            if msg.get("event") == "error":
                raise RuntimeError(f"generator: {msg['what']}")

    def call(self, event: str, **cmd) -> dict:
        self.send(**cmd)
        return self.wait_for(event)

    def close(self) -> None:
        """Ask it to write its log and end; wait; kill what hangs."""
        if self.proc.poll() is None:
            try:
                self.send(cmd="quit")
                self.wait_for("log_written")
            except (OSError, RuntimeError, ValueError) as e:
                self.log(f"generator did not end cleanly: {e}")
        for f in (self.proc.stdin, self.proc.stdout):
            try:
                f.close()
            except OSError:
                pass
        try:
            self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()


def build_plan(cell: dict, system, seed: int, log_path: str) -> dict:
    """The generator's plan: per connection its cyclic list of statements,
    with key streams and listed texts drawn from the seed."""
    from benchmarks.datagen import rf1, sysbench

    conns = []
    stream = 0
    for group in cell["traffic"]["connections"]:
        for i in range(group["count"]):
            sts = []
            for cls in group["classes"]:
                st = cell["classes"][cls]
                ent = {"class": cls, "db": st["db"], "op": st["op"]}
                if st.get("keys"):
                    ent["sql"] = st["sql"]
                    ent["keys"] = sysbench.key_stream(
                        system.rows[st["keys"]["table"]], seed, stream,
                        KEY_STREAM)
                    stream += 1
                    if st.get("retry_on"):
                        ent["retry_on"] = st["retry_on"]
                elif st.get("builder") == "rf1_lineitem":
                    ent["sql_list"], system.data["rf1"] = rf1.orders(
                        system.data["lineitem"],
                        system.data["lineitem_vocab"], seed, RF1_ORDERS)
                else:
                    ent["sql"] = st["sql"]
                sts.append(ent)
            spec = {"name": f"{'-'.join(group['classes'])}#{i}",
                    "statements": sts, "offset": i % len(sts)}
            if group.get("interval_s"):
                spec["interval_s"] = group["interval_s"]
            conns.append(spec)
    return {"port": system.port, "connections": conns,
            "warmup_rounds": cell["traffic"].get("warmup_rounds", 2),
            "log_path": log_path}


def engines_and_stages(sample: dict) -> tuple[list[str], dict, str]:
    """(engine tags, {stage: ms}, digest of the plan's operator column)."""
    from benchmarks.harness.layers import parse_stages

    cols, rows = sample["columns"], sample["rows"]
    ei, si = cols.index("engine"), cols.index("stages")
    engines = [r[ei] for r in rows if r[ei]]
    stages = max((r[si] or "" for r in rows), key=len, default="")
    digest = hashlib.sha1("\n".join(
        str(r[0]) for r in rows).encode()).hexdigest()[:12]
    return engines, parse_stages(stages), digest


def explain(child: Child, cell: dict, n: int) -> dict[str, list[dict]]:
    """EXPLAIN ANALYZE of each class that has a fixed text and returns
    rows: {class: [{"latency_s", "engines", "stages"}]}."""
    out = {}
    for cls, st in cell["classes"].items():
        if st["op"] != "query":
            continue
        got = child.call("explain_done", cmd="explain", n=n, **{"class": cls})
        out[cls] = []
        for s in got["samples"]:
            engines, stages, digest = engines_and_stages(s)
            out[cls].append({"latency_s": s["latency_s"], "plan": digest,
                             "engines": engines, "stages": stages})
    return out


def host_fallbacks(counters: dict[str, float]) -> float:
    return sum(v for k, v in counters.items()
               if k.startswith("tidb_copr_fragment_fallbacks_total")
               or (k.startswith("tidb_copr_requests_total")
                   and 'engine="host' in k))


# ---------------------------------------------------------------------------
# the comparison that decides `correct`
# ---------------------------------------------------------------------------

def judge(mod, rows, ref, **ctx) -> str | None:
    """The oracle's verdict on one answer: None where it agrees. An answer
    the oracle cannot even parse is a wrong answer, not a crash."""
    try:
        return mod.compare(rows, ref, **ctx)
    except (ValueError, KeyError, IndexError, TypeError, ArithmeticError) as e:
        return f"answer not comparable ({type(e).__name__}: {e}): {rows!r:.120}"


def compare_answers(cell: dict, data: dict, log: dict, say) -> dict:
    """Every answer the generator logged (warm-up, lead-in, window and
    drain) against its class's reference. {name: {"value", "limit"}}."""
    records = [r for r in log["records"] if r["phase"] != "solo"]
    inserts = sorted((r["t0"], r["t1"]) for r in log["records"]
                     if cell["classes"][r["class"]]["kind"] == "refresh"
                     and "error" not in r)
    compared: dict[str, dict] = {}
    for cls, st in cell["classes"].items():
        mod = importlib.import_module(f"benchmarks.oracles.{st['oracle']}")
        t0 = time.perf_counter()
        ref = mod.reference(data)
        seen: set = set()
        wrong = n = 0
        for r in records:
            if r["class"] != cls or "error" in r:
                continue
            n += 1
            fresh = None
            if st.get("sees_inserts"):
                fresh = (sum(1 for _, b in inserts if b <= r["t0"]),
                         sum(1 for a, _ in inserts if a <= r["t1"]))
            sig = (r["answer"], fresh, r.get("key"))
            if sig in seen:
                continue
            seen.add(sig)
            why = judge(mod, log["answers"][r["answer"]], ref,
                        fresh=fresh, key=r.get("key"))
            if why is not None:
                wrong += 1
                if wrong <= 3:
                    say(f"WRONG {why}")
        say(f"compared {cls}: {n} answers ({len(seen)} distinct) against "
            f"oracle {st['oracle']}, {wrong} wrong; reference built and "
            f"compared in {time.perf_counter() - t0:.1f}s")
        compared[f"{cls}_wrong"] = {"value": wrong, "limit": 0}
        compared[f"{cls}_answers"] = {"value": n, "limit_min": 1}
    return compared


def read_back(cell: dict, system, log: dict, say) -> dict:
    """After the window, from a connection of its own: every row an
    acknowledged UPDATE touched holds the loaded k plus its acknowledged
    updates; a final Q6 holds every acknowledged insert."""
    from benchmarks.harness import mysql_client

    out: dict[str, dict] = {}
    writes = [c for c, st in cell["classes"].items() if st["kind"] == "write"]
    if writes:
        acked: dict[int, int] = {}
        for r in log["records"]:
            if r["class"] in writes and "error" not in r:
                acked[r["key"]] = acked.get(r["key"], 0) + 1
        k0 = importlib.import_module(
            "benchmarks.oracles." + cell["classes"][writes[0]]["oracle"]
        ).reference(system.data)  # {key: loaded value}
        st = cell["classes"][writes[0]]
        c = mysql_client.MiniClient("127.0.0.1", system.port, db=st["db"],
                                    timeout=300)
        wrong = 0
        try:
            ids = sorted(acked)
            for lo in range(0, len(ids), READBACK_CHUNK):
                part = ids[lo:lo + READBACK_CHUNK]
                got = {r[0]: r[1] for r in c.query(st["readback_sql"].replace(
                    "{keys}", ",".join(map(str, part))))}
                for i in part:
                    if got.get(str(i)) != str(k0[i] + acked[i]):
                        wrong += 1
                        if wrong <= 3:
                            say(f"WRONG read-back id={i}: k={got.get(str(i))}, "
                                f"loaded {k0[i]} + {acked[i]} acknowledged")
        finally:
            c.close()
        say(f"read back {len(acked)} updated rows from another connection "
            f"({sum(acked.values())} acknowledged updates), {wrong} wrong")
        out["readback_wrong"] = {"value": wrong, "limit": 0}
        out["readback_rows"] = {"value": len(acked), "limit_min": 1}
    fresh = [c for c, st in cell["classes"].items() if st.get("sees_inserts")]
    if fresh and "rf1" in system.data:
        n_acked = sum(1 for r in log["records"]
                      if cell["classes"][r["class"]]["kind"] == "refresh"
                      and "error" not in r)
        wrong = 0
        for cls in fresh:
            st = cell["classes"][cls]
            mod = importlib.import_module(f"benchmarks.oracles.{st['oracle']}")
            c = mysql_client.MiniClient("127.0.0.1", system.port, db=st["db"],
                                        timeout=300)
            try:
                rows = [list(r) for r in c.query(st["sql"])]
            finally:
                c.close()
            why = judge(mod, rows, mod.reference(system.data),
                        fresh=(n_acked, n_acked))
            if why is not None:
                wrong += 1
                say(f"WRONG after the window, {n_acked} inserts "
                    f"acknowledged: {why}")
        say(f"{len(fresh)} scans after the window against the reference "
            f"with all {n_acked} acknowledged inserts, {wrong} wrong")
        out["fresh_scan_wrong"] = {"value": wrong, "limit": 0}
    return out


def verdict(compared: dict) -> bool:
    for v in compared.values():
        if "limit" in v and v["value"] > v["limit"]:
            return False
        if "limit_min" in v and v["value"] < v["limit_min"]:
            return False
    return True


# ---------------------------------------------------------------------------
# the traced run
# ---------------------------------------------------------------------------

def start_trace(path: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # a Python event per call swamps the host
    opts.host_tracer_level = 2
    jax.profiler.start_trace(path, profiler_options=opts)


def traced(child: Child, cell: dict, system, seconds: float, workdir: str,
           rehearsal: bool, say) -> tuple[dict, dict]:
    """(a) the cell's own traffic under the profiler, (b) EXPLAIN ANALYZE
    samples, (c) each class that a per-layer metric asks for, alone on one
    connection under the profiler. Returns (`obs` for the readers, the
    generator's account of (a), whose window is the run's window)."""
    import jax
    from benchmarks.harness import trace as T

    tr = cell["traffic"]["trace"]
    conc_s = min(tr["concurrent_s"], seconds)
    obs: dict = {"solo": {}}
    obs["counters_before"] = system.scrape()
    child.send(cmd="run", leadin_s=cell["traffic"]["leadin_s"],
               window_s=conc_s + 2 * tr["margin_s"])
    child.wait_for("window_start")
    time.sleep(tr["margin_s"])
    tdir = os.path.join(workdir, "trace-concurrent")
    start_trace(tdir)
    t0 = time.perf_counter()
    time.sleep(conc_s)
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    run = child.wait_for("run_done")
    obs["counters_after"] = system.scrape()
    obs["concurrent"] = T.reduce(T.load(T.find_xplane(tdir), rehearsal),
                                 window_s=t1 - t0)
    obs["explain"] = explain(child, cell, tr["explain_samples"])
    want = {m["class"] for m in cell["layer_metrics"] if "class" in m}
    want |= {c for m in cell["layer_metrics"] if m.get("solo")
             for c, st in cell["classes"].items() if st["kind"] == "analytic"}
    for cls in cell["classes"]:
        if cls not in want:
            continue
        tdir = os.path.join(workdir, f"trace-solo-{cls}")
        start_trace(tdir)
        got = child.call("solo_done", cmd="solo", n=tr["solo_statements"],
                         **{"class": cls})
        jax.profiler.stop_trace()
        red = T.reduce(T.load(T.find_xplane(tdir), rehearsal))
        obs["solo"][cls] = {"n": tr["solo_statements"],
                            "latencies_s": got["latencies_s"], "trace": red}
        say(f"solo {cls}: {tr['solo_statements']} statements, client mean "
            f"{1e3 * sum(got['latencies_s']) / len(got['latencies_s']):.2f}"
            f"ms, device busy {1e3 * red['busy_s']:.2f}ms in all")
    return obs, run


def breakdown(obs: dict) -> dict:
    ops = [[n, s] for n, s in obs["concurrent"]["ops"][:10]]
    if obs["solo"]:
        # name the ops by the class whose solo sub-window shows them, so a
        # reader can tell whose `fusion.3` it is
        solo = sorted(((f"{cls}/{n}", s) for cls, d in obs["solo"].items()
                       for n, s in d["trace"]["ops"][:10]),
                      key=lambda kv: -kv[1])
        ops = [[n, s] for n, s in solo[:10]]
    return {"device_ops": ops,
            "idle_gaps": [[n, s] for n, s in obs["concurrent"]["idle_gaps"][:10]]}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(args, t_start: float, cores=None) -> dict:
    """One run; returns the result line's object (main() prints it unless
    the run is a rehearsal)."""
    rehearsal = args.rehearse_cpu
    sys.path.insert(0, ROOT)
    from benchmarks.harness import manifest

    cell = manifest.load_cell(manifest.load_manifest(ROOT), args.workload,
                              HERE)
    chips = cell["workload"]["chips"]
    if rehearsal:
        # before the first jax import: XLA's CPU backend by name, with as
        # many virtual devices as the cell has chips
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip()
    tag = "REHEARSAL " if rehearsal else ""

    def say(msg: str, file=None) -> None:
        print(f"{tag}{msg}", file=file, flush=True)

    child = Child(cores[1] if cores else sorted(os.sched_getaffinity(0)),
                  say)
    try:
        return _run_cell(args, t_start, cores, cell, child, say, rehearsal)
    finally:
        child.close()


def _run_cell(args, t_start, cores, cell, child, say, rehearsal):
    import logging

    import jax  # noqa: F401
    from benchmarks.harness import layers as L
    from benchmarks.harness import metrics
    from benchmarks.harness import system as S

    chips = cell["workload"]["chips"]

    # the per-class lines carry the latencies; the slow log would bury a
    # real failure's traceback on stderr
    logging.getLogger("tidb_tpu.slowlog").setLevel(logging.ERROR)

    info = S.device_info()
    if rehearsal:
        if info["platform"] != "cpu":
            raise NoChip("a rehearsal runs on XLA's CPU backend only")
    elif info["platform"] != "tpu" or info["count"] != chips:
        raise NoChip(f"cell {args.workload} needs {chips} TPU chip(s); JAX "
                     f"reports {info}")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if not rehearsal and info["kind"] not in peaks:
        raise NoChip(f"device kind {info['kind']!r} is not in "
                     f"benchmarks/peaks.json: add it with its source")
    cache_dir = S.configure_compile_cache()
    say(f"device: {info}; compile cache {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        f"entries); cores {cores}")

    scale = cell["config"]["rehearsal_scale"] if rehearsal else 1.0
    workdir = os.path.join(
        ROOT, ".bench_out",
        f"{args.workload}-seed{args.seed}-trace{args.trace}"
        + ("-rehearsal" if rehearsal else ""))
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    system = None
    try:
        system = S.System(cell["config"], args.seed, scale, workdir, say)
        for cls, st in cell["classes"].items():
            st["rows_scanned"] = sum(system.rows[t] for t in st.get("scans", ()))
        plan_path = os.path.join(workdir, "plan.json")
        log_path = os.path.join(workdir, "statements.json")
        with open(plan_path, "w") as f:
            json.dump(build_plan(cell, system, args.seed, log_path), f)
        child.call("planned", cmd="plan", path=plan_path)
        counters0 = system.scrape()
        fallbacks0 = host_fallbacks(counters0)

        # ---- warm-up: first touch (compile or cache load), then every
        # class on every connection
        first_touch = explain(child, cell, 1)
        bad_engines = 0
        for cls, samples in first_touch.items():
            want = cell["classes"][cls].get("engine", "device")
            for s in samples:
                ok = bool(s["engines"]) and all(
                    e.startswith(want) for e in s["engines"])
                bad_engines += 0 if ok else 1
                say(f"first touch {cls}: {s['latency_s']:.2f}s engines="
                    f"{sorted(set(s['engines']))} plan {s['plan']} compile "
                    f"stage {s['stages'].get('compile', 0):.0f}ms")
        warm = child.call("warmup_done", cmd="warmup")
        if warm["errors"]:
            say(f"warm-up errors: {warm['errors']}")
        gc.collect()
        gc.freeze()

        # ---- the window
        obs = None
        if args.trace:
            obs, run = traced(child, cell, system, args.seconds, workdir,
                              rehearsal, say)
        else:
            before = system.scrape()
            child.send(cmd="run", leadin_s=cell["traffic"]["leadin_s"],
                       window_s=args.seconds)
            t_w0 = child.wait_for("window_start")["t"]
            setup_s = time.time() - t_start - (time.perf_counter() - t_w0)
            run = child.wait_for("run_done")
        counters_end = system.scrape()
        peak = S.memory_peak_bytes()
        # the generator writes its log when told to end; the read-back needs
        # the acknowledged keys, so end it first, then read back, then free
        child.close()
        with open(log_path) as f:
            child_log = json.load(f)
        extra = read_back(cell, system, child_log, say)
        data = system.data
        system.close()
        system.data = None
        system = None

        # ---- metrics
        w0, w1 = run["w0"], run["w1"]
        recs = child_log["records"]
        done = metrics.in_window(recs, w0, w1)
        failed = sum(1 for r in recs if "error" in r and w0 <= r["t1"] <= w1)
        per_class = metrics.by_class(done)
        for cls, lat in sorted(per_class.items()):
            say(f"window {cls}: n={len(lat)} mean={1e3 * sum(lat) / len(lat):.3f}ms "
                f"p50={1e3 * metrics.percentile(lat, 50):.3f}ms "
                f"max={1e3 * max(lat):.3f}ms")
        retried = [r for r in recs if r.get("retries")]
        if retried:
            say(f"retried after a write conflict: {len(retried)} statements, "
                f"{sum(r['retries'] for r in retried)} retries "
                f"({sum(1 for r in retried if w0 <= r['t1'] <= w1)} "
                f"statements in the window)")
        say(f"window {w1 - w0:.3f}s after a lead-in of {run['leadin_s']:.2f}s; "
            f"generator cpu {run['generator_cpu_s']:.2f}s; pacer late max "
            f"{1e3 * run['pacer_late_max_s']:.2f}ms mean "
            f"{1e3 * run['pacer_late_mean_s']:.2f}ms; peak_bytes_in_use {peak}")
        out_metrics: dict[str, dict] = {}
        device = {"platform": info["platform"], "kind": info["kind"],
                  "count": info["count"], "memory_peak_bytes": peak}
        if args.trace:
            obs.update(first_touch=first_touch, classes=cell["classes"],
                       chips=chips,
                       peaks=peaks.get(info["kind"], {}),
                       window={"records": recs, "w0": w0, "w1": w1})
            for spec in cell["layer_metrics"]:
                v = L.read(spec, obs, HERE)
                if v is None:
                    say(f"per-layer {spec['name']}: nothing to read")
                else:
                    out_metrics[spec["name"]] = {"value": v,
                                                 "unit": spec["unit"]}
            device["busy_s"] = obs["concurrent"]["busy_s"]
            device["window_s"] = obs["concurrent"]["window_s"]
            compiles = out_metrics.get("compile.in_window", {}).get("value")
        else:
            e2e = metrics.end_to_end(recs, w0, w1, cell["classes"])
            e2e["setup_s"] = setup_s
            for m in cell["end_to_end"]:
                if m["name"] in e2e:
                    out_metrics[m["name"]] = {"value": e2e[m["name"]],
                                              "unit": m["unit"]}
            compiles = counters_end.get(JIT_MISS, 0.0) - before.get(JIT_MISS, 0.0)
        say(f"compiles in the window: {compiles}")

        # ---- correct
        compared = compare_answers(cell, data, child_log, say)
        compared.update(extra)
        compared["failed"] = {"value": len(child_log["errors"]), "limit": 0}
        if cell["config"]["storage"]["sync_log"] == "commit":
            # every acknowledged write transaction went through a WAL fsync
            # at its commit: the program counts those, and counts none
            # where the log is synced later or not at all
            acked = sum(1 for r in recs if "error" not in r and
                        cell["classes"][r["class"]]["kind"]
                        in ("write", "refresh"))
            synced = (counters_end.get(FSYNCED_COMMITS, 0.0)
                      - counters0.get(FSYNCED_COMMITS, 0.0))
            say(f"acknowledged write transactions {acked}, commits made "
                f"durable by a WAL fsync at the commit {synced:.0f}")
            compared["acked_not_fsynced"] = {
                "value": max(0, acked - int(synced)), "limit": 0}
        compared["host_fallbacks"] = {
            "value": host_fallbacks(counters_end) - fallbacks0, "limit": 0}
        compared["engine_not_device"] = {"value": bad_engines, "limit": 0}
        correct = verdict(compared)
        with open(os.path.join(workdir, "run.json"), "w") as f:
            json.dump({"args": vars(args), "device": device, "run": run,
                       "first_touch": first_touch, "compared": compared,
                       "rows_scanned": {c: st["rows_scanned"] for c, st
                                        in cell["classes"].items()},
                       "metrics": out_metrics,
                       "per_class_ms": {c: [1e3 * x for x in v]
                                        for c, v in per_class.items()}}, f)
        result = {"correct": correct, "attempted": len(done) + failed,
                  "failed": failed, "metrics": out_metrics, "device": device}
        if args.trace:
            result["breakdown"] = breakdown(obs)
        result["compared"] = compared
        for name, v in compared.items():
            lim = (f"<= {v['limit']}" if "limit" in v
                   else f">= {v['limit_min']}")
            say(f"compared {name}: {v['value']} (limit {lim})", sys.stderr)
        say(f"correct: {correct}", sys.stderr)
        return result
    finally:
        if system is not None:
            system.close()
        # keep run.json; the store, the traces and the statement log are large
        for d in os.listdir(workdir) if os.path.isdir(workdir) else ():
            if d == "db" or (d != "run.json" and not args.keep):
                path = os.path.join(workdir, d)
                if os.path.isdir(path):
                    shutil.rmtree(path)
                else:
                    os.remove(path)


def main(argv=None) -> int:
    if os.environ.get("PYTHONHASHSEED") != "0":
        # one hash seed for every run: set and dict-of-set iteration order
        # is then the same in every process, so plans and jit keys are too
        env = dict(os.environ, PYTHONHASHSEED="0")
        env[T0_ENV] = repr(time.time())
        os.execve(sys.executable, [sys.executable, os.path.abspath(__file__)]
                  + list(sys.argv[1:] if argv is None else argv), env)
    t_start = float(os.environ.get(T0_ENV) or time.time())
    cores = pin_cores()
    args = parse_args(argv)
    try:
        result = run_cell(args, t_start, cores)
    except NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    except ImportError as e:
        print(f"FAIL: the benchmark measures the program in this checkout "
              f"and cannot import it: {e}", file=sys.stderr)
        return 2
    if args.rehearse_cpu:
        print("REHEARSAL ended: control flow only, not a result; correct: "
              f"{result['correct']}", flush=True)
        return 0 if result["correct"] else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
