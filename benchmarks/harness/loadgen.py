"""The load generator: a child process that drives the MySQL wire.

It imports neither jax nor tidb_tpu (sockets, time, json, threads only), so
the statements it sends are timed from outside the server's interpreter, as
a remote client's are. `run.py` starts it (argv[1]: the cores it pins itself
to) and tells it over stdin what to do; it answers on stdout, one JSON
object a line:

    {"cmd": "plan", "path": p}                connections and statements
    {"cmd": "warmup"}                         every class `rounds` times on
                                              every connection (paced
                                              connections first)
    {"cmd": "run", "leadin_s": s, "window_s": w}
                                              lead-in, then the window, then
                                              drain what is in flight
    {"cmd": "solo", "class": c, "n": k}       class c alone on connection 0
    {"cmd": "explain", "class": c, "n": k}    EXPLAIN ANALYZE of class c
    {"cmd": "quit"}

The schedule is fixed: connection i runs its classes in the plan's cyclic
order starting at offset i; a keyed class takes the next key of the
connection's own stream. The seed makes the data and the key streams, never
the mix. Every statement is logged (class, times on CLOCK_MONOTONIC, a hash
of its answer); each distinct answer is kept once, so the parent can compare
every answer of the window with the reference.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import mysql_client  # noqa: E402  (the copy beside this file)

TIMEOUT_S = 1100  # a cold compile can sit behind the first statement
MAX_RETRIES = 100  # of one statement, on the error codes its class lists


class Log:
    """Statement records and the distinct answers, shared by the threads."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.records: list[dict] = []
        self.answers: dict[str, list] = {}
        self.errors: list[str] = []

    def add(self, rec: dict, rows) -> None:
        if rows is not None:
            blob = json.dumps(rows, separators=(",", ":")).encode()
            h = hashlib.sha1(blob).hexdigest()[:16]
            rec["answer"] = h
        with self.lock:
            if rows is not None and h not in self.answers:
                self.answers[h] = rows
            self.records.append(rec)


class Conn:
    """One client: a socket per database it uses, one statement at a time."""

    def __init__(self, spec: dict, port: int, log: Log) -> None:
        self.spec = spec
        self.name = spec["name"]
        self.port = port
        self.log = log
        self.socks: dict[str, mysql_client.MiniClient] = {}
        self.pos = spec.get("offset", 0)   # next slot of the cyclic order
        self.uses: dict[str, int] = {}     # per class: keys/sql consumed
        self.cycles = 0

    def sock(self, db: str) -> mysql_client.MiniClient:
        if db not in self.socks:
            self.socks[db] = mysql_client.MiniClient(
                "127.0.0.1", self.port, db=db, timeout=TIMEOUT_S)
        return self.socks[db]

    def close(self) -> None:
        for c in self.socks.values():
            c.close()

    def next_statement(self) -> dict | None:
        """The next statement of the schedule, with its key or text filled
        in; None where a listed class has run out."""
        sts = self.spec["statements"]
        st = sts[self.pos % len(sts)]
        self.pos += 1
        if (self.pos - self.spec.get("offset", 0)) % len(sts) == 0:
            self.cycles += 1
        k = self.uses.get(st["class"], 0)
        self.uses[st["class"]] = k + 1
        out = {"class": st["class"], "db": st["db"], "op": st["op"]}
        if "retry_on" in st:
            out["retry_on"] = st["retry_on"]
        if "sql_list" in st:
            if k >= len(st["sql_list"]):
                return None
            out["sql"], out["key"] = st["sql_list"][k], k
        elif "keys" in st:
            key = st["keys"][k % len(st["keys"])]
            out["sql"], out["key"] = st["sql"].replace("{key}", str(key)), key
        else:
            out["sql"] = st["sql"]
        return out

    def run_one(self, phase: str, due: float | None = None,
                st: dict | None = None) -> dict | None:
        st = st or self.next_statement()
        if st is None:
            return None
        c = self.sock(st["db"])
        t0 = time.perf_counter()
        rows = None
        err = None
        retries = 0
        while True:
            try:
                if st["op"] == "query":
                    rows = c.query(st["sql"])
                else:
                    rows = [[c.execute(st["sql"])]]
            except mysql_client.MySQLError as e:
                # sysbench restarts a transaction that ends in an error it
                # is told to ignore (a write conflict): so does this
                # client, with the same key; the latency spans the retries
                if (e.code in st.get("retry_on", ())
                        and retries < MAX_RETRIES):
                    retries += 1
                    continue
                err = f"{type(e).__name__}: {e}"
            except (OSError, AssertionError) as e:
                err = f"{type(e).__name__}: {e}"
            break
        t1 = time.perf_counter()
        rec = {"conn": self.name, "class": st["class"], "phase": phase,
               "t0": t0, "t1": t1}
        if "key" in st:
            rec["key"] = st["key"]
        if retries:
            rec["retries"] = retries
        if due is not None:
            rec["due"] = due
        if err is not None:
            rec["error"] = err
            with self.log.lock:
                self.log.errors.append(f"{self.name} {st['class']}: {err}")
        self.log.add(rec, rows)
        return rec


class Generator:
    def __init__(self, plan: dict) -> None:
        self.plan = plan
        self.log = Log()
        self.conns = [Conn(s, plan["port"], self.log)
                      for s in plan["connections"]]
        self.stop = threading.Event()

    # ---- phases -----------------------------------------------------------
    def _each(self, conns, fn) -> None:
        threads = [threading.Thread(target=fn, args=(c,), name=c.name)
                   for c in conns]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def warmup(self) -> dict:
        rounds = self.plan.get("warmup_rounds", 2)

        def paced(c: Conn) -> None:
            for _ in range(rounds):
                c.run_one("warmup")

        def closed(c: Conn) -> None:
            for _ in range(rounds * len(c.spec["statements"])):
                c.run_one("warmup")

        self._each([c for c in self.conns if c.spec.get("interval_s")], paced)
        self._each([c for c in self.conns if not c.spec.get("interval_s")],
                   closed)
        for c in self.conns:
            c.cycles = 0
        return {"event": "warmup_done", "errors": self.log.errors[:5]}

    def run(self, leadin_s: float, window_s: float) -> dict:
        """Lead-in until every closed connection has gone once round its
        classes and `leadin_s` has passed; then the window; then drain."""
        self.stop.clear()
        for c in self.conns:
            c.cycles = 0
        phase = {"name": "leadin"}
        late: list[float] = []

        def closed(c: Conn) -> None:
            while not self.stop.is_set():
                if c.run_one(phase["name"]) is None:
                    return

        def paced(c: Conn) -> None:
            step = c.spec["interval_s"]
            due = time.perf_counter()
            while not self.stop.is_set():
                now = time.perf_counter()
                if now < due:
                    if self.stop.wait(due - now):
                        return
                late.append(time.perf_counter() - due)
                if c.run_one(phase["name"], due=due) is None:
                    return
                due += step

        threads = [threading.Thread(
            target=paced if c.spec.get("interval_s") else closed,
            args=(c,), name=c.name) for c in self.conns]
        t_begin = time.perf_counter()
        for t in threads:
            t.start()
        loops = [c for c in self.conns if not c.spec.get("interval_s")]
        while (time.perf_counter() - t_begin < leadin_s
               or any(c.cycles < 1 for c in loops)):
            time.sleep(0.005)
            if not any(t.is_alive() for t in threads):
                break
        w0 = time.perf_counter()
        phase["name"] = "window"
        emit({"event": "window_start", "t": w0})
        time.sleep(max(0.0, w0 + window_s - time.perf_counter()))
        w1 = time.perf_counter()
        phase["name"] = "drain"
        self.stop.set()
        for t in threads:
            t.join()
        t = os.times()
        return {"event": "run_done", "w0": w0, "w1": w1,
                "leadin_s": w0 - t_begin,
                "generator_cpu_s": t.user + t.system,
                "pacer_late_max_s": max(late, default=0.0),
                "pacer_late_mean_s": sum(late) / len(late) if late else 0.0}

    def _class_statement(self, cls: str) -> tuple[Conn, dict]:
        for c in self.conns:
            for st in c.spec["statements"]:
                if st["class"] == cls and "sql" in st:
                    sql = st["sql"]
                    if "keys" in st:  # a keyed class: its first key stands in
                        sql = sql.replace("{key}", str(st["keys"][0]))
                    return c, {"class": cls, "db": st["db"], "op": st["op"],
                               "sql": sql}
        raise KeyError(f"no connection runs class {cls!r} as a fixed text")

    def solo(self, cls: str, n: int) -> dict:
        c, st = self._class_statement(cls)
        lat = []
        for _ in range(n):
            rec = c.run_one("solo", st=dict(st))
            lat.append(rec["t1"] - rec["t0"])
        return {"event": "solo_done", "class": cls, "latencies_s": lat}

    def explain(self, cls: str, n: int) -> dict:
        """EXPLAIN ANALYZE the class n times: client latency of each, with
        the plan rows (engine tags, stage split) as the wire returned them.
        Not logged as answers: they are not the class's statements."""
        c, st = self._class_statement(cls)
        sock = c.sock(st["db"])
        out = []
        for _ in range(n):
            t0 = time.perf_counter()
            rows = sock.query("explain analyze " + st["sql"])
            out.append({"latency_s": time.perf_counter() - t0,
                        "columns": list(sock.columns), "rows": rows})
        return {"event": "explain_done", "class": cls, "samples": out}

    def close(self) -> None:
        for c in self.conns:
            c.close()


def emit(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def main(argv: list[str]) -> int:
    if len(argv) > 1 and argv[1]:
        os.sched_setaffinity(0, {int(c) for c in argv[1].split(",")})
    gen = plan = None
    emit({"event": "ready", "pid": os.getpid()})
    try:
        for line in sys.stdin:
            cmd = json.loads(line)
            name = cmd["cmd"]
            if name == "plan":
                with open(cmd["path"]) as f:
                    plan = json.load(f)
                gen = Generator(plan)
                emit({"event": "planned",
                      "connections": len(plan["connections"])})
            elif name == "quit":
                break
            elif gen is None:
                emit({"event": "error", "what": f"{name!r} before a plan"})
            elif name == "warmup":
                emit(gen.warmup())
            elif name == "run":
                emit(gen.run(cmd["leadin_s"], cmd["window_s"]))
            elif name == "solo":
                emit(gen.solo(cmd["class"], cmd["n"]))
            elif name == "explain":
                emit(gen.explain(cmd["class"], cmd["n"]))
            else:
                emit({"event": "error", "what": f"unknown command {name!r}"})
    finally:
        if gen is not None:
            gen.close()
            with open(plan["log_path"], "w") as f:
                json.dump({"records": gen.log.records,
                           "answers": gen.log.answers,
                           "errors": gen.log.errors}, f)
        emit({"event": "log_written",
              "records": len(gen.log.records) if gen else 0})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
