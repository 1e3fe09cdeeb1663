"""The noise study: how far do runs of the same code on the same seed differ,
and is it the process or the window that differs? A tool, not the harness.

    python3 benchmarks/tools/noise_study.py --workload tpch10_light \
        --seed 7 --seconds 30 --runs 6 --windows 4 --cut 30,45,60

(a) within: ONE process, set-up once, one long window of the normal command
whose statement log is cut into consecutive windows of each `--cut` length;
(b) across: the normal command, one process per run, `--runs` times.
A spread is the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median. Read it so:
across ~ within and both large: the window is too short or the connections
fall into convoys; across >> within: per-process state (hash seed, core
placement, page layout). This process never touches JAX: every run is a
child, so each holds the chip alone.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmarks", "run.py")


def spread(values: list[float]) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def table(title: str, runs: list[dict[str, float]]) -> None:
    print(f"-- {title}: {len(runs)} readings")
    for name in sorted({k for r in runs for k in r}):
        vals = [r[name] for r in runs if name in r]
        if len(vals) >= 2:
            print(f"{name}: median {statistics.median(vals):.6g} spread "
                  f"{spread(vals):.4f} values "
                  f"{' '.join(f'{v:.6g}' for v in vals)}")
    sys.stdout.flush()


def across(args) -> list[dict[str, float]]:
    out = []
    for i in range(args.runs):
        p = subprocess.run(
            [sys.executable, RUN, "--workload", args.workload, "--seed",
             str(args.seed), "--seconds", str(args.seconds), "--trace", "0"]
            + (["--rehearse-cpu"] if args.rehearse_cpu else []),
            cwd=ROOT, capture_output=True, text=True)
        lines = p.stdout.strip().splitlines()
        for ln in lines:
            if ln.startswith(("first touch", "window ", "compiles")):
                print(f"run {i}: {ln}")
        if p.returncode != 0 or not lines:
            print(f"run {i}: exit {p.returncode}\n{p.stderr[-2000:]}")
            continue
        res = json.loads(lines[-1])
        print(f"run {i}: correct={res['correct']} " + json.dumps(
            {k: v["value"] for k, v in res["metrics"].items()}), flush=True)
        out.append({k: v["value"] for k, v in res["metrics"].items()})
    return out


def within(args) -> dict[float, list[dict[str, float]]]:
    """ONE run of the normal command with a long window and `--keep`; its
    statement log is then cut into consecutive windows of each length in
    `--cut`, and each cut is reduced by the harness's own arithmetic. A
    long window is windows back to back with nothing between them."""
    sys.path.insert(0, ROOT)
    from benchmarks.harness import manifest, metrics

    total = max(args.cut) * args.windows
    p = subprocess.run(
        [sys.executable, RUN, "--workload", args.workload, "--seed",
         str(args.seed), "--seconds", str(total), "--trace", "0", "--keep"]
        + (["--rehearse-cpu"] if args.rehearse_cpu else []),
        cwd=ROOT, capture_output=True, text=True)
    if p.returncode != 0:
        print(f"within: exit {p.returncode}\n{p.stderr[-2000:]}")
        return {}
    out_dir = os.path.join(
        ROOT, ".bench_out", f"{args.workload}-seed{args.seed}-trace0"
        + ("-rehearsal" if args.rehearse_cpu else ""))
    with open(os.path.join(out_dir, "run.json")) as f:
        run = json.load(f)
    with open(os.path.join(out_dir, "statements.json")) as f:
        records = json.load(f)["records"]
    classes = manifest.load_cell(manifest.load_manifest(ROOT),
                                 args.workload)["classes"]
    for cls, rows in run["rows_scanned"].items():
        classes[cls]["rows_scanned"] = rows
    print(f"within: one window of {total:g} s, correct="
          f"{all('limit' not in v or v['value'] <= v['limit'] for v in run['compared'].values())}, "
          f"whole window " + json.dumps(run["metrics"]))
    w0, w1 = run["run"]["w0"], run["run"]["w1"]
    cuts = {}
    for length in args.cut:
        n = int((w1 - w0 + 1e-6) // length)
        cuts[length] = [metrics.end_to_end(
            records, w0 + i * length, w0 + (i + 1) * length, classes)
            for i in range(n)]
    return cuts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--windows", type=int, default=6,
                    help="windows of the longest --cut in the one long run")
    ap.add_argument("--cut", type=lambda t: [float(x) for x in t.split(",")],
                    default=None, help="window lengths to cut the long run "
                    "into, e.g. 30,45,60 (default: --seconds)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="pass through: a rehearsal of this tool's control "
                         "flow (the across part then has no result lines)")
    args = ap.parse_args(argv)
    args.cut = args.cut or [args.seconds]
    if args.windows:
        for length, runs in within(args).items():
            table(f"within one process, {length:g} s windows", runs)
    if args.runs:
        table(f"across processes, {args.seconds:g} s windows", across(args))
    return 0


if __name__ == "__main__":
    sys.exit(main())
