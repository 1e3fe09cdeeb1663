"""The ten per-layer metrics that read the program's stage timeline
(obs.stage over the served path, PR 24): each is a data file for the
`counter_ratio` reader. Here each loads through the manifest, reads a
number off samples spelled by the program's own `Registry.render`, and
reads nothing (None, never 0) where the program has no such series, as the
parent commit has not; and a traced rehearsal prints every one of them."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks.harness import layers as L
from benchmarks.harness import manifest as MF

NEW = {
    "wire.self_s": ("tpch10_light", "tpch10_heavy"),
    "session.parse_plan_s": ("tpch10_light", "tpch10_heavy"),
    "session.exec_self_s": ("tpch10_light", "tpch10_heavy"),
    "host.offcpu_share": ("tpch10_light", "tpch10_heavy"),
    "host.stage_coverage": ("tpch10_light", "tpch10_heavy"),
    "copr.dispatch_s": ("tpch10_light", "tpch10_heavy", "htap_sysbench"),
    "copr.fetch_s": ("tpch10_light", "tpch10_heavy", "htap_sysbench"),
    "point.queue_s": ("htap_sysbench",),
    "point.offcpu_share": ("htap_sysbench",),
    "kv.fsync_wait_s": ("htap_sysbench",),
}
STAGES = ("wire_queue", "wire_read", "parse", "plan_build", "fast_plan",
          "admission", "exec", "epilogue", "encode", "wire_write",
          "wire_repark", "prepare", "merge", "kernel", "device_get")


def scrape(text: str) -> dict[str, float]:
    """{sample{labels}: value}, as harness/system.py reads /metrics."""
    out = {}
    for ln in text.splitlines():
        if ln and not ln.startswith("#"):
            k, _, v = ln.rpartition(" ")
            out[k] = float(v)
    return out


@pytest.fixture(scope="module")
def rendered():
    """(before, after) of the program's own registries around one pass
    through every stage, one command, one fsync wait and one commit."""
    from tidb_tpu import obs as O

    commits = O.Registry().counter("tidb_group_commit_commits_total", "x")

    def text() -> str:
        reg = O.Registry()
        reg.counter("tidb_group_commit_commits_total", "x").inc(
            commits.get())
        return O.PROCESS_METRICS.render() + reg.render()

    commits.inc(3)
    before = scrape(text())
    for i, name in enumerate(STAGES):
        if name in ("wire_queue", "wire_repark"):
            O.note_stage(name, 0.002)
        else:
            with O.stage(name, clocked=name in ("exec", "device_get")):
                sum(range(2000 * (i + 1)))  # on the CPU
                time.sleep(0.0005)          # and off it
    O.CONN_COMMAND_SECONDS.observe(1.0)
    with O.wait("fsync_wait"):
        sum(range(20000))
    commits.inc(2)
    return before, scrape(text())


def spec_of(name: str, cell: str) -> dict:
    loaded = MF.load_cell(MF.load_manifest(MF.ROOT), cell)
    return next(m for m in loaded["layer_metrics"] if m["name"] == name)


@pytest.mark.parametrize("name,cell", [(n, c) for n, cells in NEW.items()
                                       for c in cells])
def test_new_metric_is_listed_and_loads(name, cell):
    man = MF.load_manifest(MF.ROOT)
    entry = next(m for m in man["per_layer"] if m["name"] == name)
    assert tuple(entry["workloads"]) == NEW[name]
    spec = spec_of(name, cell)
    assert spec["reader"] == "counter_ratio" and spec["unit"] == entry["unit"]
    # every listed cell reports the end-to-end metric this one moves
    moved = next(m for m in man["end_to_end"] if m["name"] == entry["moves"])
    assert cell in moved.get("workloads", [cell])


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_reads_the_programs_own_spelling(rendered, name):
    before, after = rendered
    spec = spec_of(name, NEW[name][0])
    v = L.read(spec, {"counters_before": before, "counters_after": after},
               MF.BENCH_DIR)
    assert isinstance(v, float) and v > 0
    if spec["unit"] == "ratio":
        assert v <= 1.0
    if name == "kv.fsync_wait_s":
        # per durable commit of the sub-window (2), not per frame
        wait = sum(after[k] - before.get(k, 0.0) for k in after
                   if k.startswith('tidb_wait_seconds_sum{state="fsync'))
        assert v == pytest.approx(wait / 2)
    if name == "point.queue_s":
        assert v == pytest.approx(0.004)


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_reads_nothing_where_the_series_are_absent(name):
    """The parent commit has none of the series: the metric is left out
    of the line (None), never reported as 0."""
    spec = spec_of(name, NEW[name][0])
    old = {'tidb_dispatch_stage_duration_seconds_sum{stage="staging"}': 1.0,
           'tidb_dispatch_stage_duration_seconds_count{stage="staging"}': 2.0,
           "tidb_group_commit_batch_size_sum": 4.0}
    for counters in ({}, old):
        assert L.read(spec, {"counters_before": counters,
                             "counters_after": counters},
                      MF.BENCH_DIR) is None


@pytest.mark.parametrize("cell", ["htap_sysbench", "tpch10_light"])
def test_traced_rehearsal_prints_every_new_metric(cell):
    p = subprocess.run(
        [sys.executable, os.path.join(MF.BENCH_DIR, "run.py"), "--workload",
         cell, "--rehearse-cpu", "--trace", "1", "--seed", "2147483659",
         "--seconds", "2"],
        cwd=MF.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    want = {n for n, cells in NEW.items() if cell in cells}
    silent = {ln.split()[2].rstrip(":") for ln in p.stdout.splitlines()
              if ln.endswith("nothing to read")}
    assert not want & silent, want & silent
    with open(os.path.join(
            MF.ROOT, ".bench_out",
            f"{cell}-seed2147483659-trace1-rehearsal", "run.json")) as f:
        got = json.load(f)["metrics"]
    assert want <= set(got), want - set(got)
    assert all(got[n]["value"] > 0 for n in want)
