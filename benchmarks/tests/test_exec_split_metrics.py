"""The seven per-layer metrics that split the executor's own time: six data
files for the `counter_ratio` reader over the stages `snapshot`, `decode`,
`gather`, `host_op` and `result_rows` (and the whole they add up to), and
`host.idle_unnamed_share`, a `.py` reader of the traced window's idle-gap
names. Each loads through the manifest and is listed for its cells; the
stage metrics read the program's own spelling and nothing (None, never 0)
where the program has no such stage; the idle share reads a hand-built gap
list; and a traced rehearsal of tpch10_light prints every one of them."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import layers as L
from benchmarks.harness import manifest as MF

ANALYTIC = ("tpch10_light", "tpch10_heavy", "mesh_agg", "tpch10_joins")
NEW = {
    "store.snapshot_s": ANALYTIC,
    "copr.decode_s": ANALYTIC,
    # only where a read returns rows: heavy and joins aggregate on the device
    "copr.gather_s": ("tpch10_light", "mesh_agg"),
    "exec.host_op_s": ANALYTIC,
    "session.result_rows_s": ANALYTIC,
    "session.exec_total_s": ANALYTIC,
    "host.idle_unnamed_share": ("tpch10_light", "tpch10_heavy",
                                "htap_sysbench", "mesh_agg", "tpch10_joins"),
}
SPLIT = ("snapshot", "decode", "gather", "host_op", "result_rows")
STAGE_OF = dict(zip(("store.snapshot_s", "copr.decode_s", "copr.gather_s",
                     "exec.host_op_s", "session.result_rows_s"), SPLIT))
SEED = "2147483661"


def spec_of(name: str, cell: str) -> dict:
    loaded = MF.load_cell(MF.load_manifest(MF.ROOT), cell)
    return next(m for m in loaded["layer_metrics"] if m["name"] == name)


def sample(stage: str, part: str) -> str:
    return f'tidb_dispatch_stage_duration_seconds_{part}{{stage="{stage}"}}'


@pytest.mark.parametrize("name,cell", [(n, c) for n, cells in NEW.items()
                                       for c in cells])
def test_new_metric_is_listed_and_loads(name, cell):
    man = MF.load_manifest(MF.ROOT)
    entry = next(m for m in man["per_layer"] if m["name"] == name)
    assert tuple(entry["workloads"]) == NEW[name]
    spec = spec_of(name, cell)
    assert spec["unit"] == entry["unit"] and spec["moves"] == entry["moves"]
    assert spec["reader"] == ("python" if name == "host.idle_unnamed_share"
                              else "counter_ratio")
    # every listed cell reports the end-to-end metric this one moves
    moved = next(m for m in man["end_to_end"] if m["name"] == entry["moves"])
    assert cell in moved.get("workloads", [cell])


def test_stage_metrics_read_the_programs_own_spelling():
    """Seconds a command of each stage; the whole is admission, exec and
    epilogue plus the five, as session.exec_self_s was before them."""
    from tidb_tpu import obs as O

    def scrape() -> dict:
        out = {}
        for ln in O.PROCESS_METRICS.render().splitlines():
            if ln and not ln.startswith("#"):
                k, _, v = ln.rpartition(" ")
                out[k] = float(v)
        return out

    before = scrape()
    for i, name in enumerate(("wire_read", "exec", "epilogue") + SPLIT):
        with O.stage(name):
            sum(range(3000 * (i + 1)))
    after = scrape()
    obs = {"counters_before": before, "counters_after": after}

    def moved(stage: str) -> float:
        k = sample(stage, "sum")
        return after.get(k, 0.0) - before.get(k, 0.0)

    commands = after[sample("wire_read", "count")] \
        - before.get(sample("wire_read", "count"), 0.0)
    assert commands == 1
    for name, stage in STAGE_OF.items():
        v = L.read(spec_of(name, "tpch10_light"), obs, MF.BENCH_DIR)
        assert v == pytest.approx(moved(stage)) and v > 0
    total = L.read(spec_of("session.exec_total_s", "tpch10_light"), obs,
                   MF.BENCH_DIR)
    assert total == pytest.approx(sum(
        moved(s) for s in ("admission", "exec", "epilogue") + SPLIT))


@pytest.mark.parametrize("name", sorted(STAGE_OF))
def test_stage_metric_reads_nothing_where_the_stage_is_absent(name):
    """A program without the stage (the parent commit) leaves the metric
    out of the line (None), never 0; the whole still reads what
    session.exec_self_s reads there."""
    old = {sample("exec", "sum"): 2.0, sample("exec", "count"): 4.0,
           sample("wire_read", "count"): 4.0}
    spec = spec_of(name, "tpch10_light")
    assert L.read(spec, {"counters_before": {}, "counters_after": old},
                  MF.BENCH_DIR) is None
    total = spec_of("session.exec_total_s", "tpch10_light")
    assert L.read(total, {"counters_before": {}, "counters_after": old},
                  MF.BENCH_DIR) == pytest.approx(0.5)


@pytest.mark.parametrize("gaps,want", [
    ([["titpu/exec", 3.0], ["titpu/decode", 0.5], ["titpu/host_op", 0.5],
      ["device:gaps_under_50us", 7.0]], 0.75),
    ([["titpu/wire_read", 1.5], ["host:no_traced_span", 0.25],
      ["device:gaps_not_looked_up", 0.25], ["titpu/exec", 0.5],
      ["titpu/gather", 0.5]], 1.0 / 3.0),
    ([["titpu/result_rows", 2.0], ["device:gaps_under_50us", 1.0]], 0.0),
    ([["device:gaps_under_50us", 1.0]], None),
    ([], None),
])
def test_idle_unnamed_share_on_a_hand_built_gap_list(gaps, want):
    spec = spec_of("host.idle_unnamed_share", "tpch10_light")
    got = L.read(spec, {"concurrent": {"idle_gaps": gaps}}, MF.BENCH_DIR)
    assert got == (None if want is None else pytest.approx(want))


def test_traced_rehearsal_prints_every_new_metric():
    p = subprocess.run(
        [sys.executable, os.path.join(MF.BENCH_DIR, "run.py"), "--workload",
         "tpch10_light", "--rehearse-cpu", "--trace", "1", "--seed", SEED,
         "--seconds", "2"],
        cwd=MF.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    silent = {ln.split()[2].rstrip(":") for ln in p.stdout.splitlines()
              if ln.endswith("nothing to read")}
    assert not set(NEW) & silent, set(NEW) & silent
    with open(os.path.join(
            MF.ROOT, ".bench_out",
            f"tpch10_light-seed{SEED}-trace1-rehearsal", "run.json")) as f:
        got = json.load(f)["metrics"]
    assert set(NEW) <= set(got), set(NEW) - set(got)
    for name in NEW:
        assert got[name]["value"] >= 0, name
    # the parts add up to no more than the whole they split
    assert sum(got[n]["value"] for n in STAGE_OF) \
        <= got["session.exec_total_s"]["value"] * (1 + 1e-9)
