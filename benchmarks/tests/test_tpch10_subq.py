"""The cell `tpch10_subq` (configuration tpch_sf10_subq_1chip): what the
manifest gives run.py, a CPU rehearsal that comes out correct with each
statement served by one fragment read with run-statistics gates, and the
same rehearsal with an altered answer planted, which does not."""

import os
import subprocess
import sys

from benchmarks.harness import manifest as MF

CELL = "tpch10_subq"
CLASSES = ["q18", "q21"]
ARGS = ["--workload", CELL, "--seed", "3000000042", "--seconds", "3",
        "--trace", "0"]


def run(argv: list[str]):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED="0")
    return subprocess.run([sys.executable] + argv, cwd=MF.ROOT, env=env,
                          capture_output=True, text=True, timeout=900)


def test_the_manifest_loads_the_cell():
    man = MF.load_manifest(MF.ROOT)
    cell = MF.load_cell(man, CELL)
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["config"] == "tpch_sf10_subq_1chip"
    cfg = cell["config"]
    full = MF.load_cell(man, "tpch10_joins")["config"]
    # the join set, its loader and its cut are tpch10_joins'
    for k in ("chips", "lineitem_scale_factor", "joinset_scale_factor",
              "reduced", "storage"):
        assert cfg[k] == full[k], k
    # a rehearsal holds more suppliers (8 500) than a dense segment space
    # (client.MAX_DENSE_SEGMENTS, 8 192), so Q21 takes its SF10 body there
    assert cfg["rehearsal_scale"] * cfg["joinset_scale_factor"] * 10_000 \
        > 8192
    (entry,) = [c for c in man["configs"] if c["name"] == cfg["name"]]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["joinset_scale_factor"]
    (group,) = cell["traffic"]["connections"]
    assert group == {"count": 1, "classes": CLASSES}
    assert cell["traffic"]["warmup_rounds"] == 1
    assert cell["traffic"]["trace"]["solo_statements"] == 3
    assert cell["traffic"]["trace"]["explain_samples"] == 1
    for cls in CLASSES:
        st = cell["classes"][cls]
        assert st["db"] == "joins" and st["kind"] == "analytic"
        assert st["engine"] == "device[fat+runstat]" and st["oracle"] == cls
        assert len(set(st["scans"])) == len(st["scans"])
    assert cell["classes"]["q18"]["min_bytes_per_row"] == 7.6
    assert cell["classes"]["q21"]["min_bytes_per_row"] == 10.6
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert e2e == {"analytic_geomean_ms", "analytic_rows_per_s", "setup_s"}
    names = {m["name"] for m in cell["layer_metrics"]}
    assert {"device.busy_ms_q18", "device.busy_ms_q21",
            "kernel.q18_roofline", "kernel.q21_roofline",
            "copr.frag_runstat_share", "copr.frag_fetched_rows",
            "compile.in_window", "compile.warm_s"} <= names
    assert "store.shared_mask_share" not in names
    chips = [w["chips"] for w in man["workloads"]]
    assert chips.count(4) <= max(1, len(chips) // 2)


def test_a_rehearsal_is_correct_with_one_gated_read_a_statement():
    p = run([os.path.join(MF.BENCH_DIR, "run.py")] + ARGS
            + ["--rehearse-cpu"])
    assert p.returncode == 0, p.stderr[-3000:]
    assert "REHEARSAL correct: True" in p.stderr
    touches = [ln for ln in p.stdout.splitlines() if "first touch" in ln]
    assert len(touches) == len(CLASSES), touches
    for cls, ln in zip(CLASSES, touches):
        assert f"first touch {cls}:" in ln
        assert "engines=['device[fat+runstat]']" in ln, ln
    for cls in CLASSES:
        assert f"REHEARSAL compared {cls}_wrong: 0 " in p.stderr
    assert "REHEARSAL compared host_fallbacks: 0 " in p.stderr
    assert "REHEARSAL compared engine_not_device: 0 " in p.stderr


def test_an_altered_answer_is_not_correct():
    p = run([os.path.join(MF.BENCH_DIR, "tests", "faults.py"),
             "answer_altered"] + ARGS)
    assert p.returncode == 1, p.stdout[-2000:] + p.stderr[-2000:]
    assert "REHEARSAL correct: False" in p.stderr
    assert "WRONG" in p.stdout + p.stderr
