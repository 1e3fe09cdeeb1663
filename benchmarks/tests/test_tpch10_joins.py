"""The cell `tpch10_joins` (configuration tpch_sf10_full_1chip): what the
manifest gives run.py, a CPU rehearsal that comes out correct with every
class aggregated inside a device fragment, and the same rehearsal with an
altered answer planted, which does not."""

import os
import subprocess
import sys

from benchmarks.harness import manifest as MF

CELL = "tpch10_joins"
CLASSES = ["q3", "q5", "q7", "q8", "q10", "q12", "q14"]
ARGS = ["--workload", CELL, "--seed", "3000000035", "--seconds", "3",
        "--trace", "0"]


def run(argv: list[str]):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED="0")
    return subprocess.run([sys.executable] + argv, cwd=MF.ROOT, env=env,
                          capture_output=True, text=True, timeout=900)


def test_the_manifest_loads_the_cell():
    man = MF.load_manifest(MF.ROOT)
    cell = MF.load_cell(man, CELL)
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["config"] == "tpch_sf10_full_1chip"
    cfg = cell["config"]
    assert cfg["joinset_scale_factor"] == 10.0 and cfg["chips"] == 1
    assert cfg["reduced"] == ["joinset_scale_factor"]
    assert cfg["published"]["joinset_scale_factor"] == 100.0
    assert cfg["storage"] == {"durable": False, "sync_log": "off"}
    # the stub table no class reads, and a rehearsal of a few thousand orders
    assert cfg["lineitem_scale_factor"] == 0.01
    assert 1000 <= 1_500_000 * cfg["joinset_scale_factor"] \
        * cfg["rehearsal_scale"] <= 10_000
    (entry,) = [c for c in man["configs"] if c["name"] == cfg["name"]]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    (group,) = cell["traffic"]["connections"]
    assert group["count"] == 4 and group["classes"] == CLASSES
    step = group["interval_s"]
    assert step > 0 and round(step, 2) == step
    assert cell["traffic"]["warmup_rounds"] == len(CLASSES)
    assert cell["traffic"]["leadin_s"] == max(3.0, round(8 * step, 2))
    for cls in CLASSES:
        st = cell["classes"][cls]
        assert st["db"] == "joins" and st["kind"] == "analytic"
        assert st["engine"] == "device" and st["oracle"] == cls
        assert st["scans"] and all(t.startswith("joins.")
                                   for t in st["scans"])
    # the two reused classes are light's, byte for byte
    light = MF.load_cell(man, "tpch10_light")["classes"]
    assert cell["classes"]["q3"] == light["q3"]
    assert cell["classes"]["q5"] == light["q5"]
    assert cell["classes"]["q7"]["min_bytes_per_row"] == 17.4
    assert cell["classes"]["q8"]["min_bytes_per_row"] == 17.9
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert e2e == {"analytic_geomean_ms", "analytic_rows_per_s", "setup_s"}
    names = {m["name"] for m in cell["layer_metrics"]}
    assert {f"device.busy_ms_{c}" for c in CLASSES} <= names
    assert {"kernel.q7_roofline", "kernel.q8_roofline",
            "copr.frag_device_agg_share", "copr.frag_fetched_rows",
            "copr.hc_block_share", "compile.in_window"} <= names
    chips = [w["chips"] for w in man["workloads"]]
    assert chips.count(4) <= max(1, len(chips) // 2)


def test_a_rehearsal_is_correct_and_aggregates_on_the_device():
    p = run([os.path.join(MF.BENCH_DIR, "run.py")] + ARGS
            + ["--rehearse-cpu"])
    assert p.returncode == 0, p.stderr[-3000:]
    assert "REHEARSAL correct: True" in p.stderr
    touches = [ln for ln in p.stdout.splitlines() if "first touch" in ln]
    assert len(touches) == len(CLASSES), touches
    for cls, ln in zip(CLASSES, touches):
        assert f"first touch {cls}:" in ln
        assert "engines=['device[" in ln and "rows" not in ln, ln
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
    assert "REHEARSAL compared host_fallbacks: 0 " in p.stderr
