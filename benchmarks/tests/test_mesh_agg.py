"""The four-chip cell `mesh_agg` (configuration tpch_sf50_mesh4): what the
manifest gives run.py, a rehearsal on four virtual CPU devices with the
mesh plane on (correct, every engine tag device@mesh4) and off (not
correct: the tag is tested by prefix, so a table quietly served from one
device fails), the skew reader on a synthetic `obs`, and the control of
`correct` at a test's size (the cell's own size: PERF.md section 4)."""

import importlib.util
import os
import subprocess
import sys

import pytest

from benchmarks.harness import manifest as MF
from benchmarks.tools import control

CELL = "mesh_agg"
CLASSES = ["mesh_q6", "mesh_topn", "mesh_row_scan"]


def rehearse(env_extra: dict, trace: int = 0):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED="0",
               **env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(MF.BENCH_DIR, "run.py"), "--workload",
         CELL, "--seed", "3000000019", "--seconds", "2", "--trace",
         str(trace), "--rehearse-cpu"],
        cwd=MF.ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_the_manifest_loads_the_cell():
    man = MF.load_manifest(MF.ROOT)
    cell = MF.load_cell(man, CELL)
    assert cell["workload"]["chips"] == 4
    assert cell["workload"]["config"] == "tpch_sf50_mesh4"
    cfg = cell["config"]
    assert cfg["lineitem_scale_factor"] == 50.0 and cfg["chips"] == 4
    assert "joinset_scale_factor" not in cfg
    assert cfg["storage"] == {"durable": False, "sync_log": "off"}
    # a rehearsal's table has to pass the mesh plane's shard threshold
    assert 6_000_000 * cfg["lineitem_scale_factor"] \
        * cfg["rehearsal_scale"] >= 1 << 20
    (group,) = cell["traffic"]["connections"]
    assert group == {"count": 4, "classes": CLASSES}
    assert cell["traffic"]["loop"] == "closed"
    for cls in CLASSES:
        st = cell["classes"][cls]
        assert st["engine"] == "device@mesh4" and st["kind"] == "analytic"
        assert st["scans"] == ["sf10.lineitem"]
        base = MF._read(MF.BENCH_DIR, "statements", cls[len("mesh_"):])
        assert (st["sql"], st["oracle"]) == (base["sql"], base["oracle"])
    assert [cell["classes"][c]["min_bytes_per_row"] for c in CLASSES] \
        == [9, 4, 5]
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert e2e == {"analytic_geomean_ms", "analytic_rows_per_s", "setup_s"}
    names = {m["name"] for m in cell["layer_metrics"]}
    assert {"mesh.collective_ms", "mesh.busy_skew", "mesh.reshard_bytes",
            "host.stage_coverage", "compile.in_window"} <= names
    for cls in CLASSES:
        assert {f"device.busy_ms_{cls}", f"kernel.{cls}_roofline"} <= names
    # one four-chip cell of four: within the limit of a half
    chips = [w["chips"] for w in man["workloads"]]
    assert chips.count(4) <= max(1, len(chips) // 2)


def test_mesh_q1_waits_beside_q1_in_no_mix():
    q1 = MF._read(MF.BENCH_DIR, "statements", "q1")
    mq1 = MF._read(MF.BENCH_DIR, "statements", "mesh_q1")
    assert (mq1["sql"], mq1["oracle"]) == (q1["sql"], q1["oracle"])
    assert mq1["engine"] == "device@mesh4"
    assert mq1["min_bytes_per_row"] == 12
    for w in MF.load_manifest(MF.ROOT)["workloads"]:
        assert "mesh_q1" not in MF.load_cell(
            MF.load_manifest(MF.ROOT), w["name"])["classes"]


def test_a_rehearsal_on_four_virtual_devices_is_served_by_the_mesh():
    p = rehearse({})
    assert p.returncode == 0, p.stderr[-3000:]
    assert "REHEARSAL correct: True" in p.stderr
    for cls in CLASSES:
        assert f"first touch {cls}:" in p.stdout
    touches = [ln for ln in p.stdout.splitlines() if "first touch" in ln]
    assert len(touches) == 3 and all(
        "engines=['device@mesh4']" in ln for ln in touches), touches
    assert "REHEARSAL compared engine_not_device: 0 " in p.stderr
    assert "REHEARSAL compared host_fallbacks: 0 " in p.stderr


def test_with_the_mesh_plane_off_the_cell_is_not_correct():
    """The same answers from one device carry the tag `device`: the cell
    asks for `device@mesh4`."""
    p = rehearse({"TIDB_TPU_MESH": "0"})
    assert p.returncode == 1, p.stdout[-2000:] + p.stderr[-2000:]
    assert "REHEARSAL correct: False" in p.stderr
    assert "engines=['device']" in p.stdout
    assert "REHEARSAL compared engine_not_device: 3 " in p.stderr
    # nothing else is at fault: the answers are right
    for cls in CLASSES:
        assert f"REHEARSAL compared {cls}_wrong: 0 " in p.stderr


def skew_reader():
    path = os.path.join(MF.BENCH_DIR, "layer_metrics", "mesh.busy_skew.py")
    spec = importlib.util.spec_from_file_location("mesh_busy_skew", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def solo(per_device: dict) -> dict:
    return {"n": 8, "latencies_s": [0.1] * 8,
            "trace": {"busy_s": 0.0, "per_device": per_device, "ops": []}}


@pytest.mark.parametrize("obs,want", [
    # even
    ({"chips": 4, "solo": {"mesh_q6": solo({0: 1.0, 1: 1.0, 2: 1.0, 3: 1.0}),
                           "mesh_topn": solo({0: 2.0, 1: 2.0, 2: 2.0,
                                              3: 2.0})}}, 1.0),
    # one device does twice the others' work, summed over the classes
    ({"chips": 4, "solo": {"mesh_q6": solo({0: 2.0, 1: 1.0, 2: 1.0, 3: 1.0}),
                           "mesh_topn": solo({0: 2.0, 1: 1.0, 2: 1.0,
                                              3: 1.0})}}, 1.6),
    # a device that ran nothing has no plane and counts as 0
    ({"chips": 4, "solo": {"mesh_q6": solo({0: 3.0, 1: 3.0})}}, 2.0),
    # a class outside the metric's list is not read
    ({"chips": 4, "solo": {"mesh_q6": solo({0: 1.0, 1: 1.0, 2: 1.0, 3: 1.0}),
                           "other": solo({0: 9.0})}}, 1.0),
    # nothing to read: no solo sub-window, or no device plane in it
    ({"chips": 4, "solo": {}}, None),
    ({"chips": 4, "solo": {"mesh_q6": solo({})}}, None),
])
def test_busy_skew_reader(obs, want):
    spec = MF._read(MF.BENCH_DIR, "layer_metrics", "mesh.busy_skew")
    got = skew_reader()(obs, spec)
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("seed", [3, 2147483659, 4000000007])
def test_float32_sums_in_the_programs_place_are_not_correct(seed, capsys):
    """The control of the cell's `correct`, 0.002 of its size (600 000
    rows): Q6's sum in float32 fails, the exact reference agrees."""
    assert control.run(CELL, [seed], scale=0.002)
    out = capsys.readouterr().out
    assert f"seed {seed} mesh_q6: exact reference -> agrees; float32 " \
        "control -> fails" in out
    assert "AGREES" not in out and "WRONG" not in out
