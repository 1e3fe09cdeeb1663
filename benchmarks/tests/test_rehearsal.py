"""Each cell's control flow end to end on XLA's CPU backend at a tiny
scale: a rehearsal prints no result line; with the timed path broken
underneath, `correct` comes out false. One subprocess per case: a run owns
its process (jax configuration, core pinning, gc.freeze)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import manifest as MF

HERE = os.path.dirname(os.path.abspath(__file__))
ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED="0")
CELLS = [(w["name"], w["chips"])
         for w in MF.load_manifest(MF.ROOT)["workloads"]]


def rehearse(fault, workload, trace=0, seed=2147483659):
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "faults.py"), fault,
         "--workload", workload, "--seed", str(seed), "--seconds", "2",
         "--trace", str(trace)],
        cwd=MF.ROOT, env=ENV, capture_output=True, text=True, timeout=600)
    return p


@pytest.mark.parametrize("workload,chips", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_is_correct_and_prints_no_result(workload, chips, trace):
    p = rehearse("none", workload, trace)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert lines and all(ln.startswith("REHEARSAL") for ln in lines)
    assert not lines[-1].startswith("{")
    assert "REHEARSAL correct: True" in p.stderr
    with open(os.path.join(
            MF.ROOT, ".bench_out",
            f"{workload}-seed2147483659-trace{trace}-rehearsal",
            "run.json")) as f:
        run = json.load(f)
    man = MF.load_manifest(MF.ROOT)
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in MF.metrics_of(man, workload, kind)}
    # a roofline needs the chip's peak: a rehearsal has none and leaves
    # it out
    cell = MF.load_cell(man, workload)
    want -= {m["name"] for m in cell["layer_metrics"]
             if m["reader"] == "roofline_hbm"}
    assert want <= set(run["metrics"]), want - set(run["metrics"])


@pytest.mark.parametrize("workload,chips", CELLS)
def test_an_altered_answer_is_not_correct(workload, chips):
    p = rehearse("answer_altered", workload)
    assert p.returncode == 1, p.stdout[-2000:] + p.stderr[-2000:]
    assert "REHEARSAL correct: False" in p.stderr
    assert "WRONG" in p.stdout


def test_an_update_acknowledged_but_not_applied_is_not_correct():
    if "htap_sysbench" not in dict(CELLS):
        pytest.skip("no cell with writes")
    p = rehearse("state_unchanged", "htap_sysbench")
    assert p.returncode == 1, p.stdout[-2000:] + p.stderr[-2000:]
    assert "REHEARSAL correct: False" in p.stderr
    assert "WRONG read-back" in p.stdout or "WRONG update_index" in p.stdout


def test_a_log_not_synced_at_the_commit_is_not_correct():
    if "htap_sysbench" not in dict(CELLS):
        pytest.skip("no cell with durable writes")
    p = rehearse("log_not_synced", "htap_sysbench")
    assert p.returncode == 1, p.stdout[-2000:] + p.stderr[-2000:]
    assert "REHEARSAL correct: False" in p.stderr
    assert "REHEARSAL compared acked_not_fsynced: 0 " not in p.stderr


def test_writers_that_meet_on_two_rows_are_correct():
    """Both writers on ids 1-2: the read-back's arithmetic holds where
    rows are shared, and a write conflict, where one comes (about one in
    a thousand updates here), is retried and fails nothing."""
    if "htap_sysbench" not in dict(CELLS):
        pytest.skip("no cell with writes")
    p = rehearse("one_hot_row", "htap_sysbench")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "REHEARSAL correct: True" in p.stderr
    assert "read back 2 updated rows" in p.stdout


def test_without_a_tpu_there_is_no_result():
    p = subprocess.run(
        [sys.executable, os.path.join(MF.BENCH_DIR, "run.py"), "--workload",
         CELLS[0][0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=MF.ROOT, env=ENV, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs" in p.stderr and "TPU" in p.stderr
