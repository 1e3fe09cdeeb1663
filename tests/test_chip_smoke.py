"""chip_smoke.py refuses to pass without a chip.

The driver runs `python chip_smoke.py` once where there is no
accelerator and requires it to fail there; only the explicit rehearsal
may run on XLA's CPU backend, and nothing it prints can be mistaken for
a device result.
"""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)


def test_no_arguments_without_a_chip_fails_and_names_the_platform():
    p = _run()
    assert p.returncode != 0
    assert "platform=cpu" in p.stdout          # the device line
    assert "no TPU" in p.stderr and "platform=cpu" in p.stderr
    assert '"ok"' not in p.stdout               # no result line


def test_rehearsal_passes_and_marks_every_line():
    p = _run("--rehearse-cpu")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) > 20
    assert all(ln.startswith("platform=cpu REHEARSAL ") for ln in lines)
    for name in ("q6", "q1", "group_top10", "row_scan", "topn", "q3", "q5",
                 "oltp"):
        assert any(f" {name}: PASS" in ln for ln in lines), name
    assert '"ok"' not in p.stdout               # never a device result


def test_result_line_has_exactly_the_contract_keys():
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    line = mod.result_line(
        {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
