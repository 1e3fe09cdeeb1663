"""BENCHMARK.json against the contract's shape, and the loader."""

import copy
import json
import os
import re
import shutil

import pytest

from benchmarks.harness import manifest as MF

ROOT = MF.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def man():
    return MF.load_manifest(ROOT)


def test_keys_and_limits(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert isinstance(man["run_seconds"], int) and 1 <= man["run_seconds"] <= 51
    assert man["paths"] == ["benchmarks"]
    assert 1 <= len(man["workloads"]) <= 24
    four = sum(1 for w in man["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(man["workloads"]) // 2)
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in man["workloads"]}
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert 1 <= len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        assert c["file"].startswith("benchmarks/")
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"]
        assert sorted(held["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert isinstance(held[k], (int, float)), k
        assert held["guarantees"] and held["background_workers"]


def test_metrics_follow_the_contract(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    cells = [w["name"] for w in man["workloads"]]
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    assert len(set(names)) == len(names)
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200
        moved = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert w in moved.get("workloads", cells), (m["name"], w)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        mine = MF.metrics_of(man, cell, "end_to_end")
        assert any(m["name"] == "setup_s" for m in mine) and len(mine) >= 2
        assert MF.metrics_of(man, cell, "per_layer")


def test_every_cell_loads_with_its_files(man):
    for w in man["workloads"]:
        cell = MF.load_cell(man, w["name"])
        assert cell["config"]["chips"] == w["chips"]
        for cls, st in cell["classes"].items():
            assert os.path.exists(os.path.join(
                MF.BENCH_DIR, "oracles", st["oracle"] + ".py"))
            assert "sql" in st or "builder" in st
        for spec in cell["layer_metrics"]:
            assert spec["reader"] and spec["moves"]


def test_files_under_paths_have_plain_names():
    for dirpath, dirs, files in os.walk(MF.BENCH_DIR):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            assert NAME.match(f), os.path.join(dirpath, f)


@pytest.mark.parametrize("field,value", [
    ("name", "p95 ms"), ("name", "a,b"), ("name", "lat/ms"),
    ("name", "x" * 65), ("name", "-lead"), ("unit", "tokens per second"),
    ("unit", "µs"), ("unit", ""), ("better", "faster"),
    ("source", "guess")])
def test_loader_rejects_what_the_manifest_alphabets_exclude(man, field, value):
    m = copy.deepcopy(man["end_to_end"][0])
    m[field] = value
    with pytest.raises(MF.ManifestError):
        MF.check_metric(m, {w["name"] for w in man["workloads"]}, "end_to_end")


def test_unknown_workload_and_missing_file_are_errors(man, tmp_path):
    with pytest.raises(MF.ManifestError):
        MF.load_cell(man, "no_such_cell")
    with pytest.raises(MF.ManifestError):
        MF._read(str(tmp_path), "traffic", "absent")
    with pytest.raises(MF.ManifestError):
        MF._read(str(tmp_path), "traffic", "../etc")


def test_a_cell_is_added_with_files_and_entries_only(man, tmp_path):
    """The README's worked example: a new configuration, mix, statement
    class and per-layer metric arrive as new files plus new entries; no
    file that is there is edited."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(MF.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs" / "tpch_sf3_1chip.json").write_text(json.dumps({
        **json.loads((bench / "configs" / "tpch_sf10_1chip.json").read_text()),
        "name": "tpch_sf3_1chip", "lineitem_scale_factor": 3.0}))
    (bench / "statements" / "count_star.json").write_text(json.dumps({
        "name": "count_star", "db": "sf10", "op": "query", "kind": "analytic",
        "sql": "select count(*) from lineitem", "oracle": "count_star",
        "scans": ["sf10.lineitem"], "engine": "device"}))
    (bench / "oracles" / "count_star.py").write_text(
        "def reference(data):\n    return len(data['lineitem']['l_orderkey'])\n"
        "def compare(rows, ref, fresh=None, key=None):\n"
        "    return None if int(rows[0][0]) == ref else 'count differs'\n")
    (bench / "traffic" / "count_only.json").write_text(json.dumps({
        "name": "count_only", "connections": [
            {"count": 2, "classes": ["count_star", "q6"]}],
        "leadin_s": 1.0, "trace": {"concurrent_s": 2, "margin_s": 0.5,
                                   "solo_statements": 4,
                                   "explain_samples": 2}}))
    (bench / "layer_metrics" / "device.busy_ms_count_star.json").write_text(
        json.dumps({"name": "device.busy_ms_count_star",
                    "reader": "device_busy_per_statement",
                    "class": "count_star", "source": "device_trace",
                    "layer": "device programs",
                    "moves": "analytic_geomean_ms"}))
    new = copy.deepcopy(man)
    new["configs"].append({"name": "tpch_sf3_1chip", "source": "TPC-H SF3",
                           "file": "benchmarks/configs/tpch_sf3_1chip.json",
                           "reduced": ["lineitem_scale_factor"], "why": "x"})
    new["workloads"].append({"name": "sf3_count", "config": "tpch_sf3_1chip",
                             "traffic": "count_only", "chips": 1, "why": "x"})
    for m in new["end_to_end"]:
        if m["name"] in ("analytic_geomean_ms", "analytic_rows_per_s"):
            m["workloads"].append("sf3_count")
    new["per_layer"].append({
        "name": "device.busy_ms_count_star", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "device programs",
        "moves": "analytic_geomean_ms", "workloads": ["sf3_count"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    loaded = MF.load_manifest(str(tmp_path))
    cell = MF.load_cell(loaded, "sf3_count", str(bench))
    assert cell["config"]["lineitem_scale_factor"] == 3.0
    assert list(cell["classes"]) == ["count_star", "q6"]
    assert [m["name"] for m in cell["layer_metrics"]] == [
        "device.busy_ms_count_star"]
    assert [m["name"] for m in cell["end_to_end"]] == [
        "analytic_geomean_ms", "analytic_rows_per_s", "setup_s"]
    for p, data in before.items():
        assert p.read_bytes() == data
