"""BENCHMARK.json and the data files a cell is made of.

`BENCHMARK.json` (root of the checkout) names the cells; everything that
belongs to one configuration, traffic mix, statement class or per-layer
metric sits in a file of its own under benchmarks/, found by that name:

    configs/<config>.json        the deployment, as it is run
    traffic/<traffic>.json       the mix: connections, classes, pacing
    statements/<class>.json      db, SQL text, kind, tables scanned, oracle
    layer_metrics/<metric>.json  reader, arguments, `moves`, `workloads`
    workloads/<cell>.json        the cell's predictions and notes (for the
                                 reader; the harness does not load them)

A later PR adds files and entries and edits none.
"""

from __future__ import annotations

import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _name(value, what: str) -> str:
    if not isinstance(value, str) or not NAME.match(value):
        raise ManifestError(
            f"{what} {value!r}: a name is 1-64 of A-Z a-z 0-9 _ . - and "
            f"does not start with . or -")
    return value


def _read(bench_dir: str, kind: str, name: str) -> dict:
    path = os.path.join(bench_dir, kind, _name(name, kind) + ".json")
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"{kind} {name!r}: no file {path}") from None


def check_metric(m: dict, cells: set[str], kind: str) -> None:
    _name(m.get("name"), f"{kind} metric")
    if not isinstance(m.get("unit"), str) or not UNIT.match(m["unit"]):
        raise ManifestError(
            f"metric {m['name']}: unit {m.get('unit')!r} is not 1-16 of "
            f"A-Z a-z 0-9 _ / % . -")
    if m.get("better") not in ("lower", "higher"):
        raise ManifestError(f"metric {m['name']}: better is lower or higher")
    if m.get("source") not in SOURCES:
        raise ManifestError(f"metric {m['name']}: source {m.get('source')!r}")
    for w in m.get("workloads", ()):
        if w not in cells:
            raise ManifestError(f"metric {m['name']}: no workload {w!r}")


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    cells = set()
    for w in man["workloads"]:
        for k in ("name", "config", "traffic"):
            _name(w.get(k), f"workload {k}")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"workload {w['name']}: chips is 1 or 4")
        cells.add(w["name"])
    for c in man["configs"]:
        _name(c.get("name"), "config")
        for k in c.get("reduced", ()):
            _name(k, f"config {c['name']} reduced key")
    for m in man["end_to_end"]:
        check_metric(m, cells, "end_to_end")
    e2e = {m["name"] for m in man["end_to_end"]}
    for m in man["per_layer"]:
        check_metric(m, cells, "per_layer")
        if m.get("moves") not in e2e:
            raise ManifestError(
                f"metric {m['name']}: moves {m.get('moves')!r} is not an "
                f"end-to-end metric")
    return man


def metrics_of(man: dict, cell: str, kind: str) -> list[dict]:
    """The manifest's metrics of `kind` that `cell` reports."""
    return [m for m in man[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(man: dict, name: str, bench_dir: str = BENCH_DIR) -> dict:
    """Everything run.py needs for one cell, gathered from its files."""
    for w in man["workloads"]:
        if w["name"] == name:
            break
    else:
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json (has: "
            f"{[w['name'] for w in man['workloads']]})")
    config = _read(bench_dir, "configs", w["config"])
    traffic = _read(bench_dir, "traffic", w["traffic"])
    classes: dict[str, dict] = {}
    for group in traffic["connections"]:
        for cls in group["classes"]:
            if cls not in classes:
                st = _read(bench_dir, "statements", cls)
                if st.get("kind") not in ("analytic", "point", "write",
                                          "refresh"):
                    raise ManifestError(f"statement {cls}: kind "
                                        f"{st.get('kind')!r}")
                classes[cls] = st
    layer = []
    for m in metrics_of(man, name, "per_layer"):
        spec = _read(bench_dir, "layer_metrics", m["name"])
        layer.append({**spec, "name": m["name"], "unit": m["unit"]})
    return {"workload": w, "config": config, "traffic": traffic,
            "classes": classes, "layer_metrics": layer,
            "end_to_end": metrics_of(man, name, "end_to_end")}
