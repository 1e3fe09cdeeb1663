"""Per-layer metric readers: a small vocabulary that the JSON files under
layer_metrics/ pick from by name, plus `.py` readers found beside them.

A reader gets what the traced run observed (`obs`, built by run.py) and its
own file (`spec`) and returns a number, or None where it found nothing to
read: the harness then leaves the metric out of the result line. A share of
a roofline is never reported as 0 for want of a reading.

obs keys: counters_before / counters_after ({sample: value} scraped off
/metrics around the concurrent sub-window), first_touch and explain
({class: [sample]}, each sample {"latency_s", "stages": {name: ms}}), solo
({class: {"n", "latencies_s", "trace"}}), concurrent (a reduced trace),
window ({"records", "w0", "w1"}), classes, chips, peaks.
"""

from __future__ import annotations

import importlib.util
import os
import re

from . import metrics as M

COPR_STAGES = ("prepare", "staging", "transfer", "compile", "kernel",
               "device_get", "merge", "shard", "reshard")


def parse_stages(text: str) -> dict[str, float]:
    """EXPLAIN ANALYZE's `stages` cell ("staging:4.1ms kernel:7.8ms ...")
    as {stage: ms}; a stage named twice is summed."""
    out: dict[str, float] = {}
    for part in text.split():
        k, _, v = part.partition(":")
        if v.endswith("ms"):
            try:
                out[k] = out.get(k, 0.0) + float(v[:-2])
            except ValueError:
                pass
    return out


def _delta(obs: dict, pattern: str) -> float | None:
    rx = re.compile(pattern)
    keys = [k for k in obs["counters_after"] if rx.search(k)]
    if not keys:
        return None
    return sum(obs["counters_after"][k] - obs["counters_before"].get(k, 0.0)
               for k in keys)


def _classes(obs: dict, spec: dict) -> list[str]:
    want = spec.get("classes", "analytic")
    if isinstance(want, list):
        return [c for c in want if c in obs["classes"]]
    return [c for c, st in obs["classes"].items() if st["kind"] == want]


def _mean(xs: list[float]) -> float | None:
    return sum(xs) / len(xs) if xs else None


def counter_delta(obs, spec):
    return _delta(obs, spec["metric"])


def counter_ratio(obs, spec):
    num, den = _delta(obs, spec["numerator"]), _delta(obs, spec["denominator"])
    return None if not den or num is None else num / den


def explain_stage_sum(obs, spec):
    """Mean per statement of the named stages, averaged over the classes."""
    per_class = []
    for c in _classes(obs, spec):
        vals = [sum(s["stages"].get(k, 0.0) for k in spec["stages"])
                for s in obs["explain"].get(c, ())]
        if vals:
            per_class.append(_mean(vals))
    return _mean(per_class)


def client_minus_stages(obs, spec):
    """Mean client latency of an EXPLAIN ANALYZE minus the coprocessor
    stages it reports: the time outside the coprocessor, in ms."""
    per_class = []
    for c in _classes(obs, spec):
        vals = [1e3 * s["latency_s"]
                - sum(s["stages"].get(k, 0.0) for k in COPR_STAGES)
                for s in obs["explain"].get(c, ()) if s["stages"]]
        if vals:
            per_class.append(_mean(vals))
    return _mean(per_class)


def first_touch_stage_s(obs, spec):
    """Seconds of one stage over each class's first execution."""
    vals = [s["stages"].get(spec["stage"], 0.0)
            for samples in obs["first_touch"].values() for s in samples]
    return sum(vals) / 1e3 if vals else None


def compile_in_window(obs, spec):
    """jit-cache misses counted over the concurrent sub-window plus the
    interleaved EXPLAIN samples that show a compile stage."""
    misses = _delta(obs, spec["metric"])
    if misses is None:
        return None
    return misses + sum(1 for samples in obs["explain"].values()
                        for s in samples if s["stages"].get("compile", 0) > 0)


def device_busy_per_statement(obs, spec):
    solo = obs["solo"].get(spec["class"])
    if not solo or not solo["trace"]["busy_s"]:
        return None
    return 1e3 * solo["trace"]["busy_s"] / solo["n"]


def roofline_hbm(obs, spec):
    """Least HBM time for the class's statement over its device time.
    Least bytes = rows scanned x `min_bytes_per_row` of the statement's
    file (from the schema's value ranges, not from what is staged)."""
    busy_ms = device_busy_per_statement(obs, spec)
    st = obs["classes"].get(spec["class"])
    if busy_ms is None or st is None or not obs["peaks"]:
        return None  # no peak for this device (a CPU rehearsal): no share
    least_s = (st["rows_scanned"] * st["min_bytes_per_row"]
               / obs["peaks"]["hbm_bytes_per_s"] / obs["chips"])
    return 100.0 * least_s / (busy_ms / 1e3)


def trace_op_ms_per_statement(obs, spec):
    """ms per statement of the device ops matching `regex`, summed over
    the classes' solo sub-windows."""
    rx = re.compile(spec["regex"], re.I)
    per_class = []
    for c in _classes(obs, spec):
        solo = obs["solo"].get(c)
        if solo and solo["trace"]["ops"]:
            per_class.append(1e3 * sum(
                s for name, s in solo["trace"]["ops"] if rx.search(name))
                / solo["n"])
    return _mean(per_class)


def latency_mean_ms(obs, spec):
    w = obs["window"]
    vals = [M.latency_s(r) for r in M.in_window(w["records"], w["w0"], w["w1"])
            if obs["classes"][r["class"]]["kind"] == spec["kind"]]
    return 1e3 * _mean(vals) if vals else None


READERS = {f.__name__: f for f in (
    counter_delta, counter_ratio, explain_stage_sum, client_minus_stages,
    first_touch_stage_s, compile_in_window, device_busy_per_statement,
    roofline_hbm, trace_op_ms_per_statement, latency_mean_ms)}


def read(spec: dict, obs: dict, bench_dir: str):
    """The metric's value by its file: a vocabulary reader, or the `read`
    function of layer_metrics/<name>.py."""
    if spec.get("reader") == "python":
        path = os.path.join(bench_dir, "layer_metrics", spec["name"] + ".py")
        mod_spec = importlib.util.spec_from_file_location(
            "layer_metric_" + re.sub(r"\W", "_", spec["name"]), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read(obs, spec)
    try:
        fn = READERS[spec["reader"]]
    except KeyError:
        raise ValueError(f"layer metric {spec['name']}: no reader "
                         f"{spec.get('reader')!r}; have {sorted(READERS)}")
    return fn(obs, spec)
