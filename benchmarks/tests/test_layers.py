"""The per-layer readers' vocabulary on hand-made observations."""

import pytest

from benchmarks.harness import layers as L

CLASSES = {"q6": {"kind": "analytic", "rows_scanned": 60_000_000,
                  "min_bytes_per_row": 9},
           "p": {"kind": "point", "rows_scanned": 0}}


def obs(**kw):
    base = {"counters_before": {"c_total{x=\"1\"}": 5.0, "h_sum": 10.0,
                                "h_count": 4.0},
            "counters_after": {"c_total{x=\"1\"}": 8.0, "h_sum": 22.0,
                               "h_count": 10.0, "c_total{x=\"2\"}": 1.0},
            "explain": {"q6": [
                {"latency_s": 0.050, "stages": {"staging": 4.0, "kernel": 6.0,
                                                "device_get": 10.0}},
                {"latency_s": 0.070, "stages": {"staging": 6.0, "kernel": 4.0,
                                                "device_get": 10.0}}]},
            "first_touch": {"q6": [{"latency_s": 2.0,
                                    "stages": {"compile": 1500.0}}]},
            "solo": {"q6": {"n": 8, "latencies_s": [0.05] * 8, "trace": {
                "busy_s": 0.020, "ops": [("fusion.1", 0.015),
                                         ("all-reduce.2", 0.005)]}}},
            "classes": CLASSES, "chips": 1,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "window": {"records": [
                {"class": "p", "t0": 0.0, "t1": 0.002},
                {"class": "p", "t0": 1.0, "t1": 1.004},
                {"class": "p", "t0": 9.0, "t1": 11.0}], "w0": 0.0, "w1": 10.0}}
    base.update(kw)
    return base


def test_parse_stages_sums_repeats_and_skips_junk():
    assert L.parse_stages("staging:4.1ms kernel:7.8ms staging:1ms x:y "
                          "compile:1.5e+03ms") == {
        "staging": 5.1, "kernel": 7.8, "compile": 1500.0}


def test_counter_readers():
    o = obs()
    assert L.counter_delta(o, {"metric": r"^c_total"}) == 4.0
    assert L.counter_delta(o, {"metric": r"^absent"}) is None
    assert L.counter_ratio(o, {"numerator": "^h_sum",
                               "denominator": "^h_count"}) == 2.0
    assert L.counter_ratio(o, {"numerator": "^h_sum",
                               "denominator": "^absent"}) is None


def test_explain_readers():
    o = obs()
    assert L.explain_stage_sum(o, {"stages": ["staging", "merge"]}) == 5.0
    assert L.client_minus_stages(o, {}) == pytest.approx(60.0 - 20.0)
    assert L.first_touch_stage_s(o, {"stage": "compile"}) == 1.5
    assert L.compile_in_window(o, {"metric": "^c_total"}) == 4.0
    o["explain"]["q6"][0]["stages"]["compile"] = 3.0
    assert L.compile_in_window(o, {"metric": "^c_total"}) == 5.0


def test_device_time_and_roofline():
    o = obs()
    assert L.device_busy_per_statement(o, {"class": "q6"}) == 2.5
    share = L.roofline_hbm(o, {"class": "q6"})
    assert share == pytest.approx(100 * (60e6 * 9 / 819e9) / 2.5e-3)
    assert 0 < share < 100
    # nothing to read is nothing, never a share of 0
    assert L.device_busy_per_statement(o, {"class": "q1"}) is None
    assert L.roofline_hbm(obs(peaks={}), {"class": "q6"}) is None
    o["solo"]["q6"]["trace"]["busy_s"] = 0.0
    assert L.roofline_hbm(o, {"class": "q6"}) is None


def test_trace_op_sum_by_regex():
    assert L.trace_op_ms_per_statement(
        obs(), {"regex": "fusion"}) == pytest.approx(15.0 / 8)
    assert L.trace_op_ms_per_statement(obs(), {"regex": "sort"}) == 0.0


def test_latency_mean_takes_the_window_only():
    assert L.latency_mean_ms(obs(), {"kind": "point"}) == pytest.approx(3.0)


def test_unknown_reader_is_an_error(tmp_path):
    with pytest.raises(ValueError):
        L.read({"name": "x", "reader": "nope"}, obs(), str(tmp_path))
    (tmp_path / "layer_metrics").mkdir()
    (tmp_path / "layer_metrics" / "my.metric.py").write_text(
        "def read(obs, spec):\n    return obs['chips'] * spec['k']\n")
    assert L.read({"name": "my.metric", "reader": "python", "k": 7}, obs(),
                  str(tmp_path)) == 7
