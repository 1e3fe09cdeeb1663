"""The end-to-end arithmetic on synthetic statement logs."""

import math

import pytest

from benchmarks.harness import metrics as M

CLASSES = {"a": {"kind": "analytic", "rows_scanned": 1000},
           "b": {"kind": "analytic", "rows_scanned": 10},
           "p": {"kind": "point", "rows_scanned": 0},
           "u": {"kind": "write", "rows_scanned": 0}}


def steady(cls, step, n, t0=0.0):
    return [{"class": cls, "t0": t0 + i * step, "t1": t0 + (i + 1) * step}
            for i in range(n)]


def test_whole_window_geomean_and_rate():
    recs = steady("a", 0.1, 100) + steady("b", 0.01, 1000)
    e = M.end_to_end(recs, 0.0, 10.0, CLASSES)
    assert e["analytic_geomean_ms"] == pytest.approx(
        1e3 * math.sqrt(0.1 * 0.01))
    assert e["analytic_rows_per_s"] == pytest.approx(
        (100 * 1000 + 1000 * 10) / 10.0)


def test_a_stall_moves_geomean_and_rate_where_a_chunk_median_would_not():
    """One 2 s stall in a 10 s window of 0.1 s statements: the median of
    1 s chunks' means is unmoved; the whole-window metrics are not."""
    calm = steady("a", 0.1, 100)
    stalled = steady("a", 0.1, 40) + [
        {"class": "a", "t0": 4.0, "t1": 6.0}] + steady("a", 0.1, 40, 6.0)
    only_a = {"a": CLASSES["a"]}
    e0 = M.end_to_end(calm, 0.0, 10.0, only_a)
    e1 = M.end_to_end(stalled, 0.0, 10.0, only_a)
    assert e1["analytic_geomean_ms"] > 1.2 * e0["analytic_geomean_ms"]
    assert e1["analytic_rows_per_s"] < 0.85 * e0["analytic_rows_per_s"]

    def chunk_median(recs):
        import statistics
        means = []
        for c in range(10):
            lat = [r["t1"] - r["t0"] for r in recs if c <= r["t1"] < c + 1]
            if lat:
                means.append(sum(lat) / len(lat))
        return statistics.median(means)
    assert chunk_median(stalled) == pytest.approx(chunk_median(calm))


def test_only_statements_completed_inside_the_window_count():
    recs = steady("a", 1.0, 10)  # completions at 1..10
    done = M.in_window(recs, 2.5, 7.5)
    assert [r["t1"] for r in done] == [3.0, 4.0, 5.0, 6.0, 7.0]
    recs[4]["error"] = "boom"
    assert len(M.in_window(recs, 2.5, 7.5)) == 4


def test_geomean_needs_every_analytic_class():
    e = M.end_to_end(steady("a", 0.1, 10), 0.0, 1.0, CLASSES)
    assert "analytic_geomean_ms" not in e  # class b completed nothing
    assert e["analytic_rows_per_s"] == pytest.approx(10 * 1000)


def test_point_p95_is_nearest_rank_and_writes_are_a_rate():
    recs = [{"class": "p", "t0": 0.0, "t1": (i + 1) / 1000} for i in range(100)]
    recs += steady("u", 0.05, 100)
    e = M.end_to_end(recs, 0.0, 5.0, CLASSES)
    assert e["point_p95_ms"] == pytest.approx(95.0)
    assert e["write_txn_per_s"] == pytest.approx(100 / 5.0)
    assert M.percentile([1.0], 95) == 1.0
    with pytest.raises(ValueError):
        M.percentile([], 95)


def test_open_loop_latency_counts_from_when_due():
    assert M.latency_s({"t0": 5.0, "t1": 5.5, "due": 3.0}) == 2.5
    assert M.latency_s({"t0": 5.0, "t1": 5.5}) == 0.5
