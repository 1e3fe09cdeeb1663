"""The reduction from trace events to busy time, idle share and names."""

import os

import pytest

from benchmarks.harness import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "solo_q6.xplane.pb")


def test_union_does_not_count_overlap_twice():
    ev = [(0, 10, "a"), (5, 10, "b"), (30, 5, "c"), (31, 1, "d")]
    assert T.union_ns(ev) == 15 + 5
    assert T.union_ns([]) == 0


def test_gaps_and_idle_share():
    ev = [(10, 10, "a"), (40, 10, "b")]
    assert T.gaps(ev, 0, 100) == [(0, 10), (20, 40), (50, 100)]
    red = T.reduce({"devices": {0: ev}, "host": []}, window_s=100e-9)
    assert red["busy_s"] == pytest.approx(20e-9)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.8)


def test_gap_takes_the_name_of_the_host_span_that_overlaps_it_most():
    ms = 1_000_000
    idle = [(0, 10 * ms), (20 * ms, 30 * ms), (40 * ms, 40 * ms + 10_000)]
    host = [(1 * ms, 2 * ms, "short"), (2 * ms, 7 * ms, "np.asarray"),
            (100 * ms, ms, "elsewhere")]
    got = dict(T.attribute_gaps(idle, host))
    assert got["np.asarray"] == pytest.approx(0.010)
    assert got["host:no_traced_span"] == pytest.approx(0.010)
    assert got["device:gaps_under_50us"] == pytest.approx(10e-6)


def test_busy_time_is_averaged_over_the_devices_that_ran():
    trace = {"devices": {0: [(0, 100, "fusion.1"), (100, 20, "all-reduce.3")],
                         1: [(0, 50, "fusion.1"), (100, 20, "all-reduce.3")]},
             "host": []}
    red = T.reduce(trace)
    assert red["per_device"] == {0: 120e-9, 1: 70e-9}
    assert red["busy_s"] == pytest.approx(95e-9)
    assert dict(red["ops"])["fusion.1"] == pytest.approx(75e-9)


def test_no_device_ops_reads_as_nothing_not_as_zero_share():
    red = T.reduce({"devices": {}, "host": [(0, 10, "x")]}, window_s=1.0)
    assert red["busy_s"] == 0.0 and red["ops"] == []


def test_solo_windows_name_their_ops_by_class():
    from benchmarks import run

    obs = {"concurrent": {"ops": [("fusion.3", 1.0)], "idle_gaps": []},
           "solo": {"q6": {"trace": {"ops": [("fusion.3", 0.2)]}},
                    "q3": {"trace": {"ops": [("fusion.3", 0.5)]}}}}
    ops = run.breakdown(obs)["device_ops"]
    assert ops == [["q3/fusion.3", 0.5], ["q6/fusion.3", 0.2]]


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this checkout")
def test_recorded_tpu_trace_reduces_to_a_busy_share_under_one():
    """A small trace recorded on the v5e (Q6 alone, 8 statements)."""
    trace = T.load(RECORDED)
    assert list(trace["devices"]) == [0]
    red = T.reduce(trace)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["ops"][0][1] >= red["ops"][-1][1]
    names = [n for n, _ in red["ops"]]
    assert all(" " not in n and "," not in n for n in names)
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
