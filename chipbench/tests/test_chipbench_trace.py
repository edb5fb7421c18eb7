"""The trace reduction (`chipbench/trace.py`) on small traces: the busy
union, the idle gaps and what the host was doing in them, collectives."""

from __future__ import annotations

import json
import os

import pytest

from chipbench import trace as T

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
MS = 1_000_000  # ns


def _trace():
    # window [0, 100) ms; TPU:0 busy [10,30) u [25,40) u [60,70) and an op
    # sticking out of the window; TPU:1 busy [0,10) with an all-gather
    return T.Trace(
        devices={
            "TPU:0": [(10 * MS, 30 * MS, "fusion.1"),
                      (25 * MS, 40 * MS, "while.2"),
                      (60 * MS, 70 * MS, "fusion.1"),
                      (95 * MS, 120 * MS, "copy.3")],
            "TPU:1": [(0, 4 * MS, "all-gather.7"),
                      (4 * MS, 10 * MS, "fusion.1")],
        },
        host=[(0, 100 * MS, "window"),
              (0, 9 * MS, "bind"),
              (40 * MS, 58 * MS, "fetch"),
              (70 * MS, 80 * MS, "submit"),
              (75 * MS, 95 * MS, "pump")])


def test_busy_union_merges_overlaps_and_clips():
    ops = _trace().devices["TPU:0"]
    assert T.busy_union(ops, 0, 100 * MS) == [[10 * MS, 40 * MS],
                                              [60 * MS, 70 * MS],
                                              [95 * MS, 100 * MS]]


def test_summary_busy_idle_and_ops():
    s = T.summarize(_trace())
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"]["TPU:0"] == pytest.approx(0.045)
    assert s["busy_s"]["TPU:1"] == pytest.approx(0.010)
    assert s["busy_mean_s"] == pytest.approx(0.0275)
    assert s["busiest"] == "TPU:0"
    # op time is clipped to the window and summed over chips
    ops = dict(s["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.036)
    assert ops["copy.3"] == pytest.approx(0.005)
    assert s["collective_s"] == pytest.approx({"TPU:0": 0.0,
                                               "TPU:1": 0.004})


def test_idle_gaps_are_longest_first_and_named_by_host_span():
    s = T.summarize(_trace())
    # TPU:0's gaps: [0,10) bind, [40,60) fetch, [70,95) submit 10/pump 20
    assert s["idle_gaps"] == [["pump", pytest.approx(0.025)],
                              ["fetch", pytest.approx(0.020)],
                              ["bind", pytest.approx(0.010)]]


def test_gap_with_no_host_span_is_none():
    t = T.Trace({"TPU:0": [(5, 10, "a")]}, [(0, 20, "window")])
    assert T.summarize(t)["idle_gaps"] == [["none", pytest.approx(1e-8)],
                                           ["none", pytest.approx(5e-9)]]


def test_window_span_must_be_unique_and_ops_present():
    with pytest.raises(ValueError):
        T.summarize(T.Trace({"TPU:0": []}, [(0, 1, "fetch")]))
    with pytest.raises(ValueError):
        T.summarize(T.Trace({}, [(0, 1, "window")]))


def test_json_round_trip():
    t = _trace()
    assert T.Trace.from_json(json.loads(json.dumps(t.to_json()))) == t


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5 lite (two warm Q15 SF1 calls, reduced
    by `trace.load` and kept as JSON): its numbers are what the reduction
    gave when it was recorded."""
    with open(os.path.join(FIXTURES, "q15_sf1_two_calls.json")) as f:
        rec = json.load(f)
    s = T.summarize(T.Trace.from_json(rec["trace"]))
    want = rec["summary"]
    assert s["window_s"] == pytest.approx(want["window_s"])
    assert s["busy_s"] == pytest.approx(want["busy_s"])
    assert [n for n, _ in s["device_ops"]] == \
        [n for n, _ in want["device_ops"]]
    assert [n for n, _ in s["idle_gaps"]] == \
        [n for n, _ in want["idle_gaps"]]
    assert 0 < s["busy_mean_s"] <= s["window_s"]


def test_async_collectives_count_only_over_busy_time():
    # an asynchronous all-gather in flight over [5, 50) ms, while TPU:0 is
    # busy over [10, 40) ms: 30 ms of busy time has a collective running
    t = T.Trace({"TPU:0": [(10 * MS, 40 * MS, "fusion.1:fusion")]},
                [(0, 100 * MS, "window")],
                {"TPU:0": [(5 * MS, 50 * MS, "all-gather-start.2:all-gather-start"),
                           (0, 90 * MS, "copy-start.3:copy-start")]})
    s = T.summarize(t)
    assert s["collective_s"]["TPU:0"] == pytest.approx(0.030)
    assert s["busy_s"]["TPU:0"] == pytest.approx(0.030)
    assert T.Trace.from_json(json.loads(json.dumps(t.to_json()))) == t
