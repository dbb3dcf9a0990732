"""The trace reduction, on hand-made traces and on a small trace recorded on
a TPU v5e (one 2x2 overlay query of arrow_lu(2, 4, 2), twice, in spans)."""
import gzip
import json
import os

import numpy as np
import pytest

from bench.metrics import trace_reduction as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "trace_v5e_tiny.json.gz")


def _brute_union(intervals, lo, hi):
    grid = np.zeros(hi - lo, bool)
    for a, b in intervals:
        grid[max(a, lo) - lo:max(min(b, hi), lo) - lo] = True
    return int(grid.sum())


@pytest.mark.parametrize("seed", range(5))
def test_merge_equals_brute_force_union(seed):
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, 900, 40)
    iv = np.stack([starts, starts + rng.integers(0, 60, 40)], axis=1)
    merged = tr.merge(iv)
    assert (merged[1:, 0] > merged[:-1, 1]).all()
    for lo, hi in [(0, 1000), (100, 400), (450, 451)]:
        assert tr.overlap(merged, lo, hi) == _brute_union(iv, lo, hi)


def test_reduce_hand_made_trace():
    ms = 1_000_000
    trace = {
        "device": {"/device:TPU:0": {
            "ops": [[1 * ms, 2 * ms, "a"], [2 * ms, 2 * ms, "b"], [8 * ms, 1 * ms, "a"]],
            "modules": [[1 * ms, 3 * ms, "m"]]}},
        "host": [[0, 5 * ms, "bench.query"], [6 * ms, 4 * ms, "bench.query"],
                 [6 * ms, 2 * ms, "pack"]],
    }
    red = tr.reduce_trace(trace, "bench.query")
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.004)
    assert [s["device_s"] for s in red["spans"]] == pytest.approx([0.003, 0.001])
    assert [s["s"] for s in red["spans"]] == pytest.approx([0.005, 0.004])
    # Longest gaps: 4-8 ms (the host packs from 6 ms), then 0-1 and 9-10 ms.
    assert red["idle_gaps"][0] == ["pack", pytest.approx(0.004)]
    assert {g[0] for g in red["idle_gaps"][1:]} == {"bench.query (no deeper host event)"}
    # Device ops come from the finest line, clipped to the window.
    assert dict(red["device_ops"]) == pytest.approx({"a": 0.003, "b": 0.002})


def test_reduce_without_spans_or_device_reads_nothing():
    assert tr.reduce_trace({"device": {}, "host": [[0, 5, "bench.query"]]},
                           "bench.query") is None
    assert tr.reduce_trace({"device": {"/device:TPU:0": {"x": [[0, 5, "a"]]}},
                            "host": []}, "bench.query") is None


def test_reduce_recorded_v5e_trace():
    with gzip.open(FIXTURE, "rt") as f:
        trace = json.load(f)
    assert list(trace["device"]) == ["/device:TPU:0"]
    red = tr.reduce_trace(trace, "bench.query")
    # Numbers as this reduction first read them from the recorded trace.
    assert red["window_s"] == pytest.approx(0.053381278, rel=1e-9)
    assert red["busy_s"] == pytest.approx(0.00976715, rel=1e-9)
    assert [s["device_s"] for s in red["spans"]] == pytest.approx(
        [0.004885957, 0.004881193], rel=1e-9)
    # Busy time is the union over every line of the device plane.
    events = [e for evs in trace["device"]["/device:TPU:0"].values() for e in evs]
    assert red["busy_s"] <= red["window_s"]
    assert sum(s["device_s"] for s in red["spans"]) <= red["busy_s"] + 1e-12
    assert red["busy_s"] * 1e9 <= sum(d for _, d, _ in events)
    gaps = [g for _, g in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= 10
    assert sum(gaps) <= red["window_s"] - red["busy_s"] + 1e-12
    assert len(red["device_ops"]) == 10
    assert red["device_ops"][0][0].split(" ")[1] == "while"
