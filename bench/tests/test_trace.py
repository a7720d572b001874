"""The reduction from a profiler trace to busy time, idle gaps and the
breakdown: on hand-made events, and on a small trace recorded on a v5e."""

import os

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "v5e_small.xplane.pb")


def test_union_merges_overlaps_and_clips_to_the_window():
    ivs = [(5, 15, "a"), (10, 20, "b"), (30, 40, "c"), (95, 120, "d")]
    assert trace.union(ivs, 0, 100) == [[5, 20], [30, 40], [95, 100]]


def test_reduce_on_hand_made_events():
    tr = trace.Trace(
        ops={"/device:TPU:0": [(100, 300, "fusion"), (300, 400, "dot"),
                               (600, 50_700, "dot"), (90_000, 100_000, "fusion")]},
        host=[(0, 200_000, "bench.window"), (0, 200_000, "bench.run"),
              (50_700, 90_000, "bench.batches")])
    red = trace.reduce(tr)
    busy_ns = 300 + 50_100 + 10_000
    assert red["window_s"] == pytest.approx(200_000e-9)
    assert red["busy_s"] == pytest.approx(busy_ns * 1e-9)
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["dot"] == pytest.approx((100 + 50_100) * 1e-9)
    assert ops["fusion"] == pytest.approx((200 + 10_000) * 1e-9)
    gaps = dict(red["breakdown"]["idle_gaps"])
    # [0,100) and [400,600) pool as small gaps; [50.7k,90k) lies in
    # bench.batches; [100k,200k) only in bench.run
    assert gaps["bench.batches (1 gaps)"] == pytest.approx(39_300e-9)
    assert gaps["bench.run (1 gaps)"] == pytest.approx(100_000e-9)
    assert gaps["(gaps under 10 us) (2 gaps)"] == pytest.approx(300e-9)
    assert red["busy_s"] + sum(gaps.values()) == pytest.approx(red["window_s"])


def test_self_time_leaves_out_nested_ops():
    ops = [(0, 100, "while"), (10, 30, "fusion.1"), (40, 90, "cond"), (50, 60, "fusion.1")]
    st = trace.self_times(ops)
    assert st == {"while": 100 - 20 - 50, "fusion.1": 20 + 10, "cond": 50 - 10}


def test_reduce_finds_no_device_work_as_none():
    tr = trace.Trace(ops={}, host=[(0, 1000, "bench.window")])
    assert trace.reduce(tr)["busy_s"] is None


def test_recorded_v5e_trace():
    tr = trace.load(DATA)
    assert any(name.startswith("/device:TPU:") for name in tr.ops)
    red = trace.reduce(tr)
    assert 0 < red["busy_s"] < red["window_s"]
    assert {n for n, _ in red["breakdown"]["device_ops"]} >= {
        "fusion", "convolution_tanh_fusion"}
    labels = " ".join(n for n, _ in red["breakdown"]["idle_gaps"])
    assert "bench.run" in labels
