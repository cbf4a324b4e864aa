"""The trace reduction: busy union, idle share, idle-gap attribution and the
operations that took most device time."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import xtrace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

HAND = {
    "devices": {
        "/device:TPU:0": [["fusion.1", 100, 50], ["fusion.2", 120, 60],  # overlap
                          ["copy", 300, 100], ["fusion.1", 950, 200]],   # past the end
        "/device:TPU:1": [["fusion.1", 0, 2000]],
    },
    "host": [["bench:window", 0, 1000], ["morph_serve:erode", 180, 100],
             ["bench:submit", 400, 550], ["bench:submit", 700, 10]],
}


def test_busy_is_the_union_clipped_to_the_window():
    evs = HAND["devices"]["/device:TPU:0"]
    assert xtrace.merged(evs, 0, 1000) == [(100, 180), (300, 400), (950, 1000)]
    assert xtrace.busy_ns(evs, 0, 1000) == 80 + 100 + 50
    assert xtrace.gaps(evs, 0, 1000) == [(0, 100), (180, 300), (400, 950)]


def test_reduce_by_hand():
    red = xtrace.reduce(HAND)
    assert red["window_s"] == pytest.approx(1e-6)
    assert red["busy_s_per_device"] == {"/device:TPU:0": 230e-9, "/device:TPU:1": 1000e-9}
    assert red["busy_s"] == pytest.approx((230 + 1000) / 2 * 1e-9)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx((50 + 50 + 1000) * 1e-9)]
    # the longest gap (400-950) is covered mostly by the long submit span
    assert red["idle_gaps"][0][0].startswith("bench:submit")
    assert red["idle_gaps"][0][1] == pytest.approx(550e-9)
    # 180-300 overlaps the erode annotation for 100 of its 120 ns
    labels = {round(s * 1e9): n for n, s in red["idle_gaps"]}
    assert labels[120].startswith("morph_serve:erode")
    assert labels[100].startswith("host:unnamed")


def test_one_plane_only():
    red = xtrace.reduce(HAND, ["/device:TPU:1"])
    assert red["busy_s"] == pytest.approx(1e-6)
    assert red["idle_gaps"] == []


def test_window_must_be_bracketed_once():
    with pytest.raises(RuntimeError):
        xtrace.reduce({"devices": {}, "host": []})


def recorded():
    with open(os.path.join(DATA, "trace_v5e_paper_slice.json")) as f:
        return json.load(f)


def test_recorded_trace_busy_against_a_count_by_microsecond():
    trace = recorded()
    t0, t1 = xtrace.window_of(trace)
    evs = trace["devices"]["/device:TPU:0"]
    busy = bytearray((t1 - t0) // 1000 + 1)
    for _, s, d in evs:
        for us in range(max(s, t0) // 1000 - t0 // 1000, min(s + d, t1) // 1000 - t0 // 1000):
            busy[us] = 1
    red = xtrace.reduce(trace)
    # each interval's two ends round to the microsecond
    assert red["busy_s"] == pytest.approx(sum(busy) * 1e-6, abs=2 * len(evs) * 1e-6)
    assert 0 < red["busy_s"] < red["window_s"] == pytest.approx(0.15)
    gaps = xtrace.gaps(evs, t0, t1)
    assert sum(b - a for a, b in gaps) / 1e9 == pytest.approx(red["window_s"] - red["busy_s"])


def test_recorded_trace_names_ops_and_gaps():
    trace = recorded()
    red = xtrace.reduce(trace)
    assert len(red["device_ops"]) == 10 and len(red["idle_gaps"]) == 10
    assert all(" = " not in name and "{" not in name for name, _ in red["device_ops"])
    assert any(name.startswith("morph2d_fused") and name.endswith("custom-call")
               for name, _ in red["device_ops"])
    secs = [s for _, s in red["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    hosts = {n for n, _, _ in trace["host"]} | {"host:unnamed"}
    for label, s in red["idle_gaps"]:
        assert label.split(" @")[0] in hosts and s > 0
    assert [s for _, s in red["idle_gaps"]] == sorted((s for _, s in red["idle_gaps"]),
                                                      reverse=True)


def test_op_name():
    assert xtrace.op_name(
        "%morph2d_fused.1 = u8[8,608,1024]{2,1,0:T(8,128)(4,1)S(1)} custom-call("
        "u8[8,728,1024]{2,1,0} %get-tuple-element.1)") == "morph2d_fused.1 u8[8,608,1024] custom-call"
    assert xtrace.op_name("fusion.1") == "fusion.1"
