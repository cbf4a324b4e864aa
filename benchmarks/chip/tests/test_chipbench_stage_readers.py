"""The readers of the program's stage spans and launch counters, each on a
hand-built run: the right number where the program records them, and
nothing (never an error) where it does not."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import harness  # noqa: E402
from chipbench.spans import TRACE_BASE  # noqa: E402

T0, T1 = 100.0, 101.0  # the window, perf_counter seconds


def counters(**values):
    return {k.replace("__", "."): {"type": "counter", "value": v} for k, v in values.items()}


def ingress(index, ts_us, dur_us, recv_us, reply_us):
    return {"name": "ingress", "ph": "X", "ts": ts_us, "dur": dur_us,
            "args": {"trace_id": TRACE_BASE + index, "span_id": index + 1,
                     "stages": {"recv": recv_us, "reply": reply_us}}}


def dispatch(ts_us, dur_us, pid="0"):
    return {"name": "dispatch", "ph": "X", "ts": ts_us, "dur": dur_us, "pid": pid,
            "args": {"trace_ids": [TRACE_BASE], "stages": {"pad": 1.0}}}


def hand_run(counters0=None, counters1=None, spans=None):
    R = harness.Record
    records = [
        R(0, 0, due=100.1, sent=100.1, done=100.110),   # 10 ms at the client
        R(1, 0, due=100.2, sent=100.2, done=100.206),   # 6 ms
        R(2, 0, due=100.3, sent=100.3, done=100.320),   # 20 ms
        R(3, 0, due=100.4, sent=100.4, done=100.5, error="ServeError: x"),
        R(4, 0, due=101.5, sent=101.5, done=101.51),    # due after the window
    ]
    return harness.Run({}, T0, T1, 1.0, records, [{}], counters0 or {},
                       counters1 or {}, 0, {}, None, spans)


SPANS = [
    ingress(0, 100.101e6, 4000.0, 500.0, 300.0),    # outside: 6 ms, inside 0.8 ms
    ingress(1, 100.201e6, 2000.0, 250.0, 250.0),    # 4 ms, 0.5 ms
    ingress(2, 100.301e6, 12000.0, 1000.0, 2000.0),  # 8 ms, 3 ms
    ingress(3, 100.401e6, 1000.0, 100.0, 100.0),    # failed: 0.2 ms inside
    ingress(4, 101.501e6, 1000.0, 9000.0, 9000.0),  # due after the window
    dispatch(99.9e6, 200e3),    # 0.1 s inside the window
    dispatch(100.5e6, 100e3),   # overlaps the next: the union counts 0.15 s
    dispatch(100.55e6, 100e3),
    dispatch(100.95e6, 100e3),  # half inside
]


@pytest.mark.parametrize("metric,run,expected", [
    ("pad_waste.tput",
     hand_run(counters(executor__pixels_valid=100, executor__pixels_launched=1000),
              counters(executor__pixels_valid=2_500_100,
                       executor__pixels_launched=4_001_000)),
     37.5),
    ("tiles_per_launch.tput",
     hand_run(counters(tiled__tiles=35, tiled__launches=3),
              counters(tiled__tiles=35 * 5, tiled__launches=3 * 5)),
     35 / 3),
    ("dispatch_busy.tput", hand_run(spans=SPANS), 30.0),
    ("worker_ingress_p50_ms.lat", hand_run(spans=SPANS), 0.65),  # of 0.8, 0.5, 3, 0.2
    ("edge_hops_p50_ms.lat", hand_run(spans=SPANS), 6.0),  # of 6, 4, 8; failures left out
])
def test_reader_on_a_hand_built_run(metric, run, expected):
    assert harness.load_reader(metric, BENCH)(run) == pytest.approx(expected)


@pytest.mark.parametrize("metric", ["pad_waste.tput", "tiles_per_launch.tput",
                                    "dispatch_busy.tput", "worker_ingress_p50_ms.lat",
                                    "edge_hops_p50_ms.lat"])
def test_reader_reads_nothing_from_a_program_without_the_spans(metric):
    """The parent program has no such counters and no ingress span: the
    reader returns None, so the result line leaves the metric out."""
    read = harness.load_reader(metric, BENCH)
    queue_only = [{"name": "queue", "ph": "X", "ts": 100.1e6, "dur": 10.0,
                   "args": {"trace_id": TRACE_BASE}}]
    assert read(hand_run(counters(requests=1), counters(requests=9), queue_only)) is None
    assert read(hand_run()) is None


def test_dispatch_busy_reads_the_busiest_replica():
    """Each replica's dispatch thread on its own lane: the busiest one's
    share, not the union over the threads (which would read 40)."""
    spans = [dispatch(100.0e6, 300e3, pid="0"), dispatch(100.2e6, 200e3, pid="1"),
             dispatch(100.6e6, 50e3, pid="1")]
    read = harness.load_reader("dispatch_busy.tput", BENCH)
    assert read(hand_run(spans=spans)) == pytest.approx(30.0)
    assert read(hand_run(spans=spans[1:])) == pytest.approx(25.0)


def trace_run(busy_s):
    """A run whose trace reduction gives each of the cell's chips its busy
    seconds."""
    run = hand_run()
    run.trace = {"window_s": 1.0, "busy_s": sum(busy_s) / len(busy_s),
                 "busy_s_per_device": {f"/device:TPU:{i}": b for i, b in enumerate(busy_s)}}
    return run


@pytest.mark.parametrize("busy_s,expected", [
    ([0.2, 0.2, 0.2, 0.2], 25.0),    # even load over four chips
    ([0.0, 0.3, 0.3, 0.0], 50.0),    # two of four chips do the work
    ([0.0, 0.0, 0.45, 0.0], 100.0),  # one chip does everything
    ([0.1, 0.3, 0.5, 0.1], 50.0),
])
def test_busiest_chip_share(busy_s, expected):
    read = harness.load_reader("busiest_chip_share.tput", BENCH)
    assert read(trace_run(busy_s)) == pytest.approx(expected)


def test_busiest_chip_share_from_a_reduced_trace():
    from chipbench import xtrace

    trace = {"devices": {"/device:TPU:0": [["fusion.1", 100, 300]],
                         "/device:TPU:1": [["fusion.1", 0, 100], ["copy", 500, 200]],
                         "/device:TPU:2": [], "/device:TPU:3": []},
             "host": [["bench:window", 0, 1000]]}
    run = hand_run()
    run.trace = xtrace.reduce(trace, [f"/device:TPU:{i}" for i in range(4)])
    read = harness.load_reader("busiest_chip_share.tput", BENCH)
    assert read(run) == pytest.approx(100.0 * 300 / 600)


def test_busiest_chip_share_reads_nothing_without_busy_time():
    read = harness.load_reader("busiest_chip_share.tput", BENCH)
    assert read(trace_run([0.0, 0.0, 0.0, 0.0])) is None
    assert read(hand_run()) is None  # an untraced run
