"""The trace reduction, on events worked by hand and on a small trace
recorded on the chip."""
import pathlib

import pytest

from benchlib.trace import Event, load_events, merge, overlap, reduce

DEV = "/device:TPU:0"
HOST = "/host:CPU"
RECORDED = pathlib.Path(__file__).parent / "data" / "recorded_trace.json.gz"


def ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur))


def hand_trace():
    """A window of 1000 ns (host seconds 0..1e-6 map to trace 100..1100):
    the device busy 200-400 and 500-700 (two overlapping ops in the
    second), the host in a prefill span 150-450 and a sampling span
    450-520, waiting 700-1100."""
    return [
        ev(HOST, "main", "bench.clock.start", 100, 0),
        ev(HOST, "main", "bench.clock.stop", 1100, 0),
        ev(HOST, "main", "bench.serve", 120, 600),
        ev(HOST, "main", "bench.prefill", 150, 300),
        ev(HOST, "main", "bench.sample", 450, 70),
        ev(HOST, "main", "bench.wait", 720, 380),
        ev(DEV, "XLA Ops", "fusion.1", 200, 200),
        ev(DEV, "XLA Ops", "fusion.2", 500, 150),
        ev(DEV, "XLA Ops", "convolution.3", 600, 100),
        ev(DEV, "XLA Modules", "jit_step", 200, 500),
    ]


def test_merge_and_overlap():
    assert merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert overlap([(0, 3), (5, 7)], [(2, 6)]) == 2


def test_busy_idle_and_spans_by_hand():
    marks = {"bench.clock.start": 0.0, "bench.clock.stop": 1e-6}
    red = reduce(hand_trace(), marks, (0.0, 1e-6))
    assert (red.t0, red.t1) == (100, 1100)
    assert red.busy[DEV] == [(200, 400), (500, 700)]
    assert red.busy_s(DEV) == pytest.approx(400e-9)
    assert red.window_s == pytest.approx(1000e-9)
    # device time inside the prefill span 150-450: 200-400
    assert red.device_time_in(DEV, "bench.prefill") == pytest.approx(200e-9)
    ops = dict(red.top_ops())
    assert ops["fusion.1"] == pytest.approx(200e-9)
    assert ops["convolution.3"] == pytest.approx(100e-9)
    # idle gaps 100-200 (mid 150: prefill starts at 150), 400-500 (mid
    # 450: sample), 700-1100 (mid 900: wait)
    gaps = dict(red.idle_gaps())
    assert gaps["bench.prefill"] == pytest.approx(100e-9)
    assert gaps["bench.sample"] == pytest.approx(100e-9)
    assert gaps["bench.wait"] == pytest.approx(400e-9)
    assert sum(gaps.values()) == pytest.approx(red.window_s
                                               - red.busy_s(DEV))


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e chip (two prefills of a served
    model and the host's wait between them, under spans named as the
    harness names its own): the device plane, the spans and the clock
    marks, read on the trace's own clock."""
    events = load_events(RECORDED)
    marks = {e.name: e.start / 1e9 for e in events
             if e.name.startswith("bench.clock")}
    dev = [e for e in events if e.plane == DEV]
    lo, hi = min(e.start for e in dev), max(e.end for e in dev)
    red = reduce(events, marks, (lo / 1e9, hi / 1e9))
    assert red.devices == [DEV]
    assert red.t1 - red.t0 == pytest.approx(hi - lo, rel=1e-9)
    busy = red.busy_s(DEV)
    assert 0 < busy < red.window_s
    gaps = dict(red.idle_gaps())
    assert sum(gaps.values()) == pytest.approx(red.window_s - busy)
    assert max(gaps, key=gaps.get) == "bench.wait"
    # both prefills ran inside their spans: nearly all the busy time
    assert red.device_time_in(DEV, "bench.prefill") > 0.95 * busy
    ops = red.top_ops()
    assert sum(t for _, t in ops) <= busy
    assert all(" " in name for name, _ in ops)      # "fusion.227 fusion"
