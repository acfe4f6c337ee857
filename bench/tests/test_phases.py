"""The kernel's phase regions: calls assembled from events worked by hand,
the three phase readers, what the accepted trace reduction makes of the
regions, and a traced CPU rehearsal with the phase readers on."""
from types import SimpleNamespace

import jax
import pytest

import benchlib.trace
import helpers
from benchlib import phases
from benchlib.trace import reduce
from test_trace import DEV, ev, hand_trace

DEV1 = "/device:TPU:1"
TRACEME = "XLA TraceMe"
PEAK = {"bf16_flops_per_s": 1e12}
PHASE_LAYER = ["moe_dispatch.stage_share", "moe_dispatch.wait_share",
               "moe_dispatch.ffn_roofline"]


def call_regions(plane, t, stage_in, dispatch, blocks, combine_wait,
                 assemble, stage_out, drop=()):
    """One kernel call's regions from ``t`` (ns): the phases back to back,
    ``blocks`` the ``(arrival_wait, ffn)`` pairs inside ``ffn_combine``,
    and 1 ns of the call outside every phase. ``drop`` names regions the
    profiler lost."""
    out, cur = [], t

    def region(name, dur):
        nonlocal cur
        if name not in drop:
            out.append(ev(plane, TRACEME, name, cur, dur))
        cur += dur

    region("stage_in", stage_in)
    region("dispatch", dispatch)
    f0 = cur
    for wait, ffn in blocks:
        region("arrival_wait", wait)
        region("ffn", ffn)
    out.append(ev(plane, TRACEME, "ffn_combine", f0, cur - f0))
    region("combine_wait", combine_wait)
    region("assemble", assemble)
    region("stage_out", stage_out)
    return [ev(plane, TRACEME, "moe_dispatch", t, cur + 1 - t)] + out


def phase_trace(drop_second=()):
    """hand_trace's window with three kernel calls on each of two devices:
    on TPU:0 each call is 100 ns (stage 14, waits 17, ffn 33), on TPU:1
    200 ns (stage 20, waits 120, ffn 10); the second call on TPU:0 loses
    the regions ``drop_second``."""
    out = []
    for i, t in enumerate((150, 450, 750)):
        out += call_regions(DEV, t, 10, 30, [(5, 15), (2, 18)], 10, 5, 4,
                            drop=drop_second if i == 1 else ())
    for t in (120, 420, 720):
        out += call_regions(DEV1, t, 12, 40, [(60, 10)], 60, 9, 8)
    return out


def by_plane(events):
    out = {}
    for e in events:
        out.setdefault(e.plane, []).append(e)
    return out


def test_regions_leave_busy_ops_and_gaps_unchanged():
    """Regions on the line the chip records them on: the accepted
    reduction's busy time, top ops and idle gaps read as without them (the
    phases window keeps them out of the driver's profile besides)."""
    marks = {"bench.clock.start": 0.0, "bench.clock.stop": 1e-6}
    plain = reduce(hand_trace(), marks, (0.0, 1e-6))
    regions = [e for e in phase_trace() if e.plane == DEV]
    red = reduce(hand_trace() + regions, marks, (0.0, 1e-6))
    assert red.busy == plain.busy
    assert red.busy_s(DEV) == plain.busy_s(DEV)
    assert red.top_ops() == plain.top_ops()
    assert red.idle_gaps() == plain.idle_gaps()
    assert red.ops == plain.ops and red.modules == plain.modules


@pytest.mark.parametrize("lost", [(), ("assemble",), ("ffn",),
                                  ("arrival_wait", "ffn")])
def test_a_call_missing_a_region_is_skipped(lost):
    seen = phases.calls(by_plane(phase_trace(lost))[DEV])
    assert len(seen) == 3
    cs, dropped = phases.complete(seen)
    assert dropped == (1 if lost else 0)
    assert [c.start for c in cs] == ([150, 750] if lost else [150, 450, 750])
    assert phases.mean_us(cs) == pytest.approx(0.1)
    assert phases.mean_us(cs, "ffn") == pytest.approx(0.033)


def phase_records(drop_second=()):
    """The records the readers get: rank 0 held by TPU:1 and rank 1 by
    TPU:0 (the mesh orders ranks otherwise than the devices), with each
    rank's complete calls as the phases window assembles them."""
    events = phase_trace(drop_second)
    # each call's custom-call op: 2 ns before its region, 3 ns after
    ops = [ev(e.plane, "XLA Ops", "shard_map.37 custom-call", e.start - 2,
              e.dur + 5) for e in events if e.name == "moe_dispatch"]
    calls = phases.rank_calls(by_plane(events), by_plane(ops), [DEV1, DEV],
                              SimpleNamespace(segments=[]))
    # rank 0's 10 ns of ffn per call hold 5 ns of FLOPs at the peak
    dispatch = SimpleNamespace(rank_flops=lambda e: {0: 5e3, 1: 1e3}[e])
    return SimpleNamespace(phases=calls, dispatch=dispatch, peaks=PEAK)


def read(metric, rec):
    from benchlib.common import BENCH, load_module
    return load_module(BENCH / "metrics" / f"{metric}.py").read(rec)


@pytest.mark.parametrize("lost", [(), ("stage_out",)])
def test_phase_readers(lost, capsys):
    rec = phase_records(drop_second=lost)
    log = capsys.readouterr().err
    assert log.count("(region / op 0.9") == 2, log
    assert log.count("0.002 before the region, 0.003 after), 0 calls "
                     "outside an op") == 2, log
    assert [len(rec.phases[r]) for r in (0, 1)] == [3, 2 if lost else 3]
    # TPU:0 (rank 1): stage 14 and waits 17 of 100 ns; TPU:1 (rank 0):
    # stage 20 and waits 120 of 200 ns
    assert read("moe_dispatch.stage_share", rec) == pytest.approx(
        (14 + 10) / 2)
    assert read("moe_dispatch.wait_share", rec) == pytest.approx(
        (17 + 60) / 2)
    assert read("moe_dispatch.ffn_roofline", rec) == pytest.approx(50.0)


def test_phase_readers_find_nothing_in_a_program_without_phases(
        monkeypatch):
    """A program without the device-phase switch (the accepted program
    before it) gets no window: the readers return None and build
    nothing."""
    import repro.core.trace
    monkeypatch.delattr(repro.core.trace, "device_phases")
    rec = SimpleNamespace(dispatch=None, peaks=PEAK)
    for metric in PHASE_LAYER:
        assert read(metric, rec) is None
    assert rec.phases == {}


def test_the_window_is_measured_once_per_run(monkeypatch):
    seen = []
    monkeypatch.setattr(phases, "measure",
                        lambda rec: seen.append(rec) or {})
    rec = SimpleNamespace(dispatch=None, peaks=PEAK)
    for metric in PHASE_LAYER:
        assert read(metric, rec) is None
    assert seen == [rec]


def test_traced_run_with_the_phase_readers(monkeypatch, capsys):
    """A traced test-size run with the phase readers: the phases window
    builds, compiles and runs the step with the phases on, after the
    driver's windows; the CPU records no device region, so the phase
    metrics are left out and the accepted ones read as before."""
    monkeypatch.setattr(benchlib.trace, "events_from_profile",
                        helpers.cpu_events)
    cell = helpers.cell("granite-moe-ep4-tiny", "tiny-calls",
                        helpers.KERNEL_E2E, helpers.KERNEL_LAYER + PHASE_LAYER)
    result, checks = helpers.run(cell, 2**40 + 13, 0.3, 1, jax.devices()[:4])
    assert result["correct"], checks
    assert set(result["metrics"]) == set(helpers.KERNEL_LAYER)
    log = capsys.readouterr().err
    assert "[trace] phases window: compiled and warmed" in log
    assert log.count("0 complete calls of 0") == 4, log
