"""Observability layer (core/trace.py, core/telemetry.py).

Tier-1 coverage that needs no simulated devices:
  * TraceWriter emits structurally valid Chrome-trace/Perfetto JSON and
    ``validate_trace`` rejects malformed events;
  * **the invariant**: for every workload x (FLUX, CONSERVATIVE) the
    rendered ``schedule_timeline`` critical path equals ``analytic_cost``
    within 1e-6 — and with a fault plan, ``fault_cost``;
  * degraded timelines (``live_ranks`` / plan splices) render and stay
    valid, including kv_transfer collapsing to its solo shape;
  * EvalRecord JSON round-trips exactly (non-finite -> null);
  * MetricsRegistry histogram quantiles + the ElasticController /
    serve-engine metric names;
  * a hypothesis property (skips when hypothesis is absent, matching
    test_schedules.py): replayed send-window depths never exceed the
    ``contexts`` cap for any schedule shape;
  * the device-phase switch: ``phase`` opens a named region only under
    ``device_phases``, stamps an attached probe either way, and a jitted
    step is traced anew when the switch flips.

The executable 4-rank probe counterpart (observed DMA order vs the
trace-time schedule) lives in tests/scripts/telemetry_suite.py.
"""
import json

import pytest

from repro.core import extract_hardware_context
from repro.core.design_space import CONSERVATIVE, EXPERT_SYSTEMS, Directive
from repro.core.faults import (DROPPED_PEER, STRAGGLER, FaultPlan, FaultSpec,
                               fault_cost)
from repro.core.schedule import (make_broadcast_schedule, make_ring_schedule,
                                 make_schedule)
from repro.core.telemetry import EvalRecord, MetricsRegistry, SearchTelemetry
from repro.core.trace import (ScheduleProbe, TraceWriter, device_phases,
                              phase, schedule_timeline, validate_trace)
from repro.launch.mesh import make_mesh
from repro.workloads import get_workload

WORKLOAD_NAMES = ("moe_dispatch", "ring_attention", "gemm_allgather",
                  "kv_transfer")
FLUX = EXPERT_SYSTEMS["FLUX"]


@pytest.fixture(scope="module")
def hw():
    return extract_hardware_context(make_mesh((1,), ("x",)))


# ------------------------------------------------------------ trace schema


def test_trace_writer_emits_valid_perfetto_json():
    w = TraceWriter()
    w.meta_process(0, "rank 0")
    w.meta_thread(0, 0, "critical path")
    w.span("gemm", 0.0, 120.5, pid=0, tid=0, args={"kind": "compute"})
    w.counter("send window", 10.0, {"in_flight": 2}, pid=0)
    w.instant("dma issue (1,0)", 12.0, pid=0, tid=1)
    obj = json.loads(w.to_json())
    assert obj["displayTimeUnit"] == "ms"
    assert validate_trace(obj) == 5
    phases = [e["ph"] for e in obj["traceEvents"]]
    assert phases == ["M", "M", "X", "C", "i"]


def test_validate_trace_rejects_malformed_events():
    with pytest.raises(ValueError):
        validate_trace({"events": []})
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"ph": "Z", "name": "x"}]})
    with pytest.raises(ValueError):          # span missing dur
        validate_trace({"traceEvents": [
            {"ph": "X", "name": "x", "ts": 0.0, "pid": 0, "tid": 0}]})
    with pytest.raises(ValueError):          # negative ts
        validate_trace({"traceEvents": [
            {"ph": "i", "name": "x", "ts": -1.0, "pid": 0, "tid": 0,
             "s": "t"}]})


# ------------------------------------------- the critical-path invariant


@pytest.mark.parametrize("name", WORKLOAD_NAMES)
@pytest.mark.parametrize("directive", [FLUX, CONSERVATIVE],
                         ids=["flux", "conservative"])
def test_timeline_critical_path_equals_analytic_cost(name, directive, hw):
    """The tentpole invariant: the rendered timeline audits exactly the
    scalar the cascade scores."""
    w = get_workload(name)
    tl = schedule_timeline(w, directive, hw)
    expect = w.analytic_cost(directive, hw)
    assert tl.critical_path_s == pytest.approx(expect, abs=1e-6)
    assert not tl.degraded
    n_events = validate_trace(tl.to_dict())
    assert n_events > 0
    # kernelized directives attach the schedule detail tracks
    if tl.breakdown.schedule is not None:
        cats = {e.get("cat") for e in tl.to_dict()["traceEvents"]}
        assert "dma" in cats


@pytest.mark.parametrize("name", WORKLOAD_NAMES)
def test_timeline_spans_match_breakdown_segments(name, hw):
    w = get_workload(name)
    tl = schedule_timeline(w, FLUX, hw)
    spans = [e for e in tl.to_dict()["traceEvents"]
             if e["ph"] == "X" and e["pid"] == 0 and e["tid"] == 0]
    # every positive-duration segment appears, back to back, in order
    expect = [s for s in tl.breakdown.segments if s.dur_s > 0]
    assert [e["name"] for e in spans] == [s.name for s in expect]
    cursor = 0.0
    for ev in spans:
        assert ev["ts"] >= cursor - 1e-9
        cursor = ev["ts"] + ev["dur"]
    assert cursor * 1e-6 == pytest.approx(tl.critical_path_s, abs=1e-6)


@pytest.mark.parametrize("name", ("moe_dispatch", "ring_attention",
                                  "gemm_allgather"))
def test_degraded_timeline_renders(name, hw):
    w = get_workload(name)
    live = tuple(range(w.n_dev))[:-1]
    tl = schedule_timeline(w, FLUX, hw, live_ranks=live)
    assert tl.degraded and tl.live_ranks == live
    validate_trace(tl.to_dict())
    degraded = w.degrade(live)
    assert tl.critical_path_s == pytest.approx(
        degraded.analytic_cost(FLUX, hw), abs=1e-6)


def test_kv_transfer_degrades_to_solo_timeline(hw):
    w = get_workload("kv_transfer")
    tl = schedule_timeline(w, FLUX, hw, live_ranks=(0,))
    assert tl.degraded
    validate_trace(tl.to_dict())
    assert tl.critical_path_s == pytest.approx(
        w.degrade((0,)).analytic_cost(FLUX, hw), abs=1e-6)


@pytest.mark.parametrize("name", WORKLOAD_NAMES)
def test_fault_plan_timeline_equals_fault_cost(name, hw):
    """With a plan the splice order mirrors fault_cost exactly: degraded
    analytic + state recovery + remesh + straggler stall."""
    w = get_workload(name)
    faults = [FaultSpec(STRAGGLER, rank=0, rounds=8, delay_s=50e-6)]
    if w.n_dev > 2:
        faults.append(FaultSpec(DROPPED_PEER, rank=1))
    plan = FaultPlan("trace-plan", tuple(faults))
    tl = schedule_timeline(w, FLUX, hw, plan=plan)
    expect = fault_cost(w, FLUX, hw, plan)
    assert tl.critical_path_s == pytest.approx(expect, abs=1e-6)
    names = [e["name"] for e in tl.to_dict()["traceEvents"]
             if e["ph"] == "X" and e["pid"] == 0]
    assert "straggler_stall" in names
    if w.n_dev > 2:
        assert "state_recovery" in names and "remesh" in names
    with pytest.raises(ValueError):
        schedule_timeline(w, FLUX, hw, plan=plan, live_ranks=(0,))


def test_timeline_writes_loadable_file(tmp_path, hw):
    w = get_workload("gemm_allgather")
    path = tmp_path / "timeline.json"
    schedule_timeline(w, FLUX, hw).write(str(path))
    validate_trace(json.loads(path.read_text()))


# --------------------------------------------------------------- telemetry


def test_eval_record_json_round_trip_is_exact():
    rec = EvalRecord(cid=7, gen=3, island=1, mutation="coarse",
                     directive="Directive(...)", level=3, score=812.5,
                     t_model_ms=11.3, t_wall_ms=None,
                     levels_s={"l1": 0.5, "l2": 1.25, "l3": 0.002},
                     retries=1, quarantined=False, fault_penalty_ms=2.0,
                     knobs={"contexts": 2, "tile_m": 128},
                     diagnostic="ok", elapsed_s=1.752)
    assert EvalRecord.from_json(rec.to_json()) == rec
    # non-finite never reaches JSON: it maps to null and stays None
    inf = EvalRecord(t_model_ms=float("inf"), t_wall_ms=float("nan"))
    back = EvalRecord.from_json(inf.to_json())
    assert back.t_model_ms is None and back.t_wall_ms is None
    assert "Infinity" not in inf.to_json() and "NaN" not in inf.to_json()


def test_search_telemetry_series_and_payload():
    tel = SearchTelemetry(workload="gemm_allgather")
    for gen in range(3):
        for i, score in enumerate((1.0, 10.0 * (gen + 1))):
            tel.observe(EvalRecord(cid=gen * 2 + i, gen=gen, island=i,
                                   mutation="coarse" if i else "fine",
                                   level=3, score=score))
        tel.note_coverage(gen, 0.1 * (gen + 1))
    gens = tel.generation_series()
    assert [g["gen"] for g in gens] == [0, 1, 2]
    assert gens[2]["best_score"] == 30.0
    assert gens[1]["archive_coverage"] == pytest.approx(0.2)
    assert {i["island"] for i in tel.island_series()} == {0, 1}
    stats = {m["mutation"]: m for m in tel.mutation_stats()}
    # "coarse" set a new global best every generation; the flat "fine"
    # stream only won the very first observation (1.0 beat the empty best)
    assert stats["coarse"]["wins"] == 3 and stats["fine"]["wins"] == 1
    payload = tel.payload(meta={"generations": 3})
    assert payload["schema"] == "bench-search/v2"
    assert payload["totals"]["evals"] == 6
    assert payload["best"]["score"] == 30.0
    json.dumps(payload)                       # JSON-clean end to end


def test_metrics_registry_histogram_quantiles():
    m = MetricsRegistry()
    h = m.histogram("decode_step_ms")
    for v in range(1, 101):                   # 1..100
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100 and s["max"] == 100.0
    assert s["mean"] == pytest.approx(50.5)
    assert s["p50"] == pytest.approx(50.5)
    assert s["p90"] == pytest.approx(90.1)
    assert s["p99"] == pytest.approx(99.01)
    m.counter("tokens").inc(8)
    m.gauge("live_ranks").set(3)
    snap = m.snapshot()
    assert snap["counters"]["tokens"] == 8
    assert snap["gauges"]["live_ranks"] == 3.0
    json.loads(m.to_json())


def test_histogram_decimation_bounds_memory():
    h = MetricsRegistry().histogram("h", max_samples=64)
    for v in range(1000):
        h.observe(float(v))
    assert len(h.samples) <= 64
    assert h.count == 1000 and h.total == sum(range(1000))
    assert h.quantile(1.0) >= 990.0           # tail survives decimation


def test_elastic_controller_exports_fleet_metrics():
    from repro.train.fault_tolerance import ElasticController
    ec = ElasticController(n_ranks=4, min_samples=2, replace_after=2,
                           threshold=1.5)
    for step in range(12):
        times = {r: 0.01 for r in ec.live_ranks}
        if step >= 4:
            times[3] = 0.1                    # persistent straggler
        ec.observe_round(times)
    snap = ec.metrics.snapshot()
    assert ec.live_ranks == (0, 1, 2)
    assert snap["gauges"]["elastic.live_ranks"] == 3.0
    assert snap["counters"]["elastic.ranks_dropped"] == 1.0
    assert snap["counters"]["elastic.straggler_incidents"] >= 2.0
    assert snap["histograms"]["elastic.step_ms"]["count"] > 0


# ------------------------------------------------------ hypothesis property

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:                           # optional test dep: skip
    HAS_HYPOTHESIS = False

if HAS_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(kind=st.sampled_from(("dispatch", "broadcast", "ring")),
           n=st.integers(2, 8), size=st.integers(1, 512),
           contexts=st.integers(1, 4), data=st.data())
    def test_send_window_depth_never_exceeds_contexts(kind, n, size,
                                                      contexts, data):
        """The window-cap half of the ScheduleProbe contract, as a pure
        trace-time property over every schedule family."""
        if kind == "dispatch":
            counts = data.draw(st.lists(st.integers(0, 4 * size),
                                        min_size=n, max_size=n))
            sched = make_schedule(counts, block_tokens=max(1, size))
        elif kind == "broadcast":
            sched = make_broadcast_schedule(n, max(size, 1), tile_m=size)
        else:
            sched = make_ring_schedule(n, max(size, 1), kv_chunk=size)
        depths = sched.send_window_depths(contexts)
        assert len(depths) == len(list(sched.rounds))
        assert all(1 <= d <= contexts for d in depths)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_send_window_depth_never_exceeds_contexts():
        pass


def _replay_into(probe, phases, cap, key):
    """Drive ``probe`` the way a kernel does: each phase's rounds through a
    SendWindow whose hooks record issues and retirements, drained at the
    phase's end."""
    from repro.core.schedule import SendWindow

    def k(rnd):
        return None if key is None else key(rnd)

    for rounds in phases:
        window = SendWindow(
            cap, start=lambda rnd: probe.issue(*rnd, key=k(rnd)),
            wait=lambda rnd: probe.wait_send(k(rnd)))
        for rnd in rounds:
            window.push(rnd, key=k(rnd))
        window.drain()


@pytest.mark.parametrize("fused", [True, False])
def test_probe_reports_peers_in_flight(fused):
    """ScheduleProbe.check on moe_dispatch's send phases: the tile-fused
    order keeps a send to every peer in flight under contexts 1, and its
    observed overlap share equals the schedule's; one offset-major window
    never has two peers in flight at contexts 1."""
    sched = make_schedule((43, 14, 5, 2), 8)
    phases, key = sched.send_phases(fused)
    probe = ScheduleProbe()
    _replay_into(probe, phases, 1, key)
    summary = probe.check(sched, 1, phases=phases, key=key)
    assert summary["max_depth"] == 1
    assert summary["rounds"] == sum(len(p) for p in phases)
    share = sched.overlap_share(1, tile_fused=fused, elide_dummy=False)
    assert summary["overlap_share"] == share
    if fused:
        assert summary["peers_in_flight"] == sched.n and share > 0.9
    else:
        assert summary["peers_in_flight"] == 1 and share == 0.0


def test_probe_rejects_keyed_window_faults():
    """The probe catches a round issued out of order, a window past its
    cap and a window left undrained where the next one opens."""
    from repro.core.schedule import offset_key
    sched = make_schedule((43, 14, 5, 2), 8)
    phases, key = sched.send_phases(True)
    # offset-major rounds where block-major ones were expected
    probe = ScheduleProbe()
    _replay_into(probe, (sched.rounds, phases[1]), 1, key)
    with pytest.raises(AssertionError, match="issue order"):
        probe.check(sched, 1, phases=phases, key=key)
    # two rounds to one peer in flight under contexts 1
    probe = ScheduleProbe()
    _replay_into(probe, phases, 2, key)
    with pytest.raises(AssertionError, match="exceeded"):
        probe.check(sched, 1, phases=phases, key=key)
    # the dispatch window not drained before the combine window opens
    probe = ScheduleProbe()
    _replay_into(probe, phases, 1, key)
    drains = [i for i, e in enumerate(probe.events)
              if e[0] == "wait_send"][len(phases[0]) - 4:len(phases[0])]
    probe.events = [e for i, e in enumerate(probe.events)
                    if i not in drains]
    with pytest.raises(AssertionError, match="not drained"):
        probe.check(sched, 1, phases=phases, key=offset_key)


def test_device_phases_switch_is_in_the_jit_key():
    """A step traced with phases off is never served from the jit entry
    traced with them on, nor the reverse; off, ``phase`` adds nothing to
    the program, and an attached probe is stamped either way."""
    import jax
    import jax.numpy as jnp

    probe, traces = ScheduleProbe(), []

    def step(x):
        traces.append(1)
        with phase("stage", probe):
            return x + 1

    f, x = jax.jit(step), jnp.ones(4)
    texts = {}
    for on in (False, True, False, True):
        with device_phases(on):
            f(x)
            texts[on] = f.lower(x).as_text(debug_info=True)
    assert len(traces) == 2
    assert probe.marks == ["stage", "stage"]
    assert "/stage/add" in texts[True]
    assert "/stage/" not in texts[False]

    def plain():
        def step(x):
            return x + 1
        return step

    with device_phases(False):
        off = jax.jit(step).lower(x).as_text()
    assert off == jax.jit(plain()).lower(x).as_text()
