"""Modeled-timeline tracing — the l3 cost model rendered as a Perfetto
trace (the observability layer of ROADMAP "auditable cost" work).

Two halves:

* :class:`TraceWriter` — a minimal Chrome-trace-event/Perfetto JSON
  emitter (complete spans, counter tracks, instant events, process/thread
  metadata). ``write()`` produces a file that loads directly in
  https://ui.perfetto.dev (timestamps in microseconds, the trace-event
  convention).
* :func:`schedule_timeline` — renders one directive's
  ``Workload.cost_breakdown`` as a per-rank modeled timeline laid over the
  ``CollectiveSchedule`` round order: the critical-path segments become
  spans, DMA-issue rounds (``issued_rounds()``) become instants inside the
  overlap span, the send-window occupancy (``send_window_depths()``)
  becomes a counter track, COUNTER arrival ticks land on the receive
  thread, window-recycle stalls render as explicit ``stall`` slices, and
  degraded-mode membership (``degrade(live_ranks)``) / fault plans splice
  recovery + remesh + straggler segments in.

**The invariant** (asserted in tests/test_trace.py): the sum of the
critical-path spans of any rendered timeline equals ``analytic_cost()``
(or ``fault_cost()`` when a plan is given) within 1e-6 — both are derived
from the same :class:`~repro.core.cost_model.CostBreakdown`, so the trace
audits exactly the scalar the cascade scores.

:class:`ScheduleProbe` is the interpret-mode observed-order probe: a
kernel body (``kernels/gemm_allgather.py``, ``kernels/moe_dispatch.py``)
records its actual DMA issue/wait sequence at trace time, and
:meth:`ScheduleProbe.check` verifies it against the trace-time lockstep
schedule — round order, window cap, and arrival count must match the
``CollectiveSchedule`` contract the cost model charged.

:func:`phase` marks a kernel's phases on the device's own clock: a Pallas
TPU kernel body opens one region per phase (``jax.named_scope``, which
Mosaic lowers to ``tpu.trace_start``/``trace_stop``), and the profiler
records each region once the compile asks for custom-call region traces
(:data:`REGION_TRACE_OPTIONS`). The regions exist only under the
:func:`device_phases` switch, which is off by default and part of the jit
key; off, a kernel lowers exactly as without the calls. The same call
stamps an attached :class:`ScheduleProbe`'s marks.

Trace-time Python, mirroring core/schedule.py; the device-phase switch is
a JAX config state.
"""
from __future__ import annotations

import contextlib
import json
from dataclasses import dataclass, field

import jax
from jax._src import config as jax_config

from repro.core.cost_model import CostSegment
from repro.core.faults import REMESH_OVERHEAD
from repro.core.schedule import DispatchSchedule, send_window_depths

__all__ = [
    "TraceWriter", "Timeline", "ScheduleProbe", "schedule_timeline",
    "validate_trace", "phase", "device_phases", "REGION_TRACE_OPTIONS",
]

# thread ids of the per-rank track layout (one process per modeled rank)
TID_CRITICAL = 0      # the critical-path spans (sum == analytic_cost)
TID_DMA = 1           # DMA-issue round instants
TID_ARRIVALS = 2      # receive-side readiness ticks


class TraceWriter:
    """Chrome-trace-event ("JSON Array with metadata") emitter.

    Event fields follow the trace-event spec: ``ph`` is the phase ("X"
    complete span, "C" counter, "i" instant, "M" metadata), ``ts``/``dur``
    are microseconds (floats allowed), ``pid``/``tid`` name the track.
    """

    def __init__(self):
        self.events = []

    # ------------------------------------------------------------- metadata
    def meta_process(self, pid, name):
        self.events.append({"ph": "M", "name": "process_name", "pid": pid,
                            "tid": 0, "args": {"name": str(name)}})

    def meta_thread(self, pid, tid, name):
        self.events.append({"ph": "M", "name": "thread_name", "pid": pid,
                            "tid": tid, "args": {"name": str(name)}})

    # --------------------------------------------------------------- events
    def span(self, name, ts_us, dur_us, *, pid=0, tid=0, cat="modeled",
             args=None):
        ev = {"ph": "X", "name": str(name), "cat": str(cat),
              "ts": float(ts_us), "dur": float(dur_us),
              "pid": pid, "tid": tid}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def counter(self, name, ts_us, values, *, pid=0, cat="modeled"):
        """One sample of a counter track; ``values`` maps series name ->
        number (Perfetto stacks multi-series counters)."""
        self.events.append({"ph": "C", "name": str(name), "cat": str(cat),
                            "ts": float(ts_us), "pid": pid, "tid": 0,
                            "args": {k: float(v) for k, v in values.items()}})

    def instant(self, name, ts_us, *, pid=0, tid=0, cat="modeled",
                args=None):
        ev = {"ph": "i", "name": str(name), "cat": str(cat),
              "ts": float(ts_us), "pid": pid, "tid": tid, "s": "t"}
        if args:
            ev["args"] = args
        self.events.append(ev)

    # --------------------------------------------------------------- output
    def to_dict(self):
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def to_json(self, indent=None):
        return json.dumps(self.to_dict(), indent=indent)

    def write(self, path, indent=None):
        with open(path, "w") as f:
            f.write(self.to_json(indent=indent))


_REQUIRED = {"X": ("name", "ts", "dur", "pid", "tid"),
             "C": ("name", "ts", "pid", "args"),
             "i": ("name", "ts", "pid", "tid", "s"),
             "M": ("name", "pid", "args")}


def validate_trace(obj):
    """Structural validity of a trace dict (the schema tests/test_trace.py
    and the telemetry suite assert): a ``traceEvents`` list whose events
    carry the per-phase required fields, non-negative timestamps and
    durations. Returns the event count; raises ``ValueError`` on the first
    malformed event."""
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        raise ValueError("trace must be a dict with a traceEvents list")
    events = obj["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("traceEvents must be a list")
    for i, ev in enumerate(events):
        ph = ev.get("ph")
        if ph not in _REQUIRED:
            raise ValueError(f"event {i}: unknown phase {ph!r}")
        for k in _REQUIRED[ph]:
            if k not in ev:
                raise ValueError(f"event {i} (ph={ph}): missing field {k!r}")
        if "ts" in ev and float(ev["ts"]) < 0:
            raise ValueError(f"event {i}: negative ts")
        if ph == "X" and float(ev["dur"]) < 0:
            raise ValueError(f"event {i}: negative dur")
    return len(events)


# --------------------------------------------------------------- timelines


@dataclass
class Timeline:
    """A rendered modeled timeline. ``critical_path_s`` is the sum of the
    critical-path spans (== ``analytic_cost`` / ``fault_cost`` by
    construction); ``breakdown`` is the CostBreakdown it was laid from."""
    writer: TraceWriter
    critical_path_s: float
    breakdown: object
    workload_name: str
    degraded: bool = False
    live_ranks: tuple = ()
    meta: dict = field(default_factory=dict)

    def to_dict(self):
        return self.writer.to_dict()

    def write(self, path, indent=None):
        self.writer.write(path, indent=indent)


_KIND_CAT = {"stall": "stall", "recovery": "recovery", "remesh": "recovery",
             "sync": "sync", "launch": "sync"}


def _anchor_segment(breakdown):
    """The span DMA rounds issue during: the first overlap segment, else
    the first wire segment, else the longest segment."""
    for kind in ("overlap", "wire"):
        for s in breakdown.segments:
            if s.kind == kind and s.dur_s > 0:
                return s
    return max(breakdown.segments, key=lambda s: s.dur_s)


def schedule_timeline(workload, directive, hw, *, live_ranks=None,
                      plan=None):
    """Render ``workload.cost_breakdown(directive, hw)`` as a per-rank
    Perfetto timeline (see module docstring for the track layout).

    ``live_ranks`` renders the degraded deployment (the workload reshapes
    via ``degrade`` exactly as ``fault_cost`` does). ``plan`` (a
    ``FaultPlan``) additionally splices the recovery / remesh / straggler
    segments so the critical path equals ``fault_cost(workload, directive,
    hw, plan)``. The healthy call renders ``analytic_cost``.
    """
    base = workload
    extra = []          # (name, dur_s, kind) appended after the breakdown
    if plan is not None:
        if live_ranks is not None:
            raise ValueError("pass live_ranks or plan, not both")
        live_ranks = plan.live_ranks(base.n_dev)
    degraded = False
    live = tuple(range(base.n_dev))
    if live_ranks is not None:
        from repro.core.schedule import check_live
        live = check_live(live_ranks, base.n_dev)
        if len(live) < base.n_dev:
            degraded = True
            dead = base.n_dev - len(live)
            if plan is not None:
                # the fault_cost recovery terms, in fault_cost's order
                extra.append(("state_recovery",
                              dead * base.state_bytes_per_rank()
                              / hw.chip.ici_link_bw, "recovery"))
                extra.append(("remesh", REMESH_OVERHEAD, "remesh"))
            workload = base.degrade(live)
    if plan is not None:
        stall = plan.straggler_stall_s(directive.contexts)
        if stall or not extra:
            extra.append(("straggler_stall", stall, "stall"))

    bd = workload.cost_breakdown(directive, hw)
    w = TraceWriter()
    n = workload.n_dev
    sched = bd.schedule
    contexts = int(bd.knobs.get("contexts", max(1, directive.contexts)))

    critical = 0.0
    for rank in range(n):
        w.meta_process(rank, f"rank {rank} · {workload.name}")
        w.meta_thread(rank, TID_CRITICAL, "modeled critical path")
        if degraded:
            w.instant("degraded: live=" + ",".join(map(str, live)), 0.0,
                      pid=rank, tid=TID_CRITICAL, cat="fault",
                      args={"live_ranks": list(live)})
        cursor = 0.0
        rank_total = 0.0
        for seg in tuple(bd.segments) + tuple(
                CostSegment(nm, dur, kind) for nm, dur, kind in extra):
            dur_us = seg.dur_s * 1e6
            if dur_us > 0.0:
                args = {"kind": seg.kind}
                args.update({k: v for k, v in seg.meta.items()
                             if isinstance(v, (int, float, str, bool))})
                w.span(seg.name, cursor, dur_us, pid=rank, tid=TID_CRITICAL,
                       cat=_KIND_CAT.get(seg.kind, "modeled"), args=args)
            cursor += dur_us
            rank_total += seg.dur_s
        if rank == 0:
            critical = rank_total

        if sched is None:
            continue
        # ------------- schedule detail tracks (kernelized directives only)
        rounds = list(sched.rounds)
        depths = sched.send_window_depths(contexts)
        if isinstance(sched, DispatchSchedule) and bd.knobs.get("tile_fused"):
            # moe_dispatch's tile-fused dispatch window: block-major, one
            # window per offset (DispatchSchedule.send_phases)
            (rounds, _), key = sched.send_phases(True)
            depths = send_window_depths(rounds, contexts, key)
            w.instant("send overlap", 0.0, pid=rank, tid=TID_DMA, cat="dma",
                      args={"overlap_share": sched.overlap_share(
                          contexts, rank, combine_tile=bd.knobs.get(
                              "combine_tile"))})
        anchor = _anchor_segment(bd)
        a0 = 0.0
        for seg in bd.segments:
            if seg is anchor:
                break
            a0 += seg.dur_s * 1e6
        a_dur = anchor.dur_s * 1e6
        w.meta_thread(rank, TID_DMA, "dma issue rounds")
        w.meta_thread(rank, TID_ARRIVALS, "arrival ticks")
        step = a_dur / max(1, len(rounds))
        for i, (edge, tile) in enumerate(rounds):
            ts = a0 + i * step
            w.instant(f"dma issue ({edge},{tile})", ts, pid=rank,
                      tid=TID_DMA, cat="dma",
                      args={"round": i, "edge": edge, "tile": tile})
            w.counter("send window", ts, {"in_flight": depths[i]}, pid=rank)
        if rounds:
            w.counter("send window", a0 + a_dur, {"in_flight": 0}, pid=rank)
        ticks = _arrival_ticks(bd, sched)
        tstep = a_dur / max(1, ticks)
        for i in range(ticks):
            w.instant(f"arrival tick {i}", a0 + (i + 1) * tstep, pid=rank,
                      tid=TID_ARRIVALS, cat="dma", args={"tick": i})

    return Timeline(writer=w, critical_path_s=critical, breakdown=bd,
                    workload_name=workload.name, degraded=degraded,
                    live_ranks=live,
                    meta={"directive": directive.as_dict(),
                          "plan": getattr(plan, "name", None)})


def _arrival_ticks(bd, sched):
    """Receive-side readiness ticks of the rendered schedule: prefer the
    count the cost model actually charged (the ``tile_sync`` segment's
    meta), fall back to the schedule's own accounting."""
    for s in bd.segments:
        if "ticks" in s.meta:
            return int(s.meta["ticks"])
    if hasattr(sched, "completion_ticks"):
        return int(sched.completion_ticks(bool(bd.knobs.get("counter", True))))
    return 0


# ------------------------------------------------- observed-order probe


class ScheduleProbe:
    """Records the DMA issue/wait order a kernel body actually performs at
    trace time (interpret mode unrolls the body in Python, so a plain
    Python recorder sees the real sequence), then checks it against the
    trace-time lockstep schedule the cost model charged.

    Kernels accept ``probe=None`` and call :meth:`issue` /
    :meth:`wait_send` / :meth:`wait_recv` next to the corresponding DMA
    operations; :meth:`check` asserts the ``CollectiveSchedule`` contract:

    * the issued round order equals the schedule's (``schedule.rounds``,
      or each send window's rounds in turn),
    * the replayed in-flight send depth of each window key never exceeds
      ``contexts`` and matches ``send_window_depths`` after every issue,
    * every in-flight send is retired (drained) by each window's end,
    * the receive-wait count equals ``completion_ticks``.

    A round's first element (its edge) names its peer: the shift offset in
    the dispatch and broadcast schedules.
    """

    def __init__(self):
        self.events = []

    def reset(self):
        self.events = []

    def issue(self, *rnd, key=None):
        """Round ``rnd`` (``(edge, tile)``, or ``(off, j, t)`` for a combine
        tile) starts in send window ``key`` (``None``: the one window)."""
        self.events.append(("issue", tuple(int(x) for x in rnd), key))

    def wait_send(self, key=None):
        """The oldest in-flight round of window ``key`` retires."""
        self.events.append(("wait_send", key))

    def wait_recv(self, slot=None):
        self.events.append(("wait_recv",
                            None if slot is None else int(slot)))

    def mark(self, name):
        """Freeform ordering marker (e.g. the two-stream serving kernel
        stamps ``shared_ffn`` between the last dispatch issue and the
        window drain). Ignored by :meth:`check`; asserted via
        :attr:`marks` by callers that care about compute/DMA interleave."""
        self.events.append(("mark", str(name)))

    @property
    def marks(self):
        return [e[1] for e in self.events if e[0] == "mark"]

    @property
    def issued(self):
        return [e[1] for e in self.events if e[0] == "issue"]

    @property
    def recv_waits(self):
        return [e for e in self.events if e[0] == "wait_recv"]

    def check(self, schedule, contexts, counter=True, *, phases=None,
              key=None):
        """Assert the observed order satisfies the schedule contract;
        returns a summary dict on success, raises ``AssertionError`` with
        the first divergence otherwise.

        ``phases`` lists each send window's rounds in issue order, each
        window drained before the next opens (default: ``schedule.rounds``
        through one window); ``key`` is the windows' key function (see
        ``core/schedule.py::send_window_depths``). The summary's
        ``peers_in_flight`` is the most peers with an unawaited send at
        once, and ``overlap_share`` the share of issues that start while a
        send to another peer is in flight."""
        cap = max(1, int(contexts))
        if phases is None:
            phases = [list(schedule.rounds)]
            expect = list(schedule.send_window_depths(cap))
        else:
            phases = [list(p) for p in phases]
            expect = [d for p in phases
                      for d in send_window_depths(p, cap, key)]
        rounds = [tuple(r) for p in phases for r in p]
        assert self.issued == rounds, (
            f"observed issue order diverges from the schedule:\n"
            f"  observed {self.issued[:8]}...\n  expected {rounds[:8]}...")
        inflight, depths, peers, overlapped = [], [], 0, 0
        bounds = {sum(len(p) for p in phases[:i + 1])
                  for i in range(len(phases))}
        for ev in self.events:
            if ev[0] == "issue":
                if len(depths) in bounds:
                    assert not inflight, (
                        f"{len(inflight)} sends left in flight where a "
                        f"window ends (not drained)")
                rnd, k = ev[1], ev[2]
                overlapped += any(p != rnd[0] for _k, p in inflight)
                inflight.append((k, rnd[0]))
                depth = sum(1 for e in inflight if e[0] == k)
                assert depth <= cap, (
                    f"send window {k} exceeded: depth {depth} > "
                    f"contexts {cap}")
                depths.append(depth)
                peers = max(peers, len({p for _k, p in inflight}))
            elif ev[0] == "wait_send":
                mine = [e for e in inflight if e[0] == ev[1]]
                assert mine, f"wait_send on window {ev[1]} with no " \
                    "in-flight send"
                inflight.remove(mine[0])
        assert not inflight, \
            f"{len(inflight)} sends left in flight (window not drained)"
        assert depths == expect, (
            f"window depth profile diverges from send_window_depths:\n"
            f"  observed {depths[:12]}...\n  expected {expect[:12]}...")
        ticks = schedule.completion_ticks(counter) \
            if hasattr(schedule, "completion_ticks") else None
        n_recv = len(self.recv_waits)
        if ticks is not None:
            assert n_recv == ticks, (
                f"receive waits {n_recv} != completion_ticks {ticks}")
        return {"rounds": len(rounds), "max_depth": max(depths, default=0),
                "recv_waits": n_recv, "peers_in_flight": peers,
                "overlap_share": overlapped / len(depths) if depths else 0.0}


# ------------------------------------------------------- device phases

# The compile option that makes the TPU compiler keep a kernel's
# trace_start/trace_stop regions, so that the profiler records them.
REGION_TRACE_OPTIONS = {"xla_enable_custom_call_region_trace": True}

# In the jit key, so a step traced with regions is never served from a jit
# entry traced without them, nor the reverse (JAX offers no public way to
# add a config state to the key).
_DEVICE_PHASES = jax_config.State(
    "repro_device_phases", False,
    "Open a named region for each phase of the device kernels "
    "(repro.core.trace.phase).",
    include_in_jit_key=True, include_in_trace_context=True)


def device_phases(on=True):
    """Context manager: kernels traced inside it open their phase regions
    (``on``) or not. Read when a kernel body is traced, so lower and
    compile inside it, with :data:`REGION_TRACE_OPTIONS`."""
    return _DEVICE_PHASES(bool(on))


@contextlib.contextmanager
def phase(name, probe=None):
    """One phase of a kernel body: stamps ``probe.mark(name)`` when a probe
    is attached, and opens the device region ``name`` only while
    :func:`device_phases` is on (otherwise nothing enters the trace)."""
    if probe is not None:
        probe.mark(name)
    if not _DEVICE_PHASES.value:
        yield
        return
    with jax.named_scope(name):
        yield
