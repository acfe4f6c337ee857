"""Reduction of a JAX profiler trace to what the per-layer metrics read.

A trace is first flattened into plain events, ``(plane, line, name,
start_ns, dur_ns)``, so that the reduction can be checked on a small
recorded trace without a chip. From them:

* **busy intervals per device**: the union of the device's op events
  (line ``XLA Ops``; ``XLA Modules`` where a plane has no op line);
* **host spans**: the harness's own ``TraceAnnotation`` events, named
  ``bench.<what>``, on the host planes;
* **device time attributed to a host span**: the part of a device's busy
  time that falls inside spans of one name (the harness ends each such
  span in ``block_until_ready``, so a program's device work lies inside
  the span that issued it);
* **idle gaps**, each labelled by the innermost host span that covers its
  middle: what the host was doing while the device waited.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
NO_SPAN = "host: outside any span"


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float          # ns
    dur: float            # ns

    @property
    def end(self):
        return self.start + self.dur


def events_from_profile(logdir):
    """Flatten the ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``logdir`` into :class:`Event` s: every device event, and the host
    spans the harness annotated."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {logdir}, "
                           f"found {len(paths)}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith(SPAN_PREFIX):
                    name = short_name(e.name) if device else e.name
                    out.append(Event(plane.name, line.name, name,
                                     float(e.start_ns), float(e.duration_ns)))
    return out


_HLO = re.compile(r"^%?([^\s=]+) = .*?\s([a-z][\w-]*)\(")


def short_name(name):
    """``%fusion.227 = bf16[...] fusion(...), kind=...`` -> ``fusion.227
    fusion``: a TPU op event is named by its whole HLO instruction."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:120]


def self_times(events):
    """Each event's duration less the durations of the events nested in
    it (a ``while`` op holds its body's ops), as ``(name, ns)`` pairs."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].end <= e.start:
            out.append((stack[-1][0].name, stack.pop()[1]))
        if stack:
            stack[-1][1] -= e.dur
        stack.append([e, e.dur])
    out += [(ev.name, t) for ev, t in stack]
    return out


def save_events(events, path):
    with gzip.open(path, "wt") as f:
        json.dump([[e.plane, e.line, e.name, e.start, e.dur]
                   for e in events], f)


def load_events(path):
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals):
    return sum(e - s for s, e in intervals)


def overlap(a, b):
    """Total length of the intersection of two disjoint sorted interval
    lists."""
    i = j = 0
    t = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            t += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return t


@dataclass
class Reduced:
    """The trace of one window ``[t0, t1]`` (ns, the trace's clock)."""
    t0: float
    t1: float
    devices: list                          # device plane names, sorted
    busy: dict                             # device -> merged intervals
    ops: dict                              # device -> [Event] (op line)
    modules: dict                          # device -> [Event]
    spans: list = field(default_factory=list)   # host spans, sorted
    to_trace: object = None                     # host seconds -> trace ns

    @property
    def window_s(self):
        return (self.t1 - self.t0) / 1e9

    def busy_s(self, device):
        return total(self.busy[device]) / 1e9

    def span_intervals(self, name):
        return merge((e.start, e.end) for e in self.spans if e.name == name)

    def device_time_in(self, device, span_name):
        """Seconds of ``device``'s busy time inside spans ``span_name``."""
        return overlap(self.busy[device], self.span_intervals(span_name)) / 1e9

    def top_ops(self, n=10):
        """The device operations that took most time (self time: a loop's
        own time, without the ops of its body), in seconds averaged over
        the devices."""
        acc = {}
        for dev in self.devices:
            for name, t in self_times(self.ops[dev]):
                acc[name] = acc.get(name, 0.0) + t / 1e9
        k = len(self.devices)
        return sorted(([name, t / k] for name, t in acc.items()),
                      key=lambda x: -x[1])[:n]

    def idle_gaps(self, n=10):
        """Idle device time, labelled by the innermost host span covering
        the middle of each gap, in seconds averaged over the devices."""
        acc = {}
        for dev in self.devices:
            t, gaps = self.t0, []
            for s, e in self.busy[dev]:
                if s > t:
                    gaps.append((t, s))
                t = max(t, e)
            if t < self.t1:
                gaps.append((t, self.t1))
            for (s, e), label in zip(gaps, self._labels(
                    [0.5 * (s + e) for s, e in gaps])):
                acc[label] = acc.get(label, 0.0) + (e - s) / 1e9
        k = len(self.devices)
        return sorted(([name, t / k] for name, t in acc.items()),
                      key=lambda x: -x[1])[:n]

    def _labels(self, times):
        """The innermost span open at each of the sorted ``times``. The
        harness's spans come from one thread, so they nest: a sweep with
        a stack of open spans finds it."""
        out, stack, k = [], [], 0
        for t in times:
            while k < len(self.spans) and self.spans[k].start <= t:
                stack.append(self.spans[k])
                k += 1
            while stack and stack[-1].end < t:
                stack.pop()
            out.append(stack[-1].name if stack else NO_SPAN)
        return out


def clock_map(events, marks):
    """Host seconds -> trace nanoseconds, fitted through the harness's
    clock marks: zero-length spans ``name`` whose host time
    ``marks[name]`` the harness read as it wrote them."""
    pts = []
    for name, host_t in marks.items():
        hits = [e.start for e in events if e.name == name]
        if len(hits) != 1:
            raise RuntimeError(f"clock mark {name!r} found {len(hits)} times")
        pts.append((host_t, hits[0]))
    if len(pts) < 2:
        raise RuntimeError("two clock marks are needed")
    (h0, t0), (h1, t1) = min(pts), max(pts)
    scale = (t1 - t0) / (h1 - h0)
    return lambda h: t0 + (h - h0) * scale


def reduce(events, marks, window):
    """Reduce flat events to the host-clock ``window`` (seconds), mapped
    onto the trace's clock through the ``marks`` (see :func:`clock_map`)."""
    to_trace = clock_map(events, marks)
    t0, t1 = to_trace(window[0]), to_trace(window[1])
    devices = sorted({e.plane for e in events
                      if e.plane.startswith(DEVICE_PREFIX)})
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    busy, ops, modules = {}, {}, {}
    for dev in devices:
        evs = [e for e in events if e.plane == dev
               and e.end > t0 and e.start < t1]
        ops[dev] = [e for e in evs if e.line == OPS_LINE]
        modules[dev] = [e for e in evs if e.line == MODULES_LINE]
        src = ops[dev] or modules[dev]
        busy[dev] = clip(merge((e.start, e.end) for e in src), t0, t1)
    spans = sorted((e for e in events if e.name.startswith(SPAN_PREFIX)
                    and e.name not in marks and e.end > t0
                    and e.start < t1), key=lambda e: e.start)
    red = Reduced(t0, t1, devices, busy, ops, modules, spans)
    red.to_trace = to_trace
    return red


def mark(name):
    """Write a clock mark into the running trace; returns its host time."""
    import time

    import jax
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        pass
    return t
