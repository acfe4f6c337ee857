"""The MoE dispatch kernel's device phases, read from its regions.

A program built with its device phases on (``repro.core.trace.phase``)
opens one region per phase of each kernel call; the profiler records them
on the device's own clock, beside the op events (on the device's ``XLA
TraceMe`` line on a v5e). Each ``moe_dispatch`` region is one call, and the
regions that start and end inside it are its phases. A call is complete
when it holds each phase that runs once per call exactly once, and as many
``arrival_wait`` and ``ffn`` regions as the most common call on its device:
a profiler that drops events leaves calls that are not, and no reader reads
them.

The regions come from a window of their own, ``bench.kernel_phases``, in a
profile of its own: the first phase reader of a traced run builds the
cell's step a second time with the phases on, compiles it with the option
that keeps the regions, and runs it back to back for
``host_baseline_seconds`` once the driver's windows and check are done
(:func:`by_rank`). The driver's windows run the plain step, so the
accepted metrics and the breakdown read the same program as without this
module. A program without device phases gets no window, and the phase
readers find nothing.
"""
from __future__ import annotations

import bisect
import gc
import glob
import math
import os
import tempfile
import time
from collections import Counter
from dataclasses import dataclass, field

from benchlib.common import log
from benchlib.trace import DEVICE_PREFIX, OPS_LINE, Event, short_name

KERNEL = "moe_dispatch"
# once per call, in the order the kernel runs them
ONCE = ("stage_in", "dispatch", "ffn_combine", "combine_wait", "assemble",
        "stage_out")
# inside ffn_combine: one per landed dispatch microblock, one per GEMM tile
REPEATED = ("arrival_wait", "ffn")
REGIONS = frozenset((KERNEL,) + ONCE + REPEATED)
# The window's rows and weights: its timings depend on the shapes and the
# workload's static routing law, not on the values (kernel_step_us spreads
# by 0.002 % over seeds), so one fixed seed serves every run.
WINDOW_SEED = 0


@dataclass
class Call:
    """One kernel call on one device: its region and its phases' regions,
    each ``(start_ns, dur_ns)``."""
    start: float
    dur: float
    phases: dict = field(default_factory=dict)

    @property
    def end(self):
        return self.start + self.dur

    def time(self, *names):
        """Nanoseconds spent in the regions ``names``, summed."""
        return sum(d for n in names for _, d in self.phases.get(n, ()))

    def shape(self):
        return tuple(len(self.phases.get(n, ())) for n in ONCE + REPEATED)


def calls(regions):
    """Region events of one device (any order) -> its calls, by start."""
    evs = sorted(regions, key=lambda e: (e.start, -e.dur))
    out = []
    for e in evs:
        if e.name == KERNEL:
            out.append(Call(e.start, e.dur))
        elif out and out[-1].start <= e.start and e.end <= out[-1].end:
            out[-1].phases.setdefault(e.name, []).append((e.start, e.dur))
    return out


def complete(device_calls):
    """The complete calls of one device, and how many were dropped."""
    if not device_calls:
        return [], 0
    common = Counter(c.shape() for c in device_calls).most_common(1)[0][0]
    once = common[:len(ONCE)]
    if once != (1,) * len(ONCE):
        return [], len(device_calls)
    ok = [c for c in device_calls if c.shape() == common]
    return ok, len(device_calls) - len(ok)


def mean_us(cs, *names):
    """Microseconds per call in the regions ``names`` (the kernel's own
    region where none is named), over the calls ``cs``."""
    if not names:
        return sum(c.dur for c in cs) / len(cs) / 1e3
    return sum(c.time(*names) for c in cs) / len(cs) / 1e3


def share(by_rank, *names):
    """Percent of the kernel's time in the regions ``names``: each rank's
    summed region time over its summed kernel time, averaged over the ranks;
    None where no rank has a complete call."""
    vals = [100.0 * sum(c.time(*names) for c in cs) / sum(c.dur for c in cs)
            for cs in by_rank.values() if cs]
    return sum(vals) / len(vals) if vals else None


def by_rank(rec):
    """The complete kernel calls of each rank in the phases window, measured
    on the first read of a run and kept on its records."""
    if not hasattr(rec, "phases"):
        rec.phases = measure(rec)
    return rec.phases


def measure(rec, devices=None):
    """Build the records' cell step with the device phases on, run it in
    the ``bench.kernel_phases`` window under a profile of its own and return
    each rank's complete calls (rank -> [Call]); {} where the program has no
    device phases. ``devices`` stand in for the cell's chips in tests."""
    try:
        from repro.core.trace import REGION_TRACE_OPTIONS, device_phases
    except ImportError:
        return {}
    import jax
    from repro.compat import make_mesh
    from repro.core import extract_hardware_context

    from benchlib import kernel
    from benchlib.traffic import call_order

    conf, mix = rec.config, rec.mix
    devices = devices or jax.devices()[:rec.chips]
    mesh = make_mesh((conf["ranks"],), ("x",), devices=devices)
    wl, point, _ = kernel.build(conf, mesh)
    gc.collect()
    xs, w1, w2 = kernel.make_inputs(conf, mix, WINDOW_SEED, mesh)
    xs_list = [xs[i] for i in range(xs.shape[0])]
    t0 = time.perf_counter()
    with device_phases():
        step = kernel.layers(wl.build(point, mesh), mesh).lower(
            xs_list[0], w1, w2).compile(
                compiler_options=REGION_TRACE_OPTIONS
                if devices[0].platform == "tpu" else None)
    jax.block_until_ready(step(xs_list[0], w1, w2))
    t1 = time.perf_counter()
    for x in xs_list:
        y = step(x, w1, w2)
    jax.block_until_ready(y)
    est = (time.perf_counter() - t1) / len(xs_list)
    order = call_order(mix, WINDOW_SEED, 4 * len(xs_list))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory(prefix="bench-phases-") as d:
        jax.profiler.start_trace(d, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench.kernel_phases"):
            n, elapsed, _ = kernel.call_loop(
                step, xs_list, w1, w2, order, mix["host_baseline_seconds"],
                max(1, int(mix["chunk_s"] / est)))
        jax.profiler.stop_trace()
        regions, ops = read_profile(d)
    del step, xs, xs_list, w1, w2, y
    L = conf["num_hidden_layers"]
    log(f"[trace] phases window: compiled and warmed in {t1 - t0:.1f} s; "
        f"{n} steps, {1e6 * elapsed / (n * L):.3f} us per kernel call with "
        f"phases on ({1e6 * rec.trace.window_s / rec.calls:.3f} in the "
        f"plain window)")
    return rank_calls(regions, ops,
                      [f"{DEVICE_PREFIX}{dev.id}" for dev in mesh.devices.flat],
                      wl.cost_breakdown(point, extract_hardware_context(mesh)))


def read_profile(logdir):
    """The phase regions on any line of each device plane, and the
    custom-call op events of its op line, as :class:`Event` s by plane."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    regions, ops = {}, {}
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith(DEVICE_PREFIX):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name in REGIONS:
                        into, name = regions, e.name
                    elif line.name == OPS_LINE and short_name(
                            e.name).endswith(" custom-call"):
                        into, name = ops, short_name(e.name)
                    else:
                        continue
                    into.setdefault(plane.name, []).append(Event(
                        plane.name, line.name, name, float(e.start_ns),
                        float(e.duration_ns)))
    return regions, ops


def rank_calls(regions, ops, planes, l3):
    """The complete calls of each rank, ``planes`` the device plane of each
    rank in rank order (the mesh may order ranks otherwise than devices).
    Logs each rank's phases in us per call beside the l3 model's segments,
    the calls dropped, and how the kernel's region sits in its custom-call
    op on the same device."""
    log("[trace] l3 segments in us: " + ", ".join(
        f"{s.name} {1e6 * s.dur_s:.3f}" for s in l3.segments))
    out = {}
    for rank, plane in enumerate(planes):
        evs = regions.get(plane, [])
        seen = calls(evs)
        cs, dropped = complete(seen)
        out[rank] = cs
        stray = len(evs) - sum(1 + sum(map(len, c.phases.values()))
                               for c in seen)
        head = (f"[trace] rank {rank} ({plane}): {len(cs)} complete calls "
                f"of {len(seen)}, {dropped} dropped, {stray} regions outside "
                f"a call")
        if not cs:
            log(head)
            continue
        inside = _enclosing_ops(cs, ops.get(plane, []))
        us = {n: mean_us(cs, n) for n in ONCE + REPEATED}
        kern = mean_us(cs)
        op_us = before = after = math.nan
        if inside:
            op_us, before, after = (sum(v) / len(inside) / 1e3
                                    for v in zip(*inside))
        log(head + f"; us per call: moe_dispatch {kern:.3f} = "
            + " + ".join(f"{n} {us[n]:.3f}" for n in ONCE)
            + f" + gaps {kern - sum(us[n] for n in ONCE):.3f}; in "
            f"ffn_combine arrival_wait {us['arrival_wait']:.3f}, ffn "
            f"{us['ffn']:.3f}; custom-call op {op_us:.3f} (region / op "
            f"{kern / op_us:.4f}: {before:.3f} before the region, "
            f"{after:.3f} after), {len(cs) - len(inside)} calls outside an "
            f"op")
    return out


def _enclosing_ops(cs, ops):
    """For each of the calls ``cs`` that lies inside a custom-call op: the
    op's duration, and the op's time before and after the call's region
    (ns)."""
    ops = sorted((e.start, e.end) for e in ops)
    starts = [s for s, _ in ops]
    out = []
    for c in cs:
        i = bisect.bisect_right(starts, c.start) - 1
        if i >= 0 and c.end <= ops[i][1]:
            s, e = ops[i]
            out.append((e - s, c.start - s, e - c.end))
    return out
