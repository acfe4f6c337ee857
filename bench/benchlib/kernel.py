"""Kernel driver: one realization point of a search workload, built by
``Workload.build`` exactly as the cascade builds it, called back to back
on the cell's mesh.

One jitted call is a model step's MoE layers: the kernel once per layer
(``num_hidden_layers``), each layer with its own expert weights and its
own rows, in a loop on the device, so the device and not the host's issue
of calls sets the pace. Set-up makes the inputs from the seed on the mesh
(``input_sets`` different steps' rows, one set of expert weights),
compiles the step and warms it. The window issues steps back to back, at
most two chunks in flight, and ends in ``block_until_ready``:
``kernel_step_us`` is the window over the kernel calls (steps x layers)
completed in it. The outputs of a seeded sample of steps (and of the
last) are kept and, after the window, compared layer by layer and shard
by shard with the configuration's per-rank reference, on each rank's own
device.

With ``--trace 1`` the profiler records the first ``trace_seconds`` of
calls, then a short run of the workload's XLA host baseline on the same
inputs, whose device time per call is printed beside the kernel's.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
from collections import deque
from types import SimpleNamespace

import numpy as np

from benchlib.common import log, seed_key
from benchlib.counts import Dispatch, skew_counts
from benchlib.trace import events_from_profile, mark, reduce
from benchlib.traffic import call_order


def make_inputs(conf, mix, seed, mesh):
    """``(xs, w1, w2)``: xs (k, L, n, T, d) rows of ``k`` steps of ``L``
    layers, w1 (L, n, d, 2f) and w2 (L, n, f, d), in float32, sharded over
    the mesh's ranks, in one jitted call from the seed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    n, T = conf["ranks"], conf["rows_per_rank"]
    d, f = conf["hidden_size"], conf["intermediate_size"]
    L, k = conf["num_hidden_layers"], int(mix["input_sets"])
    ax = mesh.axis_names[0]

    def make(key):
        ks = jax.random.split(key, 3)
        xs = jax.random.normal(ks[0], (k, L, n, T, d), jnp.float32)
        w1 = jax.random.normal(ks[1], (L, n, d, 2 * f), jnp.float32) \
            * d ** -0.5
        w2 = jax.random.normal(ks[2], (L, n, f, d), jnp.float32) * f ** -0.5
        return xs, w1, w2

    shard = (NamedSharding(mesh, P(None, None, ax)),
             NamedSharding(mesh, P(None, ax)), NamedSharding(mesh, P(None, ax)))
    return jax.jit(make, out_shardings=shard)(seed_key(seed))


def layers(fn, mesh):
    """One step: ``fn`` on each layer's rows and weights in turn, a loop
    on the device, jitted. The barriers keep XLA from fusing the loop's
    slicing of the stacked operands into the kernel's call, which would
    stage all layers' operands in the kernel's fast memory."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = NamedSharding(mesh, P(None, mesh.axis_names[0]))
    barrier = jax.lax.optimization_barrier

    def layer(args):
        return barrier(fn(*barrier(args)))

    return jax.jit(lambda x, w1, w2: jax.lax.map(layer, (x, w1, w2)),
                   in_shardings=(spec,) * 3, out_shardings=spec)


def build(conf, mesh):
    """The workload instance, the realization point and its jitted step,
    through the same calls the cascade makes."""
    from repro.core import EXPERT_SYSTEMS
    from repro.workloads import get_workload
    wl = get_workload(conf["workload"], n_dev=conf["ranks"],
                      tokens_per_rank=conf["rows_per_rank"],
                      d=conf["hidden_size"], f=conf["intermediate_size"],
                      skew=conf["skew"], axis=mesh.axis_names[0])
    point = dataclasses.replace(
        EXPERT_SYSTEMS[conf["point"]],
        tunables=tuple(sorted(wl.default_tunables().items())))
    return wl, point, layers(wl.build(point, mesh), mesh)


def call_loop(fn, xs, w1, w2, order, seconds, chunk, keep=()):
    """Issue ``fn`` back to back for ``seconds`` (whole chunks), at most two
    chunks in flight; the window ends when the last call is ready. Returns
    ``(calls, elapsed_s, kept outputs)``."""
    import jax
    kept, inflight = {}, deque()
    n, y = 0, None
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        for _ in range(chunk):
            y = fn(xs[order[n % len(order)]], w1, w2)
            if n in keep:
                kept[n] = y
            n += 1
        inflight.append(y)
        if len(inflight) > 2:
            inflight.popleft().block_until_ready()
    jax.block_until_ready(y)
    elapsed = time.perf_counter() - t0
    kept[n - 1] = y
    return n, elapsed, kept


def _worst_layer(got, ref):
    """``max |y - ref| / max |ref|`` of each layer, the largest."""
    import jax.numpy as jnp
    return jnp.max(jnp.max(jnp.abs(got - ref), axis=(1, 2))
                   / jnp.max(jnp.abs(ref), axis=(1, 2)))


def rank_shards(y):
    """An output's shards by the rank they hold (its index along the rank
    axis); the mesh may order the ranks otherwise than the devices."""
    return {sh.index[1].start or 0: sh.data for sh in y.addressable_shards}


def rel_errors(outs, order, xs, w1, w2, ref_fn, program=None):
    """Worst error of each kept output against the reference, over its
    layers and ranks: ``max |y_r - ref_r| / max |ref_r|`` of each layer on
    each rank ``r``, worked on the device that holds rank ``r``'s shard.
    ``outs`` maps a step's index to its shards by rank. With ``program``
    (the control) its per-rank outputs stand in for the kept ones."""
    import jax
    worst = jax.jit(_worst_layer)
    errs = dict.fromkeys(outs, 0.0)
    for r in range(xs.shape[2]):
        dev = next(iter(next(iter(outs.values()))[r].devices()))
        w1r, w2r = jax.device_put(w1, dev), jax.device_put(w2, dev)
        refs = {}
        for i, shards in sorted(outs.items()):
            s = order[i % len(order)]
            if s not in refs:
                x = jax.device_put(xs[s, :, r], dev)
                refs[s] = (x, ref_fn(x, w1r, w2r))
            x, ref = refs[s]
            got = shards[r][:, 0] if program is None \
                else program(x, w1r, w2r)
            errs[i] = max(errs[i], float(worst(got, ref)))
        del w1r, w2r, refs
    return [errs[i] for i in sorted(errs)]


def run(ctx):
    import jax
    from repro.compat import make_mesh
    from repro.core import extract_hardware_context

    cell, conf, mix = ctx.cell, ctx.cell.config, ctx.cell.traffic
    mesh = make_mesh((conf["ranks"],), ("x",), devices=ctx.devices)
    wl, point, fn = build(conf, mesh)
    L = conf["num_hidden_layers"]
    xs, w1, w2 = make_inputs(conf, mix, ctx.seed, mesh)
    xs_list = [xs[i] for i in range(xs.shape[0])]
    jax.block_until_ready(fn(xs_list[0], w1, w2))       # compile
    t0 = time.perf_counter()
    probe = int(mix.get("probe_calls", 20))
    for i in range(probe):
        y = fn(xs_list[i % len(xs_list)], w1, w2)
    jax.block_until_ready(y)
    est = (time.perf_counter() - t0) / probe
    chunk = max(1, int(mix["chunk_s"] / est))
    host = None
    if ctx.trace:
        host = layers(wl.host_baseline(mesh), mesh)
        jax.block_until_ready(host(xs_list[0], w1, w2))
    setup_s = time.perf_counter() - ctx.t_start
    log(f"[setup] {setup_s:.3f} s; warm step of {L} layers "
        f"{1e6 * est:.1f} us, {chunk} steps per chunk")

    seconds = min(ctx.seconds, mix["trace_seconds"]) if ctx.trace \
        else ctx.seconds
    order = call_order(mix, ctx.seed, 4 * len(xs_list))
    rng = np.random.default_rng(ctx.seed)
    n_sure = max(1, int(0.5 * seconds / est))
    keep = set(int(i) for i in rng.choice(
        n_sure, size=min(mix["check"]["sample_calls"], n_sure),
        replace=False))
    marks = {}
    if ctx.trace:
        jax.profiler.start_trace(ctx.trace_dir, profiler_options=ctx.options)
        marks["bench.clock.start"] = mark("bench.clock.start")
    w_open = time.perf_counter()
    with _span(ctx.trace, "bench.kernel"):
        calls, elapsed, kept = call_loop(fn, xs_list, w1, w2, order,
                                         seconds, chunk, keep)
    window = (w_open, w_open + elapsed)
    host_calls = host_window = None
    if ctx.trace:
        h_open = time.perf_counter()
        with _span(True, "bench.host_baseline"):
            host_calls, h_el, _ = call_loop(
                host, xs_list, w1, w2, order, mix["host_baseline_seconds"],
                chunk)
        host_window = (h_open, h_open + h_el)
        marks["bench.clock.stop"] = mark("bench.clock.stop")
        jax.profiler.stop_trace()
    kernel_calls = calls * L
    step_us = 1e6 * elapsed / kernel_calls
    model_us = 1e6 * wl.analytic_cost(point, extract_hardware_context(mesh))
    log(f"[window] {calls} steps of {L} layers in {elapsed:.4f} s: "
        f"{step_us:.3f} us per kernel call; l3 model {model_us:.3f} us "
        f"(model error {model_us / step_us - 1:+.4f} of the measured)")
    device = ctx.device_info()

    # the reference runs once the program's state is freed
    outs = {i: rank_shards(y) for i, y in kept.items()}
    del fn, host, xs, xs_list, w1, w2, kept, y
    gc.collect()
    xs, w1, w2 = make_inputs(conf, mix, ctx.seed, mesh)
    ref = cell.reference()
    held = {frozenset(d for sh in shards.values() for d in sh.devices())
            for shards in outs.values()}
    if held != {frozenset(ctx.devices)} or any(
            sorted(shards) != list(range(conf["ranks"]))
            for shards in outs.values()):
        log(f"[check] outputs held on {sorted(map(len, held))} devices, "
            f"not one rank on each of the {conf['ranks']} chips")
        errs = [math.inf]
    else:
        errs = rel_errors(outs, order, xs, w1, w2,
                          ref.make_rank_output(conf))
    limit = mix["check"]["rel_err_limit"]
    worst = max(errs)
    readings = {"rel_err": worst}
    if getattr(ctx, "control", False):
        readings["control_rel_err"] = max(rel_errors(
            outs, order, xs, w1, w2, ref.make_rank_output(conf),
            program=ref.make_rank_output(conf, control=True)))
    checks = {"rel_err": {
        "value": worst, "limit": limit,
        "rule": f"worst layer and shard's max |y - ref| / max |ref| over "
                f"{len(outs)} kept steps of {calls}"}}

    rec = None
    if ctx.trace:
        events = events_from_profile(ctx.trace_dir)
        red = reduce(events, marks, window)
        rec = SimpleNamespace(
            cell=cell, config=conf, mix=mix, peaks=ctx.peaks,
            chips=len(ctx.devices), trace=red, calls=kernel_calls,
            dispatch=Dispatch(n=conf["ranks"], d=conf["hidden_size"],
                              f=conf["intermediate_size"],
                              counts=tuple(skew_counts(
                                  conf["rows_per_rank"], conf["ranks"],
                                  conf["skew"]))))
        hred = reduce(events, marks, host_window)
        host_us = 1e6 * sum(hred.busy_s(d) for d in hred.devices) \
            / len(hred.devices) / (host_calls * L)
        kern_us = 1e6 * sum(red.busy_s(d) for d in red.devices) \
            / len(red.devices) / kernel_calls
        least, bound = rec.dispatch.least_time(ctx.peaks)
        log(f"[trace] least time per call {1e6 * least:.3f} us, set by "
            f"{bound} on the busiest rank")
        log(f"[trace] device time per call: kernel {kern_us:.3f} us, XLA "
            f"host baseline {host_us:.3f} us ({host_calls * L} calls): "
            f"co-design speedup {host_us / kern_us:.4f}x; l3 model "
            f"{model_us:.3f} us")
    return SimpleNamespace(
        correct=bool(math.isfinite(worst) and worst <= limit),
        attempted=kernel_calls, failed=0,
        metrics={"kernel_step_us": step_us, "setup_s": setup_s},
        device=device, checks=checks, records=rec, readings=readings)


def _span(on, name):
    from contextlib import nullcontext
    if not on:
        return nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)
