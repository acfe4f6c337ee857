"""Fused GEMM + device-initiated AllGather (paper workload 4) — the
FLUX/CoCoNet-grade tile-fused realization.

Each device computes ``C_local = A_local @ B`` and broadcasts it to every
peer by remote DMA into the peer's output slab (the LSA-analogue: direct
stores into peer memory — here single-hop ICI remote copies). Rank ``r``'s
slab lives at rows ``[r*M_l, (r+1)*M_l)`` of every device's output, so the
source and destination offsets of every transfer coincide.

**Broadcast-round schedule.** The schedule is trace time
(:class:`BroadcastSchedule`, the gemm_allgather analogue of
``moe_dispatch.DispatchSchedule``): rounds ``(off, t)`` where in round
``(off, t)`` rank ``r`` sends tile ``t`` of its slab to peer ``(r + off) %
n`` and receives the matching tile from ``(r - off) % n`` — a shift
permutation. The broadcast is *dense* (every rank ships every tile to every
peer), so unlike the MoE dispatch schedule there are no dummy rounds and
nothing to elide.

**Placement realizations (design-space P):**
  TILE_FUSED — rounds are ordered tile-major: tile ``t``'s broadcast DMAs
    are issued the moment tile ``t``'s GEMM finishes, while tile ``t+1``
    computes (G=PER_TILE).
  DEFERRED   — one whole-slab round per peer offset after the full local
    GEMM (G=PER_PEER; the fast-path conservative shape). Both paths share
    the same schedule object; only ``rounds``/``rows_per_round`` differ.

**Completion (design-space K):** ``COUNTER`` (the FLUX point) consumes
arrivals one tile at a time — while tile ``t``'s sends are in flight the
kernel ticks off tile ``t-1``'s landings from every peer, so readiness is
per-tile, not per-edge. ``SIGNAL`` waits once per inbound edge after the
tile loop. ``BARRIER`` (and any non-fused placement) drains whole slabs.

**Send window.** ``contexts`` bounds the in-flight send window: at most
``contexts`` broadcast rounds' send semaphores are unawaited; the oldest is
``wait_send``-ed before the next round issues (double/quad buffering) —
replacing the old kernel's wait-everything-at-``t == nt-1`` drain.

Per-edge semaphores: slot ``p`` of the send array counts outstanding sends
to peer ``p``; slot ``s`` of the receive array counts arrivals from source
``s`` (the sender's descriptor names slot ``me`` on the receiver). Arrivals
are waited through a copy descriptor of the landed rows' size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax import shard_map

from repro.compat import compiler_params, default_interpret
# The schedule machinery is defined once, in repro.core.schedule (the
# collective-schedule contract); re-exported here for the kernel's callers.
from repro.core.schedule import (BroadcastSchedule, SendWindow,  # noqa: F401
                                 make_broadcast_schedule, sanitize_tile_m)


# ------------------------------------------------------------------- kernel


def _ga_kernel(a_ref, b_ref, o_ref, atile, bbuf, ctile, ssem, rsem,
               *, axis, sched: BroadcastSchedule, counter, contexts,
               probe=None):
    n, M_l, tm, nt = sched.n, sched.M_l, sched.tile_m, sched.nt
    N = b_ref.shape[1]
    me = jax.lax.axis_index(axis)

    # GEMM operands live in ANY (HBM): B is staged into VMEM once, each A
    # tile per round — Mosaic computes on DMA-staged VMEM operands only.
    pltpu.sync_copy(b_ref, bbuf)

    def edge_dma(off, rel, rows):
        """Round (off, .): ship rows [rel, rel+rows) of my slab to peer
        (me+off)%n; the matching inbound rows land from (me-off)%n."""
        peer = jax.lax.rem(me + off, n)
        rows0 = me * M_l + rel
        return pltpu.make_async_remote_copy(
            src_ref=o_ref.at[pl.ds(rows0, rows)],
            dst_ref=o_ref.at[pl.ds(rows0, rows)],
            send_sem=ssem.at[peer], recv_sem=rsem.at[me],
            device_id=peer, device_id_type=pltpu.DeviceIdType.MESH)

    def gemm_tile(t):
        # operands and result both stage through VMEM scratch (atile/bbuf
        # in, ctile out); a_ref/o_ref live in ANY
        pltpu.sync_copy(a_ref.at[pl.ds(t * tm, tm)], atile)
        ctile[...] = jax.lax.dot_general(
            atile[...], bbuf[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(ctile.dtype)
        pltpu.sync_copy(ctile, o_ref.at[pl.ds(me * M_l + t * tm, tm)])

    def wait_arrivals(off, rel, rows):
        """Tick rows [rel, rel+rows) of the slab from source (me-off)%n: a
        copy descriptor of their size waits that source's receive slot."""
        recv_probe()
        src = jax.lax.rem(me - off + n, n)
        landed = o_ref.at[pl.ds(src * M_l + rel, rows)]
        pltpu.make_async_copy(landed, landed, rsem.at[src]).wait()

    # contexts-deep send window over the trace-time round order (the shared
    # schedule.SendWindow): every DMA is issued unconditionally, the window
    # only bounds how many rounds' send semaphores stay unawaited. An
    # attached ScheduleProbe (core/trace.py) records the trace-time
    # issue/wait order for the observed-vs-modeled check.
    if probe is None:
        window = SendWindow(contexts)
        recv_probe = lambda: None
    else:
        # the probe must observe the window's true order — retire-oldest
        # strictly before the new round starts — so both hooks record
        pending = []

        def _start(cps):
            probe.issue(*pending.pop(0))
            for cp in cps:
                cp.start()

        def _retire(cps):
            probe.wait_send()
            for cp in cps:
                cp.wait_send()

        window = SendWindow(contexts, start=_start, wait=_retire)
        recv_probe = probe.wait_recv

    def issue(off, rel, rows):
        if probe is not None:
            pending.append((off, rel // rows))
        window.push([edge_dma(off, rel, rows)])

    if sched.fused:
        # TILE_FUSED: tile t's broadcast issues the moment its GEMM ends,
        # overlapping tile t+1's compute — (off, t) round order.
        for t in range(nt):
            gemm_tile(t)
            for off in range(1, n):
                issue(off, t * tm, tm)
            if counter and t > 0:
                # COUNTER per-tile ticks: consume tile t-1's arrivals from
                # every peer while tile t's sends are still in flight
                for off in range(1, n):
                    wait_arrivals(off, (t - 1) * tm, tm)
        window.drain()
        if counter:
            for off in range(1, n):          # the final tile's ticks
                wait_arrivals(off, (nt - 1) * tm, tm)
        else:
            for off in range(1, n):          # per-edge SIGNAL drain
                wait_arrivals(off, 0, nt * tm)
    else:
        # DEFERRED: one whole-slab round per peer after the full GEMM,
        # same schedule object with rows_per_round = M_l.
        for t in range(nt):
            gemm_tile(t)
        for off in range(1, n):
            issue(off, 0, M_l)
        window.drain()
        for off in range(1, n):
            wait_arrivals(off, 0, M_l)


def gemm_allgather_sharded(a, b, *, axis, sched: BroadcastSchedule = None,
                           n_dev=None, tile_m=128, fused=True, counter=False,
                           contexts=2, interpret=None, probe=None):
    """Per-device fn (under shard_map). a: (M_l, K) local; b: (K, N)
    replicated. Returns (n_dev*M_l, N) — the full gathered GEMM output on
    every device. An explicit ``sched`` takes precedence: the
    ``n_dev``/``tile_m``/``fused`` knobs are consulted only to build one
    when ``sched`` is None. ``probe`` (a ``core/trace.py::ScheduleProbe``)
    records the trace-time DMA issue/wait order for the observed-vs-modeled
    schedule check. ``interpret=None`` picks
    :func:`repro.compat.default_interpret`."""
    M_l, K = a.shape
    N = b.shape[1]
    if sched is None:
        assert n_dev is not None, \
            "gemm_allgather_sharded needs an explicit sched= or n_dev="
        sched = make_broadcast_schedule(n_dev, M_l, tile_m, fused)
    assert sched.M_l == M_l, (sched.M_l, M_l)
    assert M_l % sched.tile_m == 0, (M_l, sched.tile_m)
    kern = functools.partial(_ga_kernel, axis=axis, sched=sched,
                             counter=bool(counter), contexts=contexts,
                             probe=probe)
    return pl.pallas_call(
        kern,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((sched.n * M_l, N), a.dtype),
        scratch_shapes=[
            pltpu.VMEM((sched.tile_m, K), a.dtype),  # staged A tile operand
            pltpu.VMEM((K, N), b.dtype),             # staged B operand
            pltpu.VMEM((sched.tile_m, N), a.dtype),  # GEMM tile staging
            pltpu.SemaphoreType.DMA((sched.n,)),     # per-peer send slots
            pltpu.SemaphoreType.DMA((sched.n,)),     # per-source recv slots
        ],
        interpret=default_interpret() if interpret is None else interpret,
        compiler_params=compiler_params(),
    )(a, b)


def gemm_allgather(a_shards, b, mesh, *, axis="x", tile_m=128, fused=True,
                   counter=False, contexts=2, probe=None, interpret=None):
    """Global entry: a_shards (n, M_l, K) sharded over axis; b replicated.
    ``tile_m`` is sanitized to a divisor of M_l; ``counter`` selects
    per-tile completion ticks (the FLUX point) on the fused path. ``probe``
    (a ``core/trace.py::ScheduleProbe``) records the trace-time DMA
    issue/wait order for ``probe.check(sched, contexts)``. ``interpret`` as
    in :func:`gemm_allgather_sharded`."""
    from jax.sharding import PartitionSpec as P
    n_dev = mesh.shape[axis]
    sched = make_broadcast_schedule(n_dev, a_shards.shape[1], tile_m, fused)

    @functools.partial(shard_map, mesh=mesh, in_specs=(P(axis), P(None, None)),
                       out_specs=P(axis), check_vma=False)
    def run(a, bb):
        out = gemm_allgather_sharded(a[0], bb, axis=axis, sched=sched,
                                     counter=counter, contexts=contexts,
                                     probe=probe, interpret=interpret)
        return out[None]

    return run(a_shards, b)
