"""KV-cache shuttle: chained GPU-triggered sends for disaggregated
prefill->decode serving (paper workload 3, Table 4 row 3) — realized
against the shared collective-schedule contract
(``repro.core.schedule.RingSchedule``, the ``n = 2`` degenerate ring:
one rotation step, prefill → decode).

The prefill rank computes K = x@Wk, starts its send, computes V = x@Wv
while K is on the wire, then sends V (signal-chained). The decode rank
waits entirely on-device. The CUCo-discovered strategy is exactly this
chain ("K GEMM -> send K -> V GEMM -> send V with signal"); the
host-driven baseline computes both projections, then transfers both.

Realizations, all driven by the one schedule:

  TILE_FUSED (+COUNTER = the FLUX point) — chunk-major rounds: the K/V
    projections run as ``kv_chunk``-row GEMM tiles and each tile's send is
    issued the moment its GEMM finishes (the next tile's GEMM hides the
    wire), under a ``contexts``-deep send window; the decode rank ticks
    arrivals off one chunk at a time (per-chunk receive semaphores).
  chained (``chained=1``, the non-fused CUCo point) — whole-tensor rounds,
    K's flight overlapping V's GEMM.
  sequential (``chained=0``) — each send awaited before the next GEMM
    starts (the host-driven shape inside one kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax import shard_map

from repro.compat import compiler_params, default_interpret
from repro.core.schedule import (RingSchedule, SendWindow,  # noqa: F401
                                 make_ring_schedule)


def _shuttle_kernel(x_ref, wk_ref, wv_ref, ko_ref, vo_ref,
                    kbuf, vbuf, ksend, krecv, vsend, vrecv,
                    *, axis, sched: RingSchedule, chained, counter,
                    contexts, decode_rank, pure=False):
    me = jax.lax.axis_index(axis)
    nc, cr = sched.nc, sched.kv_chunk
    rows_total = sched.rows                  # V half's base row (pure mode)

    def chunk_dma(buf, o_ref, ssem, rsem_slot, c, nchunks):
        return pltpu.make_async_remote_copy(
            src_ref=buf.at[pl.ds(c * cr, nchunks * cr)],
            dst_ref=o_ref.at[pl.ds(c * cr, nchunks * cr)],
            send_sem=ssem, recv_sem=rsem_slot,
            device_id=decode_rank, device_id_type=pltpu.DeviceIdType.MESH)

    # contexts-deep send window over the schedule's (step, chunk) rounds
    # (the shared schedule.SendWindow): a round's K/V pair counts as ONE
    # window entry — the K half opens the round, the V half (issued after
    # the V tile's GEMM) amends it — so the executed window depth matches
    # the schedule contract and the l3 model's window_stall_factor credit.
    window = SendWindow(contexts)

    def gemm_tile(buf, w_ref, c, nchunks, base=0):
        rows = nchunks * cr
        if pure:
            # pure shuttle: the operand already holds finished K/V rows
            # (prefill-computed cache blocks) — stage the tile verbatim;
            # the K half reads rows [0, rows_total), V [rows_total, 2*...)
            buf.at[pl.ds(c * cr, rows)][...] = \
                x_ref[pl.ds(base + c * cr, rows)].astype(buf.dtype)
            return
        buf.at[pl.ds(c * cr, rows)][...] = jax.lax.dot_general(
            x_ref[pl.ds(c * cr, rows)], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(buf.dtype)

    def _prefill():
        if sched.fused:
            # TILE_FUSED: tile c's send issues the moment its GEMM ends —
            # K tile then V tile, so each wire hides behind the next GEMM
            for c in range(nc):
                gemm_tile(kbuf, wk_ref, c, 1)
                window.push([chunk_dma(kbuf, ko_ref, ksend, krecv.at[c],
                                       c, 1)])
                gemm_tile(vbuf, wv_ref, c, 1, rows_total)
                window.amend(chunk_dma(vbuf, vo_ref, vsend, vrecv.at[c],
                                       c, 1))
            window.drain()
        else:
            # one whole-tensor round: K opens it, V amends it after its
            # GEMM (chained — K flies while V computes); the sequential
            # shape drains K's send before the V GEMM starts
            gemm_tile(kbuf, wk_ref, 0, nc)
            window.push([chunk_dma(kbuf, ko_ref, ksend, krecv.at[0],
                                   0, nc)])
            if not chained:
                window.drain()       # sequential: drain before the V GEMM
            gemm_tile(vbuf, wv_ref, 0, nc, rows_total)
            if chained:
                window.amend(chunk_dma(vbuf, vo_ref, vsend, vrecv.at[0],
                                       0, nc))
            else:
                window.push([chunk_dma(vbuf, vo_ref, vsend, vrecv.at[0],
                                       0, nc)])
            window.drain()

    def arrived(o_ref, rsem_slot, c, nchunks):
        """Wait chunks [c, c+nchunks) of ``o_ref`` through a copy
        descriptor of their size on the chunk's receive semaphore."""
        landed = o_ref.at[pl.ds(c * cr, nchunks * cr)]
        pltpu.make_async_copy(landed, landed, rsem_slot).wait()

    def _decode():
        if sched.fused and counter:
            # COUNTER: tick arrivals off one chunk at a time
            for c in range(nc):
                arrived(ko_ref, krecv.at[c], c, 1)
                arrived(vo_ref, vrecv.at[c], c, 1)
        elif sched.fused:
            for c in range(nc):      # SIGNAL: per-edge drain after the loop
                arrived(ko_ref, krecv.at[c], c, 1)
            for c in range(nc):
                arrived(vo_ref, vrecv.at[c], c, 1)
        else:
            arrived(ko_ref, krecv.at[0], 0, nc)
            arrived(vo_ref, vrecv.at[0], 0, nc)

    pl.when(me != decode_rank)(_prefill)
    pl.when(me == decode_rank)(_decode)


def kv_shuttle_sharded(x, wk, wv, *, axis, chained=True, fused=False,
                       counter=False, kv_chunk=None, contexts=2,
                       sched: RingSchedule = None, decode_rank=1,
                       interpret=None, pure=False):
    """Per-device fn (under shard_map over a 2-rank axis).
    x: (T, d); wk/wv: (d, dk). Returns (K, V) — valid on the decode rank.
    An explicit ``sched`` takes precedence over the knob arguments.

    ``pure`` is the cache-handoff mode (no projection GEMMs): x holds the
    already-computed ``[K; V]`` rows stacked as (2N, w), wk/wv are unused
    dummies, and the same signal-chained K→V schedule ships the halves —
    returns (K, V) each (N, w), valid on the decode rank."""
    T, d = x.shape
    if pure:
        assert T % 2 == 0, "pure shuttle wants stacked [K; V] rows"
        rows, dk = T // 2, d
    else:
        rows, dk = T, wk.shape[1]
    if sched is None:
        sched = make_ring_schedule(2, rows, kv_chunk or (64 if fused else rows),
                                   fused)
    assert sched.rows == rows, (sched, rows)
    kern = functools.partial(_shuttle_kernel, axis=axis, sched=sched,
                             chained=chained, counter=counter,
                             contexts=contexts, decode_rank=decode_rank,
                             pure=pure)
    return pl.pallas_call(
        kern,
        in_specs=[
            pl.BlockSpec((T, d), lambda: (0, 0)),
            pl.BlockSpec(wk.shape, lambda: (0, 0)),
            pl.BlockSpec(wv.shape, lambda: (0, 0)),
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_shape=[jax.ShapeDtypeStruct((rows, dk), x.dtype)] * 2,
        scratch_shapes=[
            pltpu.VMEM((rows, dk), x.dtype),
            pltpu.VMEM((rows, dk), x.dtype),
            pltpu.SemaphoreType.DMA,                 # k send
            pltpu.SemaphoreType.DMA((sched.nc,)),    # k per-chunk recv
            pltpu.SemaphoreType.DMA,                 # v send
            pltpu.SemaphoreType.DMA((sched.nc,)),    # v per-chunk recv
        ],
        interpret=default_interpret() if interpret is None else interpret,
        compiler_params=compiler_params(),
    )(x, wk, wv)


def kv_shuttle(x, wk, wv, mesh, *, axis="x", chained=True, fused=False,
               counter=False, kv_chunk=None, contexts=2):
    """Global entry. x: (2, T, d) sharded over the 2-rank axis (prefill rank
    holds real activations); wk/wv replicated. Returns K/V gathered per rank
    — row [1] (decode rank) holds the shuttled projections."""
    from jax.sharding import PartitionSpec as P

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(axis), P(None, None), P(None, None)),
                       out_specs=(P(axis), P(axis)), check_vma=False)
    def run(xs, k, v):
        ko, vo = kv_shuttle_sharded(xs[0], k, v, axis=axis, chained=chained,
                                    fused=fused, counter=counter,
                                    kv_chunk=kv_chunk, contexts=contexts)
        # the prefill rank never writes its own output buffers: zero them
        me = jax.lax.axis_index(axis)
        ko = jnp.where(me == 1, ko, 0.0)
        vo = jnp.where(me == 1, vo, 0.0)
        return ko[None], vo[None]

    return run(x, wk, wv)


def kv_cache_shuttle(kv, mesh, *, axis="x", chained=True, fused=False,
                     counter=False, kv_chunk=None, contexts=2):
    """Global cache-handoff entry (the disaggregated prefill→decode path
    ``serve/engine.py::prefill_remote`` rides). kv: (2, 2N, w) sharded over
    the 2-rank ``axis`` — the prefill rank's row holds the finished cache
    stacked ``[K; V]``, the decode rank's row is zeros. Returns (K, V) each
    (2, N, w); row [1] (the decode rank) holds the shuttled cache.

    Mosaic ships refs only in whole 128-lane rows, so a cache narrower
    than that (``w`` = head_dim = 64) travels viewed as 128-lane rows; the
    bytes, and where they land, are the same."""
    from jax.sharding import PartitionSpec as P
    two_n, w = kv.shape[1:]
    lanes = 128 if w % 128 and (two_n // 2 * w) % 128 == 0 else w

    @functools.partial(shard_map, mesh=mesh, in_specs=(P(axis),),
                       out_specs=(P(axis), P(axis)), check_vma=False)
    def run(kvs):
        dummy = jnp.zeros((1, 1), kvs.dtype)
        ko, vo = kv_shuttle_sharded(kvs[0].reshape(-1, lanes), dummy, dummy,
                                    axis=axis, chained=chained, fused=fused,
                                    counter=counter, kv_chunk=kv_chunk,
                                    contexts=contexts, pure=True)
        ko, vo = ko.reshape(-1, w), vo.reshape(-1, w)
        me = jax.lax.axis_index(axis)
        ko = jnp.where(me == 1, ko, 0.0)
        vo = jnp.where(me == 1, vo, 0.0)
        return ko[None], vo[None]

    return run(kv)
