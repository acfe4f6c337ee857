"""Fused ring flash-attention with device-initiated KV rotation
(the paper's Flash Attention + Context Parallelism workload, §4.2/App. N,
adapted to TPU Pallas remote DMA) — realized against the shared
collective-schedule contract (``repro.core.schedule.RingSchedule``).

Each device owns one Q shard; KV shards rotate around the ring INSIDE the
kernel via ``pltpu.make_async_remote_copy`` (the GIN-put analogue). The
kernel is a full trace-time unroll of the schedule's ``(step, chunk)``
rounds — in rotation step ``s`` every rank ships the KV shard it currently
holds one hop forward (rank ``r`` → ``(r+1) % n``, a shift permutation),
split into ``kv_chunk``-row chunks staged in chunk-major VMEM double
buffers. Arrivals are waited through a copy descriptor of the landed
chunk's size on that chunk's receive semaphore.

Placement realizations (design-space P), all driven by the one schedule:

  TILE_FUSED (+COUNTER = the FLUX point for rings) — chunk-major rounds:
    chunk ``c``'s onward send issues the moment its arrival tick clears,
    and the attention contribution of chunk ``c`` computes while chunk
    ``c+1``'s rotation is still in flight. Per-chunk receive semaphores
    tick arrivals off one chunk at a time; a ``contexts``-deep send window
    bounds the in-flight chunk sends (replacing the old kernel's
    eager/lazy-fence special cases). SIGNAL completion keeps the chunked
    sends but drains all of a step's arrivals up front.
  TILE_PIPELINED — one whole-shard round per step, issued at the top of
    the round and fenced only after the round's compute (lazy fence:
    transfer overlaps compute).
  DEFERRED — the whole-shard round is awaited immediately (sequential
    comm/compute, the host-driven shape inside one kernel). ACQREL
    ordering forces the same eager fence on the pipelined path.

Slot-reuse backpressure: step ``s``'s send writes the neighbour slot its
step ``s-1`` compute read — the sender waits the downstream free-slot
credit before issuing (a remote ``semaphore_signal`` ACK after the
consumer drains).

Every DMA is issued unconditionally in the schedule's total order; no
``pl.when`` wraps any ``dma.start()``.

Lane packing: Mosaic slices and ships refs only in whole 128-lane rows, so
heads narrower than 128 (``hd`` = 64 in the workload) travel and compute
packed ``128 // hd`` to a row. Head ``j`` of a row owns lanes
``[j*hd, (j+1)*hd)``; its scores contract a copy of Q with the other
heads' lanes zeroed, and its P·V keeps only its own lanes. The wire
carries exactly the heads' bytes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax import shard_map

from repro.compat import compiler_params, default_interpret
from repro.core.schedule import (RingSchedule, SendWindow,  # noqa: F401
                                 make_ring_schedule, sanitize_kv_chunk)

NEG_INF = -1e30
LANES = 128


def heads_per_row(BH, hd):
    """How many heads share one 128-lane row (1 when ``hd`` fills whole
    rows, or when ``BH`` heads cannot be grouped evenly)."""
    g = LANES // hd if hd < LANES and LANES % hd == 0 else 1
    return g if BH % g == 0 else 1


def _pack(x, g):
    """(BH, S, hd) -> (BH/g, S, g*hd): heads 0..g-1 of a group side by
    side in the lanes."""
    BH, S, hd = x.shape
    return x.reshape(BH // g, g, S, hd).swapaxes(1, 2).reshape(
        BH // g, S, g * hd)


def _unpack(x, g):
    G, S, L = x.shape
    return x.reshape(G, S, g, L // g).swapaxes(1, 2).reshape(G * g, S, L // g)


def _ring_kernel(q_ref, k_ref, v_ref, o_ref, kbuf, vbuf,
                 ksend, krecv, vsend, vrecv, credit,
                 *, axis, sched: RingSchedule, causal, scale, counter,
                 pipelined, eager_wait, contexts, hd):
    n, nc, cr = sched.n, sched.nc, sched.kv_chunk
    fused = sched.fused
    G, Sl, L = q_ref.shape                   # head groups, rows, lanes
    g = L // hd                              # heads packed per row
    me = jax.lax.axis_index(axis)
    nxt = jax.lax.rem(me + 1, n)
    prv = jax.lax.rem(me - 1 + n, n)

    # local KV shard -> double-buffer slot 0 (k_ref/v_ref arrive chunk-major
    # (nc, G, cr, L) from the sharded entry; kbuf rows [slot*nc + c])
    for c in range(nc):
        pltpu.sync_copy(k_ref.at[c], kbuf.at[c])
        pltpu.sync_copy(v_ref.at[c], vbuf.at[c])

    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, 1, L), 2) // hd
    own = [lane_head == j for j in range(g)]           # head j's lanes
    q = q_ref[...].astype(jnp.float32)                 # (G, Sl, L)
    qs = [q] if g == 1 else [jnp.where(own[j], q, 0.0) for j in range(g)]
    acc = jnp.zeros((G, Sl, L), jnp.float32)
    m_i = [jnp.full((G, Sl), NEG_INF, jnp.float32)] * g
    l_i = [jnp.zeros((G, Sl), jnp.float32)] * g

    def on_lanes(per_head):
        """Spread per-head (G, Sl) values over their heads' lanes."""
        if g == 1:
            return per_head[0][:, :, None]
        return sum(jnp.where(own[j], per_head[j][:, :, None], 0.0)
                   for j in range(g))

    def chunk_dma(buf, ssem, rsem_slot, src_chunk, dst_chunk, nchunks):
        """Ship kbuf/vbuf chunks [src_chunk, src_chunk+nchunks) one hop
        forward into the neighbour's matching slot — a shift permutation."""
        return pltpu.make_async_remote_copy(
            src_ref=buf.at[pl.ds(src_chunk, nchunks)],
            dst_ref=buf.at[pl.ds(dst_chunk, nchunks)],
            send_sem=ssem, recv_sem=rsem_slot,
            device_id=nxt, device_id_type=pltpu.DeviceIdType.MESH)

    # contexts-deep send window over the trace-time round order (the shared
    # schedule.SendWindow — a round's K/V pair counts as ONE entry): every
    # DMA is issued unconditionally, the window only bounds
    # how many rounds' send semaphores stay unawaited. Drained at each step
    # boundary (the slot-credit handshake needs the step's sends retired).
    window = SendWindow(contexts)

    # Receive semaphores are (landing slot, chunk) pairs: once this rank
    # ACKs a slot free, upstream's next send into the *other* slot may be
    # in flight before this rank ticks the current one, and a shared
    # semaphore could not tell the two arrivals apart.
    def issue(slot, c, nchunks):
        kd = chunk_dma(kbuf, ksend, krecv.at[1 - slot, c], slot * nc + c,
                       (1 - slot) * nc + c, nchunks)
        vd = chunk_dma(vbuf, vsend, vrecv.at[1 - slot, c], slot * nc + c,
                       (1 - slot) * nc + c, nchunks)
        window.push([kd, vd])

    def tick(slot, c, nchunks):
        """Receive-side readiness: chunks [c, c+nchunks) landed in
        ``slot`` (COUNTER consumes these one chunk at a time)."""
        for buf, rsem in ((kbuf, krecv), (vbuf, vrecv)):
            landed = buf.at[pl.ds(slot * nc + c, nchunks)]
            pltpu.make_async_copy(landed, landed, rsem.at[slot, c]).wait()

    def attend(s, c, acc, m_i, l_i):
        """Flash-accumulate the attention contribution of chunk ``c`` of
        the shard held at step ``s`` (originating rank (me - s) % n)."""
        slot = s % 2
        k_c = kbuf[slot * nc + c].astype(jnp.float32)  # (G, cr, L)
        v_c = vbuf[slot * nc + c].astype(jnp.float32)
        if causal:
            src_dev = jax.lax.rem(me - s + n, n)
            shape = (G, Sl, cr)
            qpos = me * Sl + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            kpos = src_dev * Sl + c * cr + jax.lax.broadcasted_iota(
                jnp.int32, shape, 2)
            visible = qpos >= kpos
        alphas, pvs, m_new, l_new = [], [], [], []
        for j in range(g):
            s_mat = jax.lax.dot_general(
                qs[j], k_c, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale  # (G, Sl, cr)
            if causal:
                s_mat = jnp.where(visible, s_mat, NEG_INF)
            m_j = jnp.maximum(m_i[j], jnp.max(s_mat, axis=2))
            alpha = jnp.exp(m_i[j] - m_j)
            p = jnp.exp(s_mat - m_j[:, :, None])
            l_new.append(l_i[j] * alpha + jnp.sum(p, axis=2))
            m_new.append(m_j)
            alphas.append(alpha)
            pv = jax.lax.dot_general(
                p, v_c, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)       # (G, Sl, L)
            pvs.append(pv if g == 1 else jnp.where(own[j], pv, 0.0))
        acc = acc * on_lanes(alphas) + sum(pvs)
        return acc, m_new, l_new

    for s in range(n):                       # n compute rounds, n-1 rotations
        slot = s % 2
        rotate = s <= n - 2                  # step s ships slot s%2 onward
        if rotate and s >= 1:
            # step s's send overwrites the neighbour slot its step s-1
            # compute read: wait the downstream free-slot credit first
            pltpu.semaphore_wait(credit, 1)
        if fused:
            if not counter and s >= 1:
                # SIGNAL: drain the whole step's arrivals up front
                for c in range(nc):
                    tick(slot, c, 1)
            for c in range(nc):
                if counter and s >= 1:
                    tick(slot, c, 1)         # consume chunk c's arrival ...
                if rotate:
                    issue(slot, c, 1)        # ... ship it onward (windowed)
                acc, m_i, l_i = attend(s, c, acc, m_i, l_i)
            window.drain()
        else:
            if rotate:
                issue(slot, 0, nc)           # one whole-shard round
                if eager_wait or not pipelined:
                    window.drain()           # DEFERRED/ACQREL: fully fenced
                    tick(1 - slot, 0, nc)
            for c in range(nc):
                acc, m_i, l_i = attend(s, c, acc, m_i, l_i)
            if rotate and pipelined and not eager_wait:
                window.drain()           # lazy fence: after the compute
                tick(1 - slot, 0, nc)
        if s <= n - 3:
            # slot s%2 fully consumed (compute done, outgoing sends
            # retired): upstream's next-next send may reuse it
            pltpu.semaphore_signal(credit, 1, device_id=prv,
                                   device_id_type=pltpu.DeviceIdType.MESH)

    o_ref[...] = (acc / jnp.maximum(on_lanes(l_i), 1e-30)
                  ).astype(o_ref.dtype)


def ring_attention_sharded(q, k, v, *, axis, n_dev, causal=True,
                           sched: RingSchedule = None, kv_chunk=None,
                           fused=False, counter=False, pipelined=True,
                           eager_wait=False, contexts=2, interpret=None):
    """Per-device fn (call under shard_map). q/k/v: (BH, Sl, hd) local.
    An explicit ``sched`` takes precedence: the ``kv_chunk``/``fused``
    knobs are consulted only to build one when ``sched`` is None."""
    BH, Sl, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    if sched is None:
        sched = make_ring_schedule(n_dev, Sl, kv_chunk or Sl, fused)
    assert sched.n == n_dev and sched.rows == Sl, (sched, n_dev, Sl)
    nc, cr = sched.nc, sched.kv_chunk
    g = heads_per_row(BH, hd)
    G, L = BH // g, g * hd
    q, k, v = (_pack(x, g) for x in (q, k, v))
    # chunk-major staging: the kernel's KV buffers (and rotation DMAs)
    # address whole chunks through a single leading index
    kc = k.reshape(G, nc, cr, L).swapaxes(0, 1)
    vc = v.reshape(G, nc, cr, L).swapaxes(0, 1)
    kern = functools.partial(_ring_kernel, axis=axis, sched=sched,
                             causal=causal, scale=scale, counter=counter,
                             pipelined=pipelined, eager_wait=eager_wait,
                             contexts=contexts, hd=hd)
    out = pl.pallas_call(
        kern,
        in_specs=[
            pl.BlockSpec((G, Sl, L), lambda: (0, 0, 0)),    # q in VMEM
            pl.BlockSpec(memory_space=pl.ANY),              # k chunks (HBM)
            pl.BlockSpec(memory_space=pl.ANY),              # v chunks (HBM)
        ],
        out_specs=pl.BlockSpec((G, Sl, L), lambda: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, Sl, L), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((2 * nc, G, cr, L), q.dtype),    # K double buffer
            pltpu.VMEM((2 * nc, G, cr, L), q.dtype),    # V double buffer
            pltpu.SemaphoreType.DMA,                    # k send
            pltpu.SemaphoreType.DMA((2, nc)),           # k (slot, chunk) recv
            pltpu.SemaphoreType.DMA,                    # v send
            pltpu.SemaphoreType.DMA((2, nc)),           # v (slot, chunk) recv
            pltpu.SemaphoreType.REGULAR,                # free-slot credit
        ],
        interpret=default_interpret() if interpret is None else interpret,
        compiler_params=compiler_params(),
    )(q, kc, vc)
    return _unpack(out, g)


def ring_attention(q, k, v, mesh, *, axis="x", causal=True, kv_chunk=None,
                   fused=False, counter=False, pipelined=True,
                   eager_wait=False, contexts=2):
    """Global entry: q/k/v (n_dev, BH, Sl, hd) sharded on dim 0 over `axis`.
    ``fused``+``counter`` selects the chunk-rotating FLUX-ring path
    (``kv_chunk`` rows per rotation round, sanitized to a divisor of Sl)."""
    from jax.sharding import PartitionSpec as P
    n_dev = mesh.shape[axis]
    sched = make_ring_schedule(n_dev, q.shape[2],
                               kv_chunk or (q.shape[2] if not fused else 64),
                               fused)

    @functools.partial(shard_map, mesh=mesh, in_specs=P(axis),
                       out_specs=P(axis), check_vma=False)
    def run(qs, ks, vs):
        out = ring_attention_sharded(qs[0], ks[0], vs[0], axis=axis,
                                     n_dev=n_dev, causal=causal, sched=sched,
                                     counter=counter, pipelined=pipelined,
                                     eager_wait=eager_wait,
                                     contexts=contexts)
        return out[None]

    return run(q, k, v)
