"""Fused device-initiated MoE dispatch/combine — the DeepEP analogue
(paper §4.3 / Table 3's `PALLAS_RDMA` region of C for the flagship workload).

One Pallas kernel per rank performs the whole MoE step: stage per-expert
token blocks, remote-DMA each block directly into the owning expert's
receive slab (``pltpu.make_async_remote_copy`` — the GIN/RDMA-put analogue),
run the expert FFN per source as its tokens land, and remote-DMA the results
straight back into each source's combine slab. No host round-trip between
phases: a single kernel launch replaces the quantize/dispatch/compute/combine
chain of host-driven builds.

**Tight wire sizes.** Routing here is static per step (``counts`` are trace
time Python ints, identical on every rank), so each edge ``r -> e`` carries
exactly ``counts[e]`` tokens — not the padded max-capacity ``C`` block an
XLA all-to-all would ship. Transfers are quantized into ``block_tokens``-row
microblocks; expert ``e``'s edges need ``b[e] = ceil(counts[e]/B)`` blocks.
The analytic (l3) model credits the exact token counts; the executed
schedule ships the block-rounded ones (see :func:`executed_wire_tokens`).

**Permutation-round schedule.** The trace-time schedule runs rounds
``(off, j)``: in round ``(off, j)`` rank ``r`` sends microblock ``j`` of
its block for expert ``e = (r - off) % n`` — a shift permutation. ``off =
0`` is the self edge (local expert's tokens loop back without touching the
wire — the self/remote split of the STREAM_SPLIT build, here inside the
kernel). In the padded schedule, ranks whose edge has fewer than ``j+1``
real blocks ship a dummy block into the receiver's trash row to keep every
round a full permutation; the compiled kernel elides those slots. Dummy
blocks are accounted separately and never exceed the padded baseline's
wire.

**Completion (design-space K):** ``SIGNAL`` waits per-edge DMA receive
semaphores — expert compute for the earliest-arriving peer starts while
later peers are still in flight (``TILE_PIPELINED``); ``BARRIER`` drains
every edge before any compute (DeepEP-NVL's conservative point); ``COUNTER``
(the FLUX point, ``tile_fused``) consumes dispatch arrivals one microblock
at a time and treats each landed/produced tile as a counter tick.
``contexts`` bounds the in-flight send window (double buffering): on the
per-source paths one window over all rounds, on the tile-fused path one
window per destination (keyed by the shift offset, matching the one send
semaphore per peer), so sends to different peers fly at once.

**Tile-fused combine (FLUX / CoCoNet point):** with ``tile_fused`` the
expert FFN runs as a tiled GEMM loop over ``combine_tile``-row tiles and
the combine remote-DMA for each output tile is issued the moment that tile
is ready — instead of finishing the whole per-source FFN before any
combine round. This path issues its rounds microblock-major: dispatch
``(j, off)`` and the FFN/combine loop ``(j, off, t)``
(:attr:`DispatchSchedule.block_major_rounds`), so consecutive window
entries go to different peers. The order is identical on every rank; in
the padded schedule every combine DMA is issued (dummy tiles go to the
trash row).

**Dummy elision:** with ``elide_dummy`` (default whenever the kernel is
Mosaic-compiled) dummy-slot DMAs are predicated away with ``pl.when`` and
receive waits count only the real blocks — the executed wire drops to
:meth:`DispatchSchedule.issued_rounds` real rounds per direction.

**Arrivals** are waited through a copy descriptor of the landed size
(one microblock, or one combine block) on the per-source receive
semaphore, so each tick is one landed block.

Combine is the exact reverse schedule: rank ``e`` returns ``counts[e]``
processed tokens to every source, shipped bf16/f32 (DeepSeek-V3 quantizes
dispatch only; combine stays high precision).
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
from repro.core.schedule import (DispatchSchedule, SendWindow,  # noqa: F401
                                 block_counts, make_schedule,
                                 sanitize_combine_tile, send_window_depths)
from repro.core.trace import phase

# The kernel's device phases (repro.core.trace.phase), outermost first:
# the whole call; the operands staged into VMEM; the dispatch rounds issued
# and their send window drained; the expert FFN and the combine rounds,
# which hold one ``arrival_wait`` per landed dispatch microblock and one
# ``ffn`` per GEMM tile (per source off the tile-fused path); the combine
# arrivals; the assembly of the outputs; the result staged out. The serving
# layout adds ``shared_ffn`` inside ``dispatch``.
PHASES = ("moe_dispatch", "stage_in", "dispatch", "ffn_combine",
          "arrival_wait", "ffn", "combine_wait", "assemble", "stage_out")


# ------------------------------------------------------------------- kernel


def quant_i8(x):
    """int8 wire quantization with per-row scales (shared with the XLA
    builder in workloads/moe_dispatch.py — keep one copy of the formula)."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-12
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def swiglu_ffn(x, w1, w2):
    """The expert FFN: GEMM1 (2f, gate+up) -> SwiGLU -> GEMM2."""
    g, u = jnp.split(x @ w1, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w2


def _moe_kernel(*refs, **kw):
    """The kernel body inside its ``moe_dispatch`` region (see PHASES)."""
    with phase("moe_dispatch"):
        _moe_body(*refs, **kw)


def _moe_body(*refs, axis, sched: DispatchSchedule, offsets, pipelined,
              barrier, contexts, wire_i8, tile_fused=False,
              combine_tile=None, elide_dummy=False, shared=False,
              probe=None):
    if shared:
        # two-stream serving layout: the shared-expert operands (xs, s1,
        # s2) and output ys ride along, and the shared FFN is issued
        # against the open dispatch send window (see run_rounds)
        (x_ref, w1_ref, w2_ref, xs_ref, s1_ref, s2_ref, y_ref, ys_ref,
         xbuf, w1buf, w2buf, ybuf, xsbuf, s1buf, s2buf, ysbuf,
         send_q, send_s, recv_q, recv_s, ffn_out, comb,
         dsend, drecv, qsend, qrecv, csend, crecv) = refs
    else:
        (x_ref, w1_ref, w2_ref, y_ref,
         xbuf, w1buf, w2buf, ybuf,
         send_q, send_s, recv_q, recv_s, ffn_out, comb,
         dsend, drecv, qsend, qrecv, csend, crecv) = refs
        xsbuf = s1buf = s2buf = ysbuf = ys_ref = None
    n, B = sched.n, sched.block_tokens
    b_max, blocks, counts = sched.b_max, sched.blocks, sched.counts
    stride = b_max * B                       # slab rows per edge region
    trash = n * stride                       # trash row block for dummies
    d_model = x_ref.shape[1]
    me = jax.lax.axis_index(axis)

    # Operands and results live in ANY (HBM): Mosaic computes on VMEM
    # only, so every operand is DMA-staged in and every result staged out.
    with phase("stage_in"):
        pltpu.sync_copy(x_ref, xbuf)
        pltpu.sync_copy(w1_ref, w1buf)
        pltpu.sync_copy(w2_ref, w2buf)
        if shared:
            pltpu.sync_copy(xs_ref, xsbuf)
            pltpu.sync_copy(s1_ref, s1buf)
            pltpu.sync_copy(s2_ref, s2buf)

    def _lookup(table, idx):
        # static-table lookup by traced index without capturing a constant
        # array (a Pallas kernel cannot close over non-scalar constants)
        out = jnp.int32(table[0])
        for k in range(1, n):
            out = jnp.where(idx == k, jnp.int32(table[k]), out)
        return out

    # ---- stage: per-expert token blocks into B-quantized send regions.
    # Static ref slices (offsets/counts are trace-time ints). Rows past
    # counts[e] in a region ship unwritten: the expert masks every row
    # past its token count, and assembly reads only counts[e] rows back.
    for e in range(n):
        if counts[e] == 0:
            continue
        blk = xbuf[pl.ds(offsets[e], counts[e])]
        rows = pl.ds(e * stride, counts[e])
        if wire_i8:
            q, s = quant_i8(blk.astype(jnp.float32))
            send_q[rows] = q
            send_s[rows] = s
        else:
            send_q[rows] = blk.astype(send_q.dtype)

    # ---- round helpers -------------------------------------------------
    # Receive semaphores are (sender, microblock) slots: the sender's
    # descriptor names slot [me, j] on the receiver, so each landed block
    # ticks its own slot whatever order the DMA engines complete in.
    def _dma(src_slab, dst_slab, ssems, rsems, src_off, dst_off, peer, j,
             rows):
        return pltpu.make_async_remote_copy(
            src_ref=src_slab.at[pl.ds(src_off, rows)],
            dst_ref=dst_slab.at[pl.ds(dst_off, rows)],
            send_sem=ssems.at[peer], recv_sem=rsems.at[me, j],
            device_id=peer, device_id_type=pltpu.DeviceIdType.MESH)

    # With elide_dummy (the compiled kernel) dummy rounds are predicated
    # away entirely: start and wait_send both sit under the same pl.when
    # so the send semaphore stays balanced.
    def _start(real, cps):
        def go():
            for cp in cps:
                cp.start()
        pl.when(real)(go) if elide_dummy else go()

    def _wait_sent(real, cps):
        def go():
            for cp in cps:
                cp.wait_send()
        pl.when(real)(go) if elide_dummy else go()

    def dispatch_round(off, j):
        """Shift permutation r -> (r - off) % n, microblock j (dispatch)."""
        e = jax.lax.rem(me - off + n, n)               # my receiver
        src = jax.lax.rem(me + off, n)                 # my sender
        real = j < _lookup(blocks, e)
        src_off = jnp.where(real, e * stride + j * B, 0)
        dst_off = jnp.where(real, me * stride + j * B, trash)
        cps = [_dma(send_q, recv_q, dsend, drecv, src_off, dst_off, e, j, B)]
        if wire_i8:
            cps.append(_dma(send_s, recv_s, qsend, qrecv,
                            src_off, dst_off, e, j, B))
        return real, cps

    def combine_round(off, j, t=0, rows=None):
        """Reverse shift r -> (r + off) % n: expert returns tokens. The
        tile-fused path calls this per ``rows``-row sub-tile ``t``."""
        rows = B if rows is None else rows
        q = jax.lax.rem(me + off, n)                   # my receiver (source)
        real = j < _lookup(blocks, me)                 # I own expert `me`
        rel = j * B + t * rows
        src_off = jnp.where(real, q * stride + rel, 0)
        dst_off = jnp.where(real, me * stride + rel, trash)
        cp = _dma(ffn_out, comb, csend, crecv, src_off, dst_off, q, j, rows)
        return real, [cp]

    # The send windows' rounds and key (schedule.send_phases): on the
    # tile-fused path block-major, one window per offset; on the per-source
    # paths offset-major, one window.
    (dispatch_order, _), window_key = sched.send_phases(tile_fused)

    def make_window():
        """The shared contexts-deep send window (schedule.SendWindow) with
        the elide_dummy hooks: a round's start and wait_send both sit under
        the same pl.when(real) so the send semaphore stays balanced. An
        attached probe records each round's issue and retirement."""
        def start(entry):
            rnd, key, real, cps = entry
            if probe is not None:
                probe.issue(*rnd, key=key)
            _start(real, cps)

        def wait(entry):
            _rnd, key, real, cps = entry
            if probe is not None:
                probe.wait_send(key)
            _wait_sent(real, cps)

        return SendWindow(contexts, start=start, wait=wait)

    def push(window, rnd, real_cps):
        key = None if window_key is None else window_key(rnd)
        window.push((rnd, key, *real_cps), key=key)

    def run_rounds(round_fn, order, between=None, tag=None):
        """Issue ``order``'s rounds with a bounded in-flight send window.
        ``between`` runs after the last round is pushed but *before* the
        window drains — compute issued against in-flight sends (the
        two-stream overlap slot). ``tag`` stamps probe marks around it."""
        window = make_window()
        for off, j in order:
            push(window, (off, j), round_fn(off, j))
        if probe is not None and tag:
            probe.mark(f"{tag}_issued")
        if between is not None:
            between()
        window.drain()
        if probe is not None and tag:
            probe.mark(f"{tag}_drained")

    def shared_compute():
        """The second stream: the replicated shared-expert FFN over the
        local tokens, issued while dispatch DMAs are still in flight (the
        TokenWeave overlap — communication hidden behind compute the
        serving step has to do anyway)."""
        with phase("shared_ffn", probe):
            ys = swiglu_ffn(xsbuf[...].astype(jnp.float32),
                            s1buf[...], s2buf[...])
            ysbuf[...] = ys.astype(ysbuf.dtype)
            pltpu.sync_copy(ysbuf, ys_ref)

    def wait_block(rsems, slab, src, j):
        """Tick microblock ``j`` from ``src``: a copy descriptor of the
        block's size waits its receive slot."""
        blk = slab.at[pl.ds(src * stride + j * B, B)]
        pltpu.make_async_copy(blk, blk, rsems.at[src, j]).wait()

    def wait_dispatch(src, j):
        with phase("arrival_wait"):
            wait_block(drecv, recv_q, src, j)
            if wire_i8:
                wait_block(qrecv, recv_s, src, j)

    def wait_blocks(wait, src, lo, hi):
        """Tick microblocks ``lo <= j < hi`` from ``src``; traced bounds
        predicate one block-sized wait per schedule slot."""
        for j in range(b_max):
            live = (j >= lo) & (j < hi)
            if isinstance(live, bool):
                if live:
                    wait(src, j)
            else:
                pl.when(live)(functools.partial(wait, src, j))

    def ffn_tile(src, rel, rows):
        """Expert FFN over ``rows`` landed tokens at region-relative offset
        ``rel`` of source region ``src`` (one GEMM tile of the fused loop;
        the per-source paths call it once with the whole region)."""
        with phase("ffn"):
            row0 = src * stride + rel
            blk = recv_q[pl.ds(row0, rows)]
            if wire_i8:
                blk = blk.astype(jnp.float32) * recv_s[pl.ds(row0, rows)]
            h = swiglu_ffn(blk.astype(jnp.float32), w1buf[...], w2buf[...])
            valid = (rel + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
                     < _lookup(counts, me))
            ffn_out[pl.ds(row0, rows)] = jnp.where(
                valid, h, 0.0).astype(ffn_out.dtype)

    # real blocks on every inbound dispatch edge = my expert's block count
    my_blocks = _lookup(blocks, me)

    # ---- dispatch ------------------------------------------------------
    # (with `shared`, the shared-expert stream runs against the open
    # dispatch send window — before the drain, after the last issue)
    with phase("dispatch"):
        run_rounds(dispatch_round, dispatch_order,
                   between=shared_compute if shared else None, tag="dispatch")

    # ---- expert FFN and combine ----------------------------------------
    with phase("ffn_combine"):
        if tile_fused:
            # TILE_FUSED + COUNTER (the FLUX point): the expert FFN runs
            # as a tiled GEMM loop and each output tile's combine DMA is
            # issued the moment the tile is ready. Dispatch arrivals are
            # consumed one microblock at a time (counter ticks on the edge
            # semaphore), so the first tile computes while later peers are
            # still in flight — and its combine write goes out before the
            # next tile's GEMM. Block-major: block j of every source in
            # turn, so each tile returns to a different peer than the last.
            ct = combine_tile          # sanitized by the sharded entry
            window = make_window()
            for off, j in dispatch_order:
                src = jax.lax.rem(me + off, n)             # source region
                real = j < my_blocks

                # dummy rounds are never sent under elide_dummy, so the
                # arrival wait is predicated away like every other elided op
                arrive = functools.partial(wait_dispatch, src, j)
                pl.when(real)(arrive) if elide_dummy else arrive()
                for t in range(B // ct):
                    # with elide_dummy, dummy tiles skip the GEMM too —
                    # their combine DMA is elided, so nothing reads them
                    def tile(rel=j * B + t * ct):
                        ffn_tile(src, rel, ct)
                    pl.when(real)(tile) if elide_dummy else tile()
                    push(window, (off, j, t), combine_round(off, j, t, ct))
            window.drain()
        elif barrier or not pipelined:
            # BARRIER / DEFERRED: global rendezvous — drain every edge
            # fully (real + dummy blocks) before any expert compute starts.
            for s_idx in range(n):
                src = jax.lax.rem(me + s_idx, n)
                wait_blocks(wait_dispatch, src, 0,
                            my_blocks if elide_dummy else b_max)
            for s_idx in range(n):
                ffn_tile(jax.lax.rem(me + s_idx, n), 0, stride)
        else:
            # SIGNAL + TILE_PIPELINED: consume peers in arrival order —
            # the self edge (s_idx 0) computes first, hiding later dispatch
            # edges behind expert compute; each edge waits only its own
            # semaphore, and its FFN runs immediately, before later edges
            # are fenced.
            for s_idx in range(n):
                src = jax.lax.rem(me + s_idx, n)
                wait_blocks(wait_dispatch, src, 0, my_blocks)
                ffn_tile(src, 0, stride)
            if not elide_dummy:
                # drain the dummy-block residue so every semaphore balances
                for s_idx in range(n):
                    src = jax.lax.rem(me + s_idx, n)
                    wait_blocks(wait_dispatch, src, my_blocks, b_max)

        # ---- combine (reverse path, full precision) --------------------
        if not tile_fused:
            run_rounds(combine_round, sched.rounds)

    # (a combine block lands as B/combine_tile sub-tile DMAs on the tile-
    # fused path; a block-sized wait ticks once all of them landed)
    with phase("combine_wait"):
        for s_idx in range(n):
            src = jax.lax.rem(me + s_idx, n)
            wait_blocks(functools.partial(wait_block, crecv, comb), src, 0,
                        _lookup(blocks, src) if elide_dummy else b_max)

    # ---- assemble: region e holds my tokens processed by expert e ------
    with phase("assemble"):
        for e in range(n):
            if counts[e] == 0:
                continue
            ybuf[pl.ds(offsets[e], counts[e])] = \
                comb[pl.ds(e * stride, counts[e])].astype(ybuf.dtype)
    with phase("stage_out"):
        pltpu.sync_copy(ybuf, y_ref)


def moe_dispatch_combine_sharded(x, w1, w2, *, axis, sched: DispatchSchedule,
                                 pipelined=True, barrier=False, contexts=2,
                                 wire_i8=False, tile_fused=False,
                                 combine_tile=None, elide_dummy=None,
                                 interpret=None, shared=None, probe=None):
    """Per-device fn (under shard_map). x: (T, d) local tokens sorted into
    contiguous per-expert blocks by ``sched.counts``; w1: (d, 2f); w2:
    (f, d) — this rank's expert. Returns (T, d) combined outputs.

    ``shared=(xs, s1, s2)`` enables the two-stream serving path: xs (Ts, d)
    local tokens, s1 (d, 2fs) / s2 (fs, d) the replicated shared-expert
    weights. The shared FFN is issued inside the kernel against the open
    dispatch send window and the call returns ``(y, ys)``. ``probe`` (a
    :class:`~repro.core.trace.ScheduleProbe`) records interleave marks.
    ``interpret=None`` picks :func:`repro.compat.default_interpret`."""
    T, d = x.shape
    n, B, b_max = sched.n, sched.block_tokens, sched.b_max
    assert sum(sched.counts) == T, (sched.counts, T)
    assert not (tile_fused and barrier), \
        "tile_fused (COUNTER completion) excludes a BARRIER rendezvous"
    offsets = [0] * n
    for e in range(1, n):
        offsets[e] = offsets[e - 1] + sched.counts[e - 1]
    stride = b_max * B
    slab = n * stride + B                             # + trash block
    wire_dt = jnp.int8 if wire_i8 else x.dtype
    ip = default_interpret() if interpret is None else interpret
    if elide_dummy is None:
        # the compiled kernel skips the padded schedule's dummy rounds
        elide_dummy = not ip
    kern = functools.partial(
        _moe_kernel, axis=axis, sched=sched, offsets=offsets,
        pipelined=pipelined, barrier=barrier, contexts=contexts,
        wire_i8=wire_i8, tile_fused=tile_fused,
        combine_tile=sanitize_combine_tile(combine_tile, B),
        elide_dummy=elide_dummy, shared=shared is not None, probe=probe)
    inputs = (x, w1, w2)
    out_shape = jax.ShapeDtypeStruct((T, d), x.dtype)
    out_specs = pl.BlockSpec(memory_space=pl.ANY)
    stage_scratch = [
        pltpu.VMEM((T, d), x.dtype),                    # staged x operand
        pltpu.VMEM(w1.shape, w1.dtype),                 # staged w1 operand
        pltpu.VMEM(w2.shape, w2.dtype),                 # staged w2 operand
        pltpu.VMEM((T, d), x.dtype),                    # staged y result
    ]
    if shared is not None:
        xs, s1, s2 = shared
        inputs = (x, w1, w2, xs, s1, s2)
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct(xs.shape, x.dtype))
        out_specs = (out_specs, pl.BlockSpec(memory_space=pl.ANY))
        stage_scratch += [
            pltpu.VMEM(xs.shape, xs.dtype),             # staged shared x
            pltpu.VMEM(s1.shape, s1.dtype),             # staged shared w1
            pltpu.VMEM(s2.shape, s2.dtype),             # staged shared w2
            pltpu.VMEM(xs.shape, x.dtype),              # staged ys result
        ]
    return pl.pallas_call(
        kern,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(inputs),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=stage_scratch + [
            pltpu.VMEM((n * stride, d), wire_dt),       # send slab
            pltpu.VMEM((n * stride, 1), jnp.float32),   # send scales
            pltpu.VMEM((slab, d), wire_dt),             # recv slab (+trash)
            pltpu.VMEM((slab, 1), jnp.float32),         # recv scales
            pltpu.VMEM((n * stride, d), jnp.float32),   # expert FFN out
            pltpu.VMEM((slab, d), jnp.float32),         # combine slab
            pltpu.SemaphoreType.DMA((n,)),              # dispatch send
            pltpu.SemaphoreType.DMA((n, b_max)),        # dispatch recv
            pltpu.SemaphoreType.DMA((n,)),              # scale send
            pltpu.SemaphoreType.DMA((n, b_max)),        # scale recv
            pltpu.SemaphoreType.DMA((n,)),              # combine send
            pltpu.SemaphoreType.DMA((n, b_max)),        # combine recv
        ],
        interpret=ip,
        compiler_params=compiler_params(),
    )(*inputs)


def moe_dispatch_combine(x, w1, w2, mesh, *, axis="x", counts,
                         block_tokens=64, tight=True, pipelined=True,
                         barrier=False, contexts=2, wire_i8=False,
                         tile_fused=False, combine_tile=None,
                         elide_dummy=None, shared=None, probe=None,
                         interpret=None):
    """Global entry. x: (n, T, d) token-sharded over ``axis`` (each rank's
    rows sorted into contiguous per-expert blocks, identical static
    ``counts`` on every rank); w1: (n, d, 2f), w2: (n, f, d) — expert e's
    weights on rank e. Returns (n, T, d): each rank's tokens after
    dispatch -> expert FFN -> combine.

    ``shared=(xs, s1, s2)`` — xs (n, Ts, d) token-sharded, s1 (d, 2fs) /
    s2 (fs, d) replicated shared-expert weights — returns ``(y, ys)``
    with ys (n, Ts, d) the shared-expert stream computed inside the
    kernel against the dispatch send window (the TokenWeave two-stream
    serving point). ``interpret`` as in :func:`moe_dispatch_combine_sharded`."""
    from jax.sharding import PartitionSpec as P
    sched = make_schedule(counts, block_tokens, tight)

    if shared is None:
        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P(axis), P(axis), P(axis)),
                           out_specs=P(axis), check_vma=False)
        def run(xs_, w1s, w2s):
            out = moe_dispatch_combine_sharded(
                xs_[0], w1s[0], w2s[0], axis=axis, sched=sched,
                pipelined=pipelined, barrier=barrier, contexts=contexts,
                wire_i8=wire_i8, tile_fused=tile_fused,
                combine_tile=combine_tile, elide_dummy=elide_dummy,
                probe=probe, interpret=interpret)
            return out[None]

        return run(x, w1, w2)

    xs, s1, s2 = shared

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(), P()),
        out_specs=(P(axis), P(axis)), check_vma=False)
    def run2(xs_, w1s, w2s, xss, s1r, s2r):
        y, ys = moe_dispatch_combine_sharded(
            xs_[0], w1s[0], w2s[0], axis=axis, sched=sched,
            pipelined=pipelined, barrier=barrier, contexts=contexts,
            wire_i8=wire_i8, tile_fused=tile_fused,
            combine_tile=combine_tile, elide_dummy=elide_dummy,
            shared=(xss[0], s1r, s2r), probe=probe, interpret=interpret)
        return y[None], ys[None]

    return run2(x, w1, w2, xs, s1, s2)
