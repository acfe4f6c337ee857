"""Workload 2: DeepSeek-V3 MoE dispatch/combine under skewed routing
(paper §4.3, Table 5, Figure 8).

Pipeline: (quantize) -> dispatch all-to-all -> expert GEMM1+SwiGLU+GEMM2 ->
combine all-to-all. Each rank owns one expert; routing is skewed (2:1..5:1)
so ranks are imbalanced.

Host baseline (the paper's "standard sequential flow"): padded equal-size
all-to-all on a single dependence chain — quantize, dispatch, compute,
combine, strictly sequential.

CUCo-discovered build (STREAM_SPLIT): the **self/remote split** — tokens
routed to the local expert never touch the network; their GEMM is issued with
no data dependence on the dispatch all-to-all, so dispatch hides behind
self-compute (paper Fig. 8: 3.04 ms local-chunk work covers ~1 ms dispatch).
int8 wire quantization is the paper's FP8-quantize phase, adapted.

Variable-size per-peer transfers (G=PER_PEER, `tight`): XLA's static-shape
collectives cannot express them on CPU (`ragged-all-to-all` is unimplemented
by the CPU thunk emitter) — the XLA-backend l2 path uses the padded
equivalent, while the l3 cost model credits the exact-size wire volume; on
real TPU the same builder switches to ``jax.lax.ragged_all_to_all``. This
mirrors the paper's own observation that host-level compilers cannot express
what the expert libraries do.

PALLAS_RDMA / HYBRID backends route to the fused device-initiated kernel
(repro.kernels.moe_dispatch — the DeepEP analogue): per-expert token blocks
remote-DMA'd directly into peer receive slabs at **tight per-peer sizes**
(`counts[e]` tokens per edge, not the padded max-capacity `C`), per-edge
SIGNAL completion semaphores, `contexts`-deep send windows, and the expert
GEMM for the earliest-arriving peer starting while later peers are in
flight (TILE_PIPELINED). A single kernel launch covers the whole
quantize/dispatch/compute/combine chain.

TILE_FUSED + COUNTER (the FLUX / CoCoNet point, Table 3) runs the expert
FFN as a tiled GEMM loop inside the same kernel: dispatch arrivals are
consumed one microblock at a time and each `combine_tile`-row output tile's
combine remote-DMA is issued the moment the tile is ready — per-tile
counter ticks instead of per-edge signals. Both kernelized points share
the `block_tokens`/`contexts`/`combine_tile` knobs the slow path refines;
``kernel_knobs`` (the ``Workload`` protocol's search contract) is the
single directive→knob mapping both build() and analytic_cost() consult.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.design_space import Directive
from repro.workloads.base import (BARRIER_OVERHEAD, KERNEL_LAUNCH,
                                  SIGNAL_OVERHEAD, TILE_SYNC, Workload,
                                  register)
from jax import shard_map
from repro.core.cost_model import (CostBreakdown, CostSegment,
                                   per_tile_exposed_s, window_stall_factor)
from repro.kernels.moe_dispatch import make_schedule, quant_i8, swiglu_ffn


@register
class MoEDispatch(Workload):
    name = "moe_dispatch"
    ring_topology = False
    kernelizable = True           # repro.kernels.moe_dispatch (DeepEP-style)

    def __init__(self, n_dev=4, tokens_per_rank=4096, d=512, f=1024,
                 skew=3.0, axis="x", route_weights=None):
        self.n_dev = n_dev
        self.T = tokens_per_rank
        self.d = d
        self.f = f
        self.skew = skew
        self.axis = axis
        # explicit routing shares override the skew law — the degraded
        # (post-respill) instances carry their re-routed distribution here
        self.route_weights = None if route_weights is None \
            else tuple(float(v) for v in route_weights)

    # deterministic skewed routing: expert e's share ~ skew^(-e); identical
    # on every rank; tokens sorted into contiguous per-expert blocks.
    def _counts(self, T):
        if self.route_weights is not None:
            w = np.array(self.route_weights, dtype=float)
        else:
            w = np.array([self.skew ** (-e) for e in range(self.n_dev)])
        w = w / w.sum()
        counts = np.floor(w * T).astype(int)
        counts[0] += T - counts.sum()
        return counts

    # ------------------------------------------- fault contract (core/faults)
    def degrade(self, live_ranks, capacity_factor=1.25):
        """Dead experts' tokens respill across the survivors (the
        ``respill_counts`` capacity-factor rule applied to the deployment
        routing); the respilled counts become the degraded instance's
        routing shares so every ``T`` re-derives proportionally."""
        from repro.core.schedule import check_live, respill_counts
        live = check_live(live_ranks, self.n_dev)
        if len(live) == self.n_dev:
            return self
        new_counts = respill_counts(self._counts(self.T), live,
                                    capacity_factor)
        return type(self)(n_dev=len(live), tokens_per_rank=self.T, d=self.d,
                          f=self.f, skew=self.skew, axis=self.axis,
                          route_weights=new_counts)

    def state_bytes_per_rank(self):
        # resident activations + the rank's expert weights (f32)
        return 4 * (self.T * self.d
                    + self.d * 2 * self.f + self.f * self.d)

    def _assignment(self, T):
        return jnp.asarray(np.repeat(np.arange(self.n_dev), self._counts(T)),
                           jnp.int32)

    def example_inputs(self, key, mesh, T=None):
        T = T or min(self.T, 256)
        ks = jax.random.split(key, 3)
        x = jax.random.normal(ks[0], (self.n_dev, T, self.d), jnp.float32)
        w1 = jax.random.normal(ks[1], (self.n_dev, self.d, 2 * self.f),
                               jnp.float32) / math.sqrt(self.d)
        w2 = jax.random.normal(ks[2], (self.n_dev, self.f, self.d),
                               jnp.float32) / math.sqrt(self.f)
        return x, w1, w2

    def _ffn(self, x, w1, w2):
        return swiglu_ffn(x, w1, w2)

    def reference(self, x, w1, w2):
        n, T, d = x.shape
        assign = self._assignment(T)
        outs = []
        for r in range(n):
            o = jnp.zeros_like(x[r])
            for e in range(n):
                mask = (assign == e)[:, None]
                o = o + jnp.where(mask, self._ffn(x[r], w1[e], w2[e]), 0)
            outs.append(o)
        return jnp.stack(outs)

    # ------------------------------------------------------------- builders
    def _make(self, mesh, *, overlap, wire_i8):
        axis, n = self.axis, self.n_dev

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P(axis), P(axis), P(axis)),
                           out_specs=P(axis), check_vma=False)
        def run(x, w1, w2):
            x, w1, w2 = x[0], w1[0], w2[0]
            T, d = x.shape
            me = jax.lax.axis_index(axis)
            counts = self._counts(T)
            offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
            C = int(counts.max())
            cnt_arr = jnp.asarray(counts, jnp.int32)
            off_arr = jnp.asarray(offsets, jnp.int32)

            send = jnp.stack([
                jnp.pad(jax.lax.dynamic_slice_in_dim(
                    x, int(offsets[e]), int(counts[e])),
                    ((0, C - int(counts[e])), (0, 0)))
                for e in range(n)])                      # (n, C, d)

            def wire(t):
                if wire_i8:
                    q, s = quant_i8(t)
                    return (jax.lax.all_to_all(q, axis, 0, 0, tiled=True)
                            .astype(jnp.float32)
                            * jax.lax.all_to_all(s, axis, 0, 0, tiled=True))
                return jax.lax.all_to_all(t, axis, 0, 0, tiled=True)

            if overlap:
                # self/remote split: self-chunk FFN has no a2a dependence
                xp = jnp.pad(x, ((0, C), (0, 0)))
                self_blk = jax.lax.dynamic_slice(xp, (off_arr[me], 0), (C, d))
                h_self = self._ffn(self_blk, w1, w2)      # overlaps dispatch
                got = wire(send)                          # (n, C, d)
                got = jnp.where((jnp.arange(n) != me)[:, None, None], got, 0.0)
            else:
                got = wire(send)                          # sequential chain

            h = self._ffn(got.reshape(n * C, d), w1, w2).reshape(n, C, d)
            back = jax.lax.all_to_all(h, axis, 0, 0, tiled=True)  # combine

            y = jnp.zeros_like(x)
            for e in range(n):                            # unpack padded blocks
                blk = back[e, :int(counts[e])]
                y = jax.lax.dynamic_update_slice_in_dim(
                    y, blk, int(offsets[e]), axis=0)
            if overlap:                                   # merge self chunk
                yp = jnp.pad(y, ((0, C), (0, 0)))
                cur = jax.lax.dynamic_slice(yp, (off_arr[me], 0), (C, d))
                valid = (jnp.arange(C) < cnt_arr[me])[:, None]
                yp = jax.lax.dynamic_update_slice(
                    yp, jnp.where(valid, h_self, cur), (off_arr[me], 0))
                y = yp[:T]
            return y[None]

        return run

    def host_baseline(self, mesh):
        return self._make(mesh, overlap=False, wire_i8=False)

    # directive -> kernel-knob mapping shared by build() and analytic_cost()
    # (the Workload.kernel_knobs search contract, docs/kernels.md)
    def kernel_knobs(self, d: Directive):
        k = super().kernel_knobs(d)      # tunables (raw) + contexts
        B = max(1, int(k["block_tokens"]))
        k.update(
            block_tokens=B,
            # PER_TILE (the FLUX coordinate) quantizes to microblocks too —
            # both per-peer and per-tile edges carry exact token counts
            tight=(d.granularity in ("PER_PEER", "PER_TILE")
                   and bool(k["tight"])),
            # BARRIER forces the global-rendezvous shape even under a
            # TILE_FUSED placement; COUNTER/SIGNAL fuse the combine loop
            tile_fused=(d.placement == "TILE_FUSED"
                        and d.completion != "BARRIER"),
            # combine_tile stays raw (default: one tile per microblock) —
            # the sharded kernel entry and the schedule's combine_ticks
            # each sanitize at their own boundary
            combine_tile=d.tunable("combine_tile", B),
            pipelined=d.placement in ("TILE_FUSED", "TILE_PIPELINED",
                                      "STREAM_SPLIT"),
            barrier=d.completion == "BARRIER")
        return k

    def collective_schedule(self, d: Directive):
        # the exact schedule _make_kernel hands the Pallas kernel at the
        # deployment token count — l0 (core/verify.py) lowers and checks
        # it before any build is attempted
        if d.backend not in ("PALLAS_RDMA", "HYBRID"):
            return None
        k = self.kernel_knobs(d)
        return make_schedule(self._counts(self.T), k["block_tokens"],
                             k["tight"])

    def _make_kernel(self, mesh, d: Directive):
        from repro.kernels.moe_dispatch import moe_dispatch_combine
        k = self.kernel_knobs(d)

        def run(x, w1, w2):
            return moe_dispatch_combine(
                x, w1, w2, mesh, axis=self.axis,
                counts=self._counts(x.shape[1]),
                block_tokens=k["block_tokens"], tight=k["tight"],
                pipelined=k["pipelined"], barrier=k["barrier"],
                tile_fused=k["tile_fused"], combine_tile=k["combine_tile"],
                contexts=k["contexts"], wire_i8=bool(k["wire_i8"]))

        return run

    def build(self, d: Directive, mesh):
        if d.backend in ("PALLAS_RDMA", "HYBRID"):
            return self._make_kernel(mesh, d)
        return self._make(mesh, overlap=(d.placement == "STREAM_SPLIT"),
                          wire_i8=bool(d.tunable("wire_i8", 0)))

    def default_tunables(self):
        return {"tight": 1, "wire_i8": 0, "block_tokens": 64,
                "combine_tile": 64}

    # --------------------------------------------------------- l3 cost model
    def analytic_cost(self, d: Directive, hw) -> float:
        return self.cost_breakdown(d, hw).total

    def cost_breakdown(self, d: Directive, hw) -> CostBreakdown:
        Seg = CostSegment
        n, T, dm, f = self.n_dev, self.T, self.d, self.f
        counts = self._counts(T)
        C = int(counts.max())
        kernel = d.backend in ("PALLAS_RDMA", "HYBRID")
        k = self.kernel_knobs(d) if kernel else None
        tight = k["tight"] if kernel \
            else bool(d.granularity == "PER_PEER" and d.tunable("tight", 1))
        wire_i8 = bool(d.tunable("wire_i8", 0))
        bytes_per = 1 if wire_i8 else 2
        # the busiest expert rank (rank 0 under skew) bounds the step
        recv_tokens = int(counts[0]) * n if tight else C * n
        self_tokens = int(counts[0])
        flops = 3 * 2 * recv_tokens * dm * f          # GEMM1 (2f) + GEMM2
        t_comp = flops / hw.chip.peak_bf16_flops
        t_self = t_comp * self_tokens / max(1, recv_tokens)
        t_remote = t_comp - t_self
        # tight wire: exactly the off-rank tokens (counts.sum() - counts[0]);
        # padded wire: the max-capacity block to every peer (C * (n - 1))
        sent = (counts.sum() - counts[0]) if tight else C * (n - 1)
        t_disp = sent * dm * bytes_per / hw.chip.ici_link_bw
        t_comb = sent * dm * 2 / hw.chip.ici_link_bw  # combine in bf16
        t_quant = (2 * T * dm * 2 / hw.chip.hbm_bw) if wire_i8 else 0.0

        if kernel:
            # fused device-initiated kernel: one launch for the whole
            # quantize/dispatch/compute/combine chain; per-edge signal
            # semaphores instead of a global barrier; per-round DMA
            # issue/check overhead for the permutation schedule. The l3
            # target is real TPU hardware, where the compiled kernel elides
            # the padded schedule's dummy rounds — charge the tighter executed
            # schedule, never the padded one.
            B = k["block_tokens"]
            sched = make_schedule(counts, B, k["tight"])
            disp_rounds = sched.issued_rounds(elide_dummy=True)
            # combine rounds are rank-dependent: the busiest expert (rank
            # 0) returns blocks[0] microblocks to every source
            ticks = sched.combine_ticks(k["combine_tile"], rank=0,
                                        elide_dummy=True) \
                if k["tile_fused"] \
                else sched.combine_issued_rounds(0, elide_dummy=True)
            if k["tile_fused"]:
                sync = 0.0       # readiness IS the per-tile ticks below
                # (SIGNAL and COUNTER build the identical fused kernel)
            elif d.completion == "BARRIER":
                sync = BARRIER_OVERHEAD
            else:
                sync = SIGNAL_OVERHEAD * max(1, n - 1)
            tail = (
                Seg("quant", t_quant, "quant"),
                Seg("sync", sync, "sync"),
                Seg("launch", KERNEL_LAUNCH, "launch"),
                Seg("tile_sync", (disp_rounds + ticks) * TILE_SYNC, "sync",
                    meta={"issued_rounds": disp_rounds, "ticks": ticks}),
            )
            if k["tile_fused"]:
                # FLUX credit: expert compute starts once the first
                # microblock lands, and the combine write of tile t hides
                # behind the GEMM of tile t+1 — only the final tile's
                # transfer stays exposed (per_tile_exposed_s), scaled by
                # the send-window recycle stall: a contexts-deep window
                # leaves ~1/contexts of a tile's wire unhidden while the
                # oldest send drains before the next tile may issue.
                startup = t_disp / max(1, disp_rounds)
                span = max(t_disp, startup + t_comp)
                window = window_stall_factor(k["contexts"])
                return CostBreakdown(segments=(
                    Seg("fused_span", span, "overlap",
                        meta={"wire_s": t_disp,
                              "compute_s": startup + t_comp}),
                    Seg("window_stall", window * per_tile_exposed_s(
                        sent * dm * 2, hw.chip.ici_link_bw, ticks), "stall",
                        meta={"contexts": k["contexts"]}),
                ) + tail, schedule=sched, knobs=k,
                    meta={"path": "kernel_tile_fused"})
            pipelined = (d.placement in ("TILE_PIPELINED", "STREAM_SPLIT")
                         and d.completion != "BARRIER" and d.contexts >= 2)
            if pipelined:
                # self-edge compute hides dispatch; per-peer compute hides
                # later arrivals; combine of peer p hides behind compute of
                # p+1 — only the last peer's chunks stay exposed.
                peers = max(1, n - 1)
                span = max(t_disp, t_self + t_remote * (peers - 1) / peers)
                return CostBreakdown(segments=(
                    Seg("pipeline_span", span, "overlap",
                        meta={"wire_s": t_disp,
                              "compute_s": t_self
                              + t_remote * (peers - 1) / peers}),
                    Seg("last_peer_compute", t_remote / peers, "compute"),
                    Seg("last_peer_combine", t_comb / peers, "wire"),
                ) + tail, schedule=sched, knobs=k,
                    meta={"path": "kernel_pipelined"})
            return CostBreakdown(segments=(
                Seg("dispatch", t_disp, "wire"),
                Seg("expert_ffn", t_comp, "compute"),
                Seg("combine", t_comb, "wire"),
            ) + tail, schedule=sched, knobs=k, meta={"path": "kernel_plain"})

        sync = BARRIER_OVERHEAD if d.completion == "BARRIER" else SIGNAL_OVERHEAD
        launches = KERNEL_LAUNCH * 4                  # quant/disp/comp/comb
        if d.placement == "STREAM_SPLIT":
            stage1 = max(t_disp + t_quant, t_self)    # dispatch hidden
            return CostBreakdown(segments=(
                Seg("dispatch_overlap", stage1, "overlap",
                    meta={"wire_s": t_disp + t_quant, "compute_s": t_self}),
                Seg("remote_ffn", t_remote, "compute"),
                Seg("combine", t_comb, "wire"),
                Seg("sync", sync, "sync"),
                Seg("launch", launches, "launch"),
            ), meta={"path": "xla_stream_split"})
        return CostBreakdown(segments=(
            Seg("quant", t_quant, "quant"),
            Seg("dispatch", t_disp, "wire"),
            Seg("expert_ffn", t_comp, "compute"),
            Seg("combine", t_comb, "wire"),
            Seg("sync", sync, "sync"),
            Seg("launch", launches, "launch"),
        ), meta={"path": "xla_host"})
