"""Workload 4: GEMM + AllGather (paper Appendix M; the minimal post-compute
collective).

Host baseline: local GEMM, then an XLA all-gather of the full output —
sequential by data dependence.

Device-initiated builds: repro.kernels.gemm_allgather — the second fully
kernelized workload (after moe_dispatch). TILE_FUSED broadcasts each result
tile by remote DMA the moment its GEMM finishes (G=PER_TILE; with COUNTER
completion the receive side ticks arrivals off one tile at a time — the
FLUX point); DEFERRED ships one whole slab per peer after the full GEMM.
Both run the same trace-time ``BroadcastSchedule`` under a ``contexts``-deep
send window. The XLA STREAM_SPLIT build chunks the GEMM and all-gathers
chunk c while chunk c+1 computes.

``kernel_knobs`` (the ``Workload`` protocol's search contract) is the
single directive→knob mapping both ``build()`` and ``analytic_cost()``
consult (docs/kernels.md); the ``tile_m`` tunable is drawn from the central
``TUNABLES`` grid and sanitized to a divisor of the local slab at each
shape boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.cost_model import (CostBreakdown, CostSegment,
                                   per_tile_exposed_s, window_stall_factor)
from repro.core.design_space import Directive
from repro.kernels.gemm_allgather import (gemm_allgather as ga_kernel,
                                          make_broadcast_schedule,
                                          sanitize_tile_m)
from repro.workloads.base import (BARRIER_OVERHEAD, KERNEL_LAUNCH,
                                  SIGNAL_OVERHEAD, TILE_SYNC, Workload,
                                  register)
from jax import shard_map


@register
class GemmAllGather(Workload):
    name = "gemm_allgather"
    ring_topology = False
    kernelizable = True

    def __init__(self, n_dev=4, M=4096, K=4096, N=4096, axis="x"):
        self.n_dev = n_dev
        self.M = M
        self.K = K
        self.N = N
        self.axis = axis

    def example_inputs(self, key, mesh, M_l=None):
        M_l = M_l or 128
        K, N = min(self.K, 128), min(self.N, 128)
        ks = jax.random.split(key, 2)
        a = jax.random.normal(ks[0], (self.n_dev, M_l, K), jnp.float32)
        b = jax.random.normal(ks[1], (K, N), jnp.float32)
        return a, b

    def reference(self, a, b):
        from repro.kernels.ref import gemm_allgather_ref
        return gemm_allgather_ref(a, b)

    # ------------------------------------------- fault contract (core/faults)
    def degrade(self, live_ranks):
        """The global GEMM redistributes over the survivors: the local slab
        grows to ``ceil(M / n')`` rows (M rounds up to the new rank count —
        the broadcast schedule requires equal slabs)."""
        from repro.core.schedule import check_live
        live = check_live(live_ranks, self.n_dev)
        if len(live) == self.n_dev:
            return self
        n = len(live)
        M_l = -(-self.M // n)
        return type(self)(n_dev=n, M=M_l * n, K=self.K, N=self.N,
                          axis=self.axis)

    def state_bytes_per_rank(self):
        # resident A slab + result slab (f32); B is replicated — survivors
        # already hold it, so a dead rank's copy needs no recovery wire
        M_l = self.M // self.n_dev
        return 4 * M_l * (self.K + self.N)

    # ------------------------------------------------------------- builders
    def host_baseline(self, mesh):
        axis = self.axis

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P(axis), P(None, None)),
                           out_specs=P(axis), check_vma=False)
        def run(a, b):
            c = a[0] @ b
            return jax.lax.all_gather(c, axis, tiled=True)[None]

        return run

    def _stream_split(self, mesh, chunks):
        axis = self.axis

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P(axis), P(None, None)),
                           out_specs=P(axis), check_vma=False)
        def run(a, b):
            a = a[0]
            M_l = a.shape[0]
            cs = max(1, M_l // chunks)
            outs = []
            for c0 in range(0, M_l, cs):
                c = a[c0:c0 + cs] @ b            # chunk c+1's GEMM is
                outs.append(jax.lax.all_gather(c, axis, tiled=False))
            # (n, cs, N) chunks -> (n*M_l, N)
            full = jnp.concatenate(outs, axis=1)
            return full.reshape(-1, b.shape[1])[None]

        return run

    # directive -> kernel-knob mapping shared by build() and analytic_cost()
    # (the Workload.kernel_knobs search contract, docs/kernels.md)
    def kernel_knobs(self, d: Directive, M_l=None):
        k = super().kernel_knobs(d)      # tunables (raw) + contexts
        if M_l is None:
            M_l = self.M // self.n_dev   # the deployment slab (l3 model)
        k.update(
            # the TUNABLES grid need not divide a given local slab — the
            # kernel contract requires an exact divisor, so sanitize here
            # (a slow-path diff patch must never crash the evaluator)
            tile_m=sanitize_tile_m(k["tile_m"], M_l),
            # BARRIER forces the deferred whole-slab drain even under a
            # TILE_FUSED placement (mirrors moe_dispatch.kernel_knobs)
            fused=(d.placement in ("TILE_FUSED", "TILE_PIPELINED")
                   and d.completion != "BARRIER"),
            # COUNTER = per-tile arrival ticks (the FLUX point); SIGNAL
            # keeps per-tile issue but waits once per inbound edge
            counter=d.completion == "COUNTER")
        return k

    def collective_schedule(self, d: Directive):
        # the deployment-slab broadcast schedule the kernel iterates —
        # l0 (core/verify.py) statically checks it ahead of l1 build
        if d.backend == "XLA_COLLECTIVE":
            return None
        k = self.kernel_knobs(d)
        return make_broadcast_schedule(self.n_dev, self.M // self.n_dev,
                                       k["tile_m"], k["fused"])

    def build(self, d: Directive, mesh):
        if d.backend == "XLA_COLLECTIVE":
            if d.placement == "STREAM_SPLIT":
                return self._stream_split(mesh, int(d.tunable("chunks", 4)))
            return self.host_baseline(mesh)

        def run(a, b):
            k = self.kernel_knobs(d, a.shape[1])
            return ga_kernel(a, b, mesh, axis=self.axis, tile_m=k["tile_m"],
                             fused=k["fused"], counter=k["counter"],
                             contexts=k["contexts"])

        return run

    def default_tunables(self):
        return {"tile_m": 128, "chunks": 4}

    # --------------------------------------------------------- l3 cost model
    def analytic_cost(self, d: Directive, hw) -> float:
        return self.cost_breakdown(d, hw).total

    def cost_breakdown(self, d: Directive, hw) -> CostBreakdown:
        Seg = CostSegment
        n = self.n_dev
        M_l = self.M // n
        t_gemm = 2.0 * M_l * self.K * self.N / hw.chip.peak_bf16_flops
        wire = (n - 1) * M_l * self.N * 2            # my slab to n-1 peers
        t_wire = wire / hw.chip.ici_link_bw
        sync = BARRIER_OVERHEAD if d.completion == "BARRIER" else SIGNAL_OVERHEAD
        if d.backend == "XLA_COLLECTIVE":
            if d.placement == "STREAM_SPLIT":
                chunks = max(1, int(d.tunable("chunks", 4)))
                per = t_gemm / chunks
                pw = t_wire / chunks
                # chunk c's gather overlaps chunk c+1's GEMM
                return CostBreakdown(segments=(
                    Seg("gemm_chunk0", per, "compute"),
                    Seg("gather_overlap",
                        max((chunks - 1) * per, (chunks - 1) * pw), "overlap",
                        meta={"compute_s": (chunks - 1) * per,
                              "wire_s": (chunks - 1) * pw, "chunks": chunks}),
                    Seg("gather_tail", pw, "wire"),
                    Seg("sync", sync, "sync"),
                    Seg("launch", KERNEL_LAUNCH * 2, "launch"),
                ), meta={"path": "xla_stream_split"})
            return CostBreakdown(segments=(
                Seg("gemm", t_gemm, "compute"),
                Seg("all_gather", t_wire, "wire"),
                Seg("sync", sync, "sync"),
                Seg("launch", KERNEL_LAUNCH * 2, "launch"),
            ), meta={"path": "xla_deferred"})

        # kernelized (PALLAS_RDMA / HYBRID): one fused launch; the schedule
        # charges TILE_SYNC per issued broadcast round and per completion
        # tick — same accounting shape as the moe_dispatch kernel model.
        k = self.kernel_knobs(d, M_l)
        sched = make_broadcast_schedule(n, M_l, k["tile_m"], k["fused"])
        ticks = sched.completion_ticks(k["counter"])
        if d.completion == "BARRIER":
            sync = BARRIER_OVERHEAD
        elif k["counter"]:
            sync = 0.0        # readiness IS the per-tile ticks below
        else:
            sync = SIGNAL_OVERHEAD * max(1, n - 1)
        tail = (
            Seg("sync", sync, "sync"),
            Seg("launch", KERNEL_LAUNCH, "launch"),
            Seg("tile_sync", (sched.issued_rounds() + ticks) * TILE_SYNC,
                "sync", meta={"issued_rounds": sched.issued_rounds(),
                              "ticks": ticks}),
        )
        if k["fused"]:
            # FLUX credit: tile t's broadcast hides behind tile t+1's GEMM
            # — only the final tile's transfer stays exposed
            # (per_tile_exposed_s over the per-tile issue granularity),
            # scaled by the send-window recycle stall: a contexts-deep
            # window leaves ~1/contexts of a tile's wire unhidden while
            # the oldest send drains before the next round may issue.
            per_gemm = t_gemm / max(1, sched.nt)
            span = max(t_gemm, per_gemm + t_wire)
            window = window_stall_factor(k["contexts"])
            return CostBreakdown(segments=(
                Seg("fused_span", span, "overlap",
                    meta={"compute_s": t_gemm, "wire_s": per_gemm + t_wire}),
                Seg("window_stall", window * per_tile_exposed_s(
                    wire, hw.chip.ici_link_bw, sched.issued_rounds()),
                    "stall", meta={"contexts": k["contexts"]}),
            ) + tail, schedule=sched, knobs=k, meta={"path": "kernel_fused"})
        # DEFERRED slab path: comm strictly after compute; the window
        # pipelines the per-peer slabs on the wire but the serial
        # dependence on the full GEMM remains.
        return CostBreakdown(segments=(
            Seg("gemm", t_gemm, "compute"),
            Seg("slab_broadcast", t_wire, "wire"),
        ) + tail, schedule=sched, knobs=k, meta={"path": "kernel_deferred"})
