"""Workload 3: KV-cache transfer for disaggregated prefill->decode serving
(paper Table 4 row 3, Appendix M).

Host baseline: the prefill rank computes K and V projections, then a single
host-sequenced transfer moves both — the network idles during compute and
compute idles during the transfer (the compute-to-send gap).

Device-initiated builds (repro.kernels.kv_shuttle, realized against the
shared ``core/schedule.py::RingSchedule`` — the n=2 degenerate ring): the
chained kernel — K GEMM -> start K send -> V GEMM (overlapping K's flight)
-> V send+signal — and the TILE_FUSED + COUNTER point (the FLUX point for
the shuttle): ``kv_chunk``-row K/V GEMM tiles whose sends issue the moment
each tile is ready, under a ``contexts``-deep send window, with the decode
rank ticking arrivals off one chunk at a time. The decode rank waits
entirely on-device either way. XLA STREAM_SPLIT build: two independent
ppermute chains let XLA overlap K's transfer with V's GEMM at graph level.

``kernel_knobs`` (the ``Workload`` protocol's search contract) is the
single directive→knob mapping both ``build()`` and ``analytic_cost()``
consult; the ``chained`` and ``kv_chunk`` tunables are refinable by the
slow path's diff patches (``TUNABLES`` grids).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.cost_model import (CostBreakdown, CostSegment,
                                   per_tile_exposed_s, window_stall_factor)
from repro.core.design_space import Directive
from repro.core.schedule import make_ring_schedule
from repro.kernels.kv_shuttle import kv_shuttle as shuttle_kernel
from repro.workloads.base import (KERNEL_LAUNCH, SIGNAL_OVERHEAD, TILE_SYNC,
                                  BARRIER_OVERHEAD, Workload, register)
from jax import shard_map


@register
class KVTransfer(Workload):
    name = "kv_transfer"
    ring_topology = False
    kernelizable = True

    def __init__(self, T=4096, d=4096, dk=512, axis="x", solo=False):
        # ``solo``: the degraded single-tier fallback — one rank lost, the
        # survivor runs prefill and decode colocated, so the K/V projections
        # stay local and the shuttle disappears (degrade, don't hang)
        self.solo = bool(solo)
        self.n_dev = 1 if solo else 2
        self.T = T
        self.d = d
        self.dk = dk
        self.axis = axis

    def example_inputs(self, key, mesh, T=None):
        T = T or min(self.T, 128)
        ks = jax.random.split(key, 3)
        x_real = jax.random.normal(ks[0], (T, self.d // 8), jnp.float32)
        x = x_real[None] if self.solo \
            else jnp.stack([x_real, jnp.zeros_like(x_real)])
        wk = jax.random.normal(ks[1], (self.d // 8, self.dk // 4), jnp.float32)
        wv = jax.random.normal(ks[2], (self.d // 8, self.dk // 4), jnp.float32)
        return x, wk, wv

    def reference(self, x, wk, wv):
        k = x[0] @ wk
        v = x[0] @ wv
        if self.solo:
            return k[None], v[None]
        z = jnp.zeros_like(k)
        return jnp.stack([z, k]), jnp.stack([jnp.zeros_like(v), v])

    # ------------------------------------------- fault contract (core/faults)
    def degrade(self, live_ranks):
        """Losing either tier collapses the disaggregation: the survivor
        serves prefill+decode colocated (the ``solo`` fallback — K/V stay
        local, the shuttle disappears). The recovery term of ``fault_cost``
        charges re-materializing the dead tier's cache over ICI."""
        from repro.core.schedule import check_live
        live = check_live(live_ranks, self.n_dev)
        if len(live) == self.n_dev:
            return self
        return type(self)(T=self.T, d=self.d, dk=self.dk, axis=self.axis,
                          solo=True)

    def state_bytes_per_rank(self):
        # prefill activations + the K/V cache of the handoff (f32)
        return 4 * (self.T * self.d + 2 * self.T * self.dk)

    # ------------------------------------------------------------- builders
    def host_baseline(self, mesh):
        if self.solo:
            return self._solo_local()
        axis = self.axis

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P(axis), P(None, None), P(None, None)),
                           out_specs=(P(axis), P(axis)), check_vma=False)
        def run(x, wk, wv):
            xs = x[0]
            me = jax.lax.axis_index(axis)
            k = xs @ wk
            v = xs @ wv
            kv = jnp.concatenate([k, v], axis=-1)     # one bundled transfer
            kv = jax.lax.ppermute(kv, axis, [(0, 1)])
            dk = k.shape[-1]
            k_out = jnp.where(me == 1, kv[:, :dk], 0.0)
            v_out = jnp.where(me == 1, kv[:, dk:], 0.0)
            return k_out[None], v_out[None]

        return run

    def _stream_split(self, mesh):
        axis = self.axis

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P(axis), P(None, None), P(None, None)),
                           out_specs=(P(axis), P(axis)), check_vma=False)
        def run(x, wk, wv):
            xs = x[0]
            me = jax.lax.axis_index(axis)
            k = xs @ wk
            k_sent = jax.lax.ppermute(k, axis, [(0, 1)])   # K flies while ...
            v = xs @ wv                                    # ... V computes
            v_sent = jax.lax.ppermute(v, axis, [(0, 1)])
            k_out = jnp.where(me == 1, k_sent, 0.0)
            v_out = jnp.where(me == 1, v_sent, 0.0)
            return k_out[None], v_out[None]

        return run

    # directive -> kernel-knob mapping shared by build() and analytic_cost()
    # (the Workload.kernel_knobs search contract, docs/kernels.md)
    def kernel_knobs(self, d: Directive):
        k = super().kernel_knobs(d)      # chained/kv_chunk (raw) + contexts
        fused = (d.placement == "TILE_FUSED" and d.completion != "BARRIER")
        # the K→V signal chain: placement decides the default (BARRIER
        # forces the conservative sequential shape, like every other
        # workload's BARRIER override), and the `chained` tunable lets a
        # diff patch flip it in place. None (the seeded default) means
        # "unset" — fast_path seeds directives with default_tunables, and
        # a stored None must not shadow the placement-derived default.
        ch = k["chained"]
        if ch is None:
            ch = (d.placement in ("STREAM_SPLIT", "TILE_PIPELINED",
                                  "TILE_FUSED")
                  and d.ordering != "ACQREL" and d.completion != "BARRIER")
        k.update(
            # per-tile fused K/V GEMM + send chain (the shuttle FLUX point)
            fused=fused,
            counter=(d.completion == "COUNTER" and fused),
            chained=bool(ch))
        return k

    def collective_schedule(self, d: Directive):
        # the degenerate 2-rank shuttle ring at the deployment tile count
        # — l0 (core/verify.py) statically checks it ahead of l1 build;
        # the solo tier moves nothing and verifies vacuously
        if d.backend == "XLA_COLLECTIVE" or self.n_dev < 2:
            return None
        k = self.kernel_knobs(d)
        return make_ring_schedule(2, self.T, k["kv_chunk"],
                                  fused=k["fused"])

    def _solo_local(self):
        # the single-tier fallback: both projections local, no collective
        def run(x, wk, wv):
            return (x[0] @ wk)[None], (x[0] @ wv)[None]

        return run

    def build(self, d: Directive, mesh):
        if self.solo:
            return self._solo_local()
        if d.backend == "XLA_COLLECTIVE":
            if d.placement == "STREAM_SPLIT":
                return self._stream_split(mesh)
            return self.host_baseline(mesh)
        k = self.kernel_knobs(d)

        def run(x, wk, wv):
            return shuttle_kernel(x, wk, wv, mesh, axis=self.axis,
                                  chained=k["chained"], fused=k["fused"],
                                  counter=k["counter"],
                                  kv_chunk=k["kv_chunk"],
                                  contexts=k["contexts"])

        return run

    def default_tunables(self):
        return {"chained": None, "kv_chunk": 64}

    # --------------------------------------------------------- l3 cost model
    def analytic_cost(self, d: Directive, hw) -> float:
        return self.cost_breakdown(d, hw).total

    def cost_breakdown(self, d: Directive, hw) -> CostBreakdown:
        Seg = CostSegment
        T, dd, dk = self.T, self.d, self.dk
        t_gemm = 2.0 * T * dd * dk / hw.chip.peak_bf16_flops
        t_send = T * dk * 2 / hw.chip.ici_link_bw
        if self.solo:
            # colocated fallback: both GEMMs, no wire (fault_cost adds the
            # dead tier's cache recovery on top)
            return CostBreakdown(segments=(
                Seg("kv_gemms", 2 * t_gemm, "compute"),
                Seg("launch", KERNEL_LAUNCH, "launch"),
            ), meta={"path": "solo"})
        sync = BARRIER_OVERHEAD if d.completion == "BARRIER" else SIGNAL_OVERHEAD
        if d.backend == "XLA_COLLECTIVE":
            if d.placement == "STREAM_SPLIT":
                # K send overlaps V GEMM; V send exposed
                return CostBreakdown(segments=(
                    Seg("k_gemm", t_gemm, "compute"),
                    Seg("k_send_overlap", max(t_send, t_gemm), "overlap",
                        meta={"wire_s": t_send, "compute_s": t_gemm}),
                    Seg("v_send", t_send, "wire"),
                    Seg("sync", sync, "sync"),
                    Seg("launch", 2 * KERNEL_LAUNCH, "launch"),
                ), meta={"path": "xla_stream_split"})
            # bundled: both GEMMs then one 2x transfer
            return CostBreakdown(segments=(
                Seg("kv_gemms", 2 * t_gemm, "compute"),
                Seg("kv_send", 2 * t_send, "wire"),
                Seg("sync", sync, "sync"),
                Seg("launch", 2 * KERNEL_LAUNCH, "launch"),
            ), meta={"path": "xla_host"})
        k = self.kernel_knobs(d)
        if k["fused"]:
            # shuttle FLUX credit: tile c's send hides behind tile c+1's
            # GEMM; only the startup tile and the final exposed tail (per
            # chunk, scaled by the window recycle stall) stay serial. The
            # schedule charges TILE_SYNC per issued round and per tick.
            sched = make_ring_schedule(2, T, k["kv_chunk"], fused=True)
            startup = 2 * t_gemm / sched.nc
            span = max(2 * t_gemm, startup + 2 * t_send)
            exposed = window_stall_factor(k["contexts"]) \
                * per_tile_exposed_s(2 * T * dk * 2, hw.chip.ici_link_bw,
                                     sched.nc)
            fixed = (sched.issued_rounds()
                     + sched.completion_ticks(k["counter"])) * TILE_SYNC
            return CostBreakdown(segments=(
                Seg("fused_span", span, "overlap",
                    meta={"compute_s": 2 * t_gemm,
                          "wire_s": startup + 2 * t_send}),
                Seg("window_stall", exposed, "stall",
                    meta={"contexts": k["contexts"]}),
                Seg("tile_sync", fixed, "sync",
                    meta={"issued_rounds": sched.issued_rounds(),
                          "ticks": sched.completion_ticks(k["counter"])}),
                Seg("launch", KERNEL_LAUNCH, "launch"),
            ), schedule=sched, knobs=k, meta={"path": "kernel_fused"})
        if k["chained"]:
            return CostBreakdown(segments=(
                Seg("k_gemm", t_gemm, "compute"),
                Seg("k_send_overlap", max(t_send, t_gemm), "overlap",
                    meta={"wire_s": t_send, "compute_s": t_gemm}),
                Seg("v_send", t_send, "wire"),
                Seg("sync", sync, "sync"),
                Seg("launch", KERNEL_LAUNCH, "launch"),
            ), knobs=k, meta={"path": "kernel_chained"})
        return CostBreakdown(segments=(
            Seg("kv_gemms", 2 * t_gemm, "compute"),
            Seg("kv_send", 2 * t_send, "wire"),
            Seg("sync", sync, "sync"),
            Seg("launch", KERNEL_LAUNCH, "launch"),
        ), knobs=k, meta={"path": "kernel_deferred"})
