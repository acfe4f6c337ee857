"""Workload 1: Flash Attention with Context Parallelism (ring attention).

Host-driven baseline: one attention round per held KV shard, with an XLA
``ppermute`` between rounds — each round's compute depends on the permute
result, forcing strictly sequential execution (the paper's Figure 7 host
timeline: exchange / compute / exchange / …).

Device-initiated builds rotate KV *inside* a Pallas kernel via remote DMA
(repro.kernels.ring_attention), realized against the shared
``core/schedule.py::RingSchedule``: DEFERRED rotates whole shards and
fences eagerly, TILE_PIPELINED overlaps the rotation with the round's
compute (lazy fence), and TILE_FUSED + COUNTER (the FLUX point for rings)
rotates ``kv_chunk``-row chunks under a ``contexts``-deep send window with
per-chunk arrival ticks — chunk c's attention computes while chunk c+1 is
still in flight. An XLA STREAM_SPLIT build double-buffers the permute at
graph level so XLA's async collective scheduler can overlap it with the
round's compute.

``kernel_knobs`` (the ``Workload`` protocol's search contract) is the
single directive→knob mapping both ``build()`` and ``analytic_cost()``
consult; ``kv_chunk`` is drawn from the central ``TUNABLES`` grid and
sanitized to a divisor of the local KV shard at each shape boundary.

Full deployment shape (paper §4.2): 4 devices, SEQ in {4096, 8192},
HD in {32, 64}, GPT-2-ish multi-head layout.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.cost_model import (CostBreakdown, CostSegment,
                                   per_tile_exposed_s, window_stall_factor)
from repro.core.design_space import Directive
from repro.core.schedule import make_ring_schedule
from repro.kernels.ref import ring_attention_ref
from repro.kernels.ring_attention import ring_attention as ring_kernel
from repro.workloads.base import (BARRIER_OVERHEAD, KERNEL_LAUNCH,
                                  SIGNAL_OVERHEAD, TILE_SYNC, Workload,
                                  register)
from jax import shard_map


@register
class RingAttention(Workload):
    name = "ring_attention"
    ring_topology = True
    kernelizable = True

    def __init__(self, n_dev=4, BH=8, seq=4096, hd=64, axis="x"):
        self.n_dev = n_dev
        self.BH = BH
        self.seq = seq
        self.hd = hd
        self.sl = seq // n_dev
        self.axis = axis

    def example_inputs(self, key, mesh, sl=None):
        sl = sl or min(self.sl, 128)
        ks = jax.random.split(key, 3)
        shape = (self.n_dev, self.BH, sl, self.hd)
        return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)

    def reference(self, q, k, v):
        return ring_attention_ref(q, k, v, causal=True)

    # ------------------------------------------- fault contract (core/faults)
    def degrade(self, live_ranks):
        """The global sequence re-shards over the survivors: the local KV
        shard grows to ``ceil(seq / n')`` rows (seq rounds up to the new
        rank count — the rotation requires equal shards)."""
        from repro.core.schedule import check_live
        live = check_live(live_ranks, self.n_dev)
        if len(live) == self.n_dev:
            return self
        n = len(live)
        sl = -(-self.seq // n)
        return type(self)(n_dev=n, BH=self.BH, seq=sl * n, hd=self.hd,
                          axis=self.axis)

    def state_bytes_per_rank(self):
        # resident Q/K/V shards (f32)
        return 4 * 3 * self.BH * self.sl * self.hd

    # ------------------------------------------------------------- builders
    def host_baseline(self, mesh):
        """Sequential rounds with an XLA collective-permute between them."""
        axis, n = self.axis, self.n_dev

        @functools.partial(shard_map, mesh=mesh, in_specs=P(axis),
                           out_specs=P(axis), check_vma=False)
        def run(q, k, v):
            q, k, v = q[0], k[0], v[0]
            me = jax.lax.axis_index(axis)
            sl = q.shape[1]
            perm = [(i, (i + 1) % n) for i in range(n)]
            qpos = me * sl + jnp.arange(sl)

            def round_fn(carry, r):
                k_c, v_c, m, l, acc = carry
                src = (me - r) % n
                kpos = src * sl + jnp.arange(sl)
                s = jnp.einsum("bqd,bkd->bqk", q, k_c) / math.sqrt(self.hd)
                s = jnp.where(qpos[:, None] >= kpos[None, :], s, -1e30)
                m_new = jnp.maximum(m, jnp.max(s, -1))
                p = jnp.exp(s - m_new[..., None])
                alpha = jnp.exp(m - m_new)
                l = l * alpha + jnp.sum(p, -1)
                acc = acc * alpha[..., None] + jnp.einsum("bqk,bkd->bqd", p, v_c)
                # host-driven: next round's KV arrives only after this
                # round's compute (data dependence = sequential)
                k_n = jax.lax.ppermute(k_c, axis, perm)
                v_n = jax.lax.ppermute(v_c, axis, perm)
                return (k_n, v_n, m_new, l, acc), None

            m0 = jnp.full(q.shape[:2], -1e30)
            l0 = jnp.zeros(q.shape[:2])
            a0 = jnp.zeros_like(q)
            (k_f, v_f, m, l, acc), _ = jax.lax.scan(
                round_fn, (k, v, m0, l0, a0), jnp.arange(n))
            return (acc / jnp.maximum(l, 1e-30)[..., None])[None].astype(q.dtype)

        return run

    def _stream_split(self, mesh):
        """Overlap at graph level: the permute for round r+1 is issued before
        round r's compute and carries no dependence on it."""
        axis, n = self.axis, self.n_dev

        @functools.partial(shard_map, mesh=mesh, in_specs=P(axis),
                           out_specs=P(axis), check_vma=False)
        def run(q, k, v):
            q, k, v = q[0], k[0], v[0]
            me = jax.lax.axis_index(axis)
            sl = q.shape[1]
            perm = [(i, (i + 1) % n) for i in range(n)]
            qpos = me * sl + jnp.arange(sl)

            def round_fn(carry, r):
                k_c, v_c, m, l, acc = carry
                # issue the rotation FIRST: independent of this round's math
                k_n = jax.lax.ppermute(k_c, axis, perm)
                v_n = jax.lax.ppermute(v_c, axis, perm)
                src = (me - r) % n
                kpos = src * sl + jnp.arange(sl)
                s = jnp.einsum("bqd,bkd->bqk", q, k_c) / math.sqrt(self.hd)
                s = jnp.where(qpos[:, None] >= kpos[None, :], s, -1e30)
                m_new = jnp.maximum(m, jnp.max(s, -1))
                p = jnp.exp(s - m_new[..., None])
                alpha = jnp.exp(m - m_new)
                l = l * alpha + jnp.sum(p, -1)
                acc = acc * alpha[..., None] + jnp.einsum("bqk,bkd->bqd", p, v_c)
                return (k_n, v_n, m_new, l, acc), None

            m0 = jnp.full(q.shape[:2], -1e30)
            l0 = jnp.zeros(q.shape[:2])
            a0 = jnp.zeros_like(q)
            (k_f, v_f, m, l, acc), _ = jax.lax.scan(
                round_fn, (k, v, m0, l0, a0), jnp.arange(n))
            return (acc / jnp.maximum(l, 1e-30)[..., None])[None].astype(q.dtype)

        return run

    # directive -> kernel-knob mapping shared by build() and analytic_cost()
    # (the Workload.kernel_knobs search contract, docs/kernels.md)
    def kernel_knobs(self, d: Directive):
        k = super().kernel_knobs(d)      # kv_chunk (raw) + contexts
        fused = (d.placement == "TILE_FUSED" and d.completion != "BARRIER")
        k.update(
            # chunk-major rotation rounds (the FLUX-ring path); BARRIER
            # forces the whole-shard eager drain even under TILE_FUSED
            fused=fused,
            # COUNTER = per-chunk arrival ticks; SIGNAL drains a step's
            # chunks up front (per-edge wait, chunked issue)
            counter=(d.completion == "COUNTER" and fused),
            # lazy fence: the whole-shard rotation overlaps the round's
            # compute; ACQREL orders the fence eagerly, and BARRIER's
            # global-rendezvous semantics force the same serialized drain
            pipelined=d.placement in ("TILE_PIPELINED", "TILE_FUSED"),
            eager=((d.ordering == "ACQREL" or d.completion == "BARRIER")
                   and not fused))
        return k

    def collective_schedule(self, d: Directive):
        # the deployment-shard rotation schedule the ring kernel runs —
        # l0 (core/verify.py) statically checks it ahead of l1 build
        if d.backend == "XLA_COLLECTIVE":
            return None
        k = self.kernel_knobs(d)
        return make_ring_schedule(self.n_dev, self.sl, k["kv_chunk"],
                                  fused=k["fused"])

    def build(self, d: Directive, mesh):
        if d.backend == "XLA_COLLECTIVE":
            if d.placement == "STREAM_SPLIT":
                return self._stream_split(mesh)
            return self.host_baseline(mesh)
        k = self.kernel_knobs(d)

        def run(q, k_in, v_in):
            return ring_kernel(q, k_in, v_in, mesh, axis=self.axis,
                               causal=True, fused=k["fused"],
                               counter=k["counter"], kv_chunk=k["kv_chunk"],
                               pipelined=k["pipelined"],
                               eager_wait=k["eager"],
                               contexts=k["contexts"])

        return run

    def default_tunables(self):
        # kv_chunk joins the TUNABLES grid: slow-path diff patches refine
        # the rotation chunk rows of the kernelized ring points
        return {"kv_chunk": 64}

    # --------------------------------------------------------- l3 cost model
    def analytic_cost(self, d: Directive, hw) -> float:
        return self.cost_breakdown(d, hw).total

    def cost_breakdown(self, d: Directive, hw) -> CostBreakdown:
        Seg = CostSegment
        n, BH, sl, hd = self.n_dev, self.BH, self.sl, self.hd
        flops_round = 4.0 * BH * sl * sl * hd          # qk^T + pv (causal ~1/2
        flops_round *= 0.5 * (1 + 1.0 / n)             # avg causal occupancy)
        t_comp = flops_round / hw.chip.peak_bf16_flops
        wire_round = 2 * BH * sl * hd * 2              # K and V, bf16
        t_wire = wire_round / hw.chip.ici_link_bw
        sync = BARRIER_OVERHEAD if d.completion == "BARRIER" else SIGNAL_OVERHEAD
        if d.backend == "XLA_COLLECTIVE":
            if d.placement == "STREAM_SPLIT":
                per_round = max(t_comp, t_wire) + sync
                kind, path = "overlap", "xla_stream_split"
            else:
                per_round = t_comp + t_wire + sync + KERNEL_LAUNCH
                kind, path = "compute", "xla_host"
            return CostBreakdown(segments=(
                Seg("ring_rounds", n * per_round, kind,
                    meta={"rounds": n, "per_round_s": per_round,
                          "compute_s": t_comp, "wire_s": t_wire}),
                Seg("launch", KERNEL_LAUNCH * n, "launch",
                    meta={"launches": n}),     # per-round host launches
            ), meta={"path": path})
        # Pallas device-initiated: no host launches inside the ring
        k = self.kernel_knobs(d)
        if k["fused"]:
            # FLUX-ring credit: chunk c's rotation hides behind chunk c+1's
            # attention compute; per rotation step only the final chunk's
            # wire stays exposed (per_tile_exposed_s over the chunk count),
            # scaled by the send-window recycle stall. The schedule charges
            # TILE_SYNC per issued round and per completion tick.
            sched = make_ring_schedule(n, sl, k["kv_chunk"], fused=True)
            per_round = max(t_comp, t_wire)
            exposed = window_stall_factor(k["contexts"]) \
                * per_tile_exposed_s(wire_round, hw.chip.ici_link_bw,
                                     sched.nc)
            fixed = (sched.issued_rounds()
                     + sched.completion_ticks(k["counter"])) * TILE_SYNC
            return CostBreakdown(segments=(
                Seg("ring_rounds", sched.steps * per_round, "overlap",
                    meta={"rounds": sched.steps, "per_round_s": per_round,
                          "compute_s": t_comp, "wire_s": t_wire}),
                Seg("window_stall", sched.steps * exposed, "stall",
                    meta={"contexts": k["contexts"]}),
                Seg("final_compute", t_comp, "compute"),
                Seg("tile_sync", fixed, "sync",
                    meta={"issued_rounds": sched.issued_rounds(),
                          "ticks": sched.completion_ticks(k["counter"])}),
                Seg("launch", KERNEL_LAUNCH, "launch"),
            ), schedule=sched, knobs=k, meta={"path": "kernel_fused"})
        if k["pipelined"] and not k["eager"]:
            per_round = max(t_comp, t_wire) + sync     # lazy fence overlap
            kind, path = "overlap", "kernel_pipelined"
        else:                                          # DEFERRED / ACQREL
            per_round = t_comp + t_wire + sync
            kind, path = "compute", "kernel_deferred"
        return CostBreakdown(segments=(
            Seg("ring_rounds", n * per_round, kind,
                meta={"rounds": n, "per_round_s": per_round,
                      "compute_s": t_comp, "wire_s": t_wire}),
            Seg("launch", KERNEL_LAUNCH, "launch"),   # one cooperative launch
        ), knobs=k, meta={"path": path})
