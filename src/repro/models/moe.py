"""Mixture-of-Experts layer with expert parallelism and CUCo-style overlap.

Three execution modes:

* ``local``      — no mesh (smoke tests): full experts on one device.
* ``replicated`` — activations TP-replicated; experts sharded over the model
  axis; each TP rank dispatches its local tokens to its expert shard and the
  partial outputs are psum'd over model (communication cost identical to the
  dense-MLP TP all-reduce it replaces). Used by granite-moe.
* ``alltoall``   — paper-faithful EP: experts sharded over the (pod, data)
  axes; tokens are dispatched to expert owners via ``jax.lax.all_to_all``;
  feed-forward is TP-sharded over model. Supports the CUCo-discovered
  **self/remote split**: the self-chunk expert GEMM has no data dependency on
  the dispatch all-to-all, so XLA's latency-hiding scheduler runs dispatch
  concurrently with local compute (the paper's two-stream overlap, §4.3).
  Optional int8 dispatch quantization (the paper's FP8-quantize phase,
  adapted) halves dispatch wire bytes. Used by llama4-maverick.

Capacity-based static shapes throughout (GShard-style token dropping).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init
from jax import shard_map
from jax.lax import axis_size

F32 = jnp.float32


def moe_init(key, cfg, dtype):
    E, d, f = cfg.num_experts_padded, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, E, F32).astype(F32),   # router kept f32
        "wg": (jax.random.normal(ks[1], (E, d, f), F32) / math.sqrt(d)).astype(dtype),
        "wu": (jax.random.normal(ks[2], (E, d, f), F32) / math.sqrt(d)).astype(dtype),
        "wd": (jax.random.normal(ks[3], (E, f, d), F32) / math.sqrt(f)).astype(dtype),
    }
    if cfg.shared_expert:
        from repro.models.layers import mlp_init
        p["shared"] = mlp_init(ks[4], d, cfg.moe_d_ff, "swiglu", dtype)
    return p


def moe_param_specs(cfg, rules):
    """PartitionSpecs for the MoE params (matching moe_init structure)."""
    e_ax = rules.axes("experts_data" if cfg.ep_mode == "alltoall" else "experts_model")
    f_ax = rules.axes("ff") if cfg.ep_mode == "alltoall" else None
    specs = {
        "router": P(None, None),
        "wg": P(e_ax, None, f_ax),
        "wu": P(e_ax, None, f_ax),
        "wd": P(e_ax, f_ax, None),
    }
    if cfg.shared_expert:
        specs["shared"] = {"gate": P(None, rules.axes("ff")),
                           "up": P(None, rules.axes("ff")),
                           "down": P(rules.axes("ff"), None)}
    return specs


# ------------------------------------------------------------------- routing

def _route(x2, router_w, cfg):
    """x2: (T, d) -> gates (T, k) f32, idx (T, k) int32."""
    logits = x2.astype(F32) @ router_w.astype(F32)                 # (T, E_pad)
    E_pad = logits.shape[-1]
    if E_pad > cfg.num_experts:                                    # mask pad experts
        valid = jnp.arange(E_pad) < cfg.num_experts
        logits = jnp.where(valid[None, :], logits, -jnp.inf)
    gates, idx = jax.lax.top_k(logits, cfg.experts_per_token)
    gates = jax.nn.softmax(gates, axis=-1)
    return gates, idx.astype(jnp.int32)


def _dispatch_indices(idx, E_pad, C):
    """idx: (T, k). Returns flat (T*k,) expert ids, within-expert slot, keep."""
    flat_e = idx.reshape(-1)
    oh = jax.nn.one_hot(flat_e, E_pad, dtype=jnp.int32)            # (Tk, E)
    pos = jnp.sum((jnp.cumsum(oh, axis=0) - 1) * oh, axis=-1)      # slot in expert
    keep = pos < C
    return flat_e, pos, keep


def _expert_ffn(buf, wg, wu, wd):
    """buf: (E, C, d) x w*: (E, d, f)/(E, f, d) -> (E, C, d). SwiGLU."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum(
        "ecd,edf->ecf", buf, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _capacity(T, k, E, cap_factor):
    return max(1, int(math.ceil(cap_factor * T * k / E)))


def _quantize_i8(x):
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True).astype(F32) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x.astype(F32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


# ----------------------------------------------------------- execution paths

def _local_moe(x, p, cfg):
    """Single-device path (also the oracle for the sharded paths)."""
    B, S, d = x.shape
    T = B * S
    x2 = x.reshape(T, d)
    k, E_pad = cfg.experts_per_token, cfg.num_experts_padded
    C = _capacity(T, k, cfg.num_experts, cfg.capacity_factor)
    gates, idx = _route(x2, p["router"], cfg)
    flat_e, pos, keep = _dispatch_indices(idx, E_pad, C)
    tok = jnp.arange(T * k) // k
    slot = jnp.where(keep, flat_e * C + pos, E_pad * C)
    buf = jnp.zeros((E_pad * C + 1, d), x.dtype).at[slot].add(
        x2[tok] * keep[:, None].astype(x.dtype))
    h = _expert_ffn(buf[:-1].reshape(E_pad, C, d), p["wg"], p["wu"], p["wd"])
    contrib = h.reshape(E_pad * C, d)[jnp.minimum(slot, E_pad * C - 1)]
    contrib = contrib * (gates.reshape(-1, 1) * keep[:, None]).astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[tok].add(contrib)
    if cfg.shared_expert:
        from repro.models.layers import mlp_apply
        y = y + mlp_apply(p["shared"], x2, "swiglu")
    return y.reshape(B, S, d)


def _replicated_body(x, router, wg, wu, wd, shared, *, cfg, tp_axis):
    """Per-device body: experts sharded over `tp_axis`; psum combine."""
    B, S, d = x.shape
    T = B * S
    x2 = x.reshape(T, d)
    k, E_pad = cfg.experts_per_token, cfg.num_experts_padded
    E_l = wg.shape[0]
    n_shards = E_pad // E_l
    C = _capacity(T, k, cfg.num_experts, cfg.capacity_factor)
    gates, idx = _route(x2, router, cfg)
    flat_e, pos, keep = _dispatch_indices(idx, E_pad, C)
    m = jax.lax.axis_index(tp_axis) % n_shards if tp_axis else 0
    local_e = flat_e - m * E_l
    mine = (local_e >= 0) & (local_e < E_l) & keep
    tok = jnp.arange(T * k) // k
    slot = jnp.where(mine, local_e * C + pos, E_l * C)
    buf = jnp.zeros((E_l * C + 1, d), x.dtype).at[slot].add(
        x2[tok] * mine[:, None].astype(x.dtype))
    h = _expert_ffn(buf[:-1].reshape(E_l, C, d), wg, wu, wd)
    contrib = h.reshape(E_l * C, d)[jnp.minimum(slot, E_l * C - 1)]
    contrib = contrib * (gates.reshape(-1, 1) * mine[:, None]).astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[tok].add(contrib)
    if cfg.shared_expert:
        from repro.models.layers import mlp_apply
        y = y + mlp_apply(shared, x2, "swiglu")   # ff-sharded partial: in psum
    if tp_axis:
        y = jax.lax.psum(y, tp_axis)
    return y.reshape(B, S, d)


def _alltoall_body(x, router, wg, wu, wd, shared, *, cfg, dp_axes, tp_axes,
                   overlap, quantize):
    """Paper-faithful EP: dispatch A2A -> expert FFN (ff TP) -> combine A2A.

    With ``overlap=True`` the self-chunk FFN is computed from the *local* send
    buffer (no dependency on the dispatch all-to-all) — the CUCo two-stream
    split. The remote chunk is zero-masked so its slots contribute nothing
    twice. Costs 1/ep extra FLOPs; hides dispatch latency behind self-compute.
    """
    B, S, d = x.shape
    T = B * S
    x2 = x.reshape(T, d)
    k, E_pad = cfg.experts_per_token, cfg.num_experts_padded
    ep = 1
    for a in dp_axes:
        ep *= axis_size(a)
    E_l = E_pad // ep
    C = _capacity(T, k, cfg.num_experts, cfg.capacity_factor)
    gates, idx = _route(x2, router, cfg)
    flat_e, pos, keep = _dispatch_indices(idx, E_pad, C)
    tok = jnp.arange(T * k) // k
    slot = jnp.where(keep, flat_e * C + pos, E_pad * C)
    buf = jnp.zeros((E_pad * C + 1, d), x.dtype).at[slot].add(
        x2[tok] * keep[:, None].astype(x.dtype))
    buf = buf[:-1].reshape(ep, E_l, C, d)                    # dst-major layout
    r = jax.lax.axis_index(dp_axes)

    def ffn(chunk):                                          # (..., E_l, C, d)
        c = chunk.reshape(-1, E_l, C, d)
        cg = c.transpose(1, 0, 2, 3).reshape(E_l, -1, d)     # group tokens by expert
        h = _expert_ffn(cg, wg, wu, wd)                      # ff TP partial sums
        if tp_axes:
            h = jax.lax.psum(h, tp_axes)
        h = h.reshape(E_l, -1, C, d).transpose(1, 0, 2, 3)
        return h.reshape(chunk.shape)

    if overlap:
        self_chunk = buf[r]                                  # (E_l, C, d) local
        h_self = ffn(self_chunk)                             # independent of A2A
        send = buf
        if quantize:
            q, sc = _quantize_i8(send)
            q = jax.lax.all_to_all(q, dp_axes, 0, 0, tiled=True)
            sc = jax.lax.all_to_all(sc, dp_axes, 0, 0, tiled=True)
            recv = (q.astype(F32) * sc).astype(x.dtype)
        else:
            recv = jax.lax.all_to_all(send, dp_axes, 0, 0, tiled=True)
        src = jnp.arange(ep)
        recv_remote = jnp.where((src != r)[:, None, None, None], recv, 0)
        h_remote = ffn(recv_remote)                          # self rows are 0
        h = h_remote.at[r].add(h_self)
    else:
        if quantize:
            q, sc = _quantize_i8(buf)
            q = jax.lax.all_to_all(q, dp_axes, 0, 0, tiled=True)
            sc = jax.lax.all_to_all(sc, dp_axes, 0, 0, tiled=True)
            recv = (q.astype(F32) * sc).astype(x.dtype)
        else:
            recv = jax.lax.all_to_all(buf, dp_axes, 0, 0, tiled=True)
        h = ffn(recv)
    back = jax.lax.all_to_all(h, dp_axes, 0, 0, tiled=True)  # combine
    y_slots = back.reshape(E_pad * C, d)
    contrib = y_slots[jnp.minimum(slot, E_pad * C - 1)]
    contrib = contrib * (gates.reshape(-1, 1) * keep[:, None]).astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[tok].add(contrib)
    if cfg.shared_expert:
        from repro.models.layers import mlp_apply
        sh = mlp_apply(shared, x2, "swiglu")                 # also A2A-independent
        if tp_axes:
            sh = jax.lax.psum(sh, tp_axes)                   # ff-sharded partial
        y = y + sh
    return y.reshape(B, S, d)


def _pallas_body(x, router, wg, wu, wd, shared, *, cfg, dp_axis, overlap,
                 quantize, interpret, probe):
    """The PALLAS_RDMA branch (the serving hot path): routing/capacity
    layout identical to ``_alltoall_body`` up to the dst-major capacity
    buffer, but dispatch → expert FFN → combine runs as ONE fused
    device-initiated kernel (``kernels/moe_dispatch``, FLUX knobs:
    tile_fused + COUNTER). With ``overlap`` and a shared expert, the
    shared-expert FFN is the kernel's second stream — issued against the
    open dispatch send window (the TokenWeave two-stream overlap,
    executably). Eligibility is gated by :func:`pallas_moe_eligible`;
    the capacity-slot layout makes the kernel's output slab bit-match
    the XLA path's ``y_slots``, so combine/gather code is shared."""
    from repro.core.schedule import make_schedule
    from repro.kernels.moe_dispatch import moe_dispatch_combine_sharded
    B, S, d = x.shape
    T = B * S
    x2 = x.reshape(T, d)
    k, E_pad = cfg.experts_per_token, cfg.num_experts_padded
    ep = axis_size(dp_axis)
    C = _capacity(T, k, cfg.num_experts, cfg.capacity_factor)
    gates, idx = _route(x2, router, cfg)
    flat_e, pos, keep = _dispatch_indices(idx, E_pad, C)
    tok = jnp.arange(T * k) // k
    slot = jnp.where(keep, flat_e * C + pos, E_pad * C)
    buf = jnp.zeros((E_pad * C + 1, d), x.dtype).at[slot].add(
        x2[tok] * keep[:, None].astype(x.dtype))
    # (ep*C, d): contiguous per-expert capacity blocks — exactly the
    # sorted-block layout the dispatch kernel's static counts contract
    # wants (uniform counts == C, so the schedule has no dummy blocks)
    xk = buf[:-1]
    w1 = jnp.concatenate([wg[0], wu[0]], axis=-1)        # (d, 2f) swiglu
    w2 = wd[0]                                           # (f, d)
    sched = make_schedule([C] * ep, block_tokens=min(64, C), tight=True)
    shared_op = None
    if overlap and shared is not None:
        s1 = jnp.concatenate([shared["gate"], shared["up"]], axis=-1)
        shared_op = (x2.astype(F32), s1.astype(F32),
                     shared["down"].astype(F32))
    out = moe_dispatch_combine_sharded(
        xk.astype(F32), w1.astype(F32), w2.astype(F32), axis=dp_axis,
        sched=sched, tile_fused=True, pipelined=True, barrier=False,
        contexts=2, wire_i8=quantize, shared=shared_op,
        interpret=interpret, probe=probe)
    y_slots, ys = out if shared_op is not None else (out, None)
    contrib = y_slots.astype(x.dtype)[jnp.minimum(slot, E_pad * C - 1)]
    contrib = contrib * (gates.reshape(-1, 1) * keep[:, None]).astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[tok].add(contrib)
    if shared is not None:
        if ys is not None:
            y = y + ys.astype(x.dtype)                   # second stream
        else:
            from repro.models.layers import mlp_apply
            y = y + mlp_apply(shared, x2, "swiglu")
    return y.reshape(B, S, d)


def pallas_moe_eligible(cfg, rules, B):
    """Can this (config, sharding, batch) route through the fused
    dispatch kernel? Requirements mirror the kernel contract: alltoall
    EP over exactly one data axis (the kernel's named-axis mesh), no ff
    TP (expert weights whole per rank), batch shardable, and exactly one
    expert per rank (``E_pad == ep`` — the DeepSeek-V3-style serving
    deployment). Ineligible shapes silently take the XLA paths."""
    if rules is None or rules.mesh is None or cfg.ep_mode != "alltoall":
        return False
    dp = rules.dp_size()
    if not (dp and B % dp == 0 and B >= dp):
        return False
    if len(rules.dp_axes) != 1 or rules.tp_axes:
        return False
    return cfg.num_experts_padded == dp


def _gathered_body(x, router, wg, wu, wd, shared, *, cfg, dp_axes, tp_axes):
    """Decode path when batch is too small to shard (e.g. long_500k, B=1):
    tokens replicated over DP; experts sharded over DP; ff over TP; psum-all.
    """
    B, S, d = x.shape
    T = B * S
    x2 = x.reshape(T, d)
    k, E_pad = cfg.experts_per_token, cfg.num_experts_padded
    E_l = wg.shape[0]
    ep = E_pad // E_l
    C = _capacity(T, k, cfg.num_experts, cfg.capacity_factor)
    gates, idx = _route(x2, router, cfg)
    flat_e, pos, keep = _dispatch_indices(idx, E_pad, C)
    r = jax.lax.axis_index(dp_axes) % ep
    local_e = flat_e - r * E_l
    mine = (local_e >= 0) & (local_e < E_l) & keep
    tok = jnp.arange(T * k) // k
    slot = jnp.where(mine, local_e * C + pos, E_l * C)
    buf = jnp.zeros((E_l * C + 1, d), x.dtype).at[slot].add(
        x2[tok] * mine[:, None].astype(x.dtype))
    h = _expert_ffn(buf[:-1].reshape(E_l, C, d), wg, wu, wd)
    if tp_axes:
        h = jax.lax.psum(h, tp_axes)
    contrib = h.reshape(E_l * C, d)[jnp.minimum(slot, E_l * C - 1)]
    contrib = contrib * (gates.reshape(-1, 1) * mine[:, None]).astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[tok].add(contrib)
    y = jax.lax.psum(y, dp_axes)
    if cfg.shared_expert:
        from repro.models.layers import mlp_apply
        sh = mlp_apply(shared, x2, "swiglu")
        if tp_axes:
            sh = jax.lax.psum(sh, tp_axes)                   # ff-sharded partial
        y = y + sh
    return y.reshape(B, S, d)


# ---------------------------------------------------------------- public API

def moe_apply(params, x, cfg, rules, *, overlap=False, quantize=False,
              backend="xla", interpret=None, probe=None):
    """Apply the MoE block. x: (B, S, d) global.

    ``backend="pallas"`` routes the dispatch→FFN→combine chain through the
    fused ``kernels/moe_dispatch`` kernel (FLUX point) when
    :func:`pallas_moe_eligible` holds — with ``overlap`` the shared-expert
    FFN becomes the kernel's second stream (the TokenWeave point). The
    kernel's ``interpret``/``probe`` plumb through for tests."""
    if rules is None or rules.mesh is None:
        return _local_moe(x, params, cfg)

    mesh = rules.mesh
    dp_axes = rules.dp_axes
    tp_axes = rules.tp_axes
    B = x.shape[0]
    dp = rules.dp_size()
    pspecs = moe_param_specs(cfg, rules)
    shared = params.get("shared")
    shared_spec = pspecs.get("shared")
    b_ok = dp and B % dp == 0 and B >= dp
    x_spec = P(rules.axes("batch") if b_ok else None, None, None)

    if backend == "pallas" and pallas_moe_eligible(cfg, rules, B):
        body = partial(_pallas_body, cfg=cfg, dp_axis=dp_axes[0],
                       overlap=overlap, quantize=quantize,
                       interpret=interpret, probe=probe)
        in_specs = (x_spec, pspecs["router"], pspecs["wg"], pspecs["wu"],
                    pspecs["wd"], shared_spec)
    elif cfg.ep_mode == "alltoall" and b_ok:
        body = partial(_alltoall_body, cfg=cfg, dp_axes=dp_axes, tp_axes=tp_axes,
                       overlap=overlap, quantize=quantize)
        in_specs = (x_spec, pspecs["router"], pspecs["wg"], pspecs["wu"],
                    pspecs["wd"], shared_spec)
    elif cfg.ep_mode == "alltoall":
        body = partial(_gathered_body, cfg=cfg, dp_axes=dp_axes, tp_axes=tp_axes)
        in_specs = (P(None, None, None), pspecs["router"], pspecs["wg"],
                    pspecs["wu"], pspecs["wd"], shared_spec)
        x_spec = P(None, None, None)
    else:
        body = partial(_replicated_body, cfg=cfg, tp_axis=tp_axes)
        in_specs = (x_spec, pspecs["router"], pspecs["wg"], pspecs["wu"],
                    pspecs["wd"], shared_spec)

    if shared is None:
        in_specs = in_specs[:-1] + (None,)
    fn = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=x_spec,
                       check_vma=False)
    return fn(x, params["router"], params["wg"], params["wu"], params["wd"], shared)
