"""Blockwise flash attention Pallas TPU kernel (compute core).

Online-softmax over KV blocks with explicit BlockSpec VMEM tiling. The grid
is (batch*heads, q_blocks, kv_blocks); the kv dimension is the innermost
(sequential on TPU), so the f32 accumulator scratch carries across kv steps.
Causal masking skips fully-masked kv blocks via pl.when.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import default_interpret

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_i, l_i, *, causal, scale,
               q_block, kv_block):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    q_start = qi * q_block
    k_start = ki * kv_block
    run = (not causal) or (k_start <= q_start + q_block - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)                    # (qb, hd)
        k = k_ref[0].astype(jnp.float32)                    # (kb, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_i[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_i[...] = l_i[...] * alpha + jnp.sum(p, axis=1)
        acc[...] = acc[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_i[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc[...] / jnp.maximum(l_i[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, q_block=128, kv_block=128,
                    interpret=None):
    """q/k/v: (BH, S, hd) -> (BH, S, hd). ``interpret=None`` picks
    :func:`repro.compat.default_interpret`."""
    BH, S, hd = q.shape
    Skv = k.shape[1]
    assert S % q_block == 0 and Skv % kv_block == 0, (S, Skv, q_block, kv_block)
    scale = 1.0 / math.sqrt(hd)
    grid = (BH, S // q_block, Skv // kv_block)
    kern = functools.partial(_fa_kernel, causal=causal, scale=scale,
                             q_block=q_block, kv_block=kv_block)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, kv_block, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, kv_block, hd), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, hd), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
        ],
        interpret=default_interpret() if interpret is None else interpret,
    )(q, k, v)
