"""jit'd public wrappers for the Pallas kernels in this package."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.flash_attention import flash_attention as _fa
from repro.kernels.ring_attention import ring_attention as _ring
from repro.kernels.gemm_allgather import gemm_allgather as _ga
from repro.kernels.kv_shuttle import kv_shuttle as _kv


@partial(jax.jit, static_argnames=("causal", "q_block", "kv_block", "interpret"))
def flash_attention(q, k, v, *, causal=True, q_block=128, kv_block=128,
                    interpret=None):
    return _fa(q, k, v, causal=causal, q_block=q_block, kv_block=kv_block,
               interpret=interpret)


def ring_attention(q, k, v, mesh, *, axis="x", causal=True, pipelined=True,
                   eager_wait=False, fused=False, counter=False,
                   kv_chunk=None, contexts=2):
    fn = jax.jit(partial(_ring, mesh=mesh, axis=axis, causal=causal,
                         pipelined=pipelined, eager_wait=eager_wait,
                         fused=fused, counter=counter, kv_chunk=kv_chunk,
                         contexts=contexts))
    return fn(q, k, v)


def gemm_allgather(a_shards, b, mesh, *, axis="x", tile_m=128, fused=True,
                   counter=False, contexts=2):
    fn = jax.jit(partial(_ga, mesh=mesh, axis=axis, tile_m=tile_m,
                         fused=fused, counter=counter, contexts=contexts))
    return fn(a_shards, b)


def kv_shuttle(x, wk, wv, mesh, *, axis="x", chained=True, fused=False,
               counter=False, kv_chunk=None, contexts=2):
    fn = jax.jit(partial(_kv, mesh=mesh, axis=axis, chained=chained,
                         fused=fused, counter=counter, kv_chunk=kv_chunk,
                         contexts=contexts))
    return fn(x, wk, wv)
