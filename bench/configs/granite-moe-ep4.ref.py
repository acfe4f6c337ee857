"""Plain per-rank reference of the expert-parallel MoE layers in the
configuration file: in each layer dispatch -> expert FFN -> combine, with
one expert per rank and a static routing law, in float32 at ``highest``
precision. The layers are independent: each has its own rows and weights.

It imports nothing of the program under test; the routing law is the
benchmark's own (``benchlib/counts.py::skew_counts``). Each rank's rows
come sorted into contiguous per-expert blocks; block ``e`` has ``counts[e]`` rows (the
same on every rank) and goes through expert ``e``'s SwiGLU FFN: ``w1`` is
(d, 2f) with the gate in its first ``f`` columns and the up projection in
the rest, ``w2`` is (f, d). The result returns to the row's own place.

The configuration states float32 operands multiplied at the TPU's default
precision: one bfloat16 pass with float32 accumulation, which is what the
kernel's float32 dots do (it sets no precision). ``control=True`` takes
the same matmuls with float8 (e4m3) operands, one scale per row of the
left operand and per column of the right, float32 accumulation: the
precision below.
"""
import jax
import jax.numpy as jnp
import numpy as np

from benchlib.counts import skew_counts

F32 = jnp.float32


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0 + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(a, w, control):
    if control:
        a, w = _fp8(a, -1), _fp8(w, -2)
    return jnp.dot(a, w, preferred_element_type=F32)


def _ffn(x, w1, w2, f, control):
    h = _mm(x, w1, control)
    return _mm(jax.nn.silu(h[:, :f]) * h[:, f:], w2, control)


def make_rank_output(c, control=False):
    """A jitted ``out(x_r, w1, w2) -> y_r`` for one rank's rows ``x_r``
    (L, T, d) in each of ``L`` layers, with every expert's weights ``w1``
    (L, n, d, 2f), ``w2`` (L, n, f, d)."""
    n, f = c["ranks"], c["intermediate_size"]
    cnt = skew_counts(c["rows_per_rank"], n, c["skew"])
    off = np.concatenate([[0], np.cumsum(cnt)]).astype(int)

    def layer(args):
        x, w1, w2 = args
        return jnp.concatenate([
            _ffn(x[off[e]:off[e + 1]], w1[e], w2[e], f, control)
            for e in range(n) if cnt[e]])

    def out(x, w1, w2):
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(layer, (x, w1, w2))

    return jax.jit(out)
