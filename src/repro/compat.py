"""Choices every caller of the installed jax (0.9) makes the same way.

  * :func:`make_mesh`         — ``jax.make_mesh`` with ``Auto`` axis types:
                                the shard_map/``NamedSharding`` code in this
                                repo is written for automatic sharding
                                propagation, not explicit-axis typing.
  * :func:`default_interpret` — how a Pallas TPU kernel runs when its caller
                                does not say: the TPU interpreter on the CPU
                                backend, Mosaic-compiled everywhere else.
  * :func:`compiler_params`   — the Mosaic parameters of the four collective
                                kernels, which stage whole operands in VMEM.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` over ``devices`` (default: all) with Auto axes."""
    shape, axes = tuple(shape), tuple(axes)
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kw)


def default_interpret():
    """``interpret=`` value for ``pl.pallas_call`` when the caller passes
    none: ``pltpu.InterpretParams()`` (sender-driven remote-DMA simulator)
    on the CPU backend, ``False`` (Mosaic) on any accelerator."""
    return pltpu.InterpretParams() if jax.default_backend() == "cpu" else False


# The collective kernels stage whole operands in VMEM (ROADMAP S3), which
# outgrows Mosaic's default scoped-VMEM limit (16 MiB on v5e) well before
# the 128 MiB a v5e core has. Leave the rest to the compiler's own scratch.
VMEM_LIMIT_BYTES = 100 * 2**20


def compiler_params():
    """Mosaic parameters of the collective kernels. No ``collective_id``:
    no kernel takes a custom barrier semaphore, and Mosaic's default device
    barrier already orders every rank's kernel entry before remote DMAs."""
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)
