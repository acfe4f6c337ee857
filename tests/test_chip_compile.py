"""Compile the chip path for a described TPU v5e, no chip attached.

The TPU compiler refuses what interpret mode accepts: unaligned DMA
slices, value-level dynamic slices, more VMEM than a kernel may use, a
program larger than the chip's HBM. These tests compile, for the
``v5e:2x2`` topology,

  * the moe_dispatch and gemm_allgather kernels exactly as the cascade
    builds them (``Workload.build``) at the shapes ``chip_smoke.py`` runs,
    at 1 and 4 ranks, with Mosaic (not the interpreter);
  * the ring_attention kernel (FLUX and whole-shard points) at 4 ranks
    and the kv_shuttle kernel (FLUX and chained points, and the serving
    engine's cache handoff) at 2 ranks, at their workloads' example
    shapes;
  * the serving engine's decode step for granite-moe-3b-a800m at full
    width, from ``jax.eval_shape`` shapes,

and check that each kernel is a ``tpu_custom_call`` and that each program
fits 16 GiB. They also lower the moe_dispatch kernel with its device phases
on and off (``repro.core.trace.device_phases``) and read the phase regions
that Mosaic's kernel body opens. The topology is described inside a fixture
(never at import): only one process at a time may load the TPU compiler's
library.
"""
import base64
import contextlib
import dataclasses
import re

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import make_mesh
from repro.core import EXPERT_SYSTEMS, Directive
from repro.workloads import get_workload

HBM_BYTES = 16 * 2**30          # one v5e chip
MOE_POINTS = {
    "DeepEP-NVL": EXPERT_SYSTEMS["DeepEP (NVL)"],
    "FLUX": EXPERT_SYSTEMS["FLUX"],
    # the per-source pipelined point (SIGNAL + TILE_PIPELINED)
    "pipelined": Directive("PALLAS_RDMA", "SIGNAL", "TILE_PIPELINED",
                           "LOCAL", "KERNEL", "PER_PEER", "RELEASE", 2),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # else libtpu logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back without one:
        # keep such compiles out of the persistent cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the kernels off their CPU default (the interpreter): this
    process sees the CPU backend, the compile targets the TPU."""
    import repro.kernels.gemm_allgather as ga
    import repro.kernels.kv_shuttle as kv
    import repro.kernels.moe_dispatch as md
    import repro.kernels.ring_attention as ra
    for mod in (md, ga, ra, kv):
        monkeypatch.setattr(mod, "default_interpret", lambda: False)


def _compile_workload(topo, name, n, d):
    """Compile ``Workload.build(d)`` on an n-rank mesh of described v5e
    chips at the workload's example-input shapes (chip_smoke's)."""
    return _lower_workload(topo, name, n, d).compile()


def _lower_workload(topo, name, n, d):
    wl = get_workload(name, n_dev=n) if name != "kv_transfer" \
        else get_workload(name)
    assert wl.n_dev == n
    d = dataclasses.replace(
        d, tunables=tuple(sorted(wl.default_tunables().items())))
    assert not wl.check(d), wl.check(d)
    mesh = make_mesh((n,), ("x",), devices=topo.devices[:n])
    shapes = jax.eval_shape(lambda k: wl.example_inputs(k, None),
                            jax.random.PRNGKey(0))
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(
        mesh, P("x") if s.ndim >= 3 else P())) for s in shapes]
    return jax.jit(wl.build(d, mesh)).lower(*args)


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("n", [1, 4])
@pytest.mark.parametrize("point", sorted(MOE_POINTS))
def test_moe_dispatch_compiles_for_v5e(topo, mosaic, point, n):
    compiled = _compile_workload(topo, "moe_dispatch", n, MOE_POINTS[point])
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) <= HBM_BYTES


def _regions(text):
    """The phase regions of each Mosaic kernel body in a lowered program,
    in program order, as ``(depth, name)`` per ``tpu.trace_start``."""
    from jax._src.interpreters import mlir
    from jaxlib.mlir import ir
    out = []
    for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)', text):
        with mlir.make_ir_context() as ctx:
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(body))
            seq, stack = [], []

            def walk(op):
                name = op.operation.name
                if name.endswith("tpu.trace_start"):
                    msg = ir.StringAttr(op.operation.attributes["message"])
                    seq.append((len(stack), msg.value))
                    stack.append(msg.value)
                elif name.endswith("tpu.trace_stop"):
                    stack.pop()
                for region in op.operation.regions:
                    for block in region.blocks:
                        for o in block.operations:
                            walk(o)

            for op in module.body.operations:
                walk(op)
            assert not stack, stack
            out.append(seq)
    return out


@pytest.mark.parametrize("point", sorted(MOE_POINTS))
def test_moe_dispatch_phase_regions(topo, mosaic, point):
    """Phases on: the kernel opens its regions in the order it runs them,
    with one ``arrival_wait`` per dispatch microblock slot and one ``ffn``
    per GEMM tile (per source off the tile-fused path). Phases off: no
    region, and the same lowered text as a step lowered outside the
    switch."""
    from repro.core.trace import device_phases
    from repro.kernels.moe_dispatch import PHASES, make_schedule
    d = MOE_POINTS[point]
    text = {}
    for mode in ("untouched", "on", "off"):
        with (contextlib.nullcontext() if mode == "untouched"
              else device_phases(mode == "on")):
            text[mode] = _lower_workload(topo, "moe_dispatch", 4, d).as_text()
    assert text["off"] == text["untouched"]
    assert _regions(text["off"]) == [[]]
    (seq,) = _regions(text["on"])
    assert seq[0] == (0, "moe_dispatch")
    assert [m for depth, m in seq if depth == 1] == [
        "stage_in", "dispatch", "ffn_combine", "combine_wait", "assemble",
        "stage_out"]
    wl = get_workload("moe_dispatch", n_dev=4)
    d = dataclasses.replace(
        d, tunables=tuple(sorted(wl.default_tunables().items())))
    k = wl.kernel_knobs(d)
    x = jax.eval_shape(lambda key: wl.example_inputs(key, None),
                       jax.random.PRNGKey(0))[0]
    sched = make_schedule(wl._counts(x.shape[1]), k["block_tokens"],
                          k["tight"])
    n, b = sched.n, sched.b_max
    if k["tile_fused"]:
        tiles = k["block_tokens"] // k["combine_tile"]
        inner = (["arrival_wait"] + ["ffn"] * tiles) * (n * b)
    elif k["barrier"] or not k["pipelined"]:
        inner = ["arrival_wait"] * (n * b) + ["ffn"] * n
    else:
        inner = (["arrival_wait"] * b + ["ffn"]) * n
    assert [m for depth, m in seq if depth == 2] == inner
    assert {m for _, m in seq} == set(PHASES)


def test_two_stream_phase_regions_keep_probe_marks(topo, mosaic):
    """The serving layout's shared FFN is a ``shared_ffn`` region inside
    ``dispatch``, and its probe marks are the serving suite's, phases on
    or off."""
    from repro.core import EXPERT_SYSTEMS as systems
    from repro.core.trace import ScheduleProbe, device_phases
    from repro.kernels.moe_dispatch import moe_dispatch_combine
    wl = get_workload("serving_step", n_dev=4, tokens_per_rank=96, d=128,
                      f=192, f_shared=192)
    k = wl.kernel_knobs(systems["FLUX"])
    mesh = make_mesh((4,), ("x",), devices=topo.devices[:4])
    x, w1, w2, s1, s2 = [jax.ShapeDtypeStruct(s.shape, s.dtype,
                                              sharding=NamedSharding(
                                                  mesh, P("x") if s.ndim >= 3
                                                  else P()))
                         for s in jax.eval_shape(
                             lambda key: wl.example_inputs(key, None),
                             jax.random.PRNGKey(0))]
    seqs = {}
    for on in (False, True):
        probe = ScheduleProbe()

        def step(x, w1, w2, s1, s2):
            return moe_dispatch_combine(
                x, w1, w2, mesh, axis="x", counts=wl._counts(x.shape[1]),
                block_tokens=k["block_tokens"], tight=k["tight"],
                pipelined=k["pipelined"], barrier=k["barrier"],
                tile_fused=k["tile_fused"], combine_tile=k["combine_tile"],
                contexts=k["contexts"], shared=(x, s1, s2), probe=probe)

        with device_phases(on):
            (seqs[on],) = _regions(
                jax.jit(step).lower(x, w1, w2, s1, s2).as_text())
        assert probe.marks == ["dispatch_issued", "shared_ffn",
                               "dispatch_drained"], probe.marks
    assert seqs[False] == []
    i = seqs[True].index((1, "dispatch"))
    assert seqs[True][i + 1] == (2, "shared_ffn")


@pytest.mark.parametrize("n", [1, 4])
def test_gemm_allgather_flux_compiles_for_v5e(topo, mosaic, n):
    flux = EXPERT_SYSTEMS["FLUX"]            # fused tiles + COUNTER ticks
    wl = get_workload("gemm_allgather", n_dev=n)
    k = wl.kernel_knobs(flux, 128)
    assert k["fused"] and k["counter"]
    compiled = _compile_workload(topo, "gemm_allgather", n, flux)
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) <= HBM_BYTES


RING_POINTS = {
    "FLUX": EXPERT_SYSTEMS["FLUX"],          # chunk rotation, COUNTER ticks
    # the whole-shard rotation with a lazy fence (SIGNAL, pipelined)
    "pipelined": Directive("PALLAS_RDMA", "SIGNAL", "TILE_PIPELINED",
                           "LOCAL", "KERNEL", "PER_CHUNK", "RELEASE", 2),
}
SHUTTLE_POINTS = {
    "FLUX": EXPERT_SYSTEMS["FLUX"],          # per-tile GEMM + send chain
    # the K→V signal chain over whole tensors (the non-fused CUCo point)
    "chained": Directive("PALLAS_RDMA", "SIGNAL", "STREAM_SPLIT", "LOCAL",
                         "KERNEL", "PER_PEER", "RELEASE", 2),
}


@pytest.mark.parametrize("point", sorted(RING_POINTS))
def test_ring_attention_compiles_for_v5e(topo, mosaic, point):
    wl = get_workload("ring_attention", n_dev=4)
    d = RING_POINTS[point]
    assert wl.kernel_knobs(d)["fused"] == (point == "FLUX")
    compiled = _compile_workload(topo, "ring_attention", 4, d)
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) <= HBM_BYTES


@pytest.mark.parametrize("point", sorted(SHUTTLE_POINTS))
def test_kv_shuttle_compiles_for_v5e(topo, mosaic, point):
    wl = get_workload("kv_transfer")
    k = wl.kernel_knobs(SHUTTLE_POINTS[point])
    assert k["fused"] == (point == "FLUX")
    assert k["chained"] == (point == "chained")
    compiled = _compile_workload(topo, "kv_transfer", 2,
                                 SHUTTLE_POINTS[point])
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) <= HBM_BYTES


def test_kv_cache_handoff_compiles_for_v5e(topo, mosaic):
    """The serving engine's prefill→decode handoff (``_shuttle_cache``):
    one full-width granite-moe attention cache block of one request at 64
    positions, stacked ``[K; V]`` through ``kv_cache_shuttle``."""
    from repro.configs import get_arch
    from repro.kernels.kv_shuttle import kv_cache_shuttle
    from repro.models.model import init_cache
    cfg = get_arch("granite-moe-3b-a800m")
    cache = jax.eval_shape(lambda: init_cache(cfg, 1, 64))
    k = next(b["k"] for b in cache.values()
             if isinstance(b, dict) and "k" in b)
    rows, w = int(np.prod(k.shape[:-1])), k.shape[-1]
    mesh = make_mesh((2,), ("x",), devices=topo.devices[:2])
    kv = jax.ShapeDtypeStruct((2, 2 * rows, w), k.dtype,
                              sharding=NamedSharding(mesh, P("x")))
    compiled = jax.jit(lambda x: kv_cache_shuttle(x, mesh)).lower(
        kv).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) <= HBM_BYTES


def test_granite_moe_decode_step_fits_v5e(topo):
    """The engine's own jitted decode step, full width (32 layers, 48
    padded experts, ~7.4 GiB of bf16 weights), for one request."""
    from jax.sharding import SingleDeviceSharding

    from repro.configs import get_arch
    from repro.models import init_params
    from repro.models.model import init_cache
    from repro.serve import Engine, ServeConfig

    cfg = get_arch("granite-moe-3b-a800m")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(lambda k: init_params(k, cfg),
                                    jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, 1, 64)))
    eng = Engine(cfg, params, ServeConfig(max_seq=64))
    compiled = eng._decode.lower(
        params, cache, jax.ShapeDtypeStruct((1, 1), np.int32, sharding=one),
        jax.ShapeDtypeStruct((), np.int32, sharding=one)).compile()
    n_weights = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(params))
    assert n_weights > 3.5e9                 # full width, not reduced
    assert _device_bytes(compiled) <= HBM_BYTES
