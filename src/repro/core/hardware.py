"""Hardware context (paper Appendix C, adapted to TPU v5e).

The paper injects dynamically-extracted hardware context (GPU model, SM
counts, link types) into the agent prompt. Here the equivalent is a typed
``HardwareContext`` extracted from the mesh + target-chip constants, consumed
by the cost model and by the mutation operator (so search decisions reflect
the deployment, not priors).
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipSpec:
    name: str = "tpu-v5e"
    peak_bf16_flops: float = 197e12          # FLOP/s per chip
    hbm_bw: float = 819e9                    # B/s per chip
    ici_link_bw: float = 50e9                # B/s per ICI link (one direction)
    ici_links_per_axis: int = 2              # bidirectional ring per torus axis
    dcn_bw: float = 25e9                     # B/s per host, cross-pod
    hbm_bytes: int = 16 * 2**30
    vmem_bytes: int = 128 * 2**20


V5E = ChipSpec()

# device_kind (as jax reports it) -> spec. A TPU that is not listed here is
# an error, never a default: a wrong spec silently re-ranks the search.
CHIPS = {"TPU v5 lite": V5E}


def chip_spec(device) -> ChipSpec:
    """The spec of ``device``. Off the TPU (CPU tests, the described-
    topology compiles) the l3 model targets v5e explicitly."""
    if device.platform != "tpu":
        return V5E
    try:
        return CHIPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no ChipSpec for TPU device_kind {device.device_kind!r}; "
            f"known: {sorted(CHIPS)}") from None


@dataclass(frozen=True)
class HardwareContext:
    chip: ChipSpec
    mesh_shape: tuple                        # e.g. (2, 16, 16)
    mesh_axes: tuple                         # e.g. ("pod", "data", "model")
    chips_per_pod: int
    n_chips: int
    has_dcn: bool

    @property
    def fingerprint(self) -> str:
        """Stable identity of the deployment target — the hardware half of
        the warm-start eval-cache key (docs/search.md): a cached score is
        only reusable on the chip/mesh it was modeled for."""
        shape = "x".join(str(s) for s in self.mesh_shape)
        return (f"{self.chip.name}|mesh={shape}"
                f"|axes={','.join(self.mesh_axes)}|dcn={int(self.has_dcn)}")

    @property
    def topology_summary(self) -> str:
        axes = ", ".join(f"{a}={s}" for a, s in zip(self.mesh_axes, self.mesh_shape))
        kind = "multi-pod (ICI intra-pod + DCN cross-pod)" if self.has_dcn else \
            "single-pod (ICI torus)"
        return (f"{self.chip.name} mesh [{axes}] — {self.n_chips} chips, {kind}; "
                f"{self.chip.peak_bf16_flops/1e12:.0f} TFLOP/s bf16, "
                f"{self.chip.hbm_bw/1e9:.0f} GB/s HBM, "
                f"{self.chip.ici_link_bw/1e9:.0f} GB/s/link ICI")


def extract_hardware_context(mesh, chip: ChipSpec = None) -> HardwareContext:
    """Deployment context of ``mesh``; ``chip`` defaults to the spec of the
    mesh's devices (:func:`chip_spec`)."""
    if chip is None:
        chip = chip_spec(mesh.devices.flat[0])
    shape = tuple(mesh.shape[a] for a in mesh.axis_names)
    axes = tuple(mesh.axis_names)
    has_dcn = "pod" in axes and mesh.shape["pod"] > 1
    n = 1
    for s in shape:
        n *= s
    per_pod = n // (mesh.shape["pod"] if has_dcn else 1)
    return HardwareContext(chip=chip, mesh_shape=shape, mesh_axes=axes,
                           chips_per_pod=per_pod, n_chips=n, has_dcn=has_dcn)
