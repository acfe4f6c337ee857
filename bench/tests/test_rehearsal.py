"""CPU rehearsals of whole runs at test size, with the chip's look skipped:
the kernel driver on four virtual devices (the kernel interpreted), traced
and not; and faults planted under the timed path, which ``correct`` must
catch."""
import jax
import numpy as np
import pytest

import benchlib.trace
import helpers

KERNEL_E2E = ["kernel_step_us", "setup_s"]
KERNEL_LAYER = ["moe_dispatch_roofline", "mfu.kernel", "idle_share.kernel"]


@pytest.fixture
def cpu_trace(monkeypatch):
    monkeypatch.setattr(benchlib.trace, "events_from_profile",
                        helpers.cpu_events)


def kernel_cell():
    return helpers.cell("granite-moe-ep4-tiny", "tiny-calls", KERNEL_E2E,
                        KERNEL_LAYER)


def test_kernel_run_is_correct_traced_and_not(cpu_trace):
    devs = jax.devices()[:4]
    result, checks = helpers.run(kernel_cell(), 2**40 + 3, 0.3, 0, devs)
    assert result["correct"], checks
    assert result["device"]["count"] == 4
    assert result["metrics"]["kernel_step_us"]["value"] > 0
    # every step runs the kernel once per layer
    assert result["attempted"] % 3 == 0 and result["attempted"] >= 3
    result, checks = helpers.run(kernel_cell(), 9, 0.3, 1, devs)
    assert result["correct"], checks
    assert set(result["metrics"]) == set(KERNEL_LAYER)
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert result["breakdown"]["device_ops"]


def _planted(monkeypatch, fault):
    """Plant ``fault`` in what ``MoEDispatch.build`` returns."""
    from repro.workloads.moe_dispatch import MoEDispatch
    build = MoEDispatch.build

    def broken(self, d, mesh):
        fn = build(self, d, mesh)
        if fault == "unchanged":
            return lambda x, w1, w2: x
        if fault == "altered":
            return lambda x, w1, w2: fn(x, w1, w2).at[1, 3, 5].add(1.0)
        if fault == "half_rows":
            # the second half of every rank's rows left out
            return lambda x, w1, w2: fn(
                x.at[:, x.shape[1] // 2:].set(0.0), w1, w2)
        if fault == "no_exchange":
            # every rank runs its own expert on all its rows: the rows for
            # the other experts never cross the wire
            def local(x, w1, w2):
                g, u = jax.numpy.split(
                    jax.numpy.einsum("rtd,rdf->rtf", x, w1), 2, axis=-1)
                return jax.numpy.einsum("rtf,rfd->rtd",
                                        jax.nn.silu(g) * u, w2)
            return local
        raise ValueError(fault)

    monkeypatch.setattr(MoEDispatch, "build", broken)


@pytest.mark.parametrize("fault",
                         ["unchanged", "altered", "half_rows", "no_exchange"])
def test_kernel_fault_is_not_correct(monkeypatch, fault):
    _planted(monkeypatch, fault)
    result, checks = helpers.run(kernel_cell(), 4, 0.2, 0, jax.devices()[:4])
    assert not result["correct"], checks
    assert checks["rel_err"]["value"] > checks["rel_err"]["limit"]
    assert np.isfinite(checks["rel_err"]["value"])
