"""The control at test size: the reference in the program's place,
computed in the precision below the configuration's, must come out as not
correct while the program's own run is correct."""
import types

import jax
import pytest

import helpers
from benchlib.common import device_info


def readings(c, seed, seconds, devices):
    ctx = types.SimpleNamespace(
        cell=c, seed=seed, seconds=seconds, trace=False, devices=devices,
        t_start=0.0, peaks=helpers.PEAKS, trace_dir=None, options=None,
        control=True, device_info=lambda: device_info(devices))
    return c.driver().run(ctx)


@pytest.mark.parametrize("seed", [3, 2**35 + 2])
def test_kernel_control_fails_where_the_program_passes(seed):
    c = helpers.cell("granite-moe-ep4-tiny", "tiny-calls",
                     ["kernel_step_us", "setup_s"], [])
    res = readings(c, seed, 0.2, jax.devices()[:4])
    limit = c.traffic["check"]["rel_err_limit"]
    assert res.correct and res.readings["rel_err"] <= limit
    assert res.readings["control_rel_err"] > limit
