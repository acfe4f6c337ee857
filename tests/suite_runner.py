"""Run a tests/scripts/ suite in a CPU-only subprocess with simulated devices.

jax pins the device count at first init, so every multi-device suite runs
in its own process with ``JAX_PLATFORMS=cpu`` and XLA_FLAGS set (a child
process could not share a TPU with its parent anyway). The Pallas kernels
run in the TPU interpreter.
"""
import os
import pathlib
import subprocess
import sys

SCRIPTS = pathlib.Path(__file__).parent / "scripts"
SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def run_script(name, devices=4, timeout=1500, args=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # The TPU interpreter parks one CPU-client thread per simulated device
    # at a kernel's entry barrier and needs more to stage buffers. The
    # client's pool has one thread per host CPU unless PJRT_NPROC says
    # otherwise, so a mesh as wide as the host would deadlock.
    env["PJRT_NPROC"] = str(max(os.cpu_count() or 1, 2 * devices))
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, str(SCRIPTS / name), *args],
                          env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        f"{name} failed\nSTDOUT:\n{proc.stdout[-4000:]}\n"
        f"STDERR:\n{proc.stderr[-4000:]}")
    return proc.stdout
