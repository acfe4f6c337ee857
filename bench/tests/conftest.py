"""The benchmark's own tests run on the CPU: JAX is held to it, with four
virtual devices for the four-rank kernel (Pallas kernels then run in the
TPU interpreter, which parks one thread per device)."""
import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("PJRT_NPROC", "8")

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
