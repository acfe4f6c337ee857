"""The moe_dispatch kernel and the serving tier that runs it, on simulated
devices (CPU-only, the TPU interpreter)."""
import pathlib

from suite_runner import run_script


def test_moe_dispatch_deepep_kernel():
    out = run_script("moe_dispatch_suite.py")
    assert "ALL OK" in out


def test_flux_send_windows_per_destination():
    """The FLUX point under a scaled 3:1 routing law, with and without the
    shared FFN: the output matches the f32 reference, the kernel issues
    block-major rounds through one window per destination with sends to
    several peers in flight, and the DeepEP points issue sched.rounds
    through one window."""
    out = run_script("moe_window_suite.py")
    assert "ALL OK" in out
    assert "flux shared=True" in out


def test_moe_dispatch_8rank():
    """The executable counterpart of the fig4 --n-dev 8 analytic sweep
    (ROADMAP open item): the suite's budget-capped path at 8 simulated
    ranks — Table-3 validity, DeepEP + FLUX cascades to l3, kernel
    numerics, tight-wire accounting."""
    out = run_script("moe_dispatch_suite.py", devices=8,
                     args=["--n-dev", "8"])
    assert "ALL OK" in out
    assert "flux l3 ok at 8 ranks" in out


def test_serving_suite(tmp_path):
    """Kernelized serving tier end to end: the serving_step overlap points
    cascade to l3, the two-stream kernel issues the shared-expert FFN
    inside the dispatch send window, the engine's pallas decode matches
    host greedy tokens through continuous batching, the cache handoff
    rides kv_shuttle, a mid-run rank drop keeps serving — and the
    regenerated BENCH_serving.json must match the checked-in artifact
    (the rows are modeled, hence deterministic; a diff means the cost
    model changed and the artifact needs re-checking-in)."""
    out_json = tmp_path / "BENCH_serving.json"
    out = run_script("serving_suite.py", args=["--out", str(out_json)])
    assert "ALL OK" in out
    import json
    regen = json.loads(out_json.read_text())
    assert regen["schema"] == "bench-rows/v1"
    checked_in = pathlib.Path(__file__).parents[1] / "BENCH_serving.json"
    assert json.loads(checked_in.read_text()) == regen, (
        "regenerate with: XLA_FLAGS=--xla_force_host_platform_device_count=4 "
        "PYTHONPATH=src python tests/scripts/serving_suite.py")
