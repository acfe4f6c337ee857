"""The search end to end on simulated devices (CPU-only, the TPU
interpreter): degraded-mode faults, telemetry, and the scaled search,
with their checked-in benchmark artifacts."""
import pathlib

from suite_runner import run_script


def test_fault_suite(tmp_path):
    """Degraded-mode schedules under injected faults: every workload's
    dropped-peer plan cascades to l3 on the surviving mesh, wire faults
    are classified (not crashed on), a wedged candidate quarantines, and
    the healthy-vs-degraded benchmark artifact is emitted."""
    out_json = tmp_path / "BENCH_faults.json"
    out = run_script("fault_suite.py", args=["--out", str(out_json)])
    assert "ALL OK" in out
    import json
    bench = json.loads(out_json.read_text())
    assert set(bench["workloads"]) == {"moe_dispatch", "ring_attention",
                                       "gemm_allgather", "kv_transfer"}
    for entry in bench["workloads"].values():
        assert entry["degraded_ms"] > entry["healthy_ms"] > 0.0


def test_telemetry_suite(tmp_path):
    """Observability layer end to end: the short telemetry search, one
    Perfetto timeline per workload (critical path == analytic_cost), the
    observed-vs-modeled ScheduleProbe check — and the regenerated
    BENCH_search.json must match the checked-in artifact byte for byte
    (the search is deterministic; a diff means the search or its
    telemetry changed and the artifact needs re-checking-in)."""
    out_json = tmp_path / "BENCH_search.json"
    out = run_script("telemetry_suite.py", args=["--out", str(out_json)])
    assert "ALL OK" in out
    import json
    regen = json.loads(out_json.read_text())
    assert regen["schema"] == "bench-search/v2"
    checked_in = pathlib.Path(__file__).parents[1] / "BENCH_search.json"
    assert json.loads(checked_in.read_text()) == regen, (
        "regenerate with: XLA_FLAGS=--xla_force_host_platform_device_count=4 "
        "PYTHONPATH=src python tests/scripts/telemetry_suite.py")


def test_search_scale_suite(tmp_path):
    """Scaled search end to end: batched ring_attention parity at 4 ranks,
    gemm_allgather warm-start economics (cold best reached in <= half the
    fresh evaluations), gemm_allgather -> moe_dispatch transfer seeding —
    and the regenerated BENCH_search_scale.json must match the checked-in
    artifact byte for byte (the searches are deterministic; a diff means
    the search changed and the artifact needs re-checking-in)."""
    out_json = tmp_path / "BENCH_search_scale.json"
    out = run_script("search_scale_suite.py", args=["--out", str(out_json)])
    assert "ALL OK" in out
    import json
    regen = json.loads(out_json.read_text())
    assert regen["schema"] == "bench-search-scale/v1"
    w = regen["warm_start"]
    assert w["warm_fresh_evals_to_best"] <= w["cold_evals_to_best"] // 2
    assert w["coverage_resumed"] >= w["coverage_saved"]
    x = regen["transfer"]
    assert x["transferred_seeds"] > 0
    assert x["transfer_fresh_evals_to_best"] <= x["cold_evals_to_best"] // 2
    checked_in = pathlib.Path(__file__).parents[1] / "BENCH_search_scale.json"
    assert json.loads(checked_in.read_text()) == regen, (
        "regenerate with: XLA_FLAGS=--xla_force_host_platform_device_count=4 "
        "PYTHONPATH=src python tests/scripts/search_scale_suite.py")
