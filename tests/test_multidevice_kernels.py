"""Remote-DMA kernel suites on simulated devices (CPU-only, the TPU
interpreter): ring_attention + kv_shuttle, gemm_allgather, and every
workload's directive equivalence."""
from suite_runner import run_script


def test_ring_attention_kernel_sweep():
    out = run_script("ring_kernel_suite.py")
    assert "ALL OK" in out


def test_collective_kernels():
    out = run_script("collective_kernels_suite.py")
    assert "ALL OK" in out


def test_gemm_allgather_8rank():
    """The executable counterpart of the fig6 sweep at a wider mesh
    (ROADMAP open item): the collective suite's budget-capped path at 8
    simulated ranks — FLUX + DEFERRED broadcast cascades to l3, fused and
    deferred numerics vs the oracle."""
    out = run_script("collective_kernels_suite.py", devices=8,
                     args=["--n-dev", "8"])
    assert "ALL OK" in out
    assert "flux l3 ok at 8 ranks" in out


def test_workload_directives_verify():
    out = run_script("workload_suite.py")
    assert "ALL OK" in out
