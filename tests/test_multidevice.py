"""Multi-device suites: sharded model paths, collective helpers, the
semantics-preserving schedule options and CUCo end to end. CPU-only: each
suite runs in a subprocess on simulated host devices (tests/suite_runner.py).
The remote-DMA kernel suites live in test_multidevice_kernels.py,
test_multidevice_moe.py and test_multidevice_search.py, one file per
pytest-xdist worker."""
from suite_runner import run_script


def test_sharded_model_equivalence():
    out = run_script("sharded_model_suite.py", devices=8)
    assert "ALL OK" in out


def test_cuco_end_to_end():
    out = run_script("cuco_suite.py")
    assert "ALL OK" in out


def test_collective_helpers():
    out = run_script("collectives_suite.py", devices=8)
    assert "ALL OK" in out


def test_schedule_opts_semantics_preserving():
    out = run_script("schedule_opts_suite.py", devices=8)
    assert "ALL OK" in out
