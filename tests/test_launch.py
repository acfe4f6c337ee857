"""The user entry points off the chip: the serving launcher's defaults and
its Engine.serve path, the compile-cache rule, the hardware lookup by
device kind, and chip_smoke.py's refusals (no TPU, a candidate below
cascade level 3 or quarantined)."""
import importlib.util
import json
import pathlib
import subprocess
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.cascade import EvalResult
from repro.core.hardware import CHIPS, V5E, chip_spec

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------ repro.launch.serve


def test_serve_parser_defaults_to_full_width():
    from repro.launch.serve import build_parser, model_config
    arch = "granite-moe-3b-a800m"
    args = build_parser().parse_args(["--arch", arch])
    assert args.reduced is False
    assert model_config(args) == get_arch(arch)
    args = build_parser().parse_args(["--arch", arch, "--reduced"])
    small = model_config(args)
    assert small.name == arch + "-smoke"
    assert small.d_model < get_arch(arch).d_model


def test_serve_launcher_runs_engine_serve():
    """Reduced granite-moe through the launcher's own pieces: every
    request returns its tokens via Engine.serve and the scheduler."""
    from repro.configs import reduced
    from repro.launch.serve import build_engine, make_requests, serve
    cfg = reduced(get_arch("granite-moe-3b-a800m"))
    lens = (9, 5, 3)
    eng = build_engine(cfg, seed=0, max_seq=16)
    reqs = make_requests(cfg, lens, 4, seed=0)
    out = serve(eng, reqs)
    assert sorted(out) == [0, 1, 2]
    for r in reqs:
        toks = np.asarray(out[r.rid])
        assert toks.shape == (4,)
        assert ((toks >= 0) & (toks < cfg.vocab_size)).all()
    assert eng.metrics.counter("serve.prefills").value == len(lens)
    assert eng.metrics.counter("sched.finished").value == len(lens)


def test_serve_launcher_refuses_what_engine_serve_cannot_batch():
    from repro.launch.serve import check_servable
    check_servable(get_arch("granite-moe-3b-a800m"))
    for arch in ("whisper-large-v3", "xlstm-350m"):
        with pytest.raises(ValueError, match="Engine.serve"):
            check_servable(get_arch(arch))


@pytest.mark.parametrize("arch", ["granite-moe-3b-a800m", "xlstm-350m"])
def test_serve_launcher_disaggregated_path(arch):
    """--disaggregated hands the prefill tier's cache to the decode tier,
    for archs Engine.serve refuses too (recurrent state)."""
    from repro.configs import reduced
    from repro.launch.serve import (build_engine, build_parser,
                                    serve_disaggregated)
    assert build_parser().parse_args(
        ["--arch", arch, "--disaggregated"]).disaggregated
    cfg = reduced(get_arch(arch))
    eng = build_engine(cfg, seed=0, max_seq=12)
    out = serve_disaggregated(eng, 2, 6, 3, seed=0)
    assert sorted(out) == [0, 1]
    for toks in out.values():
        toks = np.asarray(toks)
        assert toks.shape == (3,)
        assert ((toks >= 0) & (toks < cfg.vocab_size)).all()
    assert eng.metrics.counter("serve.kv_handoffs").value == 1


# ------------------------------------------------------ compile cache


def test_compile_cache_prefers_env_dir(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []                       # JAX reads the env itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = compile_cache.enable_compile_cache()
    assert fixed == str(ROOT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", fixed)]
    assert compile_cache.enable_compile_cache() == fixed   # never moves


# ------------------------------------------------------ hardware lookup


def test_chip_spec_by_device_kind():
    dev = lambda platform, kind: SimpleNamespace(platform=platform,
                                                 device_kind=kind)
    assert chip_spec(dev("tpu", "TPU v5 lite")) is V5E
    assert chip_spec(dev("cpu", "cpu")) is V5E     # the l3 target off-chip
    with pytest.raises(ValueError, match="no ChipSpec"):
        chip_spec(dev("tpu", "TPU v9 imaginary"))
    assert set(CHIPS.values()) == {V5E}


# ------------------------------------------------------ chip_smoke.py


def test_chip_smoke_refuses_to_run_off_a_tpu(tmp_path):
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(tmp_path)}
    for script in (ROOT / "chip_smoke.py",
                   tmp_path / "chip_smoke.py"):     # alone, outside the repo
        if not script.exists():
            script.write_text((ROOT / "chip_smoke.py").read_text())
        proc = subprocess.run([sys.executable, str(script)], env=env,
                              capture_output=True, text=True, timeout=300,
                              cwd=tmp_path)
        assert proc.returncode != 0, proc.stdout
        assert "needs a TPU" in proc.stderr
        for line in proc.stdout.splitlines():
            with pytest.raises(json.JSONDecodeError):
                json.loads(line)


@pytest.mark.parametrize("res", [
    EvalResult(2, 0.0, rejection="l2:mismatch", diagnostic="rel err"),
    EvalResult(0, 0.0, quarantined=True, rejection="quarantine"),
], ids=["below-l3", "quarantined"])
def test_chip_smoke_fails_candidates_short_of_level3(res):
    smoke = _chip_smoke()

    class Ev:
        def evaluate(self, cand):
            cand.code_text = "custom_call @tpu_custom_call"
            return res

    wl = SimpleNamespace(name="moe_dispatch", n_dev=1)
    with pytest.raises(smoke.SmokeFailure):
        smoke._evaluate(Ev(), wl, object(), "FLUX")
