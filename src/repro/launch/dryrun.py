import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
derive the roofline terms from the compiled artifact.

CPU-only: it forces 512 placeholder host devices and ``--all`` fans cells
out to subprocesses, so it cannot share a TPU (a chip belongs to one
process at a time). On the chip, use ``chip_smoke.py``.

The lines above MUST stay the first statements in this module — jax locks
the device count on first init, and the production meshes need 512 placeholder
devices. Do not set this flag globally; smoke tests and benches see 1 device.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--jobs 4]
"""
import argparse
import json
import pathlib
import subprocess
import sys
import time

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def run_cell(arch: str, shape_name: str, multi_pod: bool, opts_kw=None,
             mesh=None, verbose=True):
    """Three-compile dry-run for one cell.

    Memory: the scan-over-layers module (the production schedule) — the loop
    body's buffers are allocated once, so the CPU backend's no-cross-layer-
    reuse accounting matches the real per-step working set.

    Cost/collectives: HLO cost analysis counts while-loop bodies once, so
    scan modules undercount per-step work; full unrolls of 30-50-layer models
    take 15+ minutes of GSPMD/CPU codegen. Instead we compile the SAME step
    unrolled at depth R=1 (one repeat unit) and R=2 and extrapolate linearly:
    per_layer = cost(R2) - cost(R1); total = cost(R1) + (R_full-1)*per_layer.
    The R1 module carries everything outside the layer stack (embeddings,
    loss, optimizer bookkeeping for the shared params) exactly once, so the
    extrapolation is exact for layer-homogeneous models (validated against a
    full llama3.2-1b unroll in EXPERIMENTS.md §Dry-run).
    """
    import dataclasses

    import jax
    from repro.configs import get_arch, get_shape
    from repro.core.cost_model import roofline_from_compiled
    from repro.core.hardware import extract_hardware_context
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import input_specs
    from repro.models import StepOptions

    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    if shape_name == "long_500k" and not cfg.supports_long_context:
        return {"arch": arch, "shape": shape_name,
                "skipped": "full-attention arch: needs sub-quadratic attention"}
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    hw = extract_hardware_context(mesh)
    base_kw = dict(flash_threshold=2048, loss_chunk=512)
    base_kw.update(opts_kw or {})
    opts_unroll = StepOptions(scan_layers=False, **base_kw)
    opts_scan = StepOptions(scan_layers=True, **base_kw)
    t0 = time.time()

    def compile_with(c, opts):
        fn, in_sds, in_specs, donate = input_specs(c, shape, mesh, opts)
        with jax.set_mesh(mesh):
            jfn = jax.jit(fn, in_shardings=in_specs, donate_argnums=donate)
            return jfn.lower(*in_sds).compile()

    unit = cfg.repeat_unit
    R = cfg.num_repeats
    enc_per = (cfg.enc_layers // R) if cfg.is_encoder_decoder else 0

    def depth_cfg(k):
        kw = {"num_layers": k * unit}
        if cfg.is_encoder_decoder:
            kw["enc_layers"] = k * enc_per
        return dataclasses.replace(cfg, **kw)

    rep1 = roofline_from_compiled(compile_with(depth_cfg(1), opts_unroll),
                                  chips_per_pod=hw.chips_per_pod)
    if R > 1:
        rep2 = roofline_from_compiled(compile_with(depth_cfg(2), opts_unroll),
                                      chips_per_pod=hw.chips_per_pod)
        rep = rep1.extrapolate(rep2, R)
        mem = compile_with(cfg, opts_scan).memory_analysis()
    else:
        rep = rep1
        mem = compile_with(cfg, opts_scan).memory_analysis()
    t_compile = time.time() - t0
    t_lower = 0.0
    if verbose:
        print(mem)
        print({"flops": rep.flops, "bytes accessed": rep.bytes_accessed})

    # useful-FLOPs ratio: 6*N_active*D train, 2*N_active*D prefill/decode
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    model_flops = (6 if shape.kind == "train" else 2) * n_active * tokens
    per_dev_model_flops = model_flops / hw.n_chips
    arg_b = mem.argument_size_in_bytes
    tmp_b = mem.temp_size_in_bytes
    out_b = mem.output_size_in_bytes
    alias_b = mem.alias_size_in_bytes
    peak = arg_b + tmp_b + max(0, out_b - alias_b)
    # The CPU backend's buffer accounting does not model intra-body reuse, so
    # temp_bytes is an upper bound. Analytic activation estimate (documented
    # in EXPERIMENTS.md §Dry-run): remat residuals per layer + working set.
    dp = max(1, min(hw.n_chips // 16, shape.global_batch))
    B_l = max(1, shape.global_batch // dp)
    S = shape.seq_len if shape.kind != "decode" else 1
    d = cfg.d_model
    resid = cfg.num_layers * B_l * S * d * 2 if shape.kind == "train" else 0
    if base_kw.get("sp_residuals"):
        resid //= 16                     # remat carries sequence-sharded (TP)
    work = 8 * B_l * S * d * 4
    analytic = arg_b + resid + work
    # corrected memory term floored at one full read of the live arguments
    # (weights + cache must cross HBM at least once per step on any target)
    summ = rep.summary()
    summ["memory_corrected_s"] = max(
        summ["memory_corrected_s"], arg_b / hw.chip.hbm_bw)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in hw.mesh_shape),
        "n_chips": hw.n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {"argument_bytes": arg_b, "output_bytes": out_b,
                   "temp_bytes": tmp_b, "alias_bytes": alias_b,
                   "peak_bytes": peak,
                   "analytic_peak_bytes": int(analytic),
                   "fits_hbm": bool(peak <= hw.chip.hbm_bytes),
                   "fits_hbm_analytic": bool(analytic <= hw.chip.hbm_bytes)},
        "roofline": summ,
        "model_flops": model_flops,
        "useful_flops_ratio": (per_dev_model_flops / rep.flops
                               if rep.flops else 0.0),
        "collective_schedule": [c.describe() for c in sorted(
            rep.collectives, key=lambda c: -c.wire_bytes)[:20]],
    }
    if verbose:
        print(json.dumps({k: result[k] for k in
                          ("arch", "shape", "mesh", "roofline",
                           "useful_flops_ratio")}, indent=1, default=str))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument("--moe-overlap", action="store_true")
    ap.add_argument("--moe-quantize", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--kv-block", type=int, default=1024)
    ap.add_argument("--flash-threshold", type=int, default=8192)
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--sp-residuals", action="store_true")
    ap.add_argument("--loss-chunk", type=int, default=512)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    if args.all:
        from repro.configs import cells
        jobs = []
        for a, s, skip in cells():
            for mp in (False, True):
                tag = f"{a}__{s}__{'multi' if mp else 'single'}"
                out = ARTIFACTS / f"{tag}.json"
                if out.exists():
                    continue
                if skip:
                    out.write_text(json.dumps(
                        {"arch": a, "shape": s, "skipped": skip}))
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", a, "--shape", s, "--out", str(out)]
                if mp:
                    cmd.append("--multi-pod")
                jobs.append((tag, cmd))
        running = []
        while jobs or running:
            while jobs and len(running) < args.jobs:
                tag, cmd = jobs.pop(0)
                print("START", tag, flush=True)
                running.append((tag, subprocess.Popen(
                    cmd, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)))
            for tag, proc in list(running):
                if proc.poll() is not None:
                    running.remove((tag, proc))
                    status = "OK" if proc.returncode == 0 else "FAIL"
                    print(f"DONE {tag}: {status}", flush=True)
                    if proc.returncode != 0:
                        err = proc.stderr.read().decode()[-2000:]
                        (ARTIFACTS / f"{tag}.err").write_text(err)
            time.sleep(2)
        return

    opts_kw = dict(moe_overlap=args.moe_overlap, moe_quantize=args.moe_quantize,
                   remat=not args.no_remat, kv_block=args.kv_block,
                   flash_threshold=args.flash_threshold,
                   seq_parallel=args.seq_parallel,
                   sp_residuals=args.sp_residuals, loss_chunk=args.loss_chunk)
    res = run_cell(args.arch, args.shape, args.multi_pod, opts_kw)
    if args.out:
        pathlib.Path(args.out).write_text(json.dumps(res, indent=1, default=str))


if __name__ == "__main__":
    main()
