"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        [--smoke] [--steps N] [--ckpt DIR] [--moe-overlap] [--sp-residuals]

With --smoke (default when fewer devices than the production mesh are
available) the arch's reduced config trains on the local devices; on a real
slice the full config trains on the production mesh. Resumes automatically
from --ckpt; SIGTERM checkpoints and exits cleanly (preemption-safe).
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_arch, reduced
from repro.models import StepOptions
from repro.train import TrainConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=0)
    ap.add_argument("--seq-len", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--moe-overlap", action="store_true")
    ap.add_argument("--moe-quantize", action="store_true")
    ap.add_argument("--sp-residuals", action="store_true")
    ap.add_argument("--loss-chunk", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    n_dev = len(jax.devices())
    production = n_dev >= 256 and not args.smoke
    cfg = get_arch(args.arch) if production else reduced(get_arch(args.arch))
    mesh = None
    if production:
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    elif n_dev >= 2:
        from repro.launch.mesh import make_mesh
        model = 2 if n_dev % 2 == 0 else 1
        mesh = make_mesh((n_dev // model, model), ("data", "model"))

    gb = args.global_batch or (256 if production else 8)
    sl = args.seq_len or (4096 if production else 128)
    opts = StepOptions(moe_overlap=args.moe_overlap,
                       moe_quantize=args.moe_quantize,
                       sp_residuals=args.sp_residuals,
                       loss_chunk=args.loss_chunk)
    tcfg = TrainConfig(steps=args.steps, global_batch=gb, seq_len=sl,
                       ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every,
                       opts=opts)
    print(f"[launch] arch={cfg.name} devices={n_dev} "
          f"mesh={dict(mesh.shape) if mesh else None} batch={gb} seq={sl}")
    losses, last, _ = train(cfg, tcfg, mesh=mesh)
    print(f"[launch] finished at step {last}: "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
