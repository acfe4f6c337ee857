"""On-chip smoke test of the system's two user paths, in one process.

    python chip_smoke.py              # one TPU chip: serving, then search
    python chip_smoke.py --chips 4    # four chips: the collective kernels

One chip:
  * serving — granite-moe-3b-a800m at full width (random weights drawn
    from ``--seed``) serves a handful of greedy requests of different
    prompt lengths through ``Engine.serve`` and a ``Scheduler``. Every
    request must return its ``max_new_tokens`` in-vocabulary tokens,
    match the engine's one-request ``generate`` path, and come from finite
    logits.
  * search — ``fast_path`` for moe_dispatch and gemm_allgather at
    ``n_dev=1`` through ``CascadeEvaluator(wallclock=True)``, then the
    DeepEP-NVL and FLUX points. Each must reach cascade level 3 (its l2
    output matches ``Workload.reference``) with a Mosaic-compiled kernel.

``--chips 4`` runs only the multi-chip path: the moe_dispatch DeepEP-NVL
and FLUX points and the gemm_allgather FLUX point on a 4-rank ``("x",)``
mesh, each through the cascade against the workload reference; the
cascade's own l2 output is then checked against the XLA host baseline,
and shard by shard, on four distinct devices.

The last line of stdout is ``{"ok": true, "device": {...}}``; any failure
exits non-zero without it. Off a TPU the script refuses to run. The
``t_wall_ms`` it prints are small-shape smoke timings, not benchmarks.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "granite-moe-3b-a800m"
PROMPT_LENS = (16, 27, 38, 49)      # decode positions never coincide
NEW_TOKENS = 8
SEARCH_POINTS = ("DeepEP (NVL)", "FLUX")
MULTICHIP_POINTS = (("moe_dispatch", ("DeepEP (NVL)", "FLUX")),
                    ("gemm_allgather", ("FLUX",)))


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(msg, flush=True)


def serving_phase(seed):
    import jax
    import numpy as np
    from repro.configs import get_arch
    from repro.launch.serve import build_engine, make_requests, serve

    cfg = get_arch(ARCH)
    t0 = time.perf_counter()
    eng = build_engine(cfg, seed=seed,
                       max_seq=max(PROMPT_LENS) + NEW_TOKENS + 1)
    leaves = jax.tree.leaves(eng.params)
    jax.block_until_ready(leaves)
    n_params = sum(x.size for x in leaves)
    n_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    log(f"[serve] {cfg.name}: {n_params / 1e9:.3f} B params, "
        f"{n_bytes / 2**30:.2f} GiB, experts {cfg.num_experts} padded to "
        f"{cfg.num_experts_padded}; init {time.perf_counter() - t0:.1f}s")

    # every decode step's logits must be finite: wrap the engine's jitted
    # step (serve and generate below both run through it)
    decode, decode_finite = eng._decode, []

    def checked_decode(*a):
        logits, cache = decode(*a)
        decode_finite.append(bool(jax.numpy.all(jax.numpy.isfinite(logits))))
        return logits, cache

    eng._decode = checked_decode
    reqs = make_requests(cfg, PROMPT_LENS, NEW_TOKENS, seed)
    t0 = time.perf_counter()
    out = serve(eng, reqs)
    log(f"[serve] Engine.serve: {len(out)} requests in "
        f"{time.perf_counter() - t0:.1f}s (compile included)")
    check(sorted(out) == [r.rid for r in reqs],
          f"served {sorted(out)}, submitted {[r.rid for r in reqs]}")
    for r in reqs:
        toks = np.asarray(out[r.rid])
        log(f"[serve] request {r.rid}: prompt_len={r.prompt_len} "
            f"tokens={toks.tolist()}")
        check(toks.shape == (r.max_new_tokens,),
              f"request {r.rid}: {toks.shape[0]} tokens, "
              f"wanted {r.max_new_tokens}")
        check(bool(np.all((toks >= 0) & (toks < cfg.vocab_size))),
              f"request {r.rid}: token outside [0, {cfg.vocab_size})")
        batch = {"tokens": jax.numpy.asarray([r.prompt], jax.numpy.int32)}
        # the engine's jitted prefill at this prompt's shape (compiled
        # by serve above): its last-position logits must be finite
        logits, _ = eng._prefill(eng.params, batch)
        check(bool(jax.numpy.all(jax.numpy.isfinite(logits))),
              f"request {r.rid}: non-finite prefill logits")
        ref = np.asarray(eng.generate(batch, r.max_new_tokens))[0]
        check(np.array_equal(ref, toks),
              f"request {r.rid}: serve {toks.tolist()} != "
              f"generate {ref.tolist()}")
    # serve decodes NEW_TOKENS - 1 steps per request (no two requests
    # share a position), generate() as many again
    want = 2 * len(reqs) * (NEW_TOKENS - 1)
    check(len(decode_finite) == want and all(decode_finite),
          f"decode logits: {decode_finite.count(False)} of "
          f"{len(decode_finite)} steps non-finite, {want} steps expected")
    log(f"[serve] {len(decode_finite)} decode steps, all logits finite")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"[serve] peak device memory "
            f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB")
    log("[serve] ok: every request served, in vocabulary, finite prefill "
        "and decode logits, equal to generate()")


def _point(wl, name):
    from repro.core import EXPERT_SYSTEMS
    return dataclasses.replace(
        EXPERT_SYSTEMS[name],
        tunables=tuple(sorted(wl.default_tunables().items())))


def _evaluate(ev, wl, d, label):
    """One cascade evaluation that must reach l3 with a Mosaic kernel."""
    from repro.core import Candidate
    cand = Candidate(directive=d, mutation=label)
    res = ev.evaluate(cand)
    log(f"[search] {wl.name} n_dev={wl.n_dev} {label}: level={res.level} "
        f"t_model_ms={res.t_model_ms:.4f} t_wall_ms={res.t_wall_ms:.4f} "
        f"rejection={res.rejection or '-'}")
    check(not res.quarantined, f"{wl.name}/{label} quarantined")
    check(res.level == 3, f"{wl.name}/{label} stopped at level "
          f"{res.level}: {res.diagnostic[-800:]}")
    check("tpu_custom_call" in cand.code_text,
          f"{wl.name}/{label}: no Mosaic kernel in the lowered program")
    return res


def _evaluator(wl, mesh):
    from repro.core import CascadeEvaluator, extract_hardware_context

    class KeepingEvaluator(CascadeEvaluator):
        """Keeps the output of the last l2 execution, the program the
        cascade verified, for the checks that follow it."""
        last_l2 = None

        def _run_l2(self, jfn):
            self.last_l2 = super()._run_l2(jfn)
            return self.last_l2

    hw = extract_hardware_context(mesh)
    # l2 executions stay sequential on the chip (one program at a time)
    return KeepingEvaluator(wl, mesh, hw, wallclock=True,
                            batch_workers=1), hw


def search_phase():
    import jax
    from repro.compat import make_mesh
    from repro.core import fast_path
    from repro.workloads import get_workload

    mesh = make_mesh((1,), ("x",), devices=jax.devices()[:1])
    for wname in ("moe_dispatch", "gemm_allgather"):
        wl = get_workload(wname, n_dev=1)
        ev, hw = _evaluator(wl, mesh)
        log(f"[search] {wname}: {hw.topology_summary}")
        seed = fast_path(wl, mesh, hw, evaluator=ev)
        res = seed.candidate.result
        log(f"[search] {wname} fast_path seed {seed.directive.backend}/"
            f"{seed.directive.placement}: level={res.level} "
            f"t_model_ms={res.t_model_ms:.4f} t_wall_ms={res.t_wall_ms:.4f}")
        check(res.level == 3, f"{wname}: fast_path seed below level 3")
        check("tpu_custom_call" in seed.candidate.code_text,
              f"{wname}: fast_path seed has no Mosaic kernel")
        for name in SEARCH_POINTS:
            _evaluate(ev, wl, _point(wl, name), name)
        check(not ev.quarantine, f"{wname}: quarantined {ev.quarantine}")
    log("[search] ok: seeds and DeepEP-NVL/FLUX points at level 3, "
        "Mosaic-compiled")


def multichip_phase():
    import jax
    import numpy as np
    from repro.compat import make_mesh

    from repro.workloads import get_workload

    mesh = make_mesh((4,), ("x",), devices=jax.devices()[:4])
    for wname, names in MULTICHIP_POINTS:
        wl = get_workload(wname, n_dev=4)
        ev, hw = _evaluator(wl, mesh)
        log(f"[4chip] {wname}: {hw.topology_summary}")
        host = jax.jit(wl.host_baseline(mesh))(*ev.inputs)
        exp = np.asarray(ev.expected, np.float32)
        scale = float(np.max(np.abs(exp))) + 1e-9
        host_err = float(np.max(np.abs(np.asarray(host, np.float32) - exp)))
        log(f"[4chip] {wname} XLA host baseline vs reference: rel err "
            f"{host_err / scale:.3e}")
        for name in names:
            _evaluate(ev, wl, _point(wl, name), name)
            out = ev.last_l2                 # the cascade's own l2 output
            # each device's shard against its slice of the reference
            devs, shard_err = set(), 0.0
            for sh in out.addressable_shards:
                devs.add(sh.device.id)
                shard_err = max(shard_err, float(np.max(np.abs(
                    np.asarray(sh.data, np.float32) - exp[sh.index]))))
            check(len(devs) == 4, f"{wname}/{name}: output shards on "
                  f"devices {sorted(devs)}, wanted 4 distinct")
            got = np.asarray(out, np.float32)
            err_ref = float(np.max(np.abs(got - exp))) / scale
            err_host = float(np.max(np.abs(
                got - np.asarray(host, np.float32)))) / scale
            shard_err /= scale
            log(f"[4chip] {wname} {name}: shards on devices {sorted(devs)} "
                f"(worst shard rel err {shard_err:.3e}); rel err vs "
                f"reference {err_ref:.3e}, vs host baseline {err_host:.3e}")
            check(max(err_ref, err_host, shard_err) <= ev.rtol,
                  f"{wname}/{name}: rel err {err_ref:.3e} / {err_host:.3e}"
                  f" / {shard_err:.3e} > {ev.rtol}")
    log("[4chip] ok: kernels match reference and XLA host baseline on "
        "four devices")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} chip(s)", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    log(f"[smoke] {devices[0].device_kind} x{len(devices)}; jax "
        f"{jax.__version__}; compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            multichip_phase()
        else:
            serving_phase(args.seed)
            search_phase()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
