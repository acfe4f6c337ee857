"""Ring-workload kernels at simulated ranks: ring_attention (4-rank ring)
and kv_shuttle (2-rank prefill→decode), both realized against the shared
``core/schedule.py::RingSchedule``.

Covers the acceptance criteria that need devices:
  * the TILE_FUSED + COUNTER (FLUX-ring) point and the DEFERRED kernel
    point evaluate to l3 through the full cascade for BOTH ring workloads
    under interpret mode;
  * chunked kernel numerics match the oracle AND the executable host
    baseline across kv_chunk values (including a non-divisor the sanitizer
    must repair), completion/placement/ordering realizations, causal
    masks, and send-window depths;
  * a slow-path diff patch proposing any TUNABLES grid value survives the
    cascade (sanitizer coverage at 4 ranks);
  * race/deadlock freedom of the chunk-rotating path is proven by the
    static verifier (``core/verify.py`` — the same checker the cascade
    runs at l0, so there is exactly one race checker in the repo), and a
    seeded premature-slot-reuse mutation is caught.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import extract_hardware_context
from repro.core.cascade import Candidate, CascadeEvaluator
from repro.core.design_space import EXPERT_SYSTEMS, Directive
from repro.kernels.ref import kv_shuttle_ref, ring_attention_ref
from repro.kernels.kv_shuttle import kv_shuttle
from repro.kernels.ring_attention import ring_attention
from repro.launch.mesh import make_mesh
from repro.workloads import get_workload

D = Directive
FLUX = EXPERT_SYSTEMS["FLUX"]
mesh4 = make_mesh((4,), ("x",))
mesh2 = make_mesh((2,), ("x",))
key = jax.random.PRNGKey(0)

# ---- cascade: the FLUX-ring (TILE_FUSED + COUNTER) and DEFERRED kernel
# points evaluate to l3 at 4 ranks under interpret mode. The workload
# carries the paper deployment shape (the l3 model's shape); example
# inputs shrink the executable l2 verify automatically.
w = get_workload("ring_attention", n_dev=4, BH=96, seq=4096, hd=64)
hw = extract_hardware_context(mesh4)
ev = CascadeEvaluator(w, mesh4, hw)

res_f = ev.evaluate(Candidate(directive=FLUX))
assert res_f.level == 3, (res_f.level, res_f.diagnostic)
assert res_f.score > 0
print(f"cascade ring_attention flux l3 ok ({res_f.diagnostic})")

deferred = D("PALLAS_RDMA", "SIGNAL", "DEFERRED", "LOCAL", "KERNEL",
             "PER_PEER", "RELEASE", 2)
res_d = ev.evaluate(Candidate(directive=deferred))
assert res_d.level == 3, (res_d.level, res_d.diagnostic)
host_cost = w.analytic_cost(D("XLA_COLLECTIVE", placement="DEFERRED"), hw)
assert res_f.t_model_ms < res_d.t_model_ms < host_cost * 1e3
print("cascade ring_attention deferred l3 ok (flux < deferred < host)")

# a slow-path diff patch may propose any TUNABLES grid value — including a
# kv_chunk that does not divide Sl; the sanitizer must keep the evaluator
# alive and still reach l3
res_bad = ev.evaluate(Candidate(directive=FLUX.with_tunable("kv_chunk", 48)))
assert res_bad.level == 3, (res_bad.level, res_bad.diagnostic)
print("cascade ring_attention non-divisor kv_chunk ok (sanitized)")

# ---- cascade: kv_shuttle FLUX + chained points to l3 (2-rank shuttle,
# deployment shape for the l3 model; example inputs stay small)
wk = get_workload("kv_transfer")
hwk = extract_hardware_context(mesh2)
evk = CascadeEvaluator(wk, mesh2, hwk)
res_kf = evk.evaluate(Candidate(directive=FLUX))
assert res_kf.level == 3, (res_kf.level, res_kf.diagnostic)
res_kc = evk.evaluate(Candidate(
    directive=D("PALLAS_RDMA", "SIGNAL", "STREAM_SPLIT", contexts=2)))
assert res_kc.level == 3, (res_kc.level, res_kc.diagnostic)
assert res_kf.t_model_ms < res_kc.t_model_ms
print("cascade kv_shuttle flux + chained l3 ok (flux < chained)")

# ---- ring kernel numerics: chunked realizations vs oracle AND the
# executable host baseline bit-path
for (BH, Sl, hd) in [(2, 64, 64), (4, 128, 64), (1, 128, 128)]:
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (4, BH, Sl, hd),
                                 jnp.float32) for i in range(3))
    for causal in (True, False):
        ref = ring_attention_ref(q, k, v, causal=causal)
        for kw in [dict(fused=True, counter=True, kv_chunk=32, contexts=1),
                   dict(fused=True, counter=True, kv_chunk=32, contexts=2),
                   dict(fused=True, counter=True, kv_chunk=Sl, contexts=2),
                   dict(fused=True, counter=False, kv_chunk=32, contexts=2),
                   dict(fused=True, counter=True, kv_chunk=48, contexts=4),
                   dict(pipelined=True), dict(pipelined=True, eager_wait=True),
                   dict(pipelined=False)]:
            out = jax.jit(lambda a, b, c: ring_attention(
                a, b, c, mesh4, causal=causal, **kw))(q, k, v)
            assert not np.any(np.isnan(np.asarray(out))), (BH, Sl, hd, kw)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
                err_msg=str((BH, Sl, hd, causal, kw)))
print("ring_attention numerics ok (chunked flux/signal/pipelined/deferred)")

# the chunk-fused kernel also matches the executable host baseline bit-path
wv4 = get_workload("ring_attention", n_dev=4, BH=4, seq=512, hd=64)
inputs = wv4.example_inputs(jax.random.PRNGKey(9), mesh4)
host_out = np.asarray(jax.jit(wv4.host_baseline(mesh4))(*inputs))
flux_out = np.asarray(jax.jit(wv4.build(FLUX, mesh4))(*inputs))
err = np.max(np.abs(flux_out - host_out)) / (np.max(np.abs(host_out)) + 1e-9)
assert err < 2e-3, err
print("ring_attention flux matches host baseline")

# ---- kv_shuttle numerics: chunked + chained realizations
for (T, d, dk) in [(64, 128, 64), (128, 256, 128)]:
    x_real = jax.random.normal(key, (T, d), jnp.float32)
    x = jnp.stack([x_real, jnp.zeros_like(x_real)])
    wkm = jax.random.normal(jax.random.fold_in(key, 2), (d, dk), jnp.float32)
    wvm = jax.random.normal(jax.random.fold_in(key, 3), (d, dk), jnp.float32)
    kr, vr = kv_shuttle_ref(x_real, wkm, wvm)
    for kw in [dict(chained=True), dict(chained=False),
               dict(fused=True, counter=True, kv_chunk=32, contexts=2),
               dict(fused=True, counter=True, kv_chunk=T, contexts=1),
               dict(fused=True, counter=False, kv_chunk=48, contexts=4)]:
        ko, vo = kv_shuttle(x, wkm, wvm, mesh2, **kw)
        np.testing.assert_allclose(np.asarray(ko[1]), np.asarray(kr),
                                   atol=2e-4, rtol=2e-4, err_msg=str((T, kw)))
        np.testing.assert_allclose(np.asarray(vo[1]), np.asarray(vr),
                                   atol=2e-4, rtol=2e-4, err_msg=str((T, kw)))
print("kv_shuttle ok (chained + chunk-fused)")

# ---- race/deadlock freedom of the chunk-rotating path: the static
# verifier (the cascade's l0 checker — one checker for suite and search)
# proves the slot-reuse/credit-handshake contract over the whole ring
# grid, then must catch a seeded premature-slot-reuse mutation with a
# class-specific diagnostic.
from repro.core.schedule import make_ring_schedule
from repro.core.verify import apply_mutation, verify_program, verify_schedule

for n, fused, counter in [(4, True, True), (4, True, False),
                          (4, False, True), (2, True, True)]:
    sched = make_ring_schedule(n, 64, 32, fused)
    rep = verify_schedule(sched, knobs=dict(counter=counter))
    assert rep.ok, rep.summary()
    live = tuple(range(n - 1)) if n > 2 else None
    if live:
        drep = verify_schedule(sched.degrade(live), parent=sched, live=live)
        assert drep.ok, drep.summary()
print("static race verifier green over the ring grid (incl. degraded)")

from repro.core.verify import lower_ring

prog = lower_ring(make_ring_schedule(4, 64, 32, True), 2, counter=True)
mut = apply_mutation(prog, "premature_slot_reuse")
mrep = verify_program(mut)
assert not mrep.ok and mrep.errors[0].code == "slot-reuse", mrep.summary()
print(f"seeded slot-reuse race caught: {mrep.errors[0]}")
print("ALL OK")
