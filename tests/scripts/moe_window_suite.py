"""The moe_dispatch kernel's send windows, on simulated devices (CPU-only,
the TPU interpreter).

The FLUX point (COUNTER + TILE_FUSED) under a scaled 3:1 routing law
(43/14/5/2 rows per rank, ``block_tokens=8``), with and without the
two-stream shared FFN:
  * the output matches the f32 reference;
  * the issued rounds are block-major, dispatch then combine
    (``DispatchSchedule.send_phases``), through one window per offset whose
    depth never exceeds ``contexts``;
  * sends to at least two peers are in flight at once, and the observed
    overlap share equals the schedule's.
The DeepEP (NVL) and (IB) points issue exactly ``sched.rounds`` for
dispatch and combine, through one window with the unkeyed depths.
"""
import jax
import numpy as np

from repro.core.design_space import EXPERT_SYSTEMS
from repro.core.trace import ScheduleProbe
from repro.kernels.moe_dispatch import (make_schedule, moe_dispatch_combine,
                                        swiglu_ffn)
from repro.launch.mesh import make_mesh
from repro.workloads import get_workload

N, T, B = 4, 64, 8
COUNTS = (43, 14, 5, 2)
mesh = make_mesh((N,), ("x",))
w = get_workload("moe_dispatch", n_dev=N, tokens_per_rank=T, d=128, f=128,
                 route_weights=COUNTS)
assert tuple(w._counts(T)) == COUNTS
key = jax.random.PRNGKey(11)
x, w1, w2 = w.example_inputs(key, mesh, T=T)
ref = np.asarray(w.reference(x, w1, w2))
ks = jax.random.split(jax.random.fold_in(key, 1), 3)
xs = jax.random.normal(ks[0], (N, 16, 128))
s1 = jax.random.normal(ks[1], (128, 2 * 64)) / np.sqrt(128)
s2 = jax.random.normal(ks[2], (64, 128)) / np.sqrt(64)
ys_ref = np.stack([np.asarray(swiglu_ffn(xs[r], s1, s2)) for r in range(N)])
sched = make_schedule(COUNTS, B, True)


def rel_err(out, want):
    return np.max(np.abs(out - want)) / (np.max(np.abs(want)) + 1e-9)


def run(point, shared=False):
    d = EXPERT_SYSTEMS[point].with_tunable("block_tokens", B)
    k = w.kernel_knobs(d)
    probe = ScheduleProbe()
    out = moe_dispatch_combine(
        x, w1, w2, mesh, axis="x", counts=COUNTS,
        block_tokens=k["block_tokens"], tight=k["tight"],
        pipelined=k["pipelined"], barrier=k["barrier"],
        tile_fused=k["tile_fused"], combine_tile=k["combine_tile"],
        contexts=k["contexts"], shared=(xs, s1, s2) if shared else None,
        probe=probe)
    if shared:
        y, ys = out
        err = rel_err(np.asarray(ys), ys_ref)
        assert err < 2e-3, (point, "shared", err)
    else:
        y = out
    err = rel_err(np.asarray(y), ref)
    assert err < 2e-3, (point, err)
    return k, probe


for shared in (False, True):
    k, probe = run("FLUX", shared)
    assert k["tile_fused"] and k["contexts"] == 1, k
    phases, window_key = sched.send_phases(True, k["combine_tile"])
    assert list(phases[0]) == sched.block_major_rounds
    summary = probe.check(sched, k["contexts"], phases=phases,
                          key=window_key)
    assert summary["max_depth"] <= k["contexts"], summary
    assert summary["peers_in_flight"] >= 2, summary
    # interpret mode issues the padded schedule (no dummy elision)
    share = sched.overlap_share(k["contexts"], tile_fused=True,
                                combine_tile=k["combine_tile"],
                                elide_dummy=False)
    assert summary["overlap_share"] == share > 0.5, (summary, share)
    if shared:
        assert probe.marks == ["dispatch_issued", "shared_ffn",
                               "dispatch_drained"], probe.marks
    print(f"flux shared={shared}: {summary['rounds']} window entries, "
          f"{summary['peers_in_flight']} peers in flight, overlap share "
          f"{summary['overlap_share']:.3f}")

for point in ("DeepEP (NVL)", "DeepEP (IB)"):
    k, probe = run(point)
    assert not k["tile_fused"], k
    phases, window_key = sched.send_phases(False)
    assert window_key is None
    assert phases == (sched.rounds, sched.rounds)
    summary = probe.check(sched, k["contexts"], phases=phases)
    assert summary["rounds"] == 2 * len(sched.rounds)
    print(f"{point}: issues sched.rounds twice, max depth "
          f"{summary['max_depth']}")

print("ALL OK")
