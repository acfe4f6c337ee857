"""Fault model + hardened evaluator (core/faults.py, core/cascade.py).

Tier-1 coverage of the degraded-mode contract that needs no devices:
  * ``respill_counts`` / ``degrade(live_ranks)`` trace-time semantics and
    their ValueError rules;
  * ``fault_cost``: for every workload a dropped-peer plan prices strictly
    greater than healthy but finite, and the straggler stall shrinks with
    deeper send windows (``window_stall_factor``);
  * ``survival_report`` -> ``EvalResult.fault_report`` plumbing and the
    ``fault_weight`` score trade-off;
  * the evaluator's wall-clock timeout/quarantine (a wedged candidate can
    never stall slow_path) and the one-retry-with-backoff l2 seam.
"""
import math
import threading
import time

import jax.numpy as jnp
import pytest

from repro.core import extract_hardware_context
from repro.core.cascade import Candidate, CascadeEvaluator
from repro.core.design_space import CONSERVATIVE, EXPERT_SYSTEMS, Directive
from repro.core.faults import (CORRUPT_WIRE, DROPPED_PEER, STRAGGLER,
                               TRUNCATED_WIRE, FaultPlan, FaultSpec,
                               fault_cost, inject_wire_fault,
                               survival_report)
from repro.core.schedule import (check_live, make_broadcast_schedule,
                                 make_ring_schedule, make_schedule,
                                 respill_counts)
from repro.launch.mesh import make_mesh
from repro.workloads import get_workload
from repro.workloads.base import Workload

WORKLOAD_NAMES = ("moe_dispatch", "ring_attention", "gemm_allgather",
                  "kv_transfer")


@pytest.fixture(scope="module")
def hw():
    return extract_hardware_context(make_mesh((1,), ("x",)))


# ------------------------------------------------------ respill / degrade

def test_respill_conserves_tokens_and_respects_capacity():
    counts = (100, 80, 60, 40)
    new = respill_counts(counts, (0, 1, 3))
    assert len(new) == 3
    assert sum(new) == sum(counts)
    cap = math.ceil(1.25 * sum(counts) / 3)
    assert max(new) <= cap
    # overflow beyond the capacity factor spreads uniformly, still conserving
    over = respill_counts((1000, 0), (1,), capacity_factor=1.25)
    assert over == (1000,)


def test_degrade_rejects_bad_membership():
    s = make_schedule((10, 10, 10, 10))
    with pytest.raises(ValueError):
        s.degrade(())
    with pytest.raises(ValueError):
        s.degrade((0, 4))
    with pytest.raises(ValueError):
        check_live((-1,), 4)
    assert s.degrade((0, 1, 2, 3)) is s


def test_schedule_degrade_is_smaller_same_class():
    d = make_schedule((100, 80, 60, 40), 64, True).degrade((0, 2, 3))
    assert d.n == 3 and sum(d.counts) == 280
    b = make_broadcast_schedule(4, 1024, 128, True).degrade((1, 2))
    assert (b.n, b.M_l, b.tile_m) == (2, 1024, 128)
    r = make_ring_schedule(4, 512, 64, True).degrade((0, 3))
    assert (r.n, r.steps, r.rows) == (2, 1, 512)


@pytest.mark.parametrize("name", WORKLOAD_NAMES)
def test_workload_degrade_reshapes(name):
    w = get_workload(name)
    live = tuple(range(w.n_dev - 1))
    dw = w.degrade(live)
    assert dw.n_dev == w.n_dev - 1
    assert type(dw) is type(w)
    assert w.degrade(tuple(range(w.n_dev))) is w
    with pytest.raises(ValueError):
        w.degrade(())


def test_moe_degrade_respills_routing():
    w = get_workload("moe_dispatch")
    counts = w._counts(w.T)
    dw = w.degrade((0, 1, 3))
    assert int(dw._counts(dw.T).sum()) == int(counts.sum())


# ----------------------------------------------------------- l3 charging

@pytest.mark.parametrize("name", WORKLOAD_NAMES)
@pytest.mark.parametrize("directive", [EXPERT_SYSTEMS["FLUX"], CONSERVATIVE],
                         ids=["flux", "conservative"])
def test_dropped_peer_costs_more_than_healthy_but_finite(name, directive,
                                                         hw):
    w = get_workload(name)
    plan = FaultPlan("drop1", (FaultSpec(DROPPED_PEER, rank=1),))
    healthy = w.analytic_cost(directive, hw)
    degraded = fault_cost(w, directive, hw, plan)
    assert math.isfinite(degraded)
    assert degraded > healthy


def test_straggler_stall_shrinks_with_window_depth(hw):
    w = get_workload("moe_dispatch")
    spec = FaultSpec(STRAGGLER, rank=1, rounds=16, delay_s=100e-6)
    plan = FaultPlan("strag", (spec,))
    shallow = Directive("PALLAS_RDMA", "SIGNAL", "TILE_PIPELINED",
                        contexts=1)
    deep = Directive("PALLAS_RDMA", "SIGNAL", "TILE_PIPELINED", contexts=4)
    stall_1 = fault_cost(w, shallow, hw, plan) \
        - w.analytic_cost(shallow, hw)
    stall_4 = fault_cost(w, deep, hw, plan) - w.analytic_cost(deep, hw)
    assert stall_1 == pytest.approx(16 * 100e-6)       # fully exposed
    assert stall_4 == pytest.approx(stall_1 / 4)       # window-absorbed


def test_plan_with_no_survivor_reports_not_survives(hw):
    w = get_workload("kv_transfer")
    plan = FaultPlan("all-dead", (FaultSpec(DROPPED_PEER, rank=0),
                                  FaultSpec(DROPPED_PEER, rank=1)))
    with pytest.raises(ValueError):
        fault_cost(w, CONSERVATIVE, hw, plan)
    rep = survival_report(w, CONSERVATIVE, hw, (plan,))
    assert not rep["all-dead"]["survives"]
    assert rep["all-dead"]["degraded_ms"] == float("inf")


def test_fault_spec_validates_kind():
    with pytest.raises(ValueError):
        FaultSpec("meteor-strike")


def test_inject_wire_fault_marks_output():
    out = (jnp.ones((8, 4)), jnp.ones((8, 4)))
    bad = inject_wire_fault(out, FaultSpec(CORRUPT_WIRE, rows=2))
    assert bool(jnp.isnan(bad[0][:2]).all())
    short = inject_wire_fault(out, FaultSpec(TRUNCATED_WIRE, rows=3))
    assert bool((short[1][-3:] == 0).all())
    assert bool((short[1][:-3] == 1).all())


# --------------------------------------- hardened evaluator (1-rank tier)

class ToyWorkload(Workload):
    """Minimal workload for evaluator-hardening tests: ``build`` wedges
    (sleeps at trace time) on one placement and is instant on the rest."""
    name = "toy"

    def __init__(self, n_dev=2, wedge_placement=None, sleep_s=5.0):
        self.n_dev = n_dev
        self.wedge_placement = wedge_placement
        self.sleep_s = sleep_s

    def check(self, d, hw=None):
        return []

    def example_inputs(self, key, mesh):
        return (jnp.ones((4, 4), jnp.float32),)

    def reference(self, x):
        return x * 2.0

    def build(self, d, mesh):
        if d.placement == self.wedge_placement:
            def wedged(x):
                time.sleep(self.sleep_s)      # wedges the trace
                return x * 2.0
            return wedged
        return lambda x: x * 2.0

    def analytic_cost(self, d, hw):
        return 1e-3 / self.n_dev

    def degrade(self, live_ranks):
        from repro.core.schedule import check_live
        live = check_live(live_ranks, self.n_dev)
        if len(live) == self.n_dev:
            return self
        return ToyWorkload(n_dev=len(live),
                           wedge_placement=self.wedge_placement)

    def state_bytes_per_rank(self):
        return 10 * 2**20


def test_evaluator_quarantines_wedged_candidate(hw):
    mesh = make_mesh((1,), ("x",))
    w = ToyWorkload(wedge_placement="TILE_FUSED", sleep_s=5.0)
    ev = CascadeEvaluator(w, mesh, hw, timeout_s=0.5)
    t0 = time.perf_counter()
    res = ev.evaluate(Candidate(directive=Directive(
        "PALLAS_RDMA", "SIGNAL", "TILE_FUSED")))
    assert time.perf_counter() - t0 < w.sleep_s      # did not wait it out
    assert res.quarantined and res.level == 0 and res.score == 0.0
    assert "quarantined" in res.diagnostic
    assert len(ev.quarantine_report()) == 1
    # the evaluator survives: the next (healthy) candidate reaches l3
    ok = ev.evaluate(Candidate(directive=Directive(
        "PALLAS_RDMA", "SIGNAL", "DEFERRED")))
    assert ok.ok and not ok.quarantined


def test_evaluator_wedged_l2_hands_the_slot_on(hw):
    """A candidate that wedges inside its l2 execution holds the process's
    one l2 slot. Quarantined at its deadline, it hands the slot on: the
    next candidates, of this evaluator and of another, still reach l3, in
    turn and in a batch, and the abandoned execution publishes nothing
    when it comes back."""
    mesh = make_mesh((1,), ("x",))
    ev = CascadeEvaluator(ToyWorkload(), mesh, hw, timeout_s=2.0)
    orig = ev._run_l2
    wake = threading.Event()
    wedged = []

    def wedging(jfn):
        if not wedged:
            wedged.append(1)
            wake.wait(60.0)              # wedges the execution, not the trace
        return orig(jfn)

    ev._run_l2 = wedging
    try:
        res = ev.evaluate(Candidate(directive=CONSERVATIVE))
        assert res.quarantined and "at l2" in res.diagnostic
        for _ in range(2):
            ok = ev.evaluate(Candidate(directive=CONSERVATIVE))
            assert ok.ok and not ok.quarantined
        other = CascadeEvaluator(ToyWorkload(), mesh, hw, timeout_s=2.0)
        batch = other.evaluate_batch(
            [Candidate(directive=CONSERVATIVE) for _ in range(3)],
            max_workers=3)
        assert all(r.ok and not r.quarantined for r in batch)
    finally:
        wake.set()
    time.sleep(0.5)                      # the abandoned thread finishes
    assert [r.quarantined for r in ev.records] == [True, False, False]
    assert len(ev.quarantine_report()) == 1


def test_evaluator_retries_flaky_l2(hw):
    mesh = make_mesh((1,), ("x",))
    ev = CascadeEvaluator(ToyWorkload(), mesh, hw, backoff_s=0.0)
    orig = ev._run_l2
    calls = {"n": 0}

    def flaky(jfn):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient interpret hiccup")
        return orig(jfn)

    ev._run_l2 = flaky
    res = ev.evaluate(Candidate(directive=CONSERVATIVE))
    assert res.ok and res.retries == 1
    # a persistently failing execution still fails after the retry budget
    ev2 = CascadeEvaluator(ToyWorkload(), mesh, hw, backoff_s=0.0)

    def broken(jfn):
        raise RuntimeError("hard failure")

    ev2._run_l2 = broken
    res2 = ev2.evaluate(Candidate(directive=CONSERVATIVE))
    assert res2.level == 1 and res2.retries == 1
    assert "l2 execution failed" in res2.diagnostic


def test_evaluator_attaches_fault_report_and_prices_fragility(hw):
    mesh = make_mesh((1,), ("x",))
    plan = FaultPlan("drop1", (FaultSpec(DROPPED_PEER, rank=1),))
    base = CascadeEvaluator(ToyWorkload(), mesh, hw)
    res0 = base.evaluate(Candidate(directive=CONSERVATIVE))
    ev = CascadeEvaluator(ToyWorkload(), mesh, hw, fault_plans=(plan,),
                          fault_weight=1.0)
    res = ev.evaluate(Candidate(directive=CONSERVATIVE))
    assert res.ok
    entry = res.fault_report["drop1"]
    assert entry["survives"]
    assert entry["degraded_ms"] > entry["healthy_ms"]
    # the fault penalty is priced into the score, not just reported
    assert res.score < res0.score
    assert res.t_model_ms == res0.t_model_ms
