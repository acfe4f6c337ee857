"""l0 schedule sanitizer (core/verify.py) + its cascade wiring.

Tier-1 coverage of the static-verification contract that needs no
devices:

  * every sanitized schedule the four builders emit — healthy and
    degraded, across the ``TUNABLES['contexts']`` grid and the lowering
    knob grid — passes l0 with zero diagnostics (no false positives);
  * every seeded mutation class in :data:`MUTATION_CLASSES` is rejected
    with its class-specific checker code as the *first* diagnostic;
  * ``CascadeEvaluator`` runs l0 ahead of l1/l2: a failing report stops
    the candidate at level 0 with a ``"l0:<code>"`` rejection class and
    l2 is never entered; clean candidates carry an ``"l0"`` timing;
  * ``EvalRecord.rejection``/``.stage`` round-trip JSON, ``stage`` stays
    out of the batch-parity projection, and quarantine entries name the
    level that was in flight;
  * an optional Hypothesis property fuzz over schedule parameters
    (skipped when hypothesis is not installed — the grid sweep above is
    the deterministic floor).
"""
import time

import jax.numpy as jnp
import pytest

from repro.core import extract_hardware_context
from repro.core.cascade import Candidate, CascadeEvaluator
from repro.core.design_space import (CONSERVATIVE, EXPERT_SYSTEMS, TUNABLES,
                                     Directive)
from repro.core.schedule import (make_broadcast_schedule, make_ring_schedule,
                                 make_schedule)
from repro.core.telemetry import EvalRecord
from repro.core.verify import (CHECKS, EXPECTED_CODE, MUTATION_CLASSES,
                               VerifyReport, apply_mutation, lower_dispatch,
                               lower_ring, mutation_corpus, verify_directive,
                               verify_program, verify_schedule)
from repro.launch.mesh import make_mesh
from repro.workloads import WORKLOADS, get_workload
from repro.workloads.base import Workload


@pytest.fixture(scope="module")
def hw():
    return extract_hardware_context(make_mesh((1,), ("x",)))


# ------------------------------------------------- clean schedules pass l0

DISPATCH_COUNTS = ((96, 64, 33, 17), (64, 64, 64, 64), (40, 0, 23, 65))


@pytest.mark.parametrize("counts", DISPATCH_COUNTS)
@pytest.mark.parametrize("tight", [True, False])
def test_dispatch_schedules_pass_l0(counts, tight):
    sched = make_schedule(counts, 32, tight)
    rep = verify_schedule(sched)
    assert rep.ok, rep.summary()
    assert rep.checked.get("programs") == len(TUNABLES["contexts"])


@pytest.mark.parametrize("knobs", [
    dict(tile_fused=True, combine_tile=16),
    dict(tile_fused=True, combine_tile=32, wire_i8=1),
    dict(barrier=True, pipelined=False),
    dict(pipelined=True, wire_i8=1),
    dict(pipelined=False),
])
def test_dispatch_knob_grid_passes_l0(knobs):
    sched = make_schedule((96, 64, 33, 17), 32, True)
    for cx in TUNABLES["contexts"]:
        rep = verify_program(lower_dispatch(sched, cx, **knobs))
        assert rep.ok, f"{knobs} cx={cx}: {rep.summary()}"


@pytest.mark.parametrize("cx", TUNABLES["contexts"])
def test_tile_fused_dispatch_lowers_block_major_keyed_windows(cx):
    """The tile-fused lowering issues DispatchSchedule.send_phases' rounds
    (block-major, each offset in a window of its own), passes l0, and
    carries one window per offset: every wait_send names the window of a
    DMA still in flight, and sends to several peers overlap."""
    sched = make_schedule((43, 14, 5, 2), 8, True)
    prog = lower_dispatch(sched, cx, tile_fused=True, combine_tile=4)
    rep = verify_program(prog)
    assert rep.ok, rep.summary()
    (order, combine), key = sched.send_phases(True, 4)
    for r in range(sched.n):
        dmas = [op for op in prog.ops[r] if op.kind == "dma"]
        disp = [op for op in dmas if op.phase == "dispatch"]
        assert [op.rnd for op in disp] == list(range(len(order)))
        assert [op.dst for op in disp] == [(r - off) % sched.n
                                           for off, _j in order]
        assert [op.key for op in disp] == [key(rnd) for rnd in order]
        comb = [op for op in dmas if op.phase == "combine"]
        assert [op.key for op in comb] == [key(rnd) for rnd in combine]
        assert len({op.key for op in dmas}) == sched.n
        inflight, most = [], 0
        for op in prog.ops[r]:
            if op.kind == "dma":
                inflight.append(op.key)
                most = max(most, len(set(inflight)))
                assert inflight.count(op.key) <= cx
            elif op.kind == "wait_send":
                inflight.remove(op.key)
        assert not inflight and most == sched.n


@pytest.mark.parametrize("planted", ["overflow", "drain"])
def test_tile_fused_dispatch_reports_planted_window_faults(planted):
    """A per-key window overflow (a retire dropped mid-stream) or a
    missing drain (the last retire dropped) is still reported."""
    sched = make_schedule((43, 14, 5, 2), 8, True)
    prog = lower_dispatch(sched, 1, tile_fused=True)
    ops = prog.ops[0]
    retires = [i for i, op in enumerate(ops) if op.kind == "wait_send"]
    # with contexts 1 the first retire precedes a push to its own window
    i = retires[0] if planted == "overflow" else retires[-1]
    mut = prog.clone()
    del mut.ops[0][i]
    rep = verify_program(mut)
    assert not rep.ok
    code = "window-overflow" if planted == "overflow" else "missing-drain"
    assert rep.errors[0].code == code, rep.summary()
    assert rep.errors[0].rank == 0
    if planted == "overflow":
        assert f"window {ops[i].key}" in rep.errors[0].detail


@pytest.mark.parametrize("knobs", [
    dict(barrier=True, pipelined=False),
    dict(pipelined=True),
])
def test_per_source_dispatch_lowers_one_offset_major_window(knobs):
    """The DeepEP (per-source) lowerings keep sched.rounds through one
    window: no DMA carries a key and the depth is the unkeyed mirror's."""
    from repro.core.schedule import send_window_depths
    sched = make_schedule((43, 14, 5, 2), 8, True)
    prog = lower_dispatch(sched, 2, **knobs)
    assert verify_program(prog).ok
    for r in range(sched.n):
        disp = [op for op in prog.ops[r]
                if op.kind == "dma" and op.phase == "dispatch"]
        assert [op.dst for op in disp] == [(r - off) % sched.n
                                           for off, _j in sched.rounds]
        assert {op.key for op in prog.ops[r]} == {None}
        depth, depths = 0, []
        for op in prog.ops[r]:
            if op.kind == "wait_send":
                depth -= 1
            elif op.kind == "dma" and op.phase == "dispatch":
                depth += 1
                depths.append(depth)
            elif op.kind == "dma":
                break                      # the combine window opens
        assert depths == send_window_depths(sched.rounds, 2)


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("counter", [True, False])
def test_broadcast_schedules_pass_l0(fused, counter):
    sched = make_broadcast_schedule(4, 256, 64, fused)
    rep = verify_schedule(sched, knobs={"counter": counter})
    assert rep.ok, rep.summary()


@pytest.mark.parametrize("n", [2, 3, 4])
@pytest.mark.parametrize("fused", [True, False])
def test_ring_schedules_pass_l0(n, fused):
    sched = make_ring_schedule(n, 128, 32, fused)
    for knobs in (dict(counter=True), dict(counter=False),
                  dict(counter=True, pipelined=False),
                  dict(counter=False, eager=True)):
        rep = verify_schedule(sched, knobs=knobs)
        assert rep.ok, f"n={n} fused={fused} {knobs}: {rep.summary()}"


def test_degraded_schedules_pass_l0_with_parent_contract():
    disp = make_schedule((96, 64, 33, 17), 32, True)
    live = (0, 1, 3)
    rep = verify_schedule(disp.degrade(live), parent=disp, live=live)
    assert rep.ok, rep.summary()
    ring = make_ring_schedule(4, 128, 32, True)
    rep = verify_schedule(ring.degrade((0, 2, 3)), parent=ring,
                          live=(0, 2, 3))
    assert rep.ok, rep.summary()


def test_verify_directive_over_expert_system_points(hw):
    """Every deployable (workload, expert-system) point is l0-clean;
    XLA-backed points are vacuous (no collective schedule -> None)."""
    points = dict(EXPERT_SYSTEMS)
    points["CONSERVATIVE"] = CONSERVATIVE
    vacuous = kernelized = 0
    for wname in sorted(WORKLOADS):
        wl = get_workload(wname)
        for pname, d in sorted(points.items()):
            if wl.check(d, hw):
                continue
            rep = verify_directive(wl, d)
            if rep is None:
                assert d.backend == "XLA_COLLECTIVE" or wl.n_dev < 2
                vacuous += 1
            else:
                assert rep.ok, f"{wname}/{pname}: {rep.summary()}"
                kernelized += 1
    assert kernelized >= 10 and vacuous >= 5


# -------------------------------------------------- seeded-mutation corpus


def test_mutation_corpus_covers_every_class():
    corpus = mutation_corpus()
    assert tuple(e["cls"] for e in corpus) == MUTATION_CLASSES
    assert len(MUTATION_CLASSES) >= 7


@pytest.mark.parametrize("entry", mutation_corpus(),
                         ids=lambda e: e["cls"])
def test_mutation_class_caught_with_specific_code(entry):
    rep = entry["run"]()
    assert not rep.ok, f"{entry['cls']} not caught"
    first = rep.errors[0]
    assert first.code == entry["expect"] == EXPECTED_CODE[entry["cls"]]
    assert first.code in CHECKS
    assert first.detail                      # a precise, non-empty message
    assert first.code in rep.summary(limit=1)


def test_apply_mutation_rejects_schedule_level_and_unknown_classes():
    prog = lower_ring(make_ring_schedule(4, 64, 32, True), 2)
    with pytest.raises(ValueError, match="schedule-level"):
        apply_mutation(prog, "non_conserving_respill")
    with pytest.raises(ValueError, match="unknown mutation class"):
        apply_mutation(prog, "flipped_parity")
    # a mutation never aliases its input program
    mut = apply_mutation(prog, "dropped_signal")
    assert verify_program(prog).ok and not verify_program(mut).ok


# ------------------------------------------------------- cascade l0 wiring


class ToyWorkload(Workload):
    """Minimal 1-rank workload: no collective schedule, so the default
    l0 pass is vacuous — the sabotage subclass below injects reports."""
    name = "toy_verify"

    def __init__(self, n_dev=2, sleep_s=0.0):
        self.n_dev = n_dev
        self.sleep_s = sleep_s

    def check(self, d, hw=None):
        return []

    def example_inputs(self, key, mesh):
        return (jnp.ones((4, 4), jnp.float32),)

    def reference(self, x):
        return x * 2.0

    def build(self, d, mesh):
        if self.sleep_s:
            def wedged(x):
                time.sleep(self.sleep_s)
                return x * 2.0
            return wedged
        return lambda x: x * 2.0

    def analytic_cost(self, d, hw):
        return 1e-3 / self.n_dev

    def degrade(self, live_ranks):
        return self

    def state_bytes_per_rank(self):
        return 10 * 2**20


def test_cascade_clean_candidate_times_l0(hw):
    mesh = make_mesh((1,), ("x",))
    ev = CascadeEvaluator(ToyWorkload(), mesh, hw)
    res = ev.evaluate(Candidate(directive=CONSERVATIVE))
    assert res.ok and res.rejection == ""
    rec = res.record
    assert "l0" in rec.levels_s and rec.levels_s["l0"] >= 0.0
    assert rec.stage == "l3" and rec.rejection == ""


def test_cascade_l0_rejection_stops_before_l2(hw):
    mesh = make_mesh((1,), ("x",))
    entry = next(e for e in mutation_corpus()
                 if e["cls"] == "dropped_signal")
    bad_report = entry["run"]()

    class Sabotaged(CascadeEvaluator):
        def _verify_l0(self, d):
            return bad_report

    ev = Sabotaged(ToyWorkload(), mesh, hw)
    l2_calls = {"n": 0}
    orig = ev._run_l2

    def counting(jfn):
        l2_calls["n"] += 1
        return orig(jfn)

    ev._run_l2 = counting
    res = ev.evaluate(Candidate(directive=CONSERVATIVE))
    assert res.level == 0 and res.score == 0.0
    assert res.rejection == "l0:deadlock"
    assert res.diagnostic.startswith("l0 schedule verify failed")
    assert "deadlock" in res.diagnostic
    assert l2_calls["n"] == 0                 # l0 rejected, l2 never ran
    rec = res.record
    assert rec.stage == "l0"
    assert "l0" in rec.levels_s
    assert "l1" not in rec.levels_s and "l2" not in rec.levels_s


def test_cascade_invalid_directive_tagged(hw):
    mesh = make_mesh((1,), ("x",))

    class Picky(ToyWorkload):
        def check(self, d, hw=None):
            return ["toy rejects everything"]

    ev = CascadeEvaluator(Picky(), mesh, hw)
    res = ev.evaluate(Candidate(directive=CONSERVATIVE))
    assert res.level == 0 and res.rejection == "invalid"
    assert res.record.rejection == "invalid"


def test_quarantine_entry_names_stage_in_flight(hw):
    mesh = make_mesh((1,), ("x",))
    w = ToyWorkload(sleep_s=5.0)
    ev = CascadeEvaluator(w, mesh, hw, timeout_s=0.5)
    res = ev.evaluate(Candidate(directive=Directive(
        "PALLAS_RDMA", "SIGNAL", "TILE_FUSED")))
    assert res.quarantined and res.rejection == "quarantine"
    entry = ev.quarantine_report()[0]
    assert entry["stage"] in ("l0", "l1", "l2", "l3")
    assert f"at {entry['stage']}" in res.diagnostic
    assert res.record.rejection == "quarantine"


# ------------------------------------------------- telemetry record fields


def test_eval_record_rejection_round_trips_stage_stays_out_of_parity():
    rec = EvalRecord(cid=7, level=0, score=0.0, rejection="l0:slot-reuse",
                     stage="l0", levels_s={"l0": 0.01},
                     diagnostic="l0 schedule verify failed: ...")
    back = EvalRecord.from_json(rec.to_json())
    assert back.rejection == "l0:slot-reuse" and back.stage == "l0"
    det = rec.deterministic_dict()
    assert det["rejection"] == "l0:slot-reuse"
    assert "stage" not in det and "levels_s" not in det


# --------------------------------------------- hypothesis property (fuzz)


def test_property_sanitized_schedules_pass_l0():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=25, deadline=None)
    @hyp.given(
        kind=st.sampled_from(["dispatch", "broadcast", "ring"]),
        n=st.integers(min_value=2, max_value=5),
        size=st.integers(min_value=1, max_value=200),
        tile=st.sampled_from([8, 16, 32, 64]),
        flag=st.booleans(),
        cx=st.sampled_from(tuple(TUNABLES["contexts"])),
        seed=st.integers(min_value=0, max_value=2**16),
    )
    def prop(kind, n, size, tile, flag, cx, seed):
        if kind == "dispatch":
            counts = tuple((seed * (i + 3) + size) % 97 for i in range(n))
            sched = make_schedule(counts, max(1, tile // 2), flag)
        elif kind == "broadcast":
            sched = make_broadcast_schedule(n, max(size, 1), tile, flag)
        else:
            sched = make_ring_schedule(n, max(size, 1), tile, flag)
        rep = verify_schedule(sched, contexts=(cx,))
        assert rep.ok, rep.summary()
        if sched.n > 2:
            live = tuple(r for r in range(sched.n) if r != sched.n - 1)
            rep = verify_schedule(sched.degrade(live), contexts=(cx,),
                                  parent=sched, live=live)
            assert rep.ok, rep.summary()

    prop()


def test_report_merge_dedupes_and_truncates():
    prog = lower_ring(make_ring_schedule(4, 64, 32, True), 2)
    mut = apply_mutation(prog, "premature_slot_reuse")
    r1, r2 = verify_program(mut), verify_program(mut)
    merged = VerifyReport.merge([r1, r2], subject="dup")
    assert not merged.ok
    assert len(merged.errors) == len(r1.errors)   # identical rows deduped
    assert merged.subject == "dup"
