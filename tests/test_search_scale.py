"""Search-correctness tier for the scaled search (docs/search.md).

The batched cascade and restructured generation loop are only allowed to
make the search *faster*, never *different*:

1. ``CandidateDB.is_novel``'s directive-key index makes exactly the same
   accept/reject decisions as the reference linear scan on a recorded
   proposal stream.
2. ``CascadeEvaluator.evaluate_batch`` matches sequential ``evaluate``
   bit-for-bit (deterministic fields) over a mixed generation — valid,
   l1-fail, l2-mismatch, quarantine-via-wedge, and fault-plan-scored
   candidates — and the l2 fan-out never exceeds the worker bound.
3. Two sequential ``slow_path`` runs of one ``SlowPathConfig`` produce
   identical ``db.history()`` and byte-identical telemetry payloads; a
   third batched run matches both.
"""
import json
import threading
import time

import jax
import pytest

from repro.core import (CONSERVATIVE, Candidate, CandidateDB,
                        CascadeEvaluator, SlowPathConfig,
                        extract_hardware_context, fast_path, random_directive,
                        slow_path)
from repro.core.faults import STRAGGLER, FaultPlan, FaultSpec
from repro.launch.mesh import make_mesh
from repro.workloads import get_workload


@pytest.fixture(scope="module")
def rig():
    wl = get_workload("gemm_allgather", n_dev=1, M=512, K=512, N=512)
    mesh = make_mesh((1,), ("x",))
    hw = extract_hardware_context(mesh)
    return wl, mesh, hw


# ------------------------------------------------------- novelty index (a)


def _reference_is_novel(records, directive, code_text=""):
    """The pre-index implementation: per-proposal linear scan over every
    stored record (directive equality, plus the embedding branch whose
    reject condition also required ``as_dict`` equality)."""
    from repro.core.database import embed_code
    for r in records:
        if r.directive == directive:
            return False
    if code_text:
        q = embed_code(code_text)
        for r in records:
            e = embed_code(r.code_text or r.directive.render())
            if float(q @ e) > 0.995 \
                    and r.directive.as_dict() == directive.as_dict():
                return False
    return True


def test_novelty_index_matches_linear_scan(rig):
    """Replay a recorded proposal stream (mutated + resampled directives,
    heavy with duplicates) through the indexed ``is_novel`` and the
    reference scan: every accept/reject decision must be identical."""
    import random
    wl, _, hw = rig
    rng = random.Random(7)
    traits = wl.traits(hw)
    pool = [random_directive(rng, **traits) for _ in range(12)]
    stream = []
    for i in range(120):
        d = rng.choice(pool)
        if rng.random() < 0.5:      # tunable-refined variant of a pool point
            d = d.with_tunable("tile_m", rng.choice((32, 64, 128)))
        stream.append(d)
    db = CandidateDB()
    for i, d in enumerate(stream):
        want = _reference_is_novel(db.records, d, d.render())
        got = db.is_novel(d, d.render())
        assert got == want, (i, d)
        if got:                      # the search only stores accepted ones
            db.add(Candidate(directive=d))
    assert len(db.records) < len(stream)        # the stream really had dups


# ------------------------------------- batched vs sequential cascade (b/c)


class _Rigged:
    """Workload proxy that rigs specific failure modes by a sentinel
    tunable: ``rig=l1`` raises at build, ``rig=l2`` corrupts the output,
    ``rig=wedge`` sleeps far past the deadline at trace time."""

    def __init__(self, base):
        self._base = base

    def __getattr__(self, name):
        return getattr(self._base, name)

    def build(self, d, mesh):
        mode = d.tunable("rig")
        if mode == "l1":
            raise RuntimeError("rigged l1 build failure")
        if mode == "wedge":
            def wedged(*xs):
                time.sleep(60.0)
            return wedged
        fn = self._base.build(d, mesh)
        if mode == "l2":
            return lambda *xs: jax.tree.map(lambda a: a + 1.0, fn(*xs))
        return fn


class _BoundedEvaluator(CascadeEvaluator):
    """Counts concurrent ``_run_l2`` entries to assert the pool bound."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._lock = threading.Lock()
        self._inflight = 0
        self.max_inflight = 0

    def _run_l2(self, jfn):
        with self._lock:
            self._inflight += 1
            self.max_inflight = max(self.max_inflight, self._inflight)
        try:
            return super()._run_l2(jfn)
        finally:
            with self._lock:
                self._inflight -= 1


def _mixed_generation(seed_directive):
    base = seed_directive
    return [
        Candidate(directive=base, mutation="valid"),
        Candidate(directive=base.with_tunable("rig", "l1"), mutation="l1"),
        Candidate(directive=base.with_tunable("rig", "l2"), mutation="l2"),
        Candidate(directive=base.with_tunable("rig", "wedge"),
                  mutation="wedge"),
        Candidate(directive=base.with_tunable("tile_m", 64),
                  mutation="fault-scored"),
    ]


def test_batched_matches_sequential_mixed_generation(rig):
    wl, mesh, hw = rig
    rigged = _Rigged(wl)
    plan = FaultPlan("straggler", (FaultSpec(STRAGGLER, rank=0, rounds=4,
                                             delay_s=100e-6),))
    # the wedge sleeps 60 s; the deadline leaves the real candidates room
    # to queue behind each other's l2 (one at a time per process) on a
    # loaded host
    mk = lambda: _BoundedEvaluator(rigged, mesh, hw, timeout_s=6.0,
                                   fault_plans=(plan,), fault_weight=0.5)
    seed_d = CONSERVATIVE
    ev_seq, ev_bat = mk(), mk()
    seq = [ev_seq.evaluate(c) for c in _mixed_generation(seed_d)]
    bat = ev_bat.evaluate_batch(_mixed_generation(seed_d), max_workers=3)

    # every deterministic result field agrees pairwise
    for a, b in zip(seq, bat):
        assert (a.level, a.score, a.retries, a.quarantined) \
            == (b.level, b.score, b.retries, b.quarantined)
    assert [r.level for r in seq] == [3, 0, 1, 0, 3]
    assert seq[3].quarantined and bat[3].quarantined
    assert seq[4].record.to_dict()["fault_penalty_ms"] > 0.0

    # the published record / quarantine streams are identical in order
    # and content (wall-clock projection removed)
    assert [r.deterministic_dict() for r in ev_seq.records] \
        == [r.deterministic_dict() for r in ev_bat.records]
    assert [q["diagnostic"] for q in ev_seq.quarantine] \
        == [q["diagnostic"] for q in ev_bat.quarantine]

    # the l2 fan-out stayed inside the requested pool bound
    assert 1 <= ev_bat.max_inflight <= 3
    assert ev_seq.max_inflight == 1


def test_batch_worker_bound_respected(rig):
    wl, mesh, hw = rig
    ev = _BoundedEvaluator(wl, mesh, hw)
    cands = [Candidate(directive=CONSERVATIVE.with_tunable("tile_m", t))
             for t in (16, 32, 64, 128, 256, 16, 32, 64)]
    res = ev.evaluate_batch(cands, max_workers=2)
    assert all(r.ok for r in res)
    assert ev.max_inflight <= 2
    assert len(ev.records) == len(cands)


# --------------------------------------- deterministic slow_path (b)


def test_slow_path_deterministic_and_batched_parity(rig):
    wl, mesh, hw = rig
    seed = fast_path(wl, mesh, hw)
    cfg = SlowPathConfig(islands=2, generations=3, seed=3)
    r1 = slow_path(seed, mesh, hw, cfg)
    r2 = slow_path(seed, mesh, hw, cfg)
    r3 = slow_path(seed, mesh, hw, cfg, batched=True, eval_workers=3)
    assert r1.history == r2.history == r3.history
    p1 = json.dumps(r1.telemetry.payload(), sort_keys=True)
    p2 = json.dumps(r2.telemetry.payload(), sort_keys=True)
    p3 = json.dumps(r3.telemetry.payload(), sort_keys=True)
    assert p1 == p2 == p3
    assert r1.best.score >= r1.seed_score
    # the parity invariant covers the per-record projection too
    assert [r.deterministic_dict()
            for r in r1.telemetry.records] \
        == [r.deterministic_dict() for r in r3.telemetry.records]
