"""Cascade evaluation (paper §3.3): every offspring passes a fast-fail
cascade — l0 static schedule verification (``core/verify.py``: the
symbolic lockstep executor proves deadlock freedom, slot-reuse safety,
window-cap/drain invariants and wire conservation before any tracing),
l1 build+compile, l2 numerical verification against the workload oracle,
l3 benchmark. Score = 10000 / (1 + t_ms); candidates failing l0/l1/l2
score 0 and carry a diagnostic for the feedback loop plus a deterministic
``rejection`` class ("l0:<checker code>", "l1:build", "l2:mismatch", ...)
for telemetry.

l3 on this CPU-only container is the analytic v5e roofline composition of the
workload at its full deployment shape (DESIGN.md §2); ``wallclock=True``
additionally times the small-shape execution (used by ablation benchmarks).

Hardened for unattended search (the slow path runs thousands of candidates
with nobody watching):

* ``timeout_s`` — a per-candidate wall-clock budget. Evaluation runs on a
  daemon worker thread; a candidate that wedges (infinite trace, hung
  interpret) is abandoned at the deadline, recorded in ``quarantine``, and
  scored 0 with ``quarantined=True`` — it can never stall ``slow_path.py``.
* one retry with backoff for flaky l2 *executions* (``l2_retries``): a
  transient runtime error re-runs after ``backoff_s``; a deterministic
  verify mismatch never retries. ``EvalResult.retries`` records the count.
* ``fault_plans`` — fault scenarios (``core/faults.py``) priced at l3 into
  ``EvalResult.fault_report``; ``fault_weight`` folds the mean degraded-ms
  penalty into the score so the search optimizes a (throughput,
  fault-survival) trade-off.

Batched evaluation (docs/search.md — the throughput half of ROADMAP open
item 3): :meth:`CascadeEvaluator.evaluate_batch` evaluates a whole
generation at once: candidates fan out across a bounded
``concurrent.futures`` worker pool (``batch_workers``), which overlaps
their l0/l1 tracing and lowering and l3 costing. The l2 executions
themselves, wall-clock timings included, run one at a time per process
and to completion (``_L2_SLOT``): the TPU interpreter keeps
process-global state that concurrent kernels corrupt, and a timing on a
chip must not share it with another program. Each pool task runs the *same*
guarded per-candidate
cascade the sequential path runs (same ``_run_l2`` seam, same
``timeout_s``/quarantine discipline: the abandonable deadline thread stays
per candidate, so a wedged candidate releases its pool slot at the
deadline), with record/quarantine *publication* deferred and replayed in
input order — so scores, levels, retries, ``EvalResult``s and the
``records``/``quarantine`` streams are identical to calling
:meth:`evaluate` per candidate (wall-clock timings in ``levels_s`` aside).
"""
from __future__ import annotations

import os
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import jax
import numpy as np

from repro.core.design_space import Directive

class _L2Slot:
    """One l2 execution at a time in this process, held from dispatch to
    completion: the Pallas TPU interpreter keeps a process-global shared
    memory that a second concurrent kernel corrupts (observed as spurious
    l2 errors under batched evaluation), and a wall-clock timing on a chip
    must not overlap another program. The slot is owned by a candidate; a
    candidate quarantined at its deadline hands it on, so a wedged
    execution never stalls the candidates queued behind it."""

    def __init__(self):
        self._cv = threading.Condition()
        self._owner = None

    def acquire(self, owner, timeout=None) -> bool:
        with self._cv:
            if not self._cv.wait_for(lambda: self._owner is None, timeout):
                return False
            self._owner = owner
            return True

    def release(self, owner):
        """Free the slot if ``owner`` holds it (a no-op otherwise: an
        abandoned execution that comes back has already handed it on)."""
        with self._cv:
            if self._owner is owner:
                self._owner = None
                self._cv.notify_all()


_L2_SLOT = _L2Slot()


@dataclass
class EvalResult:
    level: int                    # highest level passed (0..3)
    score: float
    t_model_ms: float = float("inf")
    t_wall_ms: float = float("inf")
    diagnostic: str = ""
    hlo_ops: dict = field(default_factory=dict)
    fault_report: dict = field(default_factory=dict)  # plan -> healthy/degraded ms
    quarantined: bool = False     # abandoned at the wall-clock deadline
    retries: int = 0              # flaky-l2 re-executions that were needed
    rejection: str = ""           # deterministic rejection class ("" = passed)
    record: object = None         # telemetry.EvalRecord (every path sets one)

    @property
    def ok(self):
        return self.level >= 3


@dataclass
class Candidate:
    directive: Directive
    gen: int = 0
    island: int = 0
    parent_id: int = -1
    mutation: str = "seed"
    cid: int = -1
    result: EvalResult | None = None
    code_text: str = ""           # jaxpr text of the built program
    cached: bool = False          # result reused from a warm-start store

    @property
    def score(self):
        return self.result.score if self.result else 0.0


class CascadeEvaluator:
    def __init__(self, workload, mesh, hw, *, rtol=2e-3, wallclock=False,
                 verify_inputs=None, timeout_s=None, l2_retries=1,
                 backoff_s=0.05, fault_plans=(), fault_weight=0.0,
                 batch_workers=None):
        self.workload = workload
        self.mesh = mesh
        self.hw = hw
        self.rtol = rtol
        self.wallclock = wallclock
        self.timeout_s = timeout_s
        self.l2_retries = max(0, int(l2_retries))
        self.backoff_s = backoff_s
        self.fault_plans = tuple(fault_plans)
        self.fault_weight = fault_weight
        self.batch_workers = max(1, int(
            batch_workers or min(4, os.cpu_count() or 1)))
        self.quarantine = []          # wedged-candidate diagnostics
        self.records = []             # telemetry.EvalRecord per evaluation
        key = jax.random.PRNGKey(1234)
        self.inputs = verify_inputs or workload.example_inputs(key, mesh)
        self.expected = workload.reference(*self.inputs)

    def evaluate(self, cand: Candidate) -> EvalResult:
        """Evaluate one candidate under the wall-clock budget, publishing
        its record (and quarantine entry, if any) immediately."""
        res, _ = self._guarded(cand, publish=True)
        return res

    def evaluate_batch(self, cands, *, max_workers=None) -> list:
        """Evaluate a whole generation at once — the parity contract
        (docs/search.md): the returned ``EvalResult``s, the appended
        ``records`` and the ``quarantine`` entries are identical to calling
        :meth:`evaluate` per candidate in order (wall timings aside).

        Candidates fan out across a bounded worker pool of at most
        ``max_workers`` (default ``batch_workers``) threads, their l2
        executions serialized by ``_L2_SLOT``; l1
        build/lower and l3 analytic costing ride the same per-candidate
        pass (pure trace-time math — cheap and thread-safe). Each pool task
        keeps the sequential path's per-candidate ``timeout_s`` discipline:
        the abandonable deadline thread is spawned inside the pool task, so
        a wedged candidate frees its pool slot at the deadline instead of
        starving the batch. Publication of records and quarantine entries
        is deferred and replayed in input order after the pool drains."""
        cands = list(cands)
        if not cands:
            return []
        workers = max(1, min(int(max_workers or self.batch_workers),
                             len(cands)))
        outs = [None] * len(cands)

        def one(i):
            outs[i] = self._guarded(cands[i], publish=False)

        if workers == 1:
            for i in range(len(cands)):
                one(i)
        else:
            with ThreadPoolExecutor(max_workers=workers,
                                    thread_name_prefix="cascade-batch") as px:
                list(px.map(one, range(len(cands))))
        results = []
        for res, qentry in outs:
            if res.record is not None:
                self.records.append(res.record)
            if qentry is not None:
                self.quarantine.append(qentry)
            results.append(res)
        return results

    def _guarded(self, cand: Candidate, publish=True):
        """The full timeout-guarded cascade for one candidate: the body
        runs on a daemon thread; past ``timeout_s`` the candidate is
        quarantined (the wedged thread is abandoned, and the l2 slot it
        may hold is handed on) and the caller moves on. Returns ``(result,
        quarantine_entry_or_None)``; with ``publish=False`` nothing is
        appended to ``records``/``quarantine`` — the batch path replays
        publication in input order."""
        if not self.timeout_s:
            return self._evaluate(cand, publish=publish), None
        box = {}

        def run():
            try:
                box["res"] = self._evaluate(cand, publish=publish)
            except BaseException as e:        # surfaced below, never lost
                box["err"] = e

        th = threading.Thread(target=run, daemon=True,
                              name=f"cascade-eval-{cand.cid}")
        t0 = time.perf_counter()
        cand._deadline = t0 + self.timeout_s
        th.start()
        th.join(self.timeout_s)
        if th.is_alive():
            elapsed = time.perf_counter() - t0
            stage = getattr(cand, "_stage", "")
            diag = (f"quarantined: evaluation exceeded {self.timeout_s:.2f}s "
                    "wall-clock (wedged build/execute abandoned"
                    + (f" at {stage}" if stage else "") + ")")
            # flag first: the abandoned thread must not append a late
            # duplicate record if it ever comes back from the wedge
            cand._quarantined = True
            _L2_SLOT.release(cand)
            res = EvalResult(0, 0.0, diagnostic=diag, quarantined=True,
                             rejection="quarantine")
            res = self._record(cand, res, {"quarantine": elapsed},
                               force=True, publish=publish)
            entry = {
                "cid": cand.cid, "directive": repr(cand.directive),
                "elapsed_s": elapsed, "diagnostic": diag, "stage": stage,
                "record": res.record.to_dict()}
            if publish:
                self.quarantine.append(entry)
            return res, entry
        if "err" in box:
            elapsed = time.perf_counter() - t0
            e = box["err"]
            res = EvalResult(0, 0.0, rejection="error",
                             diagnostic="evaluator error:\n" + "".join(
                traceback.format_exception(type(e), e, e.__traceback__))[-1500:])
            return self._record(cand, res, {"error": elapsed},
                                publish=publish), None
        return box["res"], None

    def quarantine_report(self):
        """Diagnostics of every candidate abandoned at the deadline."""
        return list(self.quarantine)

    def _run_l2(self, jfn):
        """The l2 execution boundary — a deliberate seam: tests and fault
        suites wrap it to inject flaky executions or wire faults."""
        return jfn(*self.inputs)

    def _take_l2_slot(self, cand) -> bool:
        """Wait for the process's l2 slot. Past the candidate's deadline
        (plus a margin, so the deadline watcher speaks first) the wait is
        given up: the candidate has been quarantined. A quarantined
        candidate never takes the slot again."""
        if getattr(cand, "_quarantined", False):
            return False
        deadline = getattr(cand, "_deadline", None)
        wait = (None if deadline is None
                else max(0.0, deadline - time.perf_counter()) + 1.0)
        return _L2_SLOT.acquire(cand, wait)

    def _slot_lost(self, cand, levels, publish):
        return self._record(
            cand, EvalResult(1, 0.0, rejection="l2:queue",
                             diagnostic="l2 slot not free before the "
                             "deadline"), levels, publish=publish)

    def _verify_l0(self, d):
        """The l0 static-verification boundary — a seam like
        :meth:`_run_l2`: tests wrap it to inject mutated programs.
        Returns a ``verify.VerifyReport`` or ``None`` when the directive
        realizes no collective schedule (XLA backends, solo tiers) — a
        vacuous pass."""
        from repro.core.verify import verify_directive
        return verify_directive(self.workload, d)

    def _record(self, cand, res: EvalResult, levels, *, fault_penalty_ms=0.0,
                force=False, publish=True) -> EvalResult:
        """Attach the structured telemetry row for one evaluation; every
        evaluate path (success, l1/l2 fail, error, quarantine) routes
        through here. A candidate already quarantined by the deadline
        watcher is skipped unless ``force``d — the abandoned worker thread
        must not append a late duplicate. ``publish=False`` attaches the
        record to the result only; the batch path appends it to
        ``records`` later, in input order."""
        if getattr(cand, "_quarantined", False) and not force:
            return res
        from repro.core.telemetry import EvalRecord
        try:
            knobs = dict(self.workload.kernel_knobs(cand.directive))
        except Exception:
            knobs = {}
        rec = EvalRecord(
            cid=cand.cid, gen=cand.gen, island=cand.island,
            mutation=cand.mutation, directive=repr(cand.directive),
            level=res.level, score=res.score,
            t_model_ms=res.t_model_ms
            if np.isfinite(res.t_model_ms) else None,
            t_wall_ms=res.t_wall_ms if np.isfinite(res.t_wall_ms) else None,
            levels_s={k: float(v) for k, v in levels.items()},
            retries=res.retries, quarantined=res.quarantined,
            fault_penalty_ms=float(fault_penalty_ms), knobs=knobs,
            diagnostic=res.diagnostic,
            elapsed_s=float(sum(levels.values())),
            rejection=res.rejection,
            stage=getattr(cand, "_stage", ""))
        res.record = rec
        if publish:
            self.records.append(rec)
        return res

    def _evaluate(self, cand: Candidate, publish=True) -> EvalResult:
        d = cand.directive
        levels = {}
        # ---- l0: directive validity + static schedule verification ------
        cand._stage = "l0"
        viol = self.workload.check(d, self.hw)
        if viol:
            return self._record(
                cand, EvalResult(0, 0.0, rejection="invalid",
                                 diagnostic="invalid directive: "
                                 + "; ".join(viol)), levels, publish=publish)
        t0 = time.perf_counter()
        vrep = self._verify_l0(d)
        levels["l0"] = time.perf_counter() - t0
        if vrep is not None and not vrep.ok:
            # a structured VerifyError diagnostic: the mutation feedback
            # loop reads the class prefix, telemetry keys on `rejection`
            return self._record(
                cand, EvalResult(0, 0.0,
                                 rejection="l0:" + vrep.errors[0].code,
                                 diagnostic="l0 schedule verify failed: "
                                 + vrep.summary()), levels, publish=publish)
        # ---- l1: build + trace/compile ----------------------------------
        cand._stage = "l1"
        t1 = time.perf_counter()
        try:
            fn = self.workload.build(d, self.mesh)
            jfn = jax.jit(fn)
            lowered = jfn.lower(*self.inputs)
            cand.code_text = lowered.as_text()[:200_000]
        except Exception:
            levels["l1"] = time.perf_counter() - t1
            return self._record(
                cand, EvalResult(0, 0.0, rejection="l1:build",
                                 diagnostic="l1 build/lower failed:\n"
                                 + traceback.format_exc()[-1500:]), levels,
                publish=publish)
        levels["l1"] = time.perf_counter() - t1
        # ---- l2: numerical verification ---------------------------------
        # transient execution errors retry with backoff; a deterministic
        # verify mismatch below never does
        cand._stage = "l2"
        t2 = time.perf_counter()
        retries = 0
        while True:
            if not self._take_l2_slot(cand):
                return self._slot_lost(cand, levels, publish)
            failure = None
            try:
                out = jax.block_until_ready(self._run_l2(jfn))
            except Exception:
                failure = traceback.format_exc()
            finally:
                _L2_SLOT.release(cand)
            if failure is None:
                break
            if retries >= self.l2_retries:
                levels["l2"] = time.perf_counter() - t2
                return self._record(
                    cand, EvalResult(1, 0.0, retries=retries,
                                     rejection="l2:execute",
                                     diagnostic="l2 execution failed:\n"
                                     + failure[-1500:]),
                    levels, publish=publish)
            retries += 1
            time.sleep(self.backoff_s * retries)
        tol = self.rtol
        if d.tunable("wire_i8", 0):
            tol = max(tol, 8e-2)          # quantized wire is lossy by design
        for got, exp in zip(jax.tree.leaves(out),
                            jax.tree.leaves(self.expected)):
            got = np.asarray(got, np.float32)
            exp = np.asarray(exp, np.float32)
            if not np.all(np.isfinite(got)):
                levels["l2"] = time.perf_counter() - t2
                return self._record(
                    cand, EvalResult(1, 0.0, retries=retries,
                                     rejection="l2:nonfinite", diagnostic=(
                        "l2 verify failed: non-finite values (deadlock-free "
                        "but corrupt transfer — check completion/ordering)")),
                    levels, publish=publish)
            err = np.max(np.abs(got - exp)) / (np.max(np.abs(exp)) + 1e-9)
            if err > tol:
                levels["l2"] = time.perf_counter() - t2
                return self._record(
                    cand, EvalResult(1, 0.0, retries=retries,
                                     rejection="l2:mismatch", diagnostic=(
                        f"l2 verify failed: rel err {err:.3e} > {tol:.0e} "
                        f"(placement={d.placement}, "
                        f"completion={d.completion})")), levels,
                    publish=publish)
        levels["l2"] = time.perf_counter() - t2
        # ---- l3: benchmark ----------------------------------------------
        cand._stage = "l3"
        t3 = time.perf_counter()
        t_model = self.workload.analytic_cost(d, self.hw)
        t_ms = t_model * 1e3
        fault_report = {}
        if self.fault_plans:
            from repro.core.faults import survival_report
            fault_report = survival_report(self.workload, d, self.hw,
                                           self.fault_plans)
        # fault-survival trade-off: the score price of a plan is its mean
        # degraded-over-healthy penalty; a plan the candidate cannot
        # survive prices as +inf and zeroes the score (level stays 3 — the
        # candidate is correct, just fragile)
        t_eff = t_ms
        if fault_report and self.fault_weight:
            pens = [max(0.0, e["degraded_ms"] - e["healthy_ms"])
                    for e in fault_report.values()]
            t_eff = t_ms + self.fault_weight * sum(pens) / len(pens)
        levels["l3"] = time.perf_counter() - t3
        t_wall = float("inf")
        if self.wallclock:
            from repro.core.telemetry import wallclock_us
            if not self._take_l2_slot(cand):
                return self._slot_lost(cand, levels, publish)
            tw = time.perf_counter()
            try:
                t_wall = wallclock_us(jfn, self.inputs) / 1e3
            finally:
                _L2_SLOT.release(cand)
            levels["wallclock"] = time.perf_counter() - tw
        return self._record(
            cand, EvalResult(3, 10000.0 / (1.0 + t_eff), t_model_ms=t_ms,
                             t_wall_ms=t_wall, fault_report=fault_report,
                             retries=retries,
                             diagnostic=f"ok: modeled {t_ms:.3f} ms"),
            levels, fault_penalty_ms=t_eff - t_ms, publish=publish)
