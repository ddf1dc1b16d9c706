"""Solver facade for single problems.

The analog of the reference's ``sat.NewSolver``/``Solver.Solve``
(/root/reference/pkg/sat/solve.go:32-34,121-163).  The functional-options
pattern of the reference maps to plain keyword arguments; backends are
selected per solve:

  * ``"host"``  — the NumPy reference engine (semantic specification);
  * ``"tpu"``   — the batched tensor engine on the default JAX backend
    (one problem = batch of one);
  * ``"auto"``  — host for this single-problem facade (a batch of one is
    dispatch-latency-bound); the batch facade's ``auto`` picks the
    tensor engine when a JAX backend is usable.

Usage::

    from deppy_tpu import sat
    s = sat.Solver([sat.variable("a", sat.mandatory())])
    installed = s.solve()          # -> [Variable("a", ...)]
"""

from __future__ import annotations

import hashlib
import threading
import time
from collections import Counter
from typing import List, Optional, Sequence

from .. import telemetry
from .constraints import Variable, mandatory, prohibited
from .encode import Problem, encode, encode_assumed
from .errors import Incomplete, InternalSolverError, NotSatisfiable
from .host import HostEngine
from .tracer import Tracer


def assumed_variables(variables: Sequence[Variable],
                      assumptions: Sequence[tuple]) -> List[Variable]:
    """Derive the variable list a solve under ``assumptions`` answers
    for: each ``(identifier, installed)`` assumption appends a
    ``Mandatory`` (installed) or ``Prohibited`` (excluded) constraint to
    its subject variable — the wire-level form of gini's assumption
    literals (ISSUE 20).  The derived list is an ordinary problem: a
    one-shot cold solve of it is byte-for-byte the oracle for the
    scoped solve, and its unsat cores render the assumption as a real
    applied constraint (``"x is mandatory"``) instead of a synthetic
    literal."""
    extra: dict = {}
    for ident, installed in assumptions:
        extra.setdefault(ident, []).append(
            mandatory() if installed else prohibited())
    if not extra:
        return list(variables)
    out = []
    for v in variables:
        added = extra.get(v.identifier)
        if added:
            out.append(Variable(v.identifier,
                                tuple(v.constraints) + tuple(added)))
        else:
            out.append(v)
    return out


class Solver:
    """Preference-ordered, cardinality-minimized boolean-constraint solver.

    Construction validates input (raising ``DuplicateIdentifier`` like
    reference lit_mapping.go:49-57); ``solve`` returns the installed
    variables in input order, raises ``NotSatisfiable`` with a minimal core
    of applied constraints when no solution exists, or ``Incomplete`` when
    the step budget is exhausted.
    """

    def __init__(
        self,
        variables: Sequence[Variable],
        tracer: Optional[Tracer] = None,
        backend: str = "auto",
        max_steps: Optional[int] = None,
        trace_cap: Optional[int] = None,
        scheduler=None,
        tenant: str = "default",
    ):
        self.problem: Problem = encode(variables)
        self.tracer = tracer
        self.backend = backend
        self.max_steps = max_steps
        # Device-side trace buffer depth for the tensor backend (None =
        # driver default); the host engine traces unbuffered.
        self.trace_cap = trace_cap
        # Engine iterations consumed by the last solve (SURVEY.md §5).
        self.steps: int = 0
        # Structured telemetry for the last solve (SURVEY.md §5 /
        # ISSUE 1): outcome, step/decision/propagation counters, and —
        # on the tensor backend — the driver's padding/escalation data.
        self.report: Optional[telemetry.SolveReport] = None
        # ISSUE 20: an attached request scheduler makes the scope model
        # engine-registry-aware — scoped solves route through
        # ``Scheduler.submit_session`` (deadlines/breaker/fair admission
        # and portfolio racing apply unchanged, and the shared result
        # cache is bypassed) instead of being pinned to the inline host
        # engine.  ``warm_index`` is the session's private clause-set
        # index, handed to the scheduler so scoped solves warm-start
        # from the session's own last model.
        self.scheduler = scheduler
        self.tenant = tenant
        self.warm_index = None

    # ------------------------------------------- incremental (ISSUE 10)
    #
    # The gini Assume/Test/Untest surface (reference solve.go:79,99,104)
    # the paper's L0 table names and the original build never
    # reproduced.  Scopes run on the host spec engine regardless of the
    # configured backend: a propagation-only Test is host-cheap, and the
    # tensor engine's batched entry points have no notion of a pinned
    # per-solver assumption stack.

    def _scope_engine(self) -> HostEngine:
        if getattr(self, "_inc_engine", None) is None:
            self._inc_engine = HostEngine(
                self.problem, tracer=self.tracer, max_steps=self.max_steps)
        return self._inc_engine

    def assume(self, *identifiers, installed: bool = True) -> None:
        """Assume each identifier's variable installed (or not, with
        ``installed=False``) for subsequent :meth:`test` scopes — the
        analog of gini ``Assume``."""
        lits = []
        for ident in identifiers:
            idx = self.problem.id_to_index.get(ident)
            if idx is None:
                raise InternalSolverError(
                    [f'variable "{ident}" referenced but not provided'])
            lits.append((idx + 1) if installed else -(idx + 1))
        self._scope_engine().assume(lits)

    def test(self) -> int:
        """Propagation-only check of the assumed scope — gini ``Test``.
        Returns 1 (sat by propagation), -1 (conflict), 0 (undetermined);
        pushes a scope that :meth:`untest` pops."""
        return self._scope_engine().test()

    def untest(self) -> int:
        """Pop the most recent :meth:`test` scope (gini ``Untest``);
        returns the remaining scope depth."""
        return self._scope_engine().untest()

    def assumptions(self) -> List[tuple]:
        """The open assumption stack as ``(identifier, installed)``
        pairs, in assumption order — empty when no scope is open.  The
        facade's scope-owner is the host engine's literal stack, so
        :meth:`untest` truncation is reflected here for free."""
        eng = getattr(self, "_inc_engine", None)
        if eng is None:
            return []
        vs = self.problem.variables
        return [(vs[abs(lit) - 1].identifier, lit > 0)
                for lit in eng._assumed_lits]

    def scope_depth(self) -> int:
        """Open :meth:`test` scopes (gini's scope depth)."""
        eng = getattr(self, "_inc_engine", None)
        return len(eng._test_scopes) if eng is not None else 0

    def scope_state(self) -> tuple:
        """``(assumptions, scopes, scope_base)`` — the full scope-stack
        state for serialization (ISSUE 20 drain/join handoff):
        ``assumptions`` as :meth:`assumptions` renders them, ``scopes``
        the engine's pushed scope bases, ``scope_base`` the current
        one.  Replayable through the public assume/test surface."""
        eng = getattr(self, "_inc_engine", None)
        if eng is None:
            return [], [], 0
        return (self.assumptions(), list(eng._test_scopes),
                int(eng._scope_base))

    def _scope_key(self, assumptions: Sequence[tuple]) -> str:
        """Session-local lane key for a scoped solve: the base problem's
        canonical fingerprint (paid ONCE per solver, memoized) salted
        with the open assumption stack in order.  Scoped lanes bypass
        the shared result cache in both directions, so this key's only
        job is entry identity inside the session's private clause-set
        index — which makes an O(assumptions) digest legitimate where
        stateless lanes must pay the O(problem) ``fingerprint``.
        Deterministic per (catalog, stack), so revisiting an assumption
        state revisits its private-index entry."""
        base = self.problem.__dict__.get("_scope_base_key")
        if base is None:
            from ..sched.cache import fingerprint

            base = fingerprint(self.problem)
            self.problem.__dict__["_scope_base_key"] = base
        h = hashlib.sha256(base.encode())
        for ident, installed in assumptions:
            h.update(b"\x1f" + str(ident).encode("utf-8", "surrogatepass"))
            h.update(b"+" if installed else b"-")
        return "scope:" + h.hexdigest()

    def _scope_plan_args(self, assumptions: Sequence[tuple]) -> tuple:
        """``(session_key, scope_entry_key, scope_seed)`` for
        ``Scheduler.submit_session``: this solve's session-local key,
        the previous scoped solve's key (the declared warm predecessor
        in the private index — None on the session's first solve), and
        the variable indices whose assumptions CHANGED between the two
        stacks (multiset symmetric difference, so a re-assumed pair
        cancels and an assume-then-invert shows up once per side) — the
        exact seed the O(delta) cone closure needs, because every
        added/removed constraint row is a unit on one of these
        subjects."""
        key = self._scope_key(assumptions)
        prev = getattr(self, "_scope_last", None)
        if prev is None:
            return key, None, ()
        prev_key, prev_assumptions = prev
        cur_c = Counter(assumptions)
        prev_c = Counter(prev_assumptions)
        seed = sorted({
            idx for ident, _ in
            list((cur_c - prev_c).keys()) + list((prev_c - cur_c).keys())
            if (idx := self.problem.id_to_index.get(ident)) is not None})
        return key, prev_key, tuple(seed)

    def solve_scoped(self, deadline_s=None, stats: Optional[dict] = None):
        """Solve under the OPEN assumption stack and return the raw
        result object (solution dict / ``NotSatisfiable`` /
        ``Incomplete`` — the scheduler-lane contract, un-decoded so a
        serving layer can render it byte-identically to ``/v1/resolve``).

        With a scheduler attached (ISSUE 20) the solve routes through
        ``Scheduler.submit_session``: dedicated session class, registry
        backends raced, deadlines/breaker/fair admission unchanged, the
        shared result cache bypassed in BOTH directions (an
        assumption-conditioned answer must never be admitted where
        stateless traffic could read it — satellite 2), and warm starts
        planned against ``self.warm_index`` when set — O(delta) against
        the previous scoped solve's entry when one is on record, the
        generic classifier otherwise.  Without one, the derived problem
        solves on the host spec engine inline — the same answer, no
        registry awareness.

        The derived problem is lowered via ``encode_assumed`` — the
        session IS the retained encoding, so the per-step lowering cost
        is the assumption splice, not a catalog re-walk (differential
        tests pin the splice byte-identical to a full ``encode``)."""
        assumptions = self.assumptions()
        p = encode_assumed(self.problem, assumptions)
        if self.scheduler is not None:
            key, entry_key, seed = self._scope_plan_args(assumptions)
            try:
                return self.scheduler.submit_session(
                    p.variables, deadline_s=deadline_s,
                    max_steps=self.max_steps, stats=stats,
                    tenant=self.tenant, warm_index=self.warm_index,
                    session_key=key, scope_entry_key=entry_key,
                    scope_seed=seed, problem=p)
            finally:
                # Track the key/stack pair even for UNSAT/degraded
                # answers: a missing private-index entry just means the
                # next step's scoped plan misses and the generic
                # classifier (then the cold path) answers.
                self._scope_last = (key, list(assumptions))
        if p.errors:
            raise InternalSolverError(p.errors)
        engine = HostEngine(p, max_steps=self.max_steps)
        try:
            installed, _ = engine.solve()
        except (NotSatisfiable, Incomplete) as e:
            if stats is not None:
                stats["steps"] = engine.steps
            return e
        finally:
            self.steps = engine.steps
        if stats is not None:
            stats["steps"] = engine.steps
        solution = {v.identifier: False for v in p.variables}
        for v in installed:
            solution[v.identifier] = True
        return solution

    def solve(self) -> List[Variable]:
        if self.assumptions():
            # ISSUE 20: a solve under an open scope answers for the
            # ASSUMED problem (gini's Solve consumes assumptions; the
            # pre-session facade silently ignored them).  Routed through
            # solve_scoped so a scheduler-attached solver gets registry
            # engines and the cache bypass; decoded back to the facade's
            # installed-variables contract.
            r = self.solve_scoped()
            if isinstance(r, (NotSatisfiable, Incomplete)):
                raise r
            return [v for v in self.problem.variables
                    if r.get(v.identifier)]
        backend = resolve_backend(self.backend, batch=False)
        if backend == "host":
            return self._solve_host()
        from ..engine.driver import solve_one

        stats: dict = {}
        try:
            return solve_one(self.problem, max_steps=self.max_steps,
                             stats=stats, tracer=self.tracer,
                             trace_cap=self.trace_cap)
        finally:
            self.steps = stats.get("steps", 0)
            self.report = stats.get("report")

    def _solve_host(self) -> List[Variable]:
        if self.tracer is not None:
            # Tracer callbacks can't cross a process boundary: a traced
            # solve stays on the in-process engine.
            return self._solve_host_traced()
        # The shared host-path entry (ISSUE 5): one lane through
        # deppy_tpu.hostpool, which routes a batch of one inline anyway
        # (a lone problem is IPC-latency-bound the same way it is
        # dispatch-latency-bound on the device) but keeps this facade on
        # the single solve_lane implementation the pool's differential
        # tests pin.
        from .. import hostpool

        try:
            (lane,) = hostpool.solve_host_problems(
                [self.problem], max_steps=self.max_steps)
        except InternalSolverError:
            # Parity with the engine path's finally: the report exists
            # (outcome-less) even when the problem was malformed.
            self.steps = 0
            self.report = telemetry.SolveReport(backend="host",
                                                n_problems=1)
            raise
        self.steps = lane.steps
        rep = telemetry.SolveReport(backend="host", n_problems=1)
        rep.count_outcome(lane.outcome)
        rep.steps = lane.steps
        rep.decisions = lane.decisions
        rep.propagation_rounds = lane.propagation_rounds
        rep.backtracks = lane.backtracks
        rep.add_wall("solve", lane.wall_s)
        self.report = rep
        if lane.outcome == "sat":
            return [self.problem.variables[i] for i in lane.installed_idx]
        if lane.outcome == "unsat":
            raise NotSatisfiable(
                [self.problem.applied[j] for j in lane.core_idx])
        raise Incomplete()

    def _solve_host_traced(self) -> List[Variable]:
        engine = HostEngine(
            self.problem, tracer=self.tracer, max_steps=self.max_steps
        )
        t0 = time.perf_counter()
        outcome: Optional[str] = None
        try:
            installed, _ = engine.solve()
            outcome = "sat"
            return installed
        except NotSatisfiable:
            outcome = "unsat"
            raise
        except Incomplete:
            outcome = "incomplete"
            raise
        finally:
            self.steps = engine.steps
            rep = telemetry.SolveReport(backend="host", n_problems=1)
            if outcome is not None:
                rep.count_outcome(outcome)
            rep.steps = engine.steps
            rep.decisions = engine.decisions
            rep.propagation_rounds = engine.propagation_rounds
            rep.backtracks = engine.backtracks
            rep.add_wall("solve", time.perf_counter() - t0)
            self.report = rep


def resolve_backend(backend: str, *, batch: bool = True) -> str:
    """Resolve a backend name to ``"host"`` or ``"tpu"``: the single place
    the ``auto`` policy lives (shared by :class:`Solver` and the resolution
    facade).  Raises on unknown names.

    ``batch=False`` marks a single-problem solve: ``auto`` picks the host
    engine there — a batch of one is dispatch-latency-bound, and the
    tensor engine's win is batch parallelism, so ``auto`` reserves it
    for batches.  Which side wins a single problem on the chip is not
    yet measured.  Explicit ``"tpu"`` still forces the device path.

    An **open accelerator circuit breaker** (ISSUE 2: N consecutive
    device dispatch failures) also degrades ``auto`` to the host engine
    — without re-probing — until the breaker's cooldown elapses; the
    driver's half-open probe dispatch then decides whether device
    routing resumes.  Explicit ``"tpu"`` still resolves to the tensor
    *path* here, but it does not override the breaker: while it is open
    the driver's dispatch-level recovery host-routes every group (loud:
    ``deppy_fault_host_routed_total``, ``fault`` sink events), and the
    service refuses explicit-tpu requests outright with 503 +
    Retry-After.  Exact answers either way; device *timing* is only
    measurable with the breaker closed."""
    if backend == "auto":
        if not batch:
            return "host"
        from .. import faults

        if faults.default_breaker().blocks_device():
            return "host"
        return "tpu" if _engine_usable() else "host"
    if backend in ("host", "tpu"):
        return backend
    raise InternalSolverError([f"unknown backend {backend!r}"])


_ENGINE_USABLE: Optional[bool] = None
# Serializes the probe: concurrent auto callers (e.g. requests hitting a
# service while its startup pre-warm is still probing) share one probe
# and its verdict.
_ENGINE_USABLE_LOCK = threading.Lock()


def reprobe_engine() -> bool:
    """Probe engine usability again and replace the cached verdict.

    The cached verdict makes ``auto`` a routing policy, not a health
    monitor — right for short-lived processes, wrong for a long-lived
    service whose accelerator recovers from an outage.  The service's
    pre-warm loop and the scheduler's deferred re-probe call this while
    the verdict is negative or the breaker is half-open.  Returns the
    fresh verdict; a positive one also closes the circuit breaker, so
    auto routing does not stay host-only for a full cooldown."""
    global _ENGINE_USABLE
    with _ENGINE_USABLE_LOCK:
        fresh = _probe_verdict()
        _ENGINE_USABLE = fresh
    if fresh:
        from .. import faults

        faults.default_breaker().reset()
    return fresh


def _engine_usable() -> bool:
    """True when the tensor engine and a JAX backend are both usable.
    ``auto`` degrades to the host engine rather than failing, so the
    library stays usable on machines without a working accelerator
    runtime.  The verdict is cached for the process lifetime — ``auto``
    is a routing policy, not a health monitor."""
    global _ENGINE_USABLE
    if _ENGINE_USABLE is not None:
        return _ENGINE_USABLE
    with _ENGINE_USABLE_LOCK:
        if _ENGINE_USABLE is None:  # a concurrent caller may have probed
            _ENGINE_USABLE = _probe_verdict()
        return _ENGINE_USABLE


def _probe_verdict() -> bool:
    """One engine-usability probe, in this process: the tensor engine
    imports and a tiny program compiles, runs and reads back on
    ``jax.devices()[0]``.  ``jax.devices()`` alone is no evidence once
    the backend is up (it returns the cached device list), and
    :func:`reprobe_engine` closes the breaker on this verdict.  No cache
    interaction (callers manage ``_ENGINE_USABLE`` and its lock).
    In-process because the process that routes to the device is the one
    that will hold it: a locally attached chip belongs to one process at
    a time, so a child probing it would fail while this process holds
    it."""
    try:
        import jax
        import numpy as np

        from ..engine import driver  # noqa: F401

        x = jax.device_put(np.arange(8, dtype=np.int32), jax.devices()[0])
        return int((x * 2).sum()) == 56
    # deppy: lint-ok[exception-hygiene] probe: an unusable engine or backend IS the False verdict
    except Exception:
        return False
