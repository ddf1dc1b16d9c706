"""Cross-request continuous-batching scheduler (ISSUE 3 tentpole).

One :class:`Scheduler` sits between request producers (the service's
``/v1/resolve`` handler threads, ``BatchResolver`` callers) and the
engine driver.  Producers call :meth:`Scheduler.submit` and block; a
single dispatch-loop thread drains the queue into coalesced device
dispatches, so concurrent traffic shares one pad/pack + ``device_put`` +
kernel launch instead of paying one each — continuous batching, applied
to constraint resolution.

Design points, in the order the issue states them:

  * **Size-class-aware micro-batch queue.**  Each submit becomes one
    *group* (its problems never split across dispatches, so per-request
    semantics — escalation staging, report shape — match the
    unscheduled path).  Groups carry a size class — the power-of-two
    bucket of their largest :func:`engine.driver._cost_proxy` value, the
    same cost proxy ``driver.partition_buckets`` splits on — and a flush
    coalesces only same-class, same-budget groups, so one giant catalog
    problem never inflates every lane of a burst of tiny ones.
  * **Max-wait / max-fill flush.**  A flush fires when the oldest
    group has waited ``max_wait_ms`` (a lone request keeps low latency)
    or the head's class has ``max_fill`` lanes queued (a burst fills
    lanes).  Dispatches run through the driver's existing fault-domain
    recovery (``_recovering``: retry → split → host fallback, breaker
    charging) — the scheduler adds no new failure semantics.
  * **Deadlines.**  Each lane carries its request's
    :class:`faults.Deadline` object (captured on the submitting thread,
    ambient env deadline included).  Expired lanes degrade to
    ``Incomplete`` at triage — their coalesced batchmates dispatch
    unharmed — and the dispatch itself runs under the *loosest* live
    lane's deadline scope, so no batchmate is cut short by a stranger's
    tighter budget.
  * **Result cache.**  Misses queue; hits (see :mod:`.cache`) bypass the
    queue entirely and cost zero engine steps.
  * **Admission.**  :meth:`admission_retry_after` converts queue depth
    beyond ``max_depth`` into the service's 503 + Retry-After machinery;
    an open accelerator breaker does NOT reject the queue — backend
    resolution degrades ``auto`` to the host engine and the queue keeps
    draining (host-only mode).

"""

from __future__ import annotations

import threading
import time
from typing import List, Optional, Sequence

import numpy as np

from .. import faults, telemetry
from .. import profile as _profile
from ..sat.constraints import Variable
from ..sat.encode import Problem, encode
from ..sat.errors import Incomplete, InternalSolverError, NotSatisfiable
from .cache import MISS, ResultCache, fingerprint

# Knob defaults + env mirrors (CLI flags --sched-max-wait-ms,
# --sched-max-fill, --cache-size, --mesh-devices override; see
# deppy_tpu.cli).
DEFAULT_MAX_WAIT_MS = 5.0
DEFAULT_MAX_FILL = 256
DEFAULT_CACHE_SIZE = 1024
DEFAULT_MAX_DEPTH = 4096
DEFAULT_INCREMENTAL_INDEX = 512
DEFAULT_INCREMENTAL_MAX_DELTA = 0.25
# Portfolio racing (ISSUE 13): top-K backends raced per cold flush, and
# the deterministic 1-in-N fraction of non-canonical race wins that are
# cross-checked against the canonical backend through the differential
# machinery (the canonical entrant is exempted from cancellation on
# sampled races so its answer exists to compare).
DEFAULT_PORTFOLIO_K = 2
DEFAULT_PORTFOLIO_SAMPLE_CHECK = 0.0625
# Speculative pre-resolution (ISSUE 14): catalog publishes queue
# pre-solves on a SEPARATE idle-priority queue the dispatch loop drains
# only while no live group is queued — live traffic preempts at every
# flush boundary, and the backlog is capped (publishes are bursty and a
# pre-solve is pure opportunism: dropping one costs a cold solve later,
# never an answer).
DEFAULT_SPECULATE_MAX_BACKLOG = 2048

# The "incremental" size class (ISSUE 10): warm-started lanes coalesce
# with each other — their cost is a handful of host propagation passes,
# not a device dispatch, so padding them into a cold batch's lanes would
# waste device width AND serialize near-lookups behind a solve.  Cold
# classes are power-of-two cost buckets (>= 1), so -1 can never collide.
INCREMENTAL_CLASS = -1

# The "session" size class (ISSUE 20): a stateful session's incremental
# cold solves dispatch in their own bucket — they carry assumption-
# conditioned answers that must never coalesce into (or pad out) a
# stateless cold batch, and their results bypass the shared result
# cache entirely (see ``_maybe_cache``).  Warm session lanes ride
# INCREMENTAL_CLASS like any other warm-started lane: the warm flush
# machinery is per-lane and scoped-ness travels on the lane itself.
SESSION_CLASS = -2


def _env_int(name: str, default: int) -> int:
    v = faults.env_float(name, float(default), warn=True)
    return int(v if v is not None else default)


def _single_tenant(lanes: List["_Lane"]) -> Optional[str]:
    """The one tenant a flush serves, or None when mixed — profile
    events are tenant-stamped only when attribution is unambiguous."""
    tenants = {lane.tenant for lane in lanes}
    return tenants.pop() if len(tenants) == 1 else None


def _solution_dict(problem: Problem, installed_idx) -> dict:
    """The host-lane decode convention, shared by the host drain and the
    warm path: every entity id mapped to False, installed set True —
    exactly what ``driver.decode_results`` renders for a SAT lane."""
    solution = {v.identifier: False for v in problem.variables}
    for i in installed_idx:
        solution[problem.variables[i].identifier] = True
    return solution


class _Lane:
    """One problem awaiting dispatch, plus its result slot.

    ``degraded`` marks a lane the deadline triage actually expired —
    distinct from a budget-exhaustion ``Incomplete`` whose deadline
    merely ran out by readback time (ISSUE 4: only the former is an
    incident worth the flight recorder's error ring)."""

    __slots__ = ("problem", "key", "max_steps", "budget", "deadline",
                 "result", "steps", "degraded", "warm", "backtracks",
                 "index_steps", "tenant", "scoped", "session_index")

    def __init__(self, problem: Problem, key: str,
                 max_steps: Optional[int], budget: int, deadline,
                 warm=None, tenant: str = "default"):
        self.problem = problem
        self.key = key
        self.max_steps = max_steps
        self.budget = budget
        self.deadline = deadline  # faults.Deadline or None
        self.result = None
        self.steps = 0
        self.degraded = False
        # ISSUE 10: the lane's WarmPlan (incremental size class), and
        # the solve's observed search-backtrack count — None until a
        # path that measures it reports in (the clause-set index seeds
        # warm starts only from zero-backtrack solves, so an unmeasured
        # lane must never be indexed as zero).  ``index_steps`` is the
        # COLD-equivalent step cost to index under when it differs from
        # ``steps``: a warm-served lane's own step count is a fraction
        # of what a cold solve would spend, and indexing it verbatim
        # would erode the budget gate that keeps a warm SAT from
        # shadowing a cold Incomplete at tight budgets.
        self.warm = warm
        self.backtracks = None
        self.index_steps = None
        # ISSUE 11: the submitting request's tenant (X-Deppy-Tenant),
        # carried per lane so a deadline expiry at triage attributes to
        # the tenant whose lane expired, never a coalesced batchmate's.
        self.tenant = tenant
        # ISSUE 20: a scoped lane answers under a session's open
        # assumption stack — its result is assumption-conditioned and
        # must never be admitted to the shared exact LRU or clause-set
        # index (it would poison stateless traffic); instead the model
        # lands in the session's OWN index so the next op warm-starts
        # from the session's last model.
        self.scoped = False
        self.session_index = None


class _Group:
    """All queued lanes of one submit() call — flushed atomically.

    ``parent`` carries the submitting request's trace context across the
    thread hop to the dispatch loop (ISSUE 4) so a coalesced dispatch
    can link back to every request it serves; ``timing`` receives the
    request's queue-wait/dispatch/solve/decode breakdown."""

    __slots__ = ("lanes", "enq_t", "size_class", "budget", "event",
                 "error", "report", "parent", "timing", "speculative",
                 "tenant", "priority", "shadow_backend", "shadow_class",
                 "immediate")

    def __init__(self, lanes: List[_Lane], size_class: int, budget: int,
                 speculative: bool = False, priority: int = 1,
                 immediate: bool = False):
        self.lanes = lanes
        self.enq_t = time.monotonic()
        self.size_class = size_class
        self.budget = budget
        self.event = threading.Event()
        self.error: Optional[BaseException] = None
        self.report = None
        self.parent = telemetry.trace.capture_parent()
        self.timing: dict = {}
        # ISSUE 14: a speculative pre-solve group — queued on the idle
        # queue, no submitter waits on its event, and a dispatch failure
        # is a sink event rather than a raised request error.
        self.speculative = speculative
        # ISSUE 15: groups are single-tenant by construction (one
        # submit = one request = one tenant), so per-tenant queue
        # accounting and the priority-ordered flush head key off the
        # group, not per lane.
        self.tenant = lanes[0].tenant if lanes else "default"
        self.priority = priority
        # ISSUE 19: a shadow route probe — the group re-solves an
        # already-answered flush via ONE named backend on the idle
        # queue; its results feed the route ledger, never a response.
        self.shadow_backend: Optional[str] = None
        self.shadow_class: Optional[str] = None
        # ISSUE 20: a blocking interactive lane (a session op) flushes
        # as soon as it reaches the head — a human is synchronously
        # waiting on ONE lane, so holding it the coalescing window's
        # max-wait buys nothing and costs the whole window.  Batchmates
        # that are already queued still coalesce into the flush.
        self.immediate = immediate


def _count_lane_outcome(rep, r) -> None:
    """Fold one HostLaneResult into a SolveReport — exactly the
    accounting the host drain performs (degraded lanes count as
    incomplete with no engine counters)."""
    if r.degraded:
        rep.count_outcome("incomplete")
        return
    rep.count_outcome(r.outcome)
    rep.steps += r.steps
    rep.decisions += r.decisions
    rep.propagation_rounds += r.propagation_rounds
    rep.backtracks += r.backtracks


def _apply_lane_result(lane: "_Lane", r, point: str,
                       canonical: bool = True) -> None:
    """Decode one HostLaneResult onto its lane — the host drain's
    decode convention, shared so racing cannot grow a second decode
    path.  ``canonical=False`` (a race won by a non-canonical backend)
    clears the lane's backtrack observation: the winner's count is not
    the canonical engine's, and the clause-set index must never seed a
    warm start from a non-canonical cost observation."""
    if r.degraded:
        faults.note_deadline_exceeded(point, tenant=lane.tenant)
        lane.result = Incomplete()
        lane.degraded = True
        return
    if r.outcome == "sat":
        lane.result = _solution_dict(lane.problem, r.installed_idx)
    elif r.outcome == "unsat":
        lane.result = NotSatisfiable(
            [lane.problem.applied[j] for j in r.core_idx])
    else:
        lane.result = Incomplete()
    lane.steps = r.steps
    lane.backtracks = r.backtracks if canonical else None


class _RacePlan:
    """One flush's race decision: the candidate backends and the class
    they were ranked for."""

    __slots__ = ("names", "class_name", "canonical")

    def __init__(self, names: List[str], class_name: str,
                 canonical: str):
        self.names = names
        self.class_name = class_name
        self.canonical = canonical


# Abandoned race losers (a device program mid-execution, a grad descent
# mid-compile) must not be killed as daemon threads while they hold XLA
# runtime locks — the C++ runtime calls std::terminate at interpreter
# teardown.  Every race thread registers here and an atexit hook joins
# the stragglers (bounded: losers see the stop flag at their next step
# boundary; a device program runs out its dispatch).
_RACE_THREADS: List[threading.Thread] = []
_RACE_THREADS_LOCK = threading.Lock()
_RACE_ATEXIT = [False]


def _note_race_thread(t: threading.Thread) -> None:
    with _RACE_THREADS_LOCK:
        _RACE_THREADS[:] = [x for x in _RACE_THREADS if x.is_alive()]
        _RACE_THREADS.append(t)
        if not _RACE_ATEXIT[0]:
            import atexit

            atexit.register(_join_race_threads)
            _RACE_ATEXIT[0] = True


def _join_race_threads(timeout_s: float = 60.0) -> None:
    deadline = time.monotonic() + timeout_s
    with _RACE_THREADS_LOCK:
        threads = list(_RACE_THREADS)
    for t in threads:
        t.join(max(deadline - time.monotonic(), 0.0))


class PortfolioRacer:
    """First-finisher-wins racing across registered engine backends
    (ISSUE 13 tentpole).

    One coalesced cold flush is dispatched to the top-K candidate
    backends of its size class concurrently (:mod:`deppy_tpu.engine.
    registry` ranks them — measured ``portfolio`` rows first, the
    static canonical-first order otherwise); the first DEFINITIVE
    finisher (every lane answered) wins, and the losers are
    cancelled: host lanes check a cooperative stop flag at step
    boundaries, device programs run to completion with their fetch
    dropped, hostpool dispatches are abandoned.  A deterministic
    1-in-N sample of non-canonical wins is cross-checked against the
    canonical backend's answer through the differential lane
    comparison — a mismatch is a loud ``race_mismatch`` fault event
    and the canonical answer is served.

    Modes: ``on`` races wherever ≥2 candidates serve the class;
    ``auto`` races only classes with a measured ``portfolio`` row
    (the tpu_ab-learned default posture).  ``off`` never constructs a
    racer — the scheduler's dispatch path is byte-identical to the
    pre-portfolio tree."""

    def __init__(self, mode: str, k: int, sample_check: float,
                 registry: "telemetry.Registry"):
        self.mode = mode
        self.k = max(int(k), 2)
        rate = max(float(sample_check), 0.0)
        self._check_interval = (int(round(1.0 / min(rate, 1.0)))
                                if rate > 0 else 0)
        # Non-canonical wins since the last cross-check.  The sampling
        # contract is 1-in-N NON-CANONICAL WINS (not 1-in-N races —
        # counting races would let deterministic aliasing against the
        # flush pattern starve the check forever); seeded so the very
        # FIRST non-canonical win is checked.  The cancel exemption
        # must be decided before racing, so the check arms whenever
        # the next non-canonical win would be the Nth.
        self._check_lock = threading.Lock()
        self._since_check = max(self._check_interval - 1, 0)
        self._registry = registry

    # ------------------------------------------------------------- plan

    def plan(self, live: List["_Lane"], backend: str) -> Optional[_RacePlan]:
        """Decide whether THIS flush races: candidate backends for its
        ladder class, capability- and availability-filtered.  None
        means the canonical single-backend path runs untouched."""
        from ..engine import registry as engine_registry
        from ..engine.driver import padded_class

        class_name = padded_class([lane.problem for lane in live])
        device_ok = (backend != "host"
                     and not faults.default_breaker().blocks_device())
        need_card = any(lane.problem.card_act.shape[0] > 0
                        and (lane.problem.card_act >= 0).any()
                        for lane in live)
        names, measured = engine_registry.candidates(
            class_name, self.k, device_ok=device_ok,
            cardinality=need_card)
        if self.mode == "auto" and not measured:
            return None
        if len(names) < 2:
            return None
        canonical = "host" if backend == "host" else "device"
        if canonical == "device" and not device_ok:
            canonical = "host"
        return _RacePlan(names, class_name, canonical)

    # ------------------------------------------------------------- race

    def race(self, plan: _RacePlan, live: List["_Lane"], rep,
             timing: dict, mesh_fn) -> bool:
        """Run one race.  Returns True when a winner's results were
        applied to the lanes (and merged into ``rep``); False when no
        entrant finished definitively — the caller falls back to the
        canonical path exactly as if racing were off."""
        from ..engine import registry as engine_registry
        from ..sat.host import SolveCancelled

        reg = self._registry
        problems = [lane.problem for lane in live]
        deadlines = [lane.deadline for lane in live]
        dl = faults.current_deadline()
        mesh = mesh_fn() if "device" in plan.names else None
        stop = threading.Event()
        with self._check_lock:
            check = (self._check_interval > 0
                     and plan.canonical in plan.names
                     and self._since_check + 1 >= self._check_interval)
        cv = threading.Condition()
        finished: List[tuple] = []  # (name, dt, out, err, srep) in
        #                             completion order

        def run(name: str, t0: float) -> None:
            srep, owns = telemetry.begin_report(backend=name)
            out = None
            err = None
            try:
                if stop.is_set() and not (check
                                          and name == plan.canonical):
                    raise SolveCancelled()
                with faults.deadline_scope(dl):
                    faults.inject(f"sched.race.{name}")
                    out = engine_registry.solve_via(
                        name, problems, max_steps=live[0].max_steps,
                        deadlines=deadlines,
                        cancel=(None if (check and name == plan.canonical)
                                else stop),
                        mesh=mesh if name == "device" else None)
                if name != "device" and out is not None:
                    # Non-device backends don't flow through the
                    # driver's report plumbing: account their lanes
                    # here, on the entrant's own report (merged only
                    # if this entrant wins / cross-checks).
                    for r in out:
                        if r is not None:
                            _count_lane_outcome(srep, r)
            except SolveCancelled:
                err = "cancelled"
            except BaseException as e:  # noqa: BLE001 — entrant-local
                err = e
            finally:
                telemetry.detach_report(srep, owns)
            with cv:
                finished.append((name, time.perf_counter() - t0, out,
                                 err, srep))
                cv.notify_all()

        t0 = time.perf_counter()
        with reg.span("race", lanes=len(live), entrants=len(plan.names),
                      size_class=plan.class_name) as sp:
            threads = {}
            for name in plan.names:
                reg.counter(
                    "deppy_race_starts_total",
                    "Portfolio race entrant launches, by backend.",
                    labelname="backend").inc(label=name)
                t = threading.Thread(target=run, args=(name, t0),
                                     name=f"deppy-race-{name}",
                                     daemon=True)
                threads[name] = t
                _note_race_thread(t)
                t.start()

            def _definitive(name, out):
                """A non-canonical entrant's budget-exhaustion
                'incomplete' is that ENGINE's verdict, not the
                canonical one (step accounting is engine-relative) —
                letting it win would serve (and cache) Incomplete
                where racing-off decides.  Only the canonical entrant
                may call Incomplete; deadline-degraded lanes pass
                (deadline behavior is timing-dependent and never
                cached)."""
                if out is None:
                    return False
                for r in out:
                    if r is None:
                        return False
                    if (r.outcome == "incomplete" and not r.degraded
                            and name != plan.canonical):
                        return False
                return True

            def _winner_locked():
                for entry in finished:
                    name, _, out, err, _ = entry
                    if err is None and _definitive(name, out):
                        return entry
                return None

            with cv:
                winner = _winner_locked()
                while winner is None and len(finished) < len(plan.names):
                    cv.wait()
                    winner = _winner_locked()
            stop.set()
            if winner is None:
                sp.set(winner="none")
                telemetry.default_registry().event(
                    "race", size_class_name=plan.class_name,
                    entrants=list(plan.names), lanes=len(live),
                    default=plan.names[0], winner=None)
                return False

            noncanonical_win = winner[0] != plan.canonical
            checked = None
            if check and noncanonical_win:
                # Sampled differential cross-check: the canonical
                # entrant was exempt from cancellation — wait for its
                # answer and compare outcome/model/core per lane.
                # Deadline-degraded lanes are excluded on either side:
                # degradation is pure timing (the entrants admitted
                # the lane at different instants), not disagreement.
                with cv:
                    while not any(e[0] == plan.canonical
                                  for e in finished):
                        cv.wait()
                    canon = next(e for e in finished
                                 if e[0] == plan.canonical)
                if canon[3] is None and canon[2] is not None and all(
                        r is not None for r in canon[2]):
                    mismatch = any(
                        (w.outcome, tuple(w.installed_idx),
                         tuple(w.core_idx))
                        != (c.outcome, tuple(c.installed_idx),
                            tuple(c.core_idx))
                        for w, c in zip(winner[2], canon[2])
                        if not w.degraded and not c.degraded)
                    checked = "mismatch" if mismatch else "ok"
                    if mismatch:
                        reg.counter(
                            "deppy_race_check_mismatch_total",
                            "Sampled race cross-checks that disagreed "
                            "with the canonical backend (served "
                            "canonical; investigate).").inc()
                        telemetry.default_registry().event(
                            "fault", fault="race_mismatch",
                            winner=winner[0],
                            canonical=plan.canonical,
                            lanes=len(live))
                        winner = canon  # serve the canonical answer
            if noncanonical_win:
                with self._check_lock:
                    if check:
                        self._since_check = 0
                    else:
                        self._since_check += 1

            wname, wdt, wout, _, wsrep = winner
            with cv:
                # ISSUE 19 satellite: a cancelled loser can surface as
                # a PARTIAL completion — err None but a None lane (a
                # grad descent cancelled mid-certification) — whose
                # wall clock measures when the cancel landed, not how
                # fast the backend solves.  Such entrants are CENSORED:
                # recorded as losers so the regret ledger can count
                # cancels distinctly, but excluded from win-margin
                # stats and per-backend wall estimates.
                losers = []
                for e in finished:
                    if e[0] == wname:
                        continue
                    censored = (e[3] is not None or e[2] is None
                                or any(r is None for r in e[2]))
                    losers.append({"backend": e[0],
                                   "wall_s": round(e[1], 6),
                                   "censored": bool(censored)})
                done = {e[0] for e in finished}
                margins = [e[1] - wdt for e in finished
                           if e[0] != wname and e[3] is None
                           and e[2] is not None
                           and all(r is not None for r in e[2])]
                clean_done = {e[0] for e in finished if e[3] is None}
            for name in plan.names:
                if name != wname and name not in done:
                    # Still running at event time (abandoned in the
                    # background): censored, no usable wall clock.
                    losers.append({"backend": name, "wall_s": None,
                                   "censored": True})
            for name in plan.names:
                if name != wname and name not in clean_done:
                    reg.counter(
                        "deppy_race_cancels_total",
                        "Race entrants cancelled or abandoned after "
                        "losing, by backend.",
                        labelname="backend").inc(label=name)
            reg.counter(
                "deppy_race_wins_total",
                "Races won (first definitive finisher), by backend.",
                labelname="backend").inc(label=wname)
            margin = min(margins) if margins else None
            if margin is not None:
                reg.histogram(
                    "deppy_race_win_margin_seconds",
                    "Winner-vs-best-finished-loser wall-clock margin "
                    "per race.").observe(max(margin, 0.0))
            sp.set(winner=wname)
            telemetry.default_registry().event(
                "race", size_class_name=plan.class_name, winner=wname,
                canonical=plan.canonical, default=plan.names[0],
                entrants=list(plan.names),
                lanes=len(live),
                cancelled=[n for n in plan.names
                           if n != wname and n not in clean_done],
                losers=losers,
                win_margin_s=(round(margin, 6)
                              if margin is not None else None),
                checked=checked, wall_s=round(wdt, 6))
        rep.merge(wsrep)
        canonical_won = wname == plan.canonical
        for lane, r in zip(live, wout):
            _apply_lane_result(lane, r, "sched.race",
                               canonical=canonical_won)
        timing["solve_s"] = timing.get("solve_s", 0.0) + wdt
        return True


class Scheduler:
    """Coalesce concurrent resolve requests into shared dispatches."""

    def __init__(
        self,
        backend: str = "auto",
        max_steps: Optional[int] = None,
        max_wait_ms: Optional[float] = None,
        max_fill: Optional[int] = None,
        cache_size: Optional[int] = None,
        max_depth: Optional[int] = None,
        registry: Optional[telemetry.Registry] = None,
        mesh=None,
        mesh_devices: Optional[int] = None,
        lanes_per_device: Optional[int] = None,
        incremental: Optional[str] = None,
        incremental_max_delta: Optional[float] = None,
        incremental_index_size: Optional[int] = None,
        portfolio: Optional[str] = None,
        portfolio_k: Optional[int] = None,
        portfolio_sample_check: Optional[float] = None,
        speculate: Optional[str] = None,
        speculate_max_backlog: Optional[int] = None,
        fair: Optional[str] = None,
        tenant_weights: Optional[str] = None,
    ):
        self.backend = backend
        self.max_steps = max_steps
        # Mesh serving (ISSUE 6): device dispatches shard each coalesced
        # micro-batch over a jax mesh.  ``mesh`` pins one explicitly
        # (tests, library callers); otherwise ``mesh_devices`` (or the
        # DEPPY_TPU_MESH_DEVICES env mirror) sizes one LAZILY on the
        # first device dispatch.
        self._mesh = mesh
        self._mesh_devices = mesh_devices
        self._mesh_resolved = mesh is not None
        self._max_fill_explicit = max_fill is not None
        if lanes_per_device is None:
            lanes_per_device = _env_int("DEPPY_TPU_SCHED_LANES_PER_DEVICE",
                                        DEFAULT_MAX_FILL)
        self.lanes_per_device = max(int(lanes_per_device), 1)
        if max_wait_ms is None:
            max_wait_ms = faults.env_float(
                "DEPPY_TPU_SCHED_MAX_WAIT_MS", DEFAULT_MAX_WAIT_MS,
                warn=True)
        self.max_wait_s = max(float(max_wait_ms), 0.0) / 1000.0
        if max_fill is None:
            max_fill = _env_int("DEPPY_TPU_SCHED_MAX_FILL",
                                DEFAULT_MAX_FILL)
        self.max_fill = max(int(max_fill), 1)
        if max_depth is None:
            max_depth = _env_int("DEPPY_TPU_SCHED_MAX_DEPTH",
                                 DEFAULT_MAX_DEPTH)
        self.max_depth = int(max_depth)
        if cache_size is None:
            cache_size = _env_int("DEPPY_TPU_CACHE_SIZE",
                                  DEFAULT_CACHE_SIZE)
        self._registry = registry if registry is not None \
            else telemetry.default_registry()
        # Incremental tier (ISSUE 10): a delta-aware clause-set index in
        # front of the exact-fingerprint LRU.  Default on;
        # DEPPY_TPU_INCREMENTAL=off removes the tier entirely, restoring
        # the pre-change dispatch byte for byte.
        from .. import config

        if incremental is None:
            incremental = config.env_raw("DEPPY_TPU_INCREMENTAL", "on")
        index = None
        if str(incremental).strip().lower() not in ("off", "0", "false",
                                                    "no"):
            if incremental_max_delta is None:
                incremental_max_delta = faults.env_float(
                    "DEPPY_TPU_INCREMENTAL_MAX_DELTA",
                    DEFAULT_INCREMENTAL_MAX_DELTA, warn=True)
            if incremental_index_size is None:
                incremental_index_size = _env_int(
                    "DEPPY_TPU_INCREMENTAL_INDEX_SIZE",
                    DEFAULT_INCREMENTAL_INDEX)
            from ..incremental import ClauseSetIndex

            index = ClauseSetIndex(
                capacity=incremental_index_size,
                max_delta_ratio=incremental_max_delta,
                registry=self._registry)
        self.incremental = index
        self.cache = ResultCache(cache_size, registry=self._registry,
                                 incremental=index)
        # Portfolio engine racing (ISSUE 13).  "off" constructs no
        # racer at all — the dispatch path is byte-identical to the
        # pre-portfolio tree; "auto" (the default) races only size
        # classes holding a measured `portfolio` row; "on" races
        # wherever ≥2 candidate backends serve the class.
        if portfolio is None:
            portfolio = config.env_raw("DEPPY_TPU_PORTFOLIO", "auto")
        mode = str(portfolio).strip().lower()
        self._racer: Optional[PortfolioRacer] = None
        if mode not in ("off", "0", "false", "no"):
            if portfolio_k is None:
                portfolio_k = _env_int("DEPPY_TPU_PORTFOLIO_K",
                                       DEFAULT_PORTFOLIO_K)
            if portfolio_sample_check is None:
                portfolio_sample_check = faults.env_float(
                    "DEPPY_TPU_PORTFOLIO_SAMPLE_CHECK",
                    DEFAULT_PORTFOLIO_SAMPLE_CHECK, warn=True)
            self._racer = PortfolioRacer(
                "on" if mode in ("on", "1", "true", "yes") else "auto",
                portfolio_k, portfolio_sample_check, self._registry)
        # Route-health plane (ISSUE 19): installed by
        # deppy_tpu.routes.start_plane.  None (the default) leaves the
        # dispatch path byte-identical — no flush observation, no
        # shadow groups, no route events.
        self._route_plane = None
        # Weighted-fair per-tenant admission + priority lanes (ISSUE
        # 15).  "off" restores the global-depth-only gate and strict
        # FIFO flush head byte for byte; "on" (the default) is ALSO
        # byte-identical while one tenant is queued — the fairness math
        # only bites under multi-tenant contention.
        if fair is None:
            fair = config.env_raw("DEPPY_TPU_SCHED_FAIR", "on")
        self.fair = str(fair).strip().lower() not in ("off", "0",
                                                      "false", "no")
        from .fair import TenantPolicy

        if tenant_weights is None:
            tenant_weights = config.env_raw(
                "DEPPY_TPU_SCHED_TENANT_WEIGHTS")
        self.tenant_policy = TenantPolicy.from_spec(tenant_weights)
        # Queued lanes per tenant (CV-guarded, live queue only — the
        # speculative backlog has its own cap and nobody's SLO rides
        # it).
        self._tenant_depth: dict = {}
        reg = self._registry
        self._c_tenant_sheds = reg.counter(
            "deppy_sched_tenant_sheds_total",
            "Admissions shed by the weighted-fair per-tenant gate, by "
            "tenant (the offender's 503s; victims under their share "
            "keep admitting).", labelname="tenant")
        self._g_depth = reg.gauge(
            "deppy_sched_queue_depth",
            "Problems queued for a coalesced dispatch right now.")
        self._g_depth.set(0)
        self._h_coalesced = reg.histogram(
            "deppy_sched_coalesced_batch_size",
            "Problems per coalesced scheduler dispatch.",
            buckets=telemetry.LANE_BUCKETS)
        self._c_dispatches = reg.counter(
            "deppy_sched_dispatches_total",
            "Coalesced dispatch groups drained from the queue.")
        self._c_requests = reg.counter(
            "deppy_sched_coalesced_requests_total",
            "Requests (submit calls) served per drained dispatch.")
        self._c_flushes = reg.counter(
            "deppy_sched_flushes_total",
            "Queue flushes by trigger (wait = max-wait elapsed, fill = "
            "lane target reached, immediate = blocking interactive "
            "lane at the head, drain = shutdown, inline = loop not "
            "running).", labelname="reason")
        from ..analysis import lockdep

        # Named CV (ISSUE 7): lockdep-instrumented when armed.
        self._cv = lockdep.make_condition("sched.queue")
        self._queue: List[_Group] = []
        self._depth = 0
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        # EWMA of dispatch wall clock, seeding the Retry-After estimate.
        self._dispatch_ewma_s = 0.05
        # Speculative pre-resolution (ISSUE 14).  "off" constructs no
        # manager, no idle queue consumer, no metric families — the
        # submit and dispatch paths are byte-identical to the
        # pre-speculation tree.
        self._spec_queue: List[_Group] = []
        self._spec_depth = 0
        # Fingerprints queued or mid-dispatch on the idle queue (CV-
        # guarded): a duplicate publish burst arriving before the first
        # pre-solves have stored must not double-burn the backlog cap
        # solving the same families twice.
        self._spec_keys: set = set()
        if speculate is None:
            speculate = config.env_raw("DEPPY_TPU_SPECULATE", "on")
        self.speculate = None
        self._g_spec_depth = None
        if str(speculate).strip().lower() not in ("off", "0", "false",
                                                  "no"):
            if speculate_max_backlog is None:
                speculate_max_backlog = _env_int(
                    "DEPPY_TPU_SPECULATE_MAX_BACKLOG",
                    DEFAULT_SPECULATE_MAX_BACKLOG)
            self.spec_max_backlog = max(int(speculate_max_backlog), 0)
            from ..speculate import SpeculationManager

            self.speculate = SpeculationManager(self,
                                                registry=self._registry)
            self._g_spec_depth = reg.gauge(
                "deppy_speculate_backlog",
                "Speculative pre-solve lanes queued at idle priority "
                "right now.")
            self._g_spec_depth.set(0)
        # Deferred background engine re-probe (ISSUE 14 satellite): a
        # breaker-open host drain kicks ONE background probe loop that
        # upgrades `auto` routing once the accelerator recovers, instead
        # of waiting for a process restart (the service's startup
        # pre-warm loop exits once a verdict lands and never watches
        # the breaker).
        self._reprobe_stop = threading.Event()
        self._reprobe_thread: Optional[threading.Thread] = None
        self._reprobe_s = faults.env_float("DEPPY_TPU_REPROBE", 600.0,
                                           warn=True) or 0.0
        if self._mesh is not None:
            self._apply_mesh_sizing(self._mesh)

    # ----------------------------------------------------------------- mesh

    def _apply_mesh_sizing(self, mesh) -> None:
        """Size micro-batches to the mesh: ``n_devices ×
        lanes_per_device`` lanes per flush (ISSUE 6), so a full flush
        hands every device a full shard.  An explicitly passed
        ``max_fill`` wins — the operator said what they meant."""
        if mesh is None or self._max_fill_explicit:
            return
        self.max_fill = max(int(mesh.size) * self.lanes_per_device, 1)

    def _resolve_mesh(self):
        """The serving mesh, resolved lazily on the first device
        dispatch (never on the submit/queue path): by then the backend
        probe has already established that touching the device platform
        is safe.  Resolution failures degrade to single-device dispatch
        — mesh serving must never take down serving."""
        if self._mesh_resolved:
            return self._mesh
        try:
            from ..parallel.mesh import serving_mesh

            self._mesh = serving_mesh(self._mesh_devices)
        except Exception as e:  # noqa: BLE001 — degrade, don't die
            import sys

            print(f"[sched] mesh resolution failed ({e}); serving "
                  f"single-device", file=sys.stderr, flush=True)
            # On the sink too (ISSUE 7 exception-hygiene): a service
            # meant to shard across 8 chips silently serving
            # single-device is an incident, not a log line.
            telemetry.default_registry().event(
                "fault", fault="sched_mesh_unavailable",
                error=type(e).__name__)
            self._mesh = None
        self._mesh_resolved = True
        self._apply_mesh_sizing(self._mesh)
        return self._mesh

    # -------------------------------------------------------------- lifecycle

    def start(self) -> None:
        """Start the dispatch-loop thread (idempotent)."""
        # Event, not CV state: internally synchronized, touched outside
        # the lock on purpose (stop() and the re-probe loop read it
        # lock-free).
        self._reprobe_stop.clear()
        with self._cv:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop = False
            self._thread = threading.Thread(
                target=self._loop, name="deppy-sched", daemon=True)
            self._thread.start()

    def stop(self, timeout: float = 10.0) -> None:
        """Stop the loop; queued LIVE groups drain (dispatch) first so
        no submitter is left hanging — the speculative backlog is
        discarded instead (nobody waits on a pre-solve).  Submits after
        stop dispatch inline."""
        self._reprobe_stop.set()
        with self._cv:
            self._stop = True
            self._cv.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout)
        with self._cv:
            self._thread = None

    @property
    def running(self) -> bool:
        # Under the CV (ISSUE 7 concurrency-discipline): _thread/_stop
        # are written by start()/stop() on other threads, and a torn
        # pair here could route a submit inline while the loop drains
        # the same group.  Reentrant: _enqueue reads this while holding
        # the CV.
        with self._cv:
            t = self._thread
            return t is not None and t.is_alive() and not self._stop

    # ------------------------------------------------------------- admission

    def queue_depth(self) -> int:
        with self._cv:
            return self._depth

    def admission_retry_after(
            self, tenant: str = "default") -> Optional[float]:
        """Seconds a client should back off, or None to admit — the
        service mirrors this into its 503 + Retry-After response.

        With the fair gate off this is the historical GLOBAL check:
        shed everyone once total depth reaches ``max_depth``.  With it
        on (ISSUE 15) the shed is PER TENANT: a tenant sheds once its
        own queued lanes reach its weighted share of ``max_depth``
        among the tenants queued right now — a lone tenant's share is
        the whole queue (identical behavior), while under contention
        the noisy tenant sheds at its share and the victim's lanes
        always find room.  A hard GLOBAL backstop at
        2x ``max_depth`` still bounds the aggregate: per-tenant caps
        sum to ``max_depth`` for any FIXED tenant set, but
        X-Deppy-Tenant is client-controlled and sequentially minted
        fresh tenants could otherwise ratchet total depth to
        ``max_depth * H(T)`` unbounded (each new tenant's share is
        computed against the tenants queued at ITS arrival).  The
        estimate is the number of flushes needed to drain the
        relevant backlog times the recent dispatch wall clock (EWMA),
        floored at 1s."""
        if self.max_depth <= 0:
            return None
        with self._cv:
            depth = self._depth
            ewma = self._dispatch_ewma_s
            if self.fair:
                t_depth = self._tenant_depth.get(tenant, 0)
                active = [t for t, n in self._tenant_depth.items()
                          if n > 0]
            else:
                t_depth, active = depth, []
        if not self.fair:
            if depth < self.max_depth:
                return None
        elif depth >= 2 * self.max_depth:
            # Aggregate backstop: overload protection (memory, drain
            # latency) must not depend on client-chosen tenant labels.
            self._c_tenant_sheds.inc(label=tenant)
            t_depth = max(t_depth, depth)
        else:
            cap = self.tenant_policy.cap(tenant, self.max_depth, active)
            if t_depth < cap:
                return None
            self._c_tenant_sheds.inc(label=tenant)
        flushes = max(t_depth / float(self.max_fill), 1.0)
        return max(flushes * ewma, 1.0)

    # ---------------------------------------------------------------- submit

    def submit(
        self,
        problem_vars: Sequence[Sequence[Variable]],
        deadline_s: Optional[float] = None,
        max_steps: Optional[int] = None,
        stats: Optional[dict] = None,
        tenant: str = "default",
    ) -> List[object]:
        """Resolve ``problem_vars`` through the shared queue; blocks
        until every problem has an answer and returns them in input
        order (Solution dict / NotSatisfiable / Incomplete — the
        BatchResolver contract).  ``stats`` receives ``{"steps": N,
        "report": SolveReport-or-None}`` like the driver's entry
        points, plus ``deadline_misses`` — the count of THIS submit's
        lanes the deadline triage degraded (ISSUE 11: the service's
        per-tenant SLO accountant attributes them to ``tenant``, which
        also rides every lane for fault-event attribution).

        Raises what the unscheduled path raises: DuplicateIdentifier
        from encoding, InternalSolverError for unresolvable references
        (screened here, per lane, BEFORE anything queues — a malformed
        request must never abort a coalesced batchmate's dispatch)."""
        from ..engine.driver import _budget

        if max_steps is None:
            max_steps = self.max_steps
        budget = int(_budget(max_steps))
        pending: List[tuple] = []
        warm_pending: List[tuple] = []
        with telemetry.default_registry().span(
                "sched.encode", problems=len(problem_vars)):
            problems = [encode(vs) for vs in problem_vars]
            for p in problems:
                if p.errors:
                    raise InternalSolverError(p.errors)
            # Capture the request's effective deadline (explicit scope,
            # enclosing scope, or ambient env) as an OBJECT: its clock
            # keeps ticking across the thread hop to the dispatch loop.
            with faults.deadline_scope(deadline_s), \
                    faults.ambient_deadline():
                dl = faults.current_deadline()
            results: List[object] = [None] * len(problems)
            for i, p in enumerate(problems):
                key = fingerprint(p)
                if self.speculate is not None:
                    # Retain the served family so a later catalog
                    # publish can be applied to it and pre-solved.
                    self.speculate.observe(key, problem_vars[i])
                hit, plan = self.cache.lookup_or_plan(p, key, budget)
                if hit is not MISS:
                    results[i] = hit  # bypasses the queue entirely
                elif plan is not None:
                    # A certified warm plan queues in the incremental
                    # size class — warm lanes coalesce with each other
                    # instead of padding out a cold batch.
                    warm_pending.append(
                        (i, _Lane(p, key, max_steps, budget, dl, warm=plan,
                                  tenant=tenant)))
                else:
                    pending.append((i, _Lane(p, key, max_steps, budget,
                                             dl, tenant=tenant)))
        steps = 0
        deadline_misses = 0
        report = None
        timing: dict = {}
        groups: List[tuple] = []
        prio = (self.tenant_policy.priority(tenant) if self.fair
                else 1)
        if pending:
            groups.append(
                (pending, self._make_group([lane for _, lane in pending],
                                           budget, priority=prio)))
        if warm_pending:
            groups.append(
                (warm_pending,
                 _Group([lane for _, lane in warm_pending],
                        INCREMENTAL_CLASS, budget, priority=prio)))
        for _, group in groups:
            self._enqueue(group)
        for grp_pending, group in groups:
            group.event.wait()
            if group.error is not None:
                raise group.error
            if group.report is not None:
                if report is None:
                    report = group.report
                else:
                    # Never merge IN PLACE: a group's report object is
                    # shared with every request coalesced into the same
                    # dispatch — fold both into a fresh one instead.
                    merged = telemetry.SolveReport(
                        backend=report.backend)
                    merged.n_problems = 0
                    merged.merge(report)
                    merged.merge(group.report)
                    report = merged
            for k, v in group.timing.items():
                # A mixed submit spans two dispatches (cold + warm
                # groups): sequential stage durations ADD — letting the
                # second group's few-ms warm flush overwrite the first's
                # device dispatch would misreport the breakdown — but
                # the groups QUEUE concurrently, so overlapped waits
                # take the max, not the sum.
                if isinstance(v, (int, float)) and k in timing:
                    timing[k] = (max(timing[k], v)
                                 if k == "queue_wait_s"
                                 else timing[k] + v)
                else:
                    timing[k] = v
            for i, lane in grp_pending:
                results[i] = lane.result
                steps += lane.steps
                if lane.degraded:
                    deadline_misses += 1
                    # Precise error attribution (ISSUE 4): the deadline
                    # fault event rode the shared dispatch trace, but
                    # only THIS request's lane was triaged expired —
                    # flag this trace, not the batchmates', and not a
                    # budget-exhaustion Incomplete whose deadline
                    # happened to lapse by readback time.
                    telemetry.trace.mark_error()
            qw = group.timing.get("queue_wait_s")
            if qw is not None:
                # Recorded on the submitting thread so the span joins
                # THIS request's trace (the wait was measured on the
                # dispatch loop's clock).
                telemetry.default_registry().record_span(
                    "sched.queue_wait", qw, lanes=len(group.lanes))
        if stats is not None:
            stats["steps"] = steps
            stats["report"] = report
            stats["timings"] = dict(timing)
            stats["deadline_misses"] = deadline_misses
        return results

    def _make_group(self, lanes: List[_Lane], budget: int,
                    speculative: bool = False,
                    priority: int = 1) -> _Group:
        from ..engine.driver import _bucket, _cost_proxy

        size_class = _bucket(max(_cost_proxy(l.problem) for l in lanes))
        return _Group(lanes, size_class, budget, speculative=speculative,
                      priority=priority)

    # --------------------------------------------- sessions (ISSUE 20)

    def submit_session(
        self,
        problem_vars: Sequence[Variable],
        deadline_s: Optional[float] = None,
        max_steps: Optional[int] = None,
        stats: Optional[dict] = None,
        tenant: str = "default",
        warm_index=None,
        session_key: Optional[str] = None,
        scope_entry_key: Optional[str] = None,
        scope_seed=None,
        problem: Optional[Problem] = None,
    ) -> object:
        """Blocking single-problem submit for a stateful session's (or an
        open test scope's) incremental solve.  The answer is exactly what
        ``submit`` would return for the same variables — same engines,
        same racing, same deadline/breaker/fair-admission semantics — but
        the lane is **scoped**: it skips the shared result cache entirely
        (no lookup, no store — assumption-conditioned answers must never
        serve or poison stateless traffic, satellite 2 of ISSUE 20), its
        cold dispatch rides the dedicated ``SESSION_CLASS`` bucket, and
        warm starts plan against ``warm_index`` — the session's private
        clause-set index holding the session's own last model — rather
        than the shared index.  An ``assume`` appends constraints without
        touching the vocabulary, so the derived problem's delta cone
        against the session's previous solve is small and the PR 9 warm
        machinery applies unchanged.

        A per-step scoped solve must not re-pay O(problem) bookkeeping
        the caller already knows the answer to, so the session facade
        may hand over what it tracks: ``session_key`` replaces the
        canonical ``fingerprint(p)`` as the lane key — legitimate ONLY
        because scoped lanes never touch the shared result cache, the
        key's sole job is entry identity inside the session's private
        index — and ``scope_entry_key`` + ``scope_seed`` (the previous
        scoped solve's key and the assumption-stack delta's variable
        indices) let the index plan O(delta) via
        :meth:`ClauseSetIndex.plan_for_scope` instead of re-hashing and
        re-scanning the whole problem.  When the declared predecessor
        is missing (first solve, UNSAT last step, post-handoff import)
        the generic classifier answers, and when no plan survives the
        gates the lane cold-solves — identity holds on every path.
        ``problem`` is the already-lowered form of ``problem_vars``
        (the facade's ``encode_assumed`` splice) — same dense tensors
        a fresh ``encode`` would produce, without the catalog re-walk.

        Returns the single result (Solution dict / NotSatisfiable /
        Incomplete); raises what ``submit`` raises for malformed input."""
        from ..engine.driver import _budget

        if max_steps is None:
            max_steps = self.max_steps
        budget = int(_budget(max_steps))
        p = problem if problem is not None else encode(problem_vars)
        if p.errors:
            raise InternalSolverError(p.errors)
        with faults.deadline_scope(deadline_s), faults.ambient_deadline():
            dl = faults.current_deadline()
        key = session_key if session_key is not None else fingerprint(p)
        plan = None
        if warm_index is not None:
            if scope_entry_key is not None:
                plan = warm_index.plan_for_scope(
                    p, key, budget, scope_entry_key, scope_seed or ())
            if plan is None:
                plan = warm_index.plan(p, key, budget)
        lane = _Lane(p, key, max_steps, budget, dl, warm=plan,
                     tenant=tenant)
        lane.scoped = True
        lane.session_index = warm_index
        prio = (self.tenant_policy.priority(tenant) if self.fair
                else 1)
        if plan is not None:
            group = _Group([lane], INCREMENTAL_CLASS, budget,
                           priority=prio, immediate=True)
        else:
            group = _Group([lane], SESSION_CLASS, budget, priority=prio,
                           immediate=True)
        self._enqueue(group)
        group.event.wait()
        if group.error is not None:
            raise group.error
        if lane.degraded:
            telemetry.trace.mark_error()
        qw = group.timing.get("queue_wait_s")
        if qw is not None:
            telemetry.default_registry().record_span(
                "sched.queue_wait", qw, lanes=1)
        if stats is not None:
            stats["steps"] = lane.steps
            stats["report"] = group.report
            stats["timings"] = dict(group.timing)
            stats["deadline_misses"] = 1 if lane.degraded else 0
            stats["warm"] = plan is not None
        return lane.result

    # ------------------------------------------------ speculation (ISSUE 14)

    def speculative_depth(self) -> int:
        """Speculative pre-solve lanes queued at idle priority."""
        with self._cv:
            return self._spec_depth

    def submit_speculative(
        self,
        problem_vars: Sequence[Sequence[Variable]],
        max_steps: Optional[int] = None,
    ) -> tuple:
        """Queue pre-solves at IDLE priority and return immediately with
        ``(queued, dropped)`` lane counts — fire-and-forget: results
        land in the result cache and the clause-set index exactly like
        ordinary solves, and nobody blocks on them.  The dispatch loop
        drains these groups only while no live group is queued, so live
        traffic preempts at every flush boundary.  Malformed families,
        already-cached fingerprints, and within-call duplicates are
        skipped; lanes past the backlog cap (or arriving while the loop
        is not running — a pre-solve must never dispatch inline on a
        publisher's thread) are dropped."""
        if self.speculate is None:
            return 0, len(problem_vars)
        from ..engine.driver import _budget

        if max_steps is None:
            max_steps = self.max_steps
        budget = int(_budget(max_steps))
        dropped = 0
        seen: set = set()
        cold: List[_Lane] = []
        warm: List[_Lane] = []
        for vs in problem_vars:
            try:
                p = encode(vs)
            except Exception as e:  # noqa: BLE001 — a malformed family
                # must never abort the rest of a publish burst; it is a
                # counted drop with a sink event, not a request error
                # (no requester exists to answer).
                telemetry.default_registry().event(
                    "fault", fault="speculate_encode_failed",
                    error=type(e).__name__)
                dropped += 1
                continue
            if p.errors:
                dropped += 1
                continue
            key = fingerprint(p)
            if key in seen:
                continue
            seen.add(key)
            if self.cache.peek(key, budget):
                continue  # the answer is already served from cache
            plan = (self.incremental.plan(p, key, budget)
                    if self.incremental is not None else None)
            lane = _Lane(p, key, max_steps, budget, None, warm=plan,
                         tenant="speculate")
            (warm if plan is not None else cold).append(lane)
            # Retain the POST-publish family under its new fingerprint:
            # a later publish must compose on this state, not the
            # superseded one the publish just retired.
            self.speculate.observe(key, vs)
        groups: List[_Group] = []
        # One group per cold family keeps size classes honest (the
        # spec drain coalesces same-class neighbors like the live
        # drain); warm lanes coalesce as the incremental class.
        for lane in cold:
            groups.append(self._make_group([lane], budget,
                                           speculative=True))
        if warm:
            groups.append(_Group(warm, INCREMENTAL_CLASS, budget,
                                 speculative=True))
        queued = 0
        with self._cv:
            admit = self.running
            for g in groups:
                # Drop lanes whose fingerprint is already queued or
                # mid-dispatch (a duplicate publish burst): neither
                # queued nor dropped — the answer is already on its
                # way.  The cache is re-peeked HERE because a pre-solve
                # can complete (store + key release) between the
                # pre-encode peek above and this enqueue; peek is a
                # leaf lock, safe under the CV.
                g.lanes = [lane for lane in g.lanes
                           if lane.key not in self._spec_keys
                           and not self.cache.peek(lane.key, budget)]
                if not g.lanes:
                    continue
                if (not admit or self._spec_depth + len(g.lanes)
                        > self.spec_max_backlog):
                    dropped += len(g.lanes)
                    continue
                self._spec_keys.update(lane.key for lane in g.lanes)
                self._spec_queue.append(g)
                self._spec_depth += len(g.lanes)
                queued += len(g.lanes)
            if self._g_spec_depth is not None:
                self._g_spec_depth.set(self._spec_depth)
            if queued:
                self._cv.notify_all()
        return queued, dropped

    def submit_optimize(
        self,
        problem_vars: Sequence[Sequence[Variable]],
        deadline_s: Optional[float] = None,
        max_steps: Optional[int] = None,
        stats: Optional[dict] = None,
        tenant: str = "default",
    ) -> List[object]:
        """Blocking :meth:`submit` sibling for optimize-tier bound
        probes (ISSUE 18), queued at IDLE priority: probe groups ride
        the speculative queue, so a long bound-tightening loop coalesces
        at flush boundaries like churn and live resolution traffic
        preempts every iteration — but unlike pre-solves a submitter IS
        waiting, so probes are never cap-dropped (the blocked caller is
        the backpressure) and dispatch errors re-raise here.

        Probes skip the result cache and the warm-plan index on purpose:
        a probe's answer doubles as an optimality proof, so it must come
        from an actual solve, and its model (biased by the synthetic
        bound variable) must not seed warm starts for plain requests."""
        from ..engine.driver import _budget

        if max_steps is None:
            max_steps = self.max_steps
        budget = int(_budget(max_steps))
        problems = [encode(vs) for vs in problem_vars]
        for p in problems:
            if p.errors:
                raise InternalSolverError(p.errors)
        with faults.deadline_scope(deadline_s), faults.ambient_deadline():
            dl = faults.current_deadline()
        lanes = [_Lane(p, fingerprint(p), max_steps, budget, dl,
                       tenant=tenant) for p in problems]
        group = self._make_group(lanes, budget, speculative=True)
        inline = False
        with self._cv:
            if self.running:
                self._spec_queue.append(group)
                self._spec_depth += len(group.lanes)
                if self._g_spec_depth is not None:
                    self._g_spec_depth.set(self._spec_depth)
                self._cv.notify_all()
            else:
                inline = True
        if inline:
            # No loop thread (library use, or post-shutdown stragglers):
            # the probe dispatches on the caller's thread like _enqueue.
            self._dispatch([group], reason="inline")
        group.event.wait()
        if group.error is not None:
            raise group.error
        deadline_misses = 0
        for lane in lanes:
            if lane.degraded:
                deadline_misses += 1
                telemetry.trace.mark_error()
        qw = group.timing.get("queue_wait_s")
        if qw is not None:
            telemetry.default_registry().record_span(
                "sched.queue_wait", qw, lanes=len(group.lanes))
        if stats is not None:
            stats["steps"] = sum(lane.steps for lane in lanes)
            stats["report"] = group.report
            stats["timings"] = dict(group.timing)
            stats["deadline_misses"] = deadline_misses
        return [lane.result for lane in lanes]

    # ---------------------------------------------- route plane (ISSUE 19)

    def set_route_plane(self, plane) -> None:
        """Install (or, with None, remove) the route-health plane.  The
        plane observes every cold live flush after its answers are
        served and may enqueue shadow route probes via
        :meth:`submit_shadow`."""
        self._route_plane = plane

    def submit_shadow(self, backend_name: str, class_name: str,
                      problems: Sequence[Problem],
                      max_steps: Optional[int] = None) -> bool:
        """Queue one shadow route probe (ISSUE 19) at IDLE priority:
        re-solve an already-coalesced flush's problems via ONE named
        backend, timing it for the route ledger.  Rides the speculative
        queue, so live traffic preempts every shadow dispatch at the
        flush boundary; results are emitted as a ``route`` sink event
        and NEVER touch a lane result, the cache, or the warm index.
        Returns False when dropped (loop not running, or the idle
        backlog is full — a shadow probe is pure opportunism)."""
        from ..engine.driver import _budget

        if max_steps is None:
            max_steps = self.max_steps
        budget = int(_budget(max_steps))
        lanes = [_Lane(p, "", max_steps, budget, None, tenant="shadow")
                 for p in problems]
        # The size class carries a shadow-only sentinel so the idle
        # drain's coalescing can never mix a shadow probe into an
        # optimize/pre-solve flush (those dispatch through the normal
        # solve path; shadow groups do not).
        group = _Group(lanes, f"shadow:{class_name}:{backend_name}",
                       budget, speculative=True)
        group.shadow_backend = backend_name
        group.shadow_class = class_name
        cap = getattr(self, "spec_max_backlog",
                      DEFAULT_SPECULATE_MAX_BACKLOG)
        with self._cv:
            if (not self.running
                    or self._spec_depth + len(lanes) > cap):
                return False
            self._spec_queue.append(group)
            self._spec_depth += len(lanes)
            if self._g_spec_depth is not None:
                self._g_spec_depth.set(self._spec_depth)
            self._cv.notify_all()
        return True

    def _dispatch_shadow(self, groups: List[_Group]) -> None:
        """Drain shadow route probes: one timed ``solve_via`` dispatch
        per group, answers discarded, wall clock + definitiveness
        emitted as a ``route`` event for the ledger/learner.  Failures
        are counted on the sink — a shadow probe must never take down
        the dispatch loop."""
        from ..engine import registry as engine_registry

        for g in groups:
            problems = [lane.problem for lane in g.lanes]
            name = g.shadow_backend
            out = None
            err = None
            t1 = time.perf_counter()
            try:
                faults.inject(f"sched.shadow.{name}")
                mesh = (self._resolve_mesh() if name == "device"
                        else None)
                out = engine_registry.solve_via(
                    name, problems, max_steps=g.lanes[0].max_steps,
                    mesh=mesh)
            except BaseException as e:  # noqa: BLE001 — probe-local
                err = type(e).__name__
            finally:
                wall = time.perf_counter() - t1
                ok = (err is None and out is not None
                      and all(r is not None and not r.degraded
                              for r in out))
                telemetry.default_registry().event(
                    "route", phase="shadow",
                    size_class_name=g.shadow_class, backend=name,
                    lanes=len(g.lanes), wall_s=round(wall, 6),
                    ok=bool(ok), error=err)
                g.event.set()

    def _enqueue(self, group: _Group) -> None:
        with self._cv:
            if self.running:
                self._queue.append(group)
                self._depth += len(group.lanes)
                self._tenant_depth[group.tenant] = (
                    self._tenant_depth.get(group.tenant, 0)
                    + len(group.lanes))
                self._g_depth.set(self._depth)
                self._cv.notify_all()
                return
        # No loop thread (library use, or post-shutdown stragglers):
        # dispatch on the caller's thread — same code path, no queue.
        self._dispatch([group], reason="inline")

    # --------------------------------------------------------- dispatch loop

    def _loop(self) -> None:
        try:
            self._loop_inner()
        finally:
            # A normal stop drains the queue through dispatches; this
            # only fires on an unexpected loop crash — fail any still-
            # queued groups loudly so no submitter waits forever.
            with self._cv:
                orphans, self._queue = self._queue, []
                self._depth = 0
                self._tenant_depth.clear()
                self._g_depth.set(0)
                # Speculative orphans fail loudly too (ISSUE 18): a
                # pre-solve's event has no waiter, but an optimize
                # probe's does — leaving it unset parks that submitter
                # forever.
                orphans += self._spec_queue
                self._spec_queue = []
                self._spec_depth = 0
                self._spec_keys.clear()
                if self._g_spec_depth is not None:
                    self._g_spec_depth.set(0)
            for g in orphans:
                if not g.event.is_set():
                    g.error = RuntimeError(
                        "scheduler dispatch loop exited unexpectedly")
                    g.event.set()

    def _loop_inner(self) -> None:
        # The loop's two waits are timeline phases, one span per wait
        # and not per wake-up: ``sched.idle`` while nothing is queued,
        # ``sched.coalesce`` while the head is queued but not yet due.
        # Spans open and close outside the lock; a wait starts only
        # under the span of its kind, in the same hold of the lock that
        # found it due, so no notify is lost between the two.
        reg = telemetry.default_registry()
        waiting = None
        try:
            while True:
                discarded = 0
                spec_orphans: List[_Group] = []
                groups: List[_Group] = []
                reason = None
                phase = None
                with self._cv:
                    if (not self._queue and not self._spec_queue
                            and not self._stop):
                        phase = "sched.idle"
                        if waiting is not None and waiting.name == phase:
                            self._cv.wait()
                            continue
                    if self._stop and self._spec_queue:
                        # Shutdown discards the speculative backlog: no
                        # submitter waits on a pre-solve, and
                        # opportunistic work must never slow a drain.
                        # Optimize probes ride this queue WITH a waiter
                        # — their groups are failed below, outside the
                        # lock.
                        discarded = self._spec_depth
                        spec_orphans = self._spec_queue
                        self._spec_queue = []
                        self._spec_depth = 0
                        self._spec_keys.clear()
                        if self._g_spec_depth is not None:
                            self._g_spec_depth.set(0)
                    if self._queue:
                        groups, reason = self._drain_locked(
                            force=self._stop)
                        if not groups:
                            # A live flush is pending but not yet due.
                            # The speculative queue is NOT consulted in
                            # this window: a pre-solve dispatch here
                            # could push the live flush past max_wait —
                            # idle priority means idle, not "between
                            # live flushes".
                            phase = "sched.coalesce"
                            if (waiting is not None
                                    and waiting.name == phase):
                                head_due = (self._head_locked().enq_t
                                            + self.max_wait_s)
                                delay = head_due - time.monotonic()
                                self._cv.wait(timeout=max(delay, 0.001))
                                continue
                    elif self._spec_queue:
                        # Live lanes are empty — drain ONE speculative
                        # flush.  Live submits arriving during the
                        # dispatch preempt at the next loop iteration
                        # (the flush boundary).
                        groups, reason = self._drain_spec_locked()
                if waiting is not None:
                    waiting.__exit__(None, None, None)
                    waiting = None
                if phase is not None:
                    # A wait of another kind begins: the next turn
                    # waits under its span.
                    waiting = reg.span(phase).__enter__()
                    continue
                for g in spec_orphans:
                    if not g.event.is_set():
                        g.error = RuntimeError(
                            "scheduler stopped before optimize dispatch")
                        g.event.set()
                if discarded and self.speculate is not None:
                    self.speculate.note_discarded(discarded)
                if not groups:
                    return  # stopped and drained
                self._dispatch(groups, reason)
        finally:
            if waiting is not None:
                waiting.__exit__(None, None, None)

    def _drain_spec_locked(self):
        """Pick one speculative flush (caller holds the lock): the
        oldest speculative group plus its same-class, same-budget
        neighbors up to ``max_fill`` lanes — the live drain's coalescing
        rule applied to the idle queue."""
        head = self._spec_queue[0]
        take = [head]
        lanes = len(head.lanes)
        for g in self._spec_queue[1:]:
            if lanes >= self.max_fill:
                break
            if (g.size_class == head.size_class
                    and g.budget == head.budget
                    and lanes + len(g.lanes) <= self.max_fill):
                take.append(g)
                lanes += len(g.lanes)
        taken = set(map(id, take))
        self._spec_queue = [g for g in self._spec_queue
                            if id(g) not in taken]
        self._spec_depth -= lanes
        if self._g_spec_depth is not None:
            self._g_spec_depth.set(self._spec_depth)
        return take, "spec"

    # A queued group older than this many coalescing windows becomes
    # the flush head regardless of priority class: a sustained urgent
    # stream must not starve bulk lanes forever (their submitter
    # threads block on group.event with no timeout — the historical
    # FIFO head guaranteed dispatch within ~max_wait).
    PRIORITY_AGING_WINDOWS = 100

    def _head_locked(self) -> _Group:
        """The next flush head (caller holds the lock): the oldest
        group of the most urgent priority class queued (ISSUE 15 —
        priority lanes; with every group at the default priority this
        is exactly the historical FIFO head), unless the globally
        oldest group has aged past PRIORITY_AGING_WINDOWS coalescing
        windows — starvation beats priority."""
        oldest = min(self._queue, key=lambda g: g.enq_t)
        aging_s = max(self.max_wait_s * self.PRIORITY_AGING_WINDOWS,
                      0.5)
        if time.monotonic() - oldest.enq_t >= aging_s:
            return oldest
        return min(self._queue, key=lambda g: (g.priority, g.enq_t))

    def _drain_locked(self, force: bool = False):
        """Pick the flushable group set (caller holds the lock): the
        priority head plus every queued group in its size class and
        budget, up to ``max_fill`` lanes.  Coalescing ignores priority
        — same-class batchmates share the head's dispatch, which is a
        free ride for them, never a delay for the head.  Returns
        ([], None) when no flush is due yet."""
        head = self._head_locked()
        take = [head]
        lanes = len(head.lanes)
        for g in self._queue:
            if lanes >= self.max_fill:
                break
            if (g is not head and g.size_class == head.size_class
                    and g.budget == head.budget
                    and lanes + len(g.lanes) <= self.max_fill):
                take.append(g)
                lanes += len(g.lanes)
        if force:
            reason = "drain"
        elif lanes >= self.max_fill:
            reason = "fill"
        elif head.immediate:
            reason = "immediate"
        elif time.monotonic() - head.enq_t >= self.max_wait_s:
            reason = "wait"
        else:
            return [], None
        taken = set(map(id, take))
        self._queue = [g for g in self._queue if id(g) not in taken]
        self._depth -= lanes
        for g in take:
            left = self._tenant_depth.get(g.tenant, 0) - len(g.lanes)
            if left > 0:
                self._tenant_depth[g.tenant] = left
            else:
                self._tenant_depth.pop(g.tenant, None)
        self._g_depth.set(self._depth)
        return take, reason

    def _dispatch(self, groups: List[_Group], reason: str) -> None:
        if groups and groups[0].shadow_backend is not None:
            # Shadow route probes (ISSUE 19) never coalesce with real
            # groups (their size-class sentinel is shadow-only), so a
            # drained set is homogeneous.
            self._dispatch_shadow(groups)
            return
        lanes = [lane for g in groups for lane in g.lanes]
        t0 = time.monotonic()
        report = None
        timing: dict = {}
        # Everything — telemetry included — runs inside the try: the
        # finally below is the only thing standing between a failure
        # here and submitters parked forever on their group events.
        try:
            for g in groups:
                g.timing["queue_wait_s"] = max(t0 - g.enq_t, 0.0)
            self._c_flushes.inc(label=reason)
            self._c_dispatches.inc()
            self._c_requests.inc(len(groups))
            self._h_coalesced.observe(len(lanes))
            # Trace scope (ISSUE 4): on the loop thread this is a fresh
            # dispatch trace whose root span LINKS to every parent
            # request — each request's flight record then contains the
            # shared dispatch's whole span tree; inline (caller-thread)
            # dispatches nest under the request's own trace instead.
            reg = telemetry.default_registry()
            with telemetry.trace.dispatch_scope(
                    [g.parent for g in groups]) as dctx:
                with reg.span("sched.dispatch", lanes=len(lanes),
                              requests=len(groups), reason=reason) as sp:
                    if dctx is not None:
                        for link in dctx.links:
                            sp.link(link["trace_id"],
                                    link.get("span_id"))
                    faults.inject("sched.dispatch")
                    report = self._solve_lanes(lanes, timing)
            with reg.span("sched.deliver", lanes=len(lanes)):
                for lane in lanes:
                    self._maybe_cache(lane)
        except BaseException as e:  # noqa: BLE001 — re-raised per request
            for g in groups:
                g.error = e
            if any(g.speculative for g in groups):
                # No submitter exists to re-raise a speculative group's
                # error into (ISSUE 14) — surface it on the sink: a
                # publish burst silently failing to pre-solve would
                # read as "speculation working, cache cold".
                telemetry.default_registry().event(
                    "fault", fault="speculate_dispatch_failed",
                    error=type(e).__name__,
                    lanes=sum(len(g.lanes) for g in groups
                              if g.speculative))
        finally:
            dur = time.monotonic() - t0
            # The wake-ups are the rest of sched.deliver.
            with telemetry.default_registry().span("sched.deliver",
                                                   groups=len(groups)):
                # Read-modify-write under the CV: admission_retry_after
                # reads the EWMA from handler threads while the dispatch
                # loop updates it here (the first real finding the
                # concurrency audit fixed; pinned by
                # tests/test_analysis.py::TestSchedulerEwmaRegression).
                with self._cv:
                    self._dispatch_ewma_s = (0.8 * self._dispatch_ewma_s
                                             + 0.2 * dur)
                    for g in groups:
                        if g.speculative:
                            # The pre-solve is stored (or failed) —
                            # later duplicates dedupe through the cache
                            # peek, not the in-flight key set.
                            self._spec_keys.difference_update(
                                lane.key for lane in g.lanes)
                timing["dispatch_s"] = dur
                for g in groups:
                    g.timing.update(timing)
                    g.report = report
                    g.event.set()

    def _maybe_cache(self, lane: _Lane) -> None:
        r = lane.result
        if lane.scoped:
            # ISSUE 20: assumption-conditioned answers never reach the
            # shared exact LRU or clause-set index — they would poison
            # stateless traffic with results that only hold under the
            # session's assumption stack.  The session's private index
            # takes the model instead (same eligibility gate as the
            # shared index: measured, zero-backtrack-certifiable, not
            # degraded) so the session's NEXT op warm-starts from it.
            if (lane.session_index is not None and isinstance(r, dict)
                    and not lane.degraded and lane.backtracks is not None):
                model = np.fromiter(
                    (bool(r[v.identifier])
                     for v in lane.problem.variables),
                    dtype=bool, count=lane.problem.n_vars)
                lane.session_index.store(
                    lane.key, lane.problem, model,
                    lane.index_steps if lane.index_steps is not None
                    else lane.steps,
                    lane.backtracks, lazy_rows=True)
            return
        if isinstance(r, (dict, NotSatisfiable)):
            self.cache.store(lane.key, lane.budget, r)
        elif isinstance(r, Incomplete) and lane.deadline is None:
            # Budget exhaustion is reproducible; deadline degradation
            # is not — only the former may be cached.
            self.cache.store(lane.key, lane.budget, r)
        # ISSUE 10: SAT models feed the clause-set index so the NEXT
        # delta against this problem warm-starts.  Only lanes whose path
        # measured the search-backtrack count are eligible (the index
        # keeps zero-backtrack seeds only — the warm certification
        # precondition); degraded lanes never are.
        if (self.incremental is not None and isinstance(r, dict)
                and not lane.degraded and lane.backtracks is not None):
            model = np.fromiter(
                (bool(r[v.identifier])
                 for v in lane.problem.variables),
                dtype=bool, count=lane.problem.n_vars)
            self.incremental.store(
                lane.key, lane.problem, model,
                lane.index_steps if lane.index_steps is not None
                else lane.steps,
                lane.backtracks)

    # -------------------------------------------------------------- solving

    def _solve_lanes(self, lanes: List[_Lane], timing: Optional[dict] = None):
        """Solve one coalesced lane set; fills each lane's result/steps
        and returns the dispatch's SolveReport.  ``timing``, when given,
        receives the solve/decode wall-clock split (ISSUE 4)."""
        from ..sat.solver import resolve_backend

        if timing is None:
            timing = {}

        live: List[_Lane] = []
        for lane in lanes:
            if lane.deadline is not None and lane.deadline.expired():
                # Expired at triage: degrade THIS lane only — its
                # batchmates dispatch unharmed.  The fault event carries
                # the lane's tenant (ISSUE 11) so deadline misses are
                # attributable per tenant from the sink alone.
                faults.note_deadline_exceeded("sched.dispatch",
                                              tenant=lane.tenant)
                lane.result = Incomplete()
                lane.steps = 0
                lane.degraded = True
            else:
                live.append(lane)
        if not live:
            return None
        # The dispatch runs under the LOOSEST live deadline (the driver
        # degrades whole groups past the scope's expiry, and a
        # stranger's tighter budget must not cut a batchmate short).
        # Any unbounded lane means an unbounded dispatch.
        scope = None
        deadlines = [lane.deadline for lane in live]
        if all(d is not None for d in deadlines):
            scope = max(deadlines, key=lambda d: d.remaining())
        backend = resolve_backend(self.backend)
        if (self.backend == "auto" and backend == "host"
                and faults.default_breaker().blocks_device()):
            # ISSUE 14 satellite: this flush is a breaker-open host
            # drain — kick the deferred background re-probe so auto
            # routing upgrades once the accelerator recovers, instead
            # of waiting for a restart.
            self._kick_reprobe()
        rep, owns = telemetry.begin_report(backend=backend,
                                           n_problems=len(live))
        cold_flush = False
        try:
            with faults.deadline_scope(scope):
                if all(lane.warm is not None for lane in live):
                    # ISSUE 10: an incremental-class flush — warm
                    # attempts first, cold fallbacks drain through the
                    # normal backend path below.
                    t1 = time.perf_counter()
                    self._solve_incremental(live, rep, timing, backend)
                    timing["solve_s"] = time.perf_counter() - t1
                    return rep
                cold_flush = True
                # Portfolio racing (ISSUE 13): cold flushes only.  A
                # None plan (racing off / auto with no measured row /
                # <2 candidates) leaves the canonical single-backend
                # path below byte-identical to the pre-portfolio tree.
                plan = (self._racer.plan(live, backend)
                        if self._racer is not None else None)
                finisher = None
                raced = False
                try:
                    if plan is not None:
                        live, finisher = self._triage_stragglers(
                            live, plan.class_name)
                        if live:
                            raced = self._racer.race(
                                plan, live, rep, timing,
                                self._resolve_mesh)
                        else:
                            raced = True
                    if not raced:
                        if backend == "host":
                            t1 = time.perf_counter()
                            self._solve_host(live, rep)
                            timing["solve_s"] = (time.perf_counter()
                                                 - t1)
                        else:
                            self._solve_device(live, timing)
                finally:
                    if finisher is not None:
                        finisher(rep)
        finally:
            telemetry.end_report(rep, owns)
        if (self._route_plane is not None and cold_flush and live
                and any(lane.tenant not in ("speculate", "shadow")
                        for lane in live)):
            # ISSUE 19: the route plane observes the flush after its
            # answers are computed — O(1) bookkeeping plus at most one
            # idle-queue enqueue; the shadow solve itself runs later,
            # only while the live queue is empty.  Observability must
            # never fail serving.
            try:
                self._route_plane.observe_flush(self, live)
            # deppy: lint-ok[exception-hygiene] route-health bookkeeping must never fail a flush that already has answers
            except Exception:
                pass
        return rep

    def _solve_device(self, live: List[_Lane], timing: dict) -> None:
        from ..engine import driver

        problems = [lane.problem for lane in live]
        # All live lanes share one normalized budget (the flush policy
        # only coalesces equal-budget groups).  Under a serving mesh
        # (ISSUE 6) the coalesced micro-batch drains through the
        # sharded entry point — lane axis split across devices,
        # per-shard fault domains; otherwise solve_problems runs the
        # group under the process-wide fault-domain recovery wrapper.
        # Both merge their telemetry into the report begun above.
        mesh = self._resolve_mesh()
        t1 = time.perf_counter()
        if mesh is not None:
            results = driver.solve_problems_sharded(
                problems, mesh=mesh, max_steps=live[0].max_steps)
        else:
            results = driver.solve_problems(problems,
                                            max_steps=live[0].max_steps)
        timing["solve_s"] = time.perf_counter() - t1
        t1 = time.perf_counter()
        decoded = driver.decode_results(problems, results)
        timing["decode_s"] = time.perf_counter() - t1
        for lane, res, dec in zip(live, results, decoded):
            lane.steps = int(res.steps)
            lane.backtracks = int(res.trace_n)
            lane.result = dec

    def _solve_incremental(self, live: List[_Lane], rep,
                           timing: dict, backend: str) -> None:
        """Drain one incremental-class flush: device-screen the warm
        prefixes (lockstep, device backend only), run the surviving
        warm attempts on the host spec engine, and cold-solve every
        fallback through the NORMAL backend path — fault domain and
        breaker semantics unchanged.  Per-lane deadlines are admission
        checks before each warm attempt (the hostpool convention: a
        lane never preempts mid-solve), so a lapse during the flush
        degrades only the lanes not yet started."""
        from .. import incremental as inc

        prof_t0 = _profile.dispatch_t0("warm")
        warm_served = 0
        warm_steps = 0
        plans = [lane.warm for lane in live]
        screened = [True] * len(live)
        if (backend != "host" and len(live) > 1
                and not faults.default_breaker().blocks_device()):
            # The batched device lane variant: one lockstep pass over
            # the whole warm class instead of per-lane host prefix
            # tests.  Router only — failures degrade to all-pass — and
            # an OPEN breaker skips it outright: its contract is zero
            # device attempts, and a wedged accelerator would hang the
            # dispatch loop here, not raise (explicit-tpu with an open
            # breaker is already 503'd at admission; this covers the
            # race and library callers).
            screened = inc.screen(plans)
        cold: List[_Lane] = []
        for lane, plan, ok in zip(live, plans, screened):
            if lane.deadline is not None and lane.deadline.expired():
                faults.note_deadline_exceeded("sched.dispatch",
                                              tenant=lane.tenant)
                rep.count_outcome("incomplete")
                lane.result = Incomplete()
                lane.degraded = True
                continue
            res = inc.attempt(plan, lane.max_steps) if ok else None
            if res is None:
                if self.incremental is not None:
                    self.incremental.note_fallback()
                cold.append(lane)
                continue
            lane.result = _solution_dict(lane.problem, res.installed_idx)
            lane.steps = res.steps
            lane.backtracks = res.backtracks
            # Index under a cold-equivalent cost: the seeding entry's
            # cold steps plus this cone's work bounds what a cold solve
            # of THIS problem would spend far better than the warm
            # attempt's own count does.
            lane.index_steps = plan.entry_steps + res.steps
            warm_served += 1
            warm_steps += res.steps
            rep.count_outcome("sat")
            rep.steps += res.steps
            rep.decisions += res.decisions
            rep.propagation_rounds += res.propagation_rounds
            if self.incremental is not None:
                self.incremental.note_served()
        if prof_t0 is not None and warm_served:
            # ISSUE 11: warm-tier cost attribution — the screen + warm
            # attempts up to here; cold fallbacks account under their
            # own backend (device via the driver ledger, host below).
            _profile.record_backend_flush(
                "warm", warm_served, warm_steps,
                time.perf_counter() - prof_t0,
                tenant=_single_tenant(live))
        if cold:
            if backend == "host":
                self._solve_host(cold, rep)
            else:
                self._solve_device(cold, timing)

    def _triage_stragglers(self, live: List[_Lane], class_name: str):
        """Per-lane deadline triage (ISSUE 13): lanes whose remaining
        wall-clock budget cannot survive the expected device dispatch
        (the dispatch EWMA, floored by the engine registry's per-class
        device estimate — the ledger-informed cost model) are
        resubmitted to the host pool, where they start immediately
        instead of pinning — or expiring inside — a lockstep device
        batch.  Returns (kept lanes, finisher|None); the finisher joins
        the resubmission and merges its report.  Racing-path only: with
        the portfolio off, deadline semantics are untouched."""
        from ..engine import registry as engine_registry

        with self._cv:
            est = self._dispatch_ewma_s
        est = max(est,
                  engine_registry.estimate_us("device", class_name) / 1e6)
        resub = [lane for lane in live
                 if lane.deadline is not None
                 and 0.0 < lane.deadline.remaining() < est]
        if not resub:
            return live, None
        keep = [lane for lane in live
                if not any(lane is r for r in resub)]
        reg = self._registry
        reg.counter(
            "deppy_race_straggler_resubmits_total",
            "Deadline-straggler lanes resubmitted to the host pool "
            "instead of riding a device batch.").inc(len(resub))
        telemetry.default_registry().event(
            "race", resubmitted=len(resub),
            size_class_name=class_name)
        box: dict = {}

        def side() -> None:
            from .. import hostpool

            srep, owns = telemetry.begin_report(backend="hostpool")
            try:
                results = hostpool.solve_host_problems(
                    [lane.problem for lane in resub],
                    max_steps=[lane.max_steps for lane in resub],
                    deadlines=[lane.deadline for lane in resub])
                for lane, r in zip(resub, results):
                    _count_lane_outcome(srep, r)
                    _apply_lane_result(lane, r, "sched.race",
                                       canonical=False)
            except BaseException as e:  # noqa: BLE001 — re-raised at join
                box["error"] = e
            finally:
                telemetry.detach_report(srep, owns)
                box["rep"] = srep

        t = threading.Thread(target=side, name="deppy-race-resubmit",
                             daemon=True)
        t.start()

        def finisher(rep) -> None:
            t.join()
            rep.merge(box["rep"])
            if "error" in box:
                import sys

                if sys.exc_info()[1] is not None:
                    # A primary exception is already propagating out of
                    # the dispatch (the finisher runs in its finally):
                    # re-raising here would MASK it — surface the side
                    # failure on the sink instead.
                    telemetry.default_registry().event(
                        "fault", fault="race_resubmit_failed",
                        error=type(box["error"]).__name__,
                        lanes=len(resub))
                    return
                raise box["error"]

        return keep, finisher

    # ------------------------------------------- deferred re-probe (ISSUE 14)

    def _kick_reprobe(self) -> None:
        """Start the background re-probe loop (once) after a
        breaker-open host drain.  The loop waits out the breaker
        cooldown, then runs the engine probe OFF
        the serving path — a success resets the breaker and replaces
        the ``auto`` verdict (``sat.solver.reprobe_engine``), so
        routing upgrades without risking a live dispatch on the
        half-open probe; a failure retries on the
        ``DEPPY_TPU_REPROBE`` interval while the breaker stays open."""
        if self._reprobe_s <= 0:
            return
        with self._cv:
            t = self._reprobe_thread
            if t is not None and t.is_alive():
                return
            t = threading.Thread(target=self._reprobe_loop,
                                 name="deppy-sched-reprobe", daemon=True)
            self._reprobe_thread = t
        t.start()

    def _reprobe_loop(self) -> None:
        from ..sat import solver as sat_solver

        c_reprobes = self._registry.counter(
            "deppy_sched_reprobes_total",
            "Deferred background engine re-probes after a breaker-open "
            "host drain, by result.", labelname="result")
        # First wake lands right after the cooldown elapses (probing a
        # still-open breaker earlier would burn the probe timeout
        # re-learning the failure that opened it — whatever the
        # configured interval); FAILED probes retry on the full
        # DEPPY_TPU_REPROBE interval — remaining_s() is 0 once the
        # cooldown lapses, and the probe must not hot-loop against a
        # dead accelerator.
        delay = max(faults.default_breaker().remaining_s(), 1.0)
        while True:
            if self._reprobe_stop.wait(delay):
                return
            state = faults.default_breaker().state()
            if state == "closed":
                # Recovered through the normal dispatch path while we
                # slept — nothing left to upgrade.  A HALF-OPEN breaker
                # is exactly what this loop exists for: probe it off
                # the serving path so no live request pays the
                # half-open dispatch gamble.
                return
            if state == "open":
                # Re-opened (or still cooling) while we slept: wait out
                # the (new) cooldown instead of probing a breaker that
                # already knows the answer.
                delay = max(faults.default_breaker().remaining_s(), 1.0)
                continue
            ok = sat_solver.reprobe_engine()  # a verdict; never raises
            c_reprobes.inc(label="upgraded" if ok else "failed")
            if ok:
                telemetry.default_registry().event(
                    "fault", fault="sched_reprobe_upgraded")
                return
            delay = max(self._reprobe_s, 1.0)

    def _solve_host(self, live: List[_Lane], rep) -> None:
        """Host-engine drain — the breaker's host-only mode and the
        explicit host backend.  Lanes run through the shared hostpool
        entry (ISSUE 5): concurrent across the host worker pool when one
        is available, so a wedged accelerator degrades throughput to
        the host's cores instead of one; inline (bit-identical)
        otherwise.  Each LANE's own deadline rides along per lane:
        completed lanes keep their answers, expired ones degrade
        individually without poisoning their pool batchmates."""
        from .. import hostpool

        reg = telemetry.default_registry()
        prof_t0 = _profile.dispatch_t0("host")
        with reg.span("sched.host_solve", problems=len(live)):
            results = hostpool.solve_host_problems(
                [lane.problem for lane in live],
                max_steps=[lane.max_steps for lane in live],
                deadlines=[lane.deadline for lane in live])
            if prof_t0 is not None:
                _profile.record_backend_flush(
                    "host", len(live),
                    int(sum(r.steps for r in results)),
                    time.perf_counter() - prof_t0,
                    tenant=_single_tenant(live))
            for lane, r in zip(live, results):
                # The ONE lane decode + accounting (shared with the
                # racer's winner application and the straggler
                # resubmission, so the paths cannot drift).
                _count_lane_outcome(rep, r)
                _apply_lane_result(lane, r, "sched.host_solve")
