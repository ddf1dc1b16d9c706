"""Delta-aware incremental resolution suite (ISSUE 10).

The acceptance surface:

  * **Byte-identity** — warm-started solves are byte-identical to cold
    solves (models, unsat cores) across randomized single-constraint
    add / remove / flip deltas; whenever the warm machinery cannot
    certify identity it falls back to a cold solve, so the differential
    holds over EVERY case, served or fallen back — including a chaos
    case where a poisoned cached model makes the warm prefix conflict
    and the fallback engages.
  * **Classification** — the clause-set index classifies deltas as
    identical / additive / retractive / mixed and computes a closed
    touched cone (no structural row spans the boundary).
  * **Scheduler integration** — warm lanes ride their own incremental
    size class; responses are byte-identical with the tier on and off
    (``DEPPY_TPU_INCREMENTAL=off`` restores pre-tier dispatch); exact
    repeats still hit the exact-fingerprint cache first.
  * **Cache satellites** — the canonical fingerprint is memoized on the
    problem, and ``deppy_cache_entries`` / ``deppy_cache_bytes`` track
    residency.
"""

from __future__ import annotations

import random
from collections import Counter

import numpy as np
import pytest

from deppy_tpu import faults, sat, telemetry
from deppy_tpu.incremental import (
    DELTA_ADDITIVE,
    DELTA_IDENTICAL,
    DELTA_MIXED,
    DELTA_RETRACTIVE,
    ClauseSetIndex,
    clauseset,
    problem_rows,
    touched_cone,
    vocab_key,
)
from deppy_tpu.models import random_instance
from deppy_tpu.sat.encode import encode
from deppy_tpu.sat.errors import Incomplete, NotSatisfiable
from deppy_tpu.sat.host import HostEngine, WarmStartConflict
from deppy_tpu.sched import Scheduler
from deppy_tpu.sched.cache import ResultCache, fingerprint
from _depth import depth

pytestmark = pytest.mark.incremental


@pytest.fixture(autouse=True)
def fresh_fault_state():
    """Isolate the process-global breaker/plan/registry per test (the
    sched suite's contract — the scheduler tests here share it)."""
    prev_breaker = faults.set_default_breaker(faults.CircuitBreaker())
    prev_plan = faults.configure_plan(None)
    yield
    faults.set_default_breaker(prev_breaker)
    faults.configure_plan(prev_plan)


# ------------------------------------------------------------ workloads


def bundle_catalog(rng=None, n_bundles=6, bsize=6, tweak=None):
    """Independent dependency bundles — the churn shape: a catalog of
    packages where one bundle's constraints change between requests.
    ``tweak=(kind, bundle)`` mutates exactly one bundle; dependencies
    carry two candidates so propagation alone cannot decide (the warm
    tier's target regime is search-needing problems)."""
    vs = []
    for b in range(n_bundles):
        for j in range(bsize):
            cons = []
            if j == 0:
                cons.append(sat.mandatory())
            if j < bsize - 2:
                if rng is not None:
                    cands = rng.sample(range(j + 1, bsize), 2)
                else:
                    cands = [j + 1, j + 2]
                cons.append(sat.dependency(
                    *[f"b{b}v{k}" for k in cands]))
            if tweak is not None and tweak[1] == b:
                kind = tweak[0]
                if kind == "add-conflict" and j == 1:
                    cons.append(sat.conflict(f"b{b}v{bsize - 1}"))
                elif kind == "add-dep" and j == 2:
                    cons.append(sat.dependency(f"b{b}v{bsize - 1}",
                                               f"b{b}v{bsize - 2}"))
                elif kind == "add-atmost" and j == 0:
                    cons.append(sat.at_most(1, f"b{b}v{bsize - 2}",
                                            f"b{b}v{bsize - 1}"))
                elif kind == "add-mandatory" and j == 3:
                    cons.append(sat.mandatory())
                elif kind == "drop-dep" and j == 1:
                    cons = [c for c in cons
                            if not isinstance(c, sat.Dependency)]
                elif kind == "flip-dep" and j == 1:
                    cons = [c for c in cons
                            if not isinstance(c, sat.Dependency)]
                    cons.append(sat.dependency(f"b{b}v{bsize - 1}",
                                               f"b{b}v{bsize - 2}"))
            vs.append(sat.variable(f"b{b}v{j}", *cons))
    return vs


def solve_cold(problem, max_steps=None):
    """(outcome, payload) of one cold host solve — the identity oracle."""
    eng = HostEngine(problem, max_steps=max_steps)
    try:
        _, idx = eng.solve()
        return ("sat", tuple(idx)), eng
    except NotSatisfiable as e:
        ids = {id(c) for c in e.constraints}
        core = tuple(j for j, c in enumerate(problem.applied)
                     if id(c) in ids)
        return ("unsat", core), eng
    except Incomplete:
        return ("incomplete", ()), eng


def indexed(problem, eng, idx, **kw):
    """A ClauseSetIndex seeded with one solved problem."""
    index = ClauseSetIndex(registry=telemetry.Registry(), **kw)
    model = np.zeros(problem.n_vars, dtype=bool)
    model[list(idx)] = True
    index.store(fingerprint(problem), problem, model, eng.steps,
                eng.backtracks)
    return index


# -------------------------------------------------- delta classification


class TestClauseSetIndex:
    def _plan(self, base_tweak, new_tweak, **kw):
        base = encode(bundle_catalog(tweak=base_tweak))
        (outcome, idx), eng = solve_cold(base)
        assert outcome == "sat"
        index = indexed(base, eng, idx,
                        **{"max_delta_ratio": 1.0, **kw})
        new = encode(bundle_catalog(tweak=new_tweak))
        return index.plan(new, fingerprint(new), 1 << 24), new

    def test_additive_delta(self):
        plan, new = self._plan(None, ("add-conflict", 2))
        assert plan is not None and plan.klass == DELTA_ADDITIVE
        # The cone is one bundle of six vars out of 36.
        assert 0 < plan.cone.sum() <= 6
        assert plan.cone_fraction <= 6 / 36

    def test_retractive_delta(self):
        plan, _ = self._plan(("add-conflict", 2), None)
        assert plan is not None and plan.klass == DELTA_RETRACTIVE

    def test_mixed_delta(self):
        # flip-dep drops one dependency row and adds a different one.
        plan, _ = self._plan(None, ("flip-dep", 2))
        assert plan is not None and plan.klass == DELTA_MIXED

    def test_identical_content_different_strings(self):
        # Same clause multiset, different rendered fingerprint is the
        # identical class with an empty cone (constraint strings are
        # vocabulary, not structure — the exact cache misses, the delta
        # tier does not).
        base = encode(bundle_catalog())
        (_, idx), eng = solve_cold(base)
        index = indexed(base, eng, idx)
        new = encode(bundle_catalog())
        assert fingerprint(new) == fingerprint(base)  # true repeat
        plan = index.plan(new, fingerprint(new), 1 << 24)
        assert plan is not None and plan.klass == DELTA_IDENTICAL
        assert plan.cone.sum() == 0

    def test_cone_is_closed(self):
        plan, new = self._plan(None, ("add-dep", 1))
        assert plan is not None
        cone = plan.cone
        n = new.n_vars
        for row in np.where(np.abs(new.clauses) <= n, new.clauses, 0):
            vars_ = [abs(int(l)) - 1 for l in row if l != 0]
            if vars_:
                hit = [cone[v] for v in vars_]
                assert all(hit) or not any(hit), \
                    "clause spans the cone boundary"

    def test_max_delta_cutoff_blocks_plan(self):
        plan, _ = self._plan(None, ("add-conflict", 2),
                             max_delta_ratio=0.01)
        assert plan is None

    def test_vocab_mismatch_no_plan(self):
        base = encode(bundle_catalog())
        (_, idx), eng = solve_cold(base)
        index = indexed(base, eng, idx)
        new = encode(bundle_catalog(n_bundles=7))
        assert index.plan(new, fingerprint(new), 1 << 24) is None

    def test_tight_budget_no_plan(self):
        plan, new = self._plan(None, ("add-conflict", 2))
        assert plan is not None
        base = encode(bundle_catalog())
        (_, idx), eng = solve_cold(base)
        index = indexed(base, eng, idx)
        assert index.plan(new, fingerprint(new), 64) is None

    def test_backtracking_solves_never_indexed(self):
        base = encode(bundle_catalog())
        index = ClauseSetIndex(registry=telemetry.Registry())
        index.store(fingerprint(base), base,
                    np.zeros(base.n_vars, bool), 10, backtracks=3)
        assert len(index) == 0

    def test_lru_capacity(self):
        index = ClauseSetIndex(capacity=2,
                               registry=telemetry.Registry())
        for b in range(4):
            p = encode(bundle_catalog(tweak=("add-conflict", b)))
            index.store(fingerprint(p), p, np.zeros(p.n_vars, bool),
                        5, 0)
        assert len(index) == 2


# ------------------------------------- classifier equivalence (oracle)


def legacy_rows(problem):
    """The row multiset as the per-row numpy classifier built it."""
    n = problem.n_vars
    rows = Counter()
    c = problem.clauses
    per_subject = {}
    if c.size:
        kept = np.where(np.abs(c) <= n, c, 0)
        for row in kept:
            lits = tuple(row[row != 0].tolist())
            subj = abs(lits[0]) - 1 if lits else -1
            ordinal = per_subject.get(subj, 0)
            per_subject[subj] = ordinal + 1
            rows[("c", ordinal) + lits] += 1
    for ids_row, bound in zip(problem.card_ids, problem.card_n):
        members = ids_row[ids_row >= 0]
        rows[("k", int(bound)) + tuple(sorted(members.tolist()))] += 1
    return rows


def legacy_plan(index, problem, budget):
    """``(delta label, plan fields or None)`` of the Counter-based
    classifier: ``Counter &`` nearest scan over the bucket's most recent
    SCAN_CAP entries, then the cone closure before the fraction check."""
    bucket = index._by_vocab.get(vocab_key(problem))
    if not bucket:
        return "none", None
    rows = legacy_rows(problem)
    best = best_delta = None
    n_rows = sum(rows.values())
    for k in list(reversed(bucket))[:clauseset.SCAN_CAP]:
        entry = index._entries.get(k)
        if entry is None:
            continue
        shared = sum((rows & entry.rows).values())
        delta = (n_rows - shared) + (sum(entry.rows.values()) - shared)
        if best_delta is None or delta < best_delta:
            best, best_delta = entry, delta
        if best_delta <= clauseset.ACCEPT_DELTA:
            break
    added = rows - best.rows
    removed = best.rows - rows
    if not added and not removed:
        klass = DELTA_IDENTICAL
    elif not removed:
        klass = DELTA_ADDITIVE
    elif not added:
        klass = DELTA_RETRACTIVE
    else:
        klass = DELTA_MIXED
    seed = []
    for k in list(added) + list(removed):
        seed.extend(clauseset._row_vars(k))
    cone = touched_cone(problem, seed, removed.keys())
    fraction = float(cone.sum()) / max(problem.n_vars, 1)
    if fraction > index.max_delta_ratio:
        return klass, None
    if int(budget) < max(clauseset.MIN_WARM_BUDGET,
                         clauseset.WARM_BUDGET_FACTOR * (best.steps + 1)):
        return klass, None
    return klass, (klass, cone.tolist(), fraction, best.key, best.steps)


def assert_plans_match_legacy(index, problems, budget=1 << 24):
    """Every problem plans exactly as the oracle: the same None or the
    same class, cone, fraction, entry and steps, and the same delta
    counter increment."""
    for p in problems:
        assert problem_rows(p) == legacy_rows(p)
        label, want = legacy_plan(index, p, budget)
        before = index._c_delta.value
        plan = index.plan(p, fingerprint(p), budget)
        got = None if plan is None else (
            plan.klass, plan.cone.tolist(), plan.cone_fraction,
            plan.entry_key, plan.entry_steps)
        assert got == want
        after = index._c_delta.value
        assert after.get(label, 0) == before.get(label, 0) + 1
        assert sum(after.values()) == sum(before.values()) + 1


def bench_problems(seeds):
    """``deppy-sat-bench``-style states: 256 variables named ``"0"`` to
    ``"255"``, so every state shares one vocabulary."""
    return [encode(random_instance(256, seed=s)) for s in seeds]


def full_index(problems, capacity, **kw):
    index = ClauseSetIndex(capacity=capacity, registry=telemetry.Registry(),
                           **kw)
    rng = random.Random(len(problems))
    for p in problems:
        index.store(fingerprint(p), p, np.zeros(p.n_vars, bool),
                    rng.randrange(1, 5000), 0)
    return index


def dup_atmost_catalog(copies):
    """A catalog whose one cardinality row appears ``copies`` times:
    a row multiset count above 1."""
    vs = bundle_catalog(n_bundles=2)
    x = vs[0]
    vs[0] = sat.variable(x.identifier, *(
        list(x.constraints) + [sat.at_most(1, "b0v4", "b0v5")] * copies))
    return vs


CHURN_CASES = {
    "identical": (None, [None]),
    "additive": (None, [("add-conflict", 2), ("add-dep", 4)]),
    "retractive": (("add-conflict", 2), [None]),
    "mixed": (None, [("flip-dep", 2), ("drop-dep", 5)]),
    "cardinality": (None, [("add-atmost", 1), ("add-mandatory", 3)]),
}


class TestClassifierEquivalence:
    @pytest.mark.parametrize("seed", range(4))
    def test_set_form_is_the_multiset(self, seed):
        rng = random.Random(seed)

        def draw():
            return Counter({("k", 1, r): rng.randrange(1, 4)
                            for r in rng.sample(range(12), 8)})

        a, b = draw(), draw()
        sa = clauseset._multiset_as_set(a)
        sb = clauseset._multiset_as_set(b)
        assert sa[0] == frozenset(a) and sa[2] == sum(a.values())
        assert clauseset._shared(sa, sb) == sum((a & b).values())
        assert clauseset._excess(sa, sb) == set(a - b)
        assert clauseset._excess(sb, sa) == set(b - a)

    @pytest.mark.parametrize("ratio", [0.25, 1.0])
    def test_random_states_against_full_index(self, ratio):
        stored = bench_problems(range(1000, 1048))
        index = full_index(stored, capacity=40, max_delta_ratio=ratio)
        assert len(index) == 40
        fresh = bench_problems(range(2000, 2012))
        # A stored state again is the identical class at delta 0.
        assert_plans_match_legacy(index, fresh + stored[-3:])

    @pytest.mark.parametrize("case", sorted(CHURN_CASES))
    @pytest.mark.parametrize("ratio", [0.1, 0.25, 1.0])
    def test_churn_catalogs(self, case, ratio):
        base_tweak, new_tweaks = CHURN_CASES[case]
        base = encode(bundle_catalog(tweak=base_tweak))
        (_, idx), eng = solve_cold(base)
        index = indexed(base, eng, idx, max_delta_ratio=ratio)
        news = [encode(bundle_catalog(tweak=t)) for t in new_tweaks]
        assert_plans_match_legacy(index, news)
        assert_plans_match_legacy(index, news, budget=64)

    def test_tie_between_equally_near_entries(self):
        index = ClauseSetIndex(registry=telemetry.Registry(),
                               max_delta_ratio=1.0)
        for b in (1, 3):
            p = encode(bundle_catalog(tweak=("flip-dep", b)))
            index.store(fingerprint(p), p, np.zeros(p.n_vars, bool),
                        10 + b, 0)
        new = encode(bundle_catalog())
        assert_plans_match_legacy(index, [new])
        plan = index.plan(new, fingerprint(new), 1 << 24)
        # Both sit two rows away; the more recent store wins the tie.
        assert plan.entry_steps == 13

    @pytest.mark.parametrize("copies", [1, 2, 3])
    def test_duplicate_rows(self, copies):
        base = encode(dup_atmost_catalog(2))
        assert max(problem_rows(base).values()) == 2
        (_, idx), eng = solve_cold(base)
        index = indexed(base, eng, idx, max_delta_ratio=1.0)
        assert_plans_match_legacy(index, [encode(dup_atmost_catalog(copies))])


class TestPlanRejects:
    def test_random_traffic_rejects_before_the_cone(self, monkeypatch):
        index = full_index(bench_problems(range(1000, 1040)), capacity=32)
        fresh = bench_problems(range(3000, 3004))

        def boom(*a, **k):
            raise AssertionError("touched_cone ran")

        monkeypatch.setattr(clauseset, "touched_cone", boom)
        for p in fresh:
            assert index.plan(p, fingerprint(p), 1 << 24) is None
        assert index._c_rejects.value == {"seed_bound": len(fresh)}

    def test_near_duplicate_plans_without_reject(self):
        base = encode(bundle_catalog())
        (_, idx), eng = solve_cold(base)
        index = indexed(base, eng, idx)
        new = encode(bundle_catalog(tweak=("add-conflict", 2)))
        assert index.plan(new, fingerprint(new), 1 << 24) is not None
        assert sum(index._c_rejects.value.values()) == 0

    @pytest.mark.parametrize("ratio,budget,reason", [
        (0.1, 1 << 24, "cone"),
        (0.25, 64, "budget"),
    ])
    def test_reject_reasons(self, ratio, budget, reason):
        base = encode(bundle_catalog())
        (_, idx), eng = solve_cold(base)
        index = indexed(base, eng, idx, max_delta_ratio=ratio)
        # add-dep's seed is three of 36 variables; its cone is the
        # bundle's six.
        new = encode(bundle_catalog(tweak=("add-dep", 1)))
        assert index.plan(new, fingerprint(new), budget) is None
        assert index._c_rejects.value == {reason: 1}

    def test_preview_counts_no_reject(self):
        index = full_index(bench_problems(range(1000, 1040)), capacity=32)
        (p,) = bench_problems([3000])
        assert index.plan(p, fingerprint(p), 1 << 24, account=False) \
            is None
        assert index._c_rejects.value == {}


# ------------------------------------------------------- warm identity


class TestWarmIdentity:
    KINDS = ("add-conflict", "add-dep", "add-atmost", "add-mandatory",
             "drop-dep", "flip-dep")

    def test_fuzz_differential_warm_vs_cold(self):
        """The pin: across randomized single-constraint add/remove/flip
        deltas, a warm-started solve either serves a result byte-
        identical to the cold solve or falls back to one — so the
        end-to-end answer always equals cold, and a healthy fraction
        must actually be served warm for the tier to mean anything."""
        rng = random.Random(0xD417A)
        n_cases = depth(120, 30)
        served = 0
        for _ in range(n_cases):
            seed = rng.randint(0, 10 ** 9)
            base = encode(bundle_catalog(random.Random(seed)))
            (outcome, idx), eng = solve_cold(base)
            if outcome != "sat" or eng.backtracks != 0:
                continue
            index = indexed(base, eng, idx, max_delta_ratio=1.0)
            kind = rng.choice(self.KINDS)
            new = encode(bundle_catalog(random.Random(seed),
                                        tweak=(kind, rng.randrange(6))))
            plan = index.plan(new, fingerprint(new), 1 << 24)
            cold, _ = solve_cold(new)
            if plan is None:
                continue
            weng = HostEngine(new)
            try:
                _, widx = weng.solve_warm(plan.warm_assign, plan.cone)
                warm = ("sat", tuple(widx))
                served += 1
            except (WarmStartConflict, Incomplete):
                # Fallback: the cold oracle IS the answer by definition.
                continue
            assert warm == cold, (
                f"warm/cold divergence (kind={kind}): {warm} != {cold}")
        assert served >= n_cases // 8, \
            f"warm tier served only {served}/{n_cases} — tier is inert"

    def test_chaos_poisoned_model_falls_back(self):
        """The chaos case: a poisoned cached model conflicts with the
        warm prefix; the warm attempt must fall back, and the scheduler
        path must still answer byte-identically (counted as a
        warm fallback, not served)."""
        base = encode(bundle_catalog())
        (_, idx), eng = solve_cold(base)
        index = indexed(base, eng, idx)
        new = encode(bundle_catalog(tweak=("add-conflict", 2)))
        plan = index.plan(new, fingerprint(new), 1 << 24)
        assert plan is not None
        # Flip an off-cone mandatory anchor false: the prefix conflicts.
        anchor = next(int(a) for a in new.anchors if not plan.cone[a])
        plan.warm_assign = plan.warm_assign.copy()
        plan.warm_assign[anchor] = -1
        weng = HostEngine(new)
        with pytest.raises(WarmStartConflict):
            weng.solve_warm(plan.warm_assign, plan.cone)
        from deppy_tpu import incremental as inc

        assert inc.attempt(plan) is None  # the lane-level fallback
        cold, _ = solve_cold(new)
        assert cold[0] == "sat"

    def _warm_or_cold(self, index, new):
        """Serve ``new`` exactly like the scheduler would (plan → warm →
        cold fallback) and return the installed tuple."""
        plan = index.plan(new, fingerprint(new), 1 << 24)
        if plan is not None:
            eng = HostEngine(new)
            try:
                _, widx = eng.solve_warm(plan.warm_assign, plan.cone)
                return tuple(widx)
            except (WarmStartConflict, Incomplete):
                pass
        (_, cidx), _ = solve_cold(new)
        return tuple(cidx)

    def test_reordered_dependency_candidates_stay_identical(self):
        """Review regression: dependency candidate order is PREFERENCE
        — dep('a','b') and dep('b','a') share a literal set but cold
        solves install different candidates.  The row keys must keep
        the emitted order so the reordered twin never serves the cached
        model as an 'identical' empty-cone warm hit."""
        def cat(first, second):
            return [sat.variable("x", sat.mandatory(),
                                 sat.dependency(first, second)),
                    sat.variable("a"), sat.variable("b")]

        base = encode(cat("a", "b"))
        (res, idx), eng = solve_cold(base)
        index = indexed(base, eng, idx, max_delta_ratio=1.0)
        new = encode(cat("b", "a"))
        got = self._warm_or_cold(index, new)
        (_, want), _ = solve_cold(new)
        assert got == tuple(want)

    def test_swapped_same_subject_constraints_stay_identical(self):
        """Same trap one level up: a variable's constraint ORDER decides
        choice spawn order (dep(a,b) before dep(b,d) assumes {a,b};
        swapped it assumes {b} — already-satisfied).  Per-subject
        ordinals in the row keys keep the swap a real delta."""
        def cat(swap):
            deps = [sat.dependency("a", "b"), sat.dependency("b", "d")]
            if swap:
                deps.reverse()
            return [sat.variable("v", sat.mandatory(), *deps),
                    sat.variable("a"), sat.variable("b"),
                    sat.variable("d")]

        base = encode(cat(False))
        (_, idx), eng = solve_cold(base)
        index = indexed(base, eng, idx, max_delta_ratio=1.0)
        new = encode(cat(True))
        got = self._warm_or_cold(index, new)
        (_, want), _ = solve_cold(new)
        assert got == tuple(want)

    def test_unsat_delta_falls_back_to_cold_core(self):
        """A delta that makes the problem UNSAT can never serve warm;
        the cold fallback's unsat core is the oracle's."""
        base = encode(bundle_catalog(n_bundles=2))
        (_, idx), eng = solve_cold(base)
        index = indexed(base, eng, idx, max_delta_ratio=1.0)

        def poisoned():
            vs = bundle_catalog(n_bundles=2)
            # b0v0 mandatory + prohibited: unsatisfiable bundle.
            broken = vs[0]
            vs[0] = sat.variable(broken.identifier,
                                 *(list(broken.constraints)
                                   + [sat.prohibited()]))
            return vs

        new = encode(poisoned())
        plan = index.plan(new, fingerprint(new), 1 << 24)
        cold, _ = solve_cold(new)
        assert cold[0] == "unsat" and cold[1]
        if plan is not None:
            weng = HostEngine(new)
            with pytest.raises(WarmStartConflict):
                weng.solve_warm(plan.warm_assign, plan.cone)


# ------------------------------------------------------ solver scopes


class TestSolverScopes:
    def test_assume_test_untest(self):
        s = sat.Solver([
            sat.variable("a", sat.mandatory(), sat.dependency("b", "c")),
            sat.variable("b"),
            sat.variable("c", sat.conflict("b")),
        ])
        assert s.test() == 0  # undetermined: b-or-c choice open
        s.assume("b")
        assert s.test() == 1  # propagation total: b true forces c false
        assert s.untest() == 1
        s.untest()
        s.assume("b")
        s.assume("c")
        assert s.test() == -1  # b+c conflict
        s.untest()

    def test_untest_actually_drops_the_tested_assumptions(self):
        """Review regression: the scope marker must be the length at
        the PREVIOUS test boundary — recording it after this scope's
        assumptions made untest a no-op, permanently accumulating every
        tried candidate (gini's Untest drops them)."""
        s = sat.Solver([
            sat.variable("a", sat.mandatory(), sat.dependency("b", "c")),
            sat.variable("b"),
            sat.variable("c", sat.conflict("b")),
        ])
        s.assume("b")
        assert s.test() == 1
        assert s.untest() == 0
        # b must be gone: the choice is open again, not decided.
        assert s.test() == 0
        s.untest()
        # The canonical candidate loop: tried candidates never leak.
        s.assume("b")
        assert s.test() == 1
        s.untest()
        s.assume("c")
        assert s.test() == 1  # c alone propagates (b forced out)
        s.untest()

    def test_assume_unknown_identifier_raises(self):
        from deppy_tpu.sat.errors import InternalSolverError

        s = sat.Solver([sat.variable("a")])
        with pytest.raises(InternalSolverError):
            s.assume("nope")

    def test_untest_underflow_raises(self):
        from deppy_tpu.sat.errors import InternalSolverError

        s = sat.Solver([sat.variable("a")])
        with pytest.raises(InternalSolverError):
            s.untest()


# ------------------------------------------------------- device screen


class TestWarmScreen:
    def test_screen_flags_conflicting_prefix(self):
        from deppy_tpu.engine import driver

        p = encode(bundle_catalog())
        (_, idx), _ = solve_cold(p)
        good = np.zeros(p.n_vars, bool)
        good[list(idx)] = True
        bad = np.zeros(p.n_vars, bool)  # anchors false: dead clauses
        cone = np.zeros(p.n_vars, bool)
        ok = driver.warm_screen([p, p], [good, bad], [cone, cone])
        assert list(ok) == [True, False]

    def test_screen_open_cone_is_not_a_conflict(self):
        from deppy_tpu.engine import driver

        p = encode(bundle_catalog())
        bad = np.zeros(p.n_vars, bool)
        cone = np.ones(p.n_vars, bool)  # everything open: nothing dead
        ok = driver.warm_screen([p], [bad], [cone])
        assert list(ok) == [True]


# ------------------------------------------------- scheduler integration


def _mk_sched(**kw):
    s = Scheduler(backend="host", registry=telemetry.Registry(), **kw)
    s.start()
    return s


class TestSchedulerIncremental:
    def test_warm_hit_and_byte_identity_vs_off(self):
        on = _mk_sched()
        off = _mk_sched(incremental="off")
        try:
            docs = [bundle_catalog(), bundle_catalog(tweak=("add-dep", 3)),
                    bundle_catalog(tweak=("add-conflict", 1))]
            got_on = [on.submit([d])[0] for d in docs]
            got_off = [off.submit([d])[0] for d in docs]
            assert got_on == got_off
            assert off.incremental is None
            assert on.incremental is not None
            assert on.incremental.hit_ratio() > 0.0
        finally:
            on.stop()
            off.stop()

    def test_exact_repeat_still_hits_exact_cache(self):
        s = _mk_sched()
        try:
            doc = bundle_catalog()
            first = s.submit([doc])[0]
            hits_before = s.cache._hits.value
            again = s.submit([doc])[0]
            assert again == first
            assert s.cache._hits.value == hits_before + 1
        finally:
            s.stop()

    def test_warm_lanes_coalesce_in_incremental_class(self):
        from deppy_tpu.sched.scheduler import INCREMENTAL_CLASS

        s = _mk_sched()
        try:
            s.submit([bundle_catalog()])
            seen = []
            orig = s._solve_lanes

            def spy(lanes, timing=None):
                seen.append([lane.warm is not None for lane in lanes])
                return orig(lanes, timing)

            s._solve_lanes = spy
            s.submit([bundle_catalog(tweak=("add-dep", 2)),
                      bundle_catalog(tweak=("add-dep", 4))])
            # One all-warm flush (its own size class), no mixed group.
            assert any(all(flags) and flags for flags in seen)
            assert all(all(flags) or not any(flags) for flags in seen)
            assert INCREMENTAL_CLASS == -1
        finally:
            s.stop()

    def test_poisoned_entry_falls_back_through_scheduler(self):
        s = _mk_sched()
        try:
            doc = bundle_catalog()
            s.submit([doc])
            # Poison the indexed model in place (chaos): warm prefix
            # conflicts, the lane cold-solves, the answer stays right.
            with s.incremental._lock:
                for e in s.incremental._entries.values():
                    e.model[:] = False
            fb_before = s.incremental._c_fallbacks.value
            got = s.submit([bundle_catalog(tweak=("add-dep", 3))])[0]
            cold = _mk_sched(incremental="off")
            try:
                want = cold.submit(
                    [bundle_catalog(tweak=("add-dep", 3))])[0]
            finally:
                cold.stop()
            assert got == want
            assert s.incremental._c_fallbacks.value == fb_before + 1
        finally:
            s.stop()

    def test_warm_served_lanes_index_cold_equivalent_steps(self):
        """Review regression: indexing a warm-served lane under its own
        (tiny) step count would erode the budget gate — a later tight-
        budget request could warm-serve SAT where a cold solve returns
        Incomplete.  The index entry must carry a cold-equivalent
        cost (seed entry steps + cone work)."""
        s = _mk_sched()
        try:
            s.submit([bundle_catalog()])
            s.submit([bundle_catalog(tweak=("add-dep", 3))])  # warm
            with s.incremental._lock:
                entries = list(s.incremental._entries.values())
            base_rows = problem_rows(encode(bundle_catalog()))
            (base_entry,) = [e for e in entries if e.rows == base_rows]
            for e in entries:
                assert e.steps >= base_entry.steps, (
                    "warm-served entry indexed below its seed's cold "
                    "cost — budget gate eroded")
        finally:
            s.stop()

    def test_exact_hits_refresh_index_recency(self):
        """Review regression: exact-cache hits bypass the solve/store
        path; without a recency touch a cycling catalog drifts the
        bounded nearest scan off the revisited states."""
        s = _mk_sched()
        try:
            a, b = bundle_catalog(), bundle_catalog(tweak=("add-dep", 1))
            s.submit([a])
            s.submit([b])
            # Re-ask A: exact hit — A must move to the bucket's end.
            s.submit([a])
            key_a = fingerprint(encode(a))
            with s.incremental._lock:
                (bucket,) = s.incremental._by_vocab.values()
                assert next(reversed(bucket)) == key_a
        finally:
            s.stop()

    def test_env_off_switch(self, monkeypatch):
        monkeypatch.setenv("DEPPY_TPU_INCREMENTAL", "off")
        s = Scheduler(backend="host", registry=telemetry.Registry())
        assert s.incremental is None
        assert s.cache.incremental is None


# -------------------------------------------------- cache satellites


class TestCacheSatellites:
    def test_fingerprint_memoized_on_problem(self, monkeypatch):
        p = encode(bundle_catalog())
        first = fingerprint(p)
        # The second call must not re-sort the clause tensor.
        monkeypatch.setattr(np, "lexsort", lambda *a, **k: (_ for _ in ()
                            ).throw(AssertionError("re-sorted")))
        assert fingerprint(p) == first

    def test_entries_and_bytes_gauges(self):
        reg = telemetry.Registry()
        cache = ResultCache(capacity=2, registry=reg)
        solution = {"a": True, "b": False}
        cache.store("k1", 100, solution)
        cache.store("k2", 100, solution)
        assert cache._g_entries.value == 2
        assert cache._g_bytes.value > 0
        b2 = cache._g_bytes.value
        cache.store("k3", 100, solution)  # evicts k1
        assert cache._g_entries.value == 2
        assert cache._g_bytes.value == b2
        # Budget-escalation invalidation shrinks both.
        from deppy_tpu.sched.cache import MISS

        cache.store("k4", 50, Incomplete())
        assert cache._g_entries.value == 2  # k2 evicted by k4
        assert cache.lookup("k4", 200) is MISS  # budget escalation
        assert cache._g_entries.value == 1
