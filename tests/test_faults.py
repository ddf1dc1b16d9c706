"""Fault-domain layer (ISSUE 2): policy, breaker, injection harness, and
the chaos suite exercising every recovery path on CPU.

Everything here runs against the *real* dispatch pipeline — faults are
scripted through the deterministic injection harness
(`deppy_tpu.faults.inject`), never by monkeypatching the driver — so a
refactor that disconnects a recovery path fails these tests instead of
silently shipping.
"""

from __future__ import annotations

import json
import threading
import time
from http.client import HTTPConnection

import numpy as np
import pytest

from deppy_tpu import faults, telemetry

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def fresh_fault_state():
    """Isolate the process-global breaker, fault plan, and telemetry
    registry per test."""
    prev_breaker = faults.set_default_breaker(faults.CircuitBreaker())
    prev_plan = faults.configure_plan(None)
    prev_reg = telemetry.set_default_registry(telemetry.Registry())
    yield
    telemetry.set_default_registry(prev_reg)
    faults.configure_plan(prev_plan)
    faults.set_default_breaker(prev_breaker)


# ---------------------------------------------------------------- policy


class TestRetryPolicy:
    def test_backoff_grows_and_clamps(self):
        p = faults.RetryPolicy(base_backoff_s=0.1, max_backoff_s=0.5,
                               multiplier=2.0, jitter=0.0)
        assert p.backoff_s(1) == pytest.approx(0.1)
        assert p.backoff_s(2) == pytest.approx(0.2)
        assert p.backoff_s(3) == pytest.approx(0.4)
        assert p.backoff_s(4) == pytest.approx(0.5)  # clamped
        assert p.backoff_s(10) == pytest.approx(0.5)

    def test_jitter_bounds(self):
        p = faults.RetryPolicy(base_backoff_s=0.1, jitter=0.5)
        assert p.backoff_s(1, rng=lambda: 0.0) == pytest.approx(0.1)
        assert p.backoff_s(1, rng=lambda: 1.0) == pytest.approx(0.15)

    def test_from_env(self, monkeypatch):
        monkeypatch.setenv("DEPPY_TPU_FAULT_RETRIES", "5")
        monkeypatch.setenv("DEPPY_TPU_FAULT_BACKOFF_S", "0.25")
        p = faults.RetryPolicy.from_env()
        assert p.max_attempts == 5
        assert p.base_backoff_s == 0.25

    def test_from_env_malformed_degrades_to_defaults(self, monkeypatch):
        monkeypatch.setenv("DEPPY_TPU_FAULT_RETRIES", "lots")
        p = faults.RetryPolicy.from_env()
        assert p.max_attempts == faults.RetryPolicy.max_attempts


class TestDeadline:
    def test_expiry(self):
        t = [0.0]
        dl = faults.Deadline(1.0, clock=lambda: t[0])
        assert not dl.expired()
        assert dl.remaining() == pytest.approx(1.0)
        t[0] = 1.5
        assert dl.expired()
        assert dl.remaining() == pytest.approx(-0.5)

    def test_scope_thread_local(self):
        assert faults.current_deadline() is None
        with faults.deadline_scope(10.0) as dl:
            assert faults.current_deadline() is dl
            seen = []
            th = threading.Thread(
                target=lambda: seen.append(faults.current_deadline()))
            th.start()
            th.join()
            assert seen == [None]  # other threads unaffected
        assert faults.current_deadline() is None

    def test_nested_scope_keeps_tighter_deadline(self):
        with faults.deadline_scope(0.0) as outer:
            with faults.deadline_scope(100.0) as inner:
                # An inner, looser deadline must not extend the outer one.
                assert inner is outer
                assert faults.current_deadline().expired()

    def test_none_scope_is_noop(self):
        with faults.deadline_scope(None) as dl:
            assert dl is None

    def test_ambient_deadline_from_env(self, monkeypatch):
        monkeypatch.setenv("DEPPY_TPU_BATCH_DEADLINE_S", "30")
        with faults.ambient_deadline() as dl:
            assert dl is not None and dl.seconds == 30.0
        monkeypatch.setenv("DEPPY_TPU_BATCH_DEADLINE_S", "not-a-number")
        with faults.ambient_deadline() as dl:
            assert dl is None

    def test_ambient_defers_to_active_scope(self, monkeypatch):
        monkeypatch.setenv("DEPPY_TPU_BATCH_DEADLINE_S", "30")
        with faults.deadline_scope(5.0) as outer:
            with faults.ambient_deadline() as dl:
                assert dl is outer


# ---------------------------------------------------------------- breaker


class TestCircuitBreaker:
    def test_trips_after_threshold_consecutive_failures(self):
        br = faults.CircuitBreaker(failure_threshold=3, reset_after_s=60)
        assert br.record_failure() is False
        assert br.record_failure() is False
        assert br.state() == "closed" and br.allow()
        assert br.record_failure() is True
        assert br.state() == "open"
        assert not br.allow()
        assert br.blocks_device()

    def test_success_resets_streak(self):
        br = faults.CircuitBreaker(failure_threshold=2, reset_after_s=60)
        br.record_failure()
        br.record_success()
        assert br.record_failure() is False  # streak restarted
        assert br.state() == "closed"

    def test_half_open_probe_closes_on_success(self):
        t = [0.0]
        br = faults.CircuitBreaker(failure_threshold=1, reset_after_s=10,
                                   clock=lambda: t[0])
        br.record_failure()
        assert br.state() == "open" and not br.allow()
        t[0] = 11.0
        assert br.state() == "half_open"
        assert not br.blocks_device()
        assert br.allow()          # the single probe slot
        assert not br.allow()      # everyone else denied while it flies
        br.record_success()
        assert br.state() == "closed" and br.allow()

    def test_abandoned_probe_slot_is_reclaimable(self):
        """A half-open probe that exits without a device verdict
        (semantic outcome passed through) must release the slot — a
        leaked slot would deny device dispatch forever."""
        t = [0.0]
        br = faults.CircuitBreaker(failure_threshold=1, reset_after_s=10,
                                   clock=lambda: t[0])
        br.record_failure()
        t[0] = 11.0
        assert br.allow()
        br.abandon_probe()          # probe exited, no verdict
        assert br.allow()           # next dispatch may probe again
        br.record_success()
        assert br.state() == "closed"

    def test_half_open_probe_failure_reopens(self):
        t = [0.0]
        br = faults.CircuitBreaker(failure_threshold=1, reset_after_s=10,
                                   clock=lambda: t[0])
        br.record_failure()
        t[0] = 11.0
        assert br.allow()
        assert br.record_failure() is True
        assert br.state() == "open"
        assert br.remaining_s() == pytest.approx(10.0)
        t[0] = 15.0
        assert br.remaining_s() == pytest.approx(6.0)

    def test_transitions_export_telemetry(self):
        br = faults.CircuitBreaker(failure_threshold=1, reset_after_s=60)
        br.record_failure()
        snap = telemetry.default_registry().snapshot()
        assert snap["deppy_breaker_state"] == faults.BREAKER_OPEN
        assert snap["deppy_breaker_transitions_total"] == {"open": 1}
        br.reset()
        snap = telemetry.default_registry().snapshot()
        assert snap["deppy_breaker_state"] == faults.BREAKER_CLOSED

    def test_default_breaker_env_config(self, monkeypatch):
        monkeypatch.setenv("DEPPY_TPU_BREAKER_THRESHOLD", "7")
        monkeypatch.setenv("DEPPY_TPU_BREAKER_RESET_S", "2.5")
        faults.set_default_breaker(None)  # force re-create from env
        br = faults.default_breaker()
        assert br.failure_threshold == 7
        assert br.reset_after_s == 2.5


# ------------------------------------------------------------- injection


class TestFaultInjection:
    def test_times_and_after(self):
        plan = faults.FaultPlan.from_doc(
            [{"point": "p", "kind": "error", "after": 1, "times": 2}])
        faults.configure_plan(plan)
        faults.inject("p")  # skipped (after=1)
        with pytest.raises(faults.InjectedFault):
            faults.inject("p")
        with pytest.raises(faults.InjectedFault):
            faults.inject("p")
        faults.inject("p")  # exhausted

    def test_unlimited_and_unmatched_points(self):
        faults.configure_plan(faults.FaultPlan.from_doc(
            [{"point": "p", "times": -1}]))
        for _ in range(3):
            with pytest.raises(faults.InjectedFault):
                faults.inject("p")
        faults.inject("other")  # never fires

    def test_period_fires_every_cycle(self):
        # "every first of 2 attempts": hits 0, 2, 4 fire; 1, 3, 5 pass.
        faults.configure_plan(faults.FaultPlan.from_doc(
            [{"point": "p", "period": 2, "times": 1}]))
        fired = []
        for i in range(6):
            try:
                faults.inject("p")
                fired.append(False)
            except faults.InjectedFault:
                fired.append(True)
        assert fired == [True, False, True, False, True, False]

    def test_glob_point_match(self):
        faults.configure_plan(faults.FaultPlan.from_doc(
            [{"point": "driver.*", "times": -1}]))
        with pytest.raises(faults.InjectedFault):
            faults.inject("driver.device_put")
        faults.inject("service.resolve")

    def test_shadowed_error_rule_keeps_its_budget(self):
        """Two error rules matching one hit: only the first raises, and
        the shadowed rule's firing budget must NOT be spent — it fires
        on the next hit instead of silently evaporating."""
        faults.configure_plan(faults.FaultPlan.from_doc([
            {"point": "p", "kind": "error", "times": 1,
             "message": "first"},
            {"point": "p*", "kind": "error", "times": 1,
             "message": "second"},
        ]))
        with pytest.raises(faults.InjectedFault, match="first"):
            faults.inject("p")
        with pytest.raises(faults.InjectedFault, match="second"):
            faults.inject("p")
        faults.inject("p")  # both budgets spent now

    def test_latency_injection_sleeps(self):
        faults.configure_plan(faults.FaultPlan.from_doc(
            [{"point": "p", "kind": "latency", "latency_s": 0.05,
              "times": 1}]))
        t0 = time.monotonic()
        faults.inject("p")
        assert time.monotonic() - t0 >= 0.05
        t0 = time.monotonic()
        faults.inject("p")  # exhausted: no sleep
        assert time.monotonic() - t0 < 0.05

    def test_injections_counted(self):
        faults.configure_plan(faults.FaultPlan.from_doc(
            [{"point": "p", "times": 1}]))
        with pytest.raises(faults.InjectedFault):
            faults.inject("p")
        snap = telemetry.default_registry().snapshot()
        assert snap["deppy_faults_injected_total"] == {"p": 1}

    def test_plan_from_spec_inline_file_and_at(self, tmp_path):
        inline = faults.plan_from_spec('[{"point": "x"}]')
        assert inline.rules[0].point == "x"
        obj = faults.plan_from_spec('{"faults": [{"point": "y"}]}')
        assert obj.rules[0].point == "y"
        f = tmp_path / "plan.json"
        f.write_text('[{"point": "z", "times": 3}]')
        for spec in (str(f), "@" + str(f)):
            plan = faults.plan_from_spec(spec)
            assert plan.rules[0].point == "z" and plan.rules[0].times == 3

    def test_malformed_plan_raises(self):
        with pytest.raises(ValueError):
            faults.plan_from_spec('[{"kind": "error"}]')  # no point
        with pytest.raises(ValueError):
            faults.plan_from_spec('[{"point": "p", "kind": "explode"}]')
        with pytest.raises(ValueError):
            faults.plan_from_spec('[{"point": "p", "tiems": 1}]')  # typo
        with pytest.raises(ValueError):
            faults.plan_from_spec('["not an object"]')

    def test_env_plan(self, monkeypatch):
        monkeypatch.setenv("DEPPY_TPU_FAULT_PLAN", '[{"point": "p"}]')
        plan = faults.plan_from_env()
        assert plan is not None and plan.rules[0].point == "p"
        monkeypatch.delenv("DEPPY_TPU_FAULT_PLAN")
        assert faults.plan_from_env() is None


# ----------------------------------------------------- driver chaos suite

jax = pytest.importorskip("jax")

from deppy_tpu.engine import driver  # noqa: E402
from deppy_tpu.models import random_instance  # noqa: E402
from deppy_tpu.sat.encode import encode  # noqa: E402


def _problems(n=8, seed0=0):
    return [encode(random_instance(length=10, seed=seed0 + s))
            for s in range(n)]


def _same_results(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert int(a.outcome) == int(b.outcome)
        assert (np.nonzero(np.asarray(a.installed))[0].tolist()
                == np.nonzero(np.asarray(b.installed))[0].tolist())
        assert (np.nonzero(np.asarray(a.core))[0].tolist()
                == np.nonzero(np.asarray(b.core))[0].tolist())


@pytest.fixture(scope="module")
def batch():
    return _problems()


@pytest.fixture(scope="module")
def clean(batch):
    return driver.solve_problems(batch)


class TestDriverRecovery:
    def test_transient_dispatch_failure_retried(self, batch, clean,
                                                monkeypatch):
        monkeypatch.setenv("DEPPY_TPU_FAULT_BACKOFF_S", "0.001")
        faults.configure_plan(faults.plan_from_spec(
            '[{"point": "driver.dispatch", "kind": "error", "times": 1}]'))
        _same_results(driver.solve_problems(batch), clean)
        snap = telemetry.default_registry().snapshot()
        assert snap["deppy_fault_retries"] >= 1
        assert faults.default_breaker().state() == "closed"

    def test_transient_device_put_failure_retried(self, batch, clean,
                                                  monkeypatch):
        monkeypatch.setenv("DEPPY_TPU_FAULT_BACKOFF_S", "0.001")
        faults.configure_plan(faults.plan_from_spec(
            '[{"point": "driver.device_put", "kind": "error", "times": 1}]'))
        _same_results(driver.solve_problems(batch), clean)
        assert (telemetry.default_registry().snapshot()
                ["deppy_fault_retries"]) >= 1

    def test_acceptance_every_first_attempt_fails(self, batch, clean,
                                                  monkeypatch, tmp_path):
        """ISSUE 2 acceptance: a fault plan injecting a device failure
        into every first chunk attempt — the batch still resolves
        correctly (retry path), and the fault metrics reach the
        telemetry sink."""
        monkeypatch.setenv("DEPPY_TPU_FAULT_BACKOFF_S", "0.001")
        sink = tmp_path / "sink.jsonl"
        telemetry.default_registry().configure_sink(str(sink))
        faults.configure_plan(faults.plan_from_spec(
            '[{"point": "driver.dispatch", "kind": "error",'
            ' "period": 2, "times": 1}]'))
        _same_results(driver.solve_problems(batch), clean)
        snap = telemetry.default_registry().snapshot()
        assert snap["deppy_fault_retries"] >= 1
        events = [json.loads(line)
                  for line in sink.read_text().splitlines()]
        kinds = {e["kind"] for e in events}
        assert "fault" in kinds and "span" in kinds

    def test_persistent_failure_falls_back_to_host(self, batch, clean,
                                                   monkeypatch):
        """Device permanently dead: retries exhaust, the breaker trips at
        its threshold, and the whole batch still resolves correctly on
        the host engine."""
        monkeypatch.setenv("DEPPY_TPU_FAULT_BACKOFF_S", "0.001")
        faults.set_default_breaker(
            faults.CircuitBreaker(failure_threshold=2, reset_after_s=60))
        faults.configure_plan(faults.plan_from_spec(
            '[{"point": "driver.dispatch", "kind": "error", "times": -1}]'))
        _same_results(driver.solve_problems(batch), clean)
        assert faults.default_breaker().state() == "open"
        snap = telemetry.default_registry().snapshot()
        assert snap["deppy_fault_host_routed_total"] == len(batch)
        assert snap["deppy_breaker_state"] == faults.BREAKER_OPEN

    def test_open_breaker_short_circuits_to_host(self, batch, clean):
        """No fault plan, breaker already open: groups route straight to
        the host engine without paying a device attempt."""
        br = faults.CircuitBreaker(failure_threshold=1, reset_after_s=60)
        faults.set_default_breaker(br)
        br.record_failure()
        calls = []
        faults.configure_plan(faults.FaultPlan.from_doc(
            [{"point": "driver.dispatch", "kind": "latency",
              "latency_s": 0, "times": -1}]))
        plan = faults.current_plan()
        _same_results(driver.solve_problems(batch), clean)
        del calls
        # The dispatch fault point was never reached: zero hits.
        assert plan.rules[0].hits == 0
        snap = telemetry.default_registry().snapshot()
        assert snap["deppy_fault_host_routed_total"] == len(batch)

    def test_half_open_probe_recovers_device_path(self, batch, clean,
                                                  monkeypatch):
        """Breaker open, cooldown elapsed, fault cleared: the next solve
        is the half-open probe — it succeeds on device and closes the
        breaker."""
        monkeypatch.setenv("DEPPY_TPU_FAULT_BACKOFF_S", "0.001")
        faults.set_default_breaker(
            faults.CircuitBreaker(failure_threshold=1, reset_after_s=0.01))
        # threshold 1: the first failure opens the breaker, which blocks
        # the retry — so exactly one error fires and the plan exhausts.
        faults.configure_plan(faults.plan_from_spec(
            '[{"point": "driver.dispatch", "kind": "error", "times": 1}]'))
        _same_results(driver.solve_problems(batch), clean)  # trips open
        assert faults.default_breaker().state_code() != faults.BREAKER_CLOSED
        time.sleep(0.02)  # cooldown elapses; plan is exhausted by now
        _same_results(driver.solve_problems(batch), clean)
        assert faults.default_breaker().state() == "closed"

    def test_poison_group_isolated_by_split(self, batch, clean,
                                            monkeypatch):
        """A group that keeps failing splits in half before host
        fallback, so sub-groups that dispatch cleanly stay on device."""
        monkeypatch.setenv("DEPPY_TPU_FAULT_BACKOFF_S", "0.001")
        monkeypatch.setenv("DEPPY_TPU_FAULT_RETRIES", "1")
        # Generous threshold so the breaker never blocks the split path.
        faults.set_default_breaker(
            faults.CircuitBreaker(failure_threshold=100, reset_after_s=60))
        # Fail the first 8-problem dispatch; the 4-problem halves pass.
        faults.configure_plan(faults.FaultPlan.from_doc(
            [{"point": "driver.dispatch", "kind": "error", "times": 1}]))
        _same_results(driver.solve_problems(batch), clean)
        snap = telemetry.default_registry().snapshot()
        # Split happened and nothing was host-routed.
        assert snap.get("deppy_fault_host_routed_total", 0) == 0

    def test_expired_deadline_degrades_to_incomplete(self, batch):
        with faults.deadline_scope(0.0):
            results = driver.solve_problems(batch)
        assert all(int(r.outcome) == 0 for r in results)  # RUNNING
        snap = telemetry.default_registry().snapshot()
        assert snap["deppy_deadline_exceeded"] >= 1

    def test_env_batch_deadline(self, batch, monkeypatch):
        monkeypatch.setenv("DEPPY_TPU_BATCH_DEADLINE_S", "0.000001")
        results = driver.solve_problems(batch)
        assert all(int(r.outcome) == 0 for r in results)

    def test_chunk_deadline_overrun_charges_breaker(self, batch, clean,
                                                    monkeypatch):
        """A dispatch slower than the chunk deadline keeps its (valid)
        result but counts as a breaker failure — the minutes-long-
        execution crash class becomes a trip signal."""
        monkeypatch.setenv("DEPPY_TPU_CHUNK_DEADLINE_S", "0.001")
        faults.set_default_breaker(
            faults.CircuitBreaker(failure_threshold=1, reset_after_s=60))
        faults.configure_plan(faults.plan_from_spec(
            '[{"point": "driver.dispatch", "kind": "latency",'
            ' "latency_s": 0.05, "times": 1}]'))
        _same_results(driver.solve_problems(batch), clean)
        assert faults.default_breaker().state() == "open"
        snap = telemetry.default_registry().snapshot()
        assert snap["deppy_deadline_exceeded"] >= 1

    def test_host_fallback_preserves_unsat_cores(self, monkeypatch):
        """The host fallback path must carry exact conflict sets, not
        just outcomes (the decode contract)."""
        monkeypatch.setenv("DEPPY_TPU_FAULT_BACKOFF_S", "0.001")
        from deppy_tpu import sat

        probs = [
            encode([sat.variable("a", sat.mandatory(), sat.prohibited())]),
            encode([sat.variable("b", sat.mandatory())]),
        ]
        clean = driver.solve_problems(probs)
        faults.set_default_breaker(
            faults.CircuitBreaker(failure_threshold=1, reset_after_s=60))
        faults.configure_plan(faults.plan_from_spec(
            '[{"point": "driver.dispatch", "kind": "error", "times": -1}]'))
        _same_results(driver.solve_problems(probs), clean)

    def test_host_fallback_unsat_within_budget_stays_unsat(self,
                                                           monkeypatch):
        """The fallback must not re-run the core sweep solve() already
        paid for: an UNSAT that fits the budget once must not flip to
        Incomplete by being charged twice."""
        monkeypatch.setenv("DEPPY_TPU_FAULT_BACKOFF_S", "0.001")
        from deppy_tpu import sat
        from deppy_tpu.sat.host import HostEngine

        p = encode([sat.variable("a", sat.mandatory(), sat.prohibited()),
                    sat.variable("b", sat.mandatory())])
        probe = HostEngine(p)
        with pytest.raises(Exception):
            probe.solve()
        exact_budget = probe.steps  # solve + its core sweep, no slack
        faults.set_default_breaker(
            faults.CircuitBreaker(failure_threshold=1, reset_after_s=60))
        faults.configure_plan(faults.plan_from_spec(
            '[{"point": "driver.dispatch", "kind": "error", "times": -1}]'))
        (res,) = driver.solve_problems([p], max_steps=exact_budget)
        assert int(res.outcome) == -1  # UNSAT, not Incomplete
        assert np.asarray(res.core)[: p.n_cons].any()

    def test_env_deadline_bounds_host_backend(self, monkeypatch):
        """DEPPY_TPU_BATCH_DEADLINE_S must bound the facade's host
        serial loop too (the degraded mode where deadlines matter most),
        counting ONE deadline event for the whole remainder."""
        from deppy_tpu import sat
        from deppy_tpu.resolution import BatchResolver
        from deppy_tpu.sat.errors import Incomplete as Inc

        monkeypatch.setenv("DEPPY_TPU_BATCH_DEADLINE_S", "0.000001")
        out = BatchResolver(backend="host").solve(
            [[sat.variable(f"v{i}", sat.mandatory())] for i in range(5)])
        assert all(isinstance(r, Inc) for r in out)
        snap = telemetry.default_registry().snapshot()
        assert snap["deppy_deadline_exceeded"] == 1

    def test_budget_exhaustion_survives_host_fallback(self, monkeypatch):
        """An Incomplete (budget-starved) verdict must be identical on
        the fallback path — the step budget carries over."""
        monkeypatch.setenv("DEPPY_TPU_FAULT_BACKOFF_S", "0.001")
        probs = _problems(4)
        faults.set_default_breaker(
            faults.CircuitBreaker(failure_threshold=1, reset_after_s=60))
        faults.configure_plan(faults.plan_from_spec(
            '[{"point": "driver.dispatch", "kind": "error", "times": -1}]'))
        results = driver.solve_problems(probs, max_steps=1)
        assert all(int(r.outcome) == 0 for r in results)


# --------------------------------------------------- auto-routing + breaker


class TestAutoRouting:
    def test_open_breaker_degrades_auto_to_host(self, monkeypatch):
        from deppy_tpu.sat import solver as sat_solver

        monkeypatch.setattr(sat_solver, "_ENGINE_USABLE", True)
        assert sat_solver.resolve_backend("auto") == "tpu"
        br = faults.CircuitBreaker(failure_threshold=1, reset_after_s=60)
        faults.set_default_breaker(br)
        br.record_failure()
        assert sat_solver.resolve_backend("auto") == "host"
        # Explicit tpu bypasses the breaker (the caller insisted).
        assert sat_solver.resolve_backend("tpu") == "tpu"

    def test_successful_reprobe_closes_breaker(self, monkeypatch):
        from deppy_tpu.sat import solver as sat_solver

        br = faults.CircuitBreaker(failure_threshold=1, reset_after_s=60)
        faults.set_default_breaker(br)
        br.record_failure()
        assert br.state() == "open"
        monkeypatch.setattr(sat_solver, "_probe_verdict", lambda: True)
        monkeypatch.setattr(sat_solver, "_ENGINE_USABLE", None)
        assert sat_solver.reprobe_engine() is True
        assert br.state() == "closed"
        monkeypatch.setattr(sat_solver, "_ENGINE_USABLE", None)

    def test_reprobe_needs_a_dispatch_not_a_device_list(self, monkeypatch):
        """jax.devices() answers from its cache once the backend is up;
        the breaker closes only on a dispatch that ran."""
        import jax

        from deppy_tpu.sat import solver as sat_solver

        br = faults.CircuitBreaker(failure_threshold=1, reset_after_s=60)
        faults.set_default_breaker(br)
        br.record_failure()
        assert jax.devices()

        def dead(*a, **k):
            raise RuntimeError("device lost")

        monkeypatch.setattr(jax, "device_put", dead)
        monkeypatch.setattr(sat_solver, "_ENGINE_USABLE", None)
        assert sat_solver.reprobe_engine() is False
        assert br.state() == "open"
        monkeypatch.setattr(sat_solver, "_ENGINE_USABLE", None)


# ------------------------------------------------------------ service chaos


def _request(port, method, path, body=None, headers=None):
    conn = HTTPConnection("127.0.0.1", port, timeout=10)
    h = dict(headers or {})
    if body is not None:
        h["Content-Type"] = "application/json"
    conn.request(method, path,
                 body=json.dumps(body) if body is not None else None,
                 headers=h)
    resp = conn.getresponse()
    data = resp.read()
    retry_after = resp.getheader("Retry-After")
    conn.close()
    return resp.status, data, retry_after


_DOC = {"variables": [{"id": "a", "constraints": [{"type": "mandatory"}]}]}


@pytest.fixture()
def server():
    from deppy_tpu.service import Server

    srv = Server(bind_address="127.0.0.1:0", probe_address="127.0.0.1:0",
                 backend="host")
    srv.start()
    yield srv
    srv.shutdown()


class TestServiceFaultSurface:
    def test_metrics_expose_fault_families(self, server):
        status, data, _ = _request(server.api_port, "GET", "/metrics")
        text = data.decode()
        assert status == 200
        assert "deppy_breaker_state 0" in text
        assert "deppy_fault_retries 0" in text
        assert "deppy_deadline_exceeded 0" in text
        # Every family in docs/observability.md's fault table scrapes.
        for family in ("deppy_breaker_transitions_total",
                       "deppy_fault_failures_total",
                       "deppy_fault_host_routed_total",
                       "deppy_faults_injected_total"):
            assert f"# TYPE {family} counter" in text, family

    def test_metrics_reflect_open_breaker(self, server):
        br = faults.CircuitBreaker(failure_threshold=1, reset_after_s=60)
        faults.set_default_breaker(br)
        br.record_failure()
        _, data, _ = _request(server.api_port, "GET", "/metrics")
        assert "deppy_breaker_state 2" in data.decode()

    def test_spent_deadline_rejected_503_retry_after(self, server):
        status, data, retry_after = _request(
            server.api_port, "POST", "/v1/resolve", _DOC,
            {"X-Deppy-Deadline-S": "0"})
        assert status == 503
        doc = json.loads(data)
        assert "deadline" in doc["error"]
        assert retry_after is not None and int(retry_after) >= 1
        snap = telemetry.default_registry().snapshot()
        assert snap["deppy_deadline_exceeded"] >= 1

    def test_invalid_deadline_header_400(self, server):
        status, data, _ = _request(
            server.api_port, "POST", "/v1/resolve", _DOC,
            {"X-Deppy-Deadline-S": "soon"})
        assert status == 400
        assert b"X-Deppy-Deadline-S" in data

    def test_generous_deadline_resolves(self, server):
        status, data, _ = _request(
            server.api_port, "POST", "/v1/resolve", _DOC,
            {"X-Deppy-Deadline-S": "30"})
        assert status == 200
        assert json.loads(data)["results"][0]["status"] == "sat"

    def test_tpu_backend_with_open_breaker_503(self):
        from deppy_tpu.service import Server

        br = faults.CircuitBreaker(failure_threshold=1, reset_after_s=60)
        faults.set_default_breaker(br)
        br.record_failure()
        srv = Server(bind_address="127.0.0.1:0",
                     probe_address="127.0.0.1:0", backend="tpu")
        srv.start()
        try:
            status, _, retry_after = _request(
                srv.api_port, "POST", "/v1/resolve", _DOC)
            assert status == 503
            assert retry_after is not None
        finally:
            srv.shutdown()

    def test_readyz_flags_degraded_mode(self, server):
        status, data, _ = _request(server.probe_port, "GET", "/readyz")
        assert (status, data) == (200, b"ok")
        br = faults.CircuitBreaker(failure_threshold=1, reset_after_s=60)
        faults.set_default_breaker(br)
        br.record_failure()
        status, data, _ = _request(server.probe_port, "GET", "/readyz")
        assert status == 200  # still serving (host engine)
        assert b"degraded" in data

    def test_graceful_shutdown_drains_inflight_requests(self):
        """In-flight /v1/resolve requests finish before the listeners
        close (bounded by the drain budget)."""
        from deppy_tpu.service import Server

        srv = Server(bind_address="127.0.0.1:0",
                     probe_address="127.0.0.1:0", backend="host",
                     drain_s=10.0)
        srv.start()
        faults.configure_plan(faults.plan_from_spec(
            '[{"point": "service.resolve", "kind": "latency",'
            ' "latency_s": 0.3, "times": 1}]'))
        result = {}

        def slow():
            result["r"] = _request(srv.api_port, "POST", "/v1/resolve",
                                   _DOC)

        th = threading.Thread(target=slow)
        th.start()
        deadline = time.monotonic() + 5
        while srv._inflight == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv._inflight == 1
        srv.shutdown()
        th.join(5)
        assert result["r"][0] == 200
        assert json.loads(result["r"][1])["results"][0]["status"] == "sat"

    def test_shutdown_drain_is_bounded(self):
        """A request slower than the drain budget does not wedge
        shutdown."""
        from deppy_tpu.service import Server

        srv = Server(bind_address="127.0.0.1:0",
                     probe_address="127.0.0.1:0", backend="host",
                     drain_s=0.05)
        srv.start()
        faults.configure_plan(faults.plan_from_spec(
            '[{"point": "service.resolve", "kind": "latency",'
            ' "latency_s": 1.0, "times": 1}]'))
        th = threading.Thread(
            target=lambda: _request(srv.api_port, "POST", "/v1/resolve",
                                    _DOC))
        th.start()
        deadline = time.monotonic() + 5
        while srv._inflight == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        t0 = time.monotonic()
        srv.shutdown()
        assert time.monotonic() - t0 < 2.0
        th.join(5)

    def test_request_deadline_config_default(self, monkeypatch):
        from deppy_tpu.service import Server

        monkeypatch.setenv("DEPPY_TPU_REQUEST_DEADLINE_S", "12.5")
        srv = Server(bind_address="127.0.0.1:0",
                     probe_address="127.0.0.1:0", backend="host")
        try:
            assert srv.request_deadline_s == 12.5
        finally:
            srv.shutdown()


# -------------------------------------------------------------- CLI wiring


class TestCLI:
    def test_resolve_with_fault_plan_recovers(self, tmp_path, capsys,
                                              monkeypatch):
        monkeypatch.setenv("DEPPY_TPU_FAULT_BACKOFF_S", "0.001")
        from deppy_tpu.cli import main

        path = tmp_path / "problem.json"
        path.write_text(json.dumps(_DOC))
        plan = tmp_path / "plan.json"
        plan.write_text(
            '[{"point": "driver.dispatch", "kind": "error", "times": 1}]')
        rc = main(["resolve", str(path), "--backend", "tpu",
                   "--fault-plan", str(plan), "--output", "json"])
        out = capsys.readouterr().out
        assert rc == 0
        assert json.loads(out)["status"] == "sat"

    def test_resolve_bad_fault_plan_usage_error(self, tmp_path, capsys):
        from deppy_tpu.cli import main

        path = tmp_path / "problem.json"
        path.write_text(json.dumps(_DOC))
        rc = main(["resolve", str(path), "--fault-plan", "{nope"])
        assert rc == 2
        assert "invalid fault plan" in capsys.readouterr().err

    def test_resolve_deadline_flag(self, tmp_path, capsys):
        from deppy_tpu.cli import main

        path = tmp_path / "problem.json"
        path.write_text(json.dumps(_DOC))
        rc = main(["resolve", str(path), "--backend", "host",
                   "--deadline", "0"])
        out = capsys.readouterr().out
        assert rc == 3  # incomplete: the deadline expired before solving
        assert "incomplete" in out

    def test_serve_config_request_deadline_key(self, tmp_path):
        from deppy_tpu.cli import _load_serve_config

        cfg = tmp_path / "cfg.json"
        cfg.write_text('{"requestDeadlineSeconds": 7}')
        assert _load_serve_config(str(cfg)) == {"request_deadline_s": 7.0}

    def test_stats_survives_torn_binary_line(self, tmp_path, capsys):
        """A partially written (binary-garbage) sink line counts as
        malformed instead of raising UnicodeDecodeError."""
        from deppy_tpu.cli import main

        sink = tmp_path / "telemetry.jsonl"
        with open(sink, "wb") as fh:
            fh.write(json.dumps(
                {"ts": 1.0, "kind": "span", "name": "driver.solve",
                 "dur_s": 0.5, "attrs": {}}).encode() + b"\n")
            fh.write(b'{"ts": 2.0, "kind": "span", "na\xff\xfe\x00TORN')
        rc = main(["stats", str(sink)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "1 malformed lines skipped" in out
        assert "driver.solve" in out
