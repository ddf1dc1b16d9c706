"""Host-routed core extraction for giant problems.

Problems above ``driver.HOST_CORE_NCONS`` applied constraints route their
unsat-core extraction to the host spec engine (the deletion loop's
kept-member probes are full SAT searches the serial host resolves faster).
The
host loop IS the spec the device's chunked deletion provably matches, so
routing must be observably invisible: same cores, same outcomes.  These
tests pin that equivalence by forcing the routing threshold down so small
(fast-compiling) problems take the host path, and comparing against the
device path with the threshold forced up.
"""

import numpy as np
import pytest

from deppy_tpu import sat
from deppy_tpu.engine import core, driver
from deppy_tpu.models import gvk_conflict_catalog, random_instance
from deppy_tpu.sat.encode import encode


def _unsat_instances():
    """A handful of UNSAT instances with nontrivial cores."""
    out = [
        encode([
            sat.variable("a", sat.mandatory(), sat.prohibited()),
            sat.variable("b"),
        ]),
        encode([
            sat.variable("a", sat.mandatory(), sat.conflict("b")),
            sat.variable("b", sat.mandatory()),
            sat.variable("c", sat.dependency("b")),
        ]),
        encode([
            # Two disjoint cores: deletion order decides which survives —
            # exactly the case where routing must not change the answer.
            sat.variable("a", sat.mandatory(), sat.prohibited()),
            sat.variable("b", sat.mandatory(), sat.conflict("c")),
            sat.variable("c", sat.mandatory()),
            sat.variable("d", sat.dependency("c")),
        ]),
    ]
    for seed in (3, 7, 11, 19):
        p = encode(random_instance(length=32, seed=seed))
        try:
            from deppy_tpu.sat.host import HostEngine

            HostEngine(p).solve()
        except sat.NotSatisfiable:
            out.append(p)
        except Exception:
            pass
    assert len(out) >= 3
    return out


@pytest.fixture
def instances():
    return _unsat_instances()


def _solve_with_threshold(problems, threshold, monkeypatch):
    monkeypatch.setattr(driver, "HOST_CORE_NCONS", threshold)
    return driver.solve_problems(problems)


def test_monolith_host_routing_matches_device(instances, monkeypatch):
    for p in instances:
        (dev,) = _solve_with_threshold([p], 1 << 30, monkeypatch)
        (host,) = _solve_with_threshold([p], 0, monkeypatch)
        assert int(dev.outcome) == int(host.outcome) == core.UNSAT
        np.testing.assert_array_equal(dev.core, host.core)


def test_split_host_routing_matches_device(instances, monkeypatch):
    # A real batch (split path): UNSAT instances mixed with SAT siblings.
    sats = [encode(random_instance(length=32, seed=s)) for s in (0, 1)]
    batch = sats + instances
    dev = _solve_with_threshold(batch, 1 << 30, monkeypatch)
    host = _solve_with_threshold(batch, 0, monkeypatch)
    assert len(dev) == len(host) == len(batch)
    for a, b in zip(dev, host):
        assert int(a.outcome) == int(b.outcome)
        if int(a.outcome) == core.UNSAT:
            np.testing.assert_array_equal(a.core, b.core)
        elif int(a.outcome) == core.SAT:
            np.testing.assert_array_equal(a.installed, b.installed)


def test_host_routed_core_decodes_to_reference_error(monkeypatch):
    # End-to-end through the public facade: the rendered NotSatisfiable
    # message is the reference's format regardless of routing.
    monkeypatch.setattr(driver, "HOST_CORE_NCONS", 0)
    with pytest.raises(sat.NotSatisfiable) as ei:
        sat.Solver(
            [sat.variable("a", sat.mandatory(), sat.prohibited())],
            backend="tpu",
        ).solve()
    assert "constraints not satisfiable" in str(ei.value)
    assert "a is mandatory" in str(ei.value)


def test_speculative_core_matches_spec(instances, monkeypatch):
    # The batched-probe shortcut (trust-but-verify) must be observably
    # identical to the spec sweep on every instance — including the
    # disjoint-cores one, where its verification probe fails and it falls
    # back.  Forced on (it defaults off on CPU backends, where it loses).
    # One problem per solve: only the monolith path (device idle by core
    # time) attempts speculative probes; the split path deliberately
    # keeps the overlapped host sweep.
    monkeypatch.setattr(driver, "HOST_CORE_NCONS", 0)
    for p in instances:
        monkeypatch.setattr(driver, "SPEC_CORE", "1")
        (a,) = driver.solve_problems([p])
        monkeypatch.setattr(driver, "SPEC_CORE", "0")
        (b,) = driver.solve_problems([p])
        assert int(a.outcome) == int(b.outcome) == core.UNSAT
        np.testing.assert_array_equal(a.core, b.core)


def test_speculative_core_falls_back_on_order_dependence(monkeypatch):
    # Two disjoint cores: K (constraints critical against the FULL set) is
    # empty, so the shortcut must return None rather than guess.
    p = encode([
        sat.variable("a", sat.mandatory(), sat.prohibited()),
        sat.variable("b", sat.mandatory(), sat.conflict("c")),
        sat.variable("c", sat.mandatory()),
        sat.variable("d", sat.dependency("c")),
    ])
    mask, steps = driver._speculative_core_mask(p, 1 << 24)
    assert mask is None
    assert steps > 0


def test_speculative_core_exhausted_budget(monkeypatch):
    p = encode([
        sat.variable("a", sat.mandatory(), sat.prohibited()),
        sat.variable("b"),
    ])
    assert driver._speculative_core_mask(p, 0) == (None, 0)


def test_speculative_search_dispatches_are_budget_capped(monkeypatch):
    """Stage-2/verification probes must never ship the caller's whole
    (potentially multi-million-step) budget into one device program —
    minutes-long single executions are a known worker-crash trigger; the
    dispatch budget is clamped to SPEC_CORE_CAP and a capped-out lane
    falls back to the host sweep."""
    from deppy_tpu.engine import core

    seen = []
    orig = core.batched_probe

    def capture(V, NCON, NV):
        fn = orig(V, NCON, NV)

        def wrapped(pt, trials, budget):
            seen.append(int(budget))
            return fn(pt, trials, budget)

        return wrapped

    monkeypatch.setattr(core, "batched_probe", capture)
    p = encode([
        sat.variable("a", sat.mandatory(), sat.dependency("b", "c")),
        sat.variable("b", sat.conflict("c")),
        sat.variable("c", sat.mandatory()),
        sat.variable("d", sat.mandatory(), sat.prohibited()),
    ])
    driver._speculative_core_mask(p, 1 << 24)
    assert seen, "expected at least one search-stage dispatch"
    assert all(b <= driver.SPEC_CORE_CAP for b in seen)


def test_gvk_conflict_core_parity(monkeypatch):
    # A conflict-heavy catalog (the UNSAT-prone workload family) with the
    # threshold at 0: every UNSAT lane host-routes; results must match the
    # pure device run lane for lane.
    batch = [
        encode(gvk_conflict_catalog(
            n_groups=4, providers_per_group=2, n_required=3, seed=s
        ))
        for s in range(6)
    ]
    dev = _solve_with_threshold(batch, 1 << 30, monkeypatch)
    host = _solve_with_threshold(batch, 0, monkeypatch)
    for a, b in zip(dev, host):
        assert int(a.outcome) == int(b.outcome)
        if int(a.outcome) == core.UNSAT:
            np.testing.assert_array_equal(a.core, b.core)


def test_spec_core_auto_defaults_off(monkeypatch):
    """Round-4 policy pin: auto resolves OFF on every backend until a
    real accelerator measurement exists in the measured-defaults
    registry.
    This must not silently revert to backend-sniffing."""
    monkeypatch.setattr(driver, "SPEC_CORE", "auto")
    assert driver._spec_core_enabled() is False
    monkeypatch.setattr(driver, "SPEC_CORE", "1")
    assert driver._spec_core_enabled() is True
