"""Mesh-serving shard path (ISSUE 6).

``tests/conftest.py`` forces an 8-device virtual CPU platform
(``--xla_force_host_platform_device_count=8``), so these tests exercise
the real batch-axis sharded dispatch — per-device single programs, one
fault domain per shard — without TPU hardware.  Three acceptance pins:

* the scheduler's sharded drain is **byte-identical** to unsharded
  dispatch (same models, same unsat cores, same step counts);
* a fuzz differential over the sharded driver entry point;
* a chaos run where a fault plan poisons ONE shard's dispatch and only
  that slice degrades (recovered on the host engine) while batchmates
  on the other devices complete on-device, with the poisoned device's
  breaker — and only that breaker — charged.
"""

from __future__ import annotations

import numpy as np
import pytest

from deppy_tpu import faults, sat, telemetry
from deppy_tpu.models import random_instance
from deppy_tpu.sat.encode import encode
from deppy_tpu.sat.errors import BackendCapabilityError

jax = pytest.importorskip("jax")

from deppy_tpu.engine import core, driver  # noqa: E402
from deppy_tpu.parallel.mesh import (default_mesh,  # noqa: E402
                                     mesh_devices_from_env, serving_mesh)
from deppy_tpu.sched import Scheduler  # noqa: E402

pytestmark = pytest.mark.shard


@pytest.fixture(autouse=True)
def fresh_fault_state():
    """Isolate the process-global breaker fleet, fault plan, and
    telemetry registry per test (same contract as the chaos suite),
    including the ISSUE 6 per-device breakers."""
    prev_breaker = faults.set_default_breaker(faults.CircuitBreaker())
    prev_plan = faults.configure_plan(None)
    prev_reg = telemetry.set_default_registry(telemetry.Registry())
    faults.reset_device_breakers()
    yield
    faults.reset_device_breakers()
    telemetry.set_default_registry(prev_reg)
    faults.configure_plan(prev_plan)
    faults.set_default_breaker(prev_breaker)


def _problems(n=16, length=20, seed0=0):
    """Mixed SAT/UNSAT batch: benchmark distribution plus a
    conflict-heavy tail so models AND unsat cores cross the wire."""
    half = n // 2
    return (
        [encode(random_instance(length=length, seed=s))
         for s in range(seed0, seed0 + half)]
        + [encode(random_instance(length=length, seed=s, p_mandatory=0.5,
                                  p_conflict=0.5, n_conflict=4))
           for s in range(seed0, seed0 + (n - half))]
    )


def _assert_results_identical(problems, base, other, ctx=""):
    """Per-lane identity on the LIVE prefix of every result tensor —
    verdict, model, core, step count.  The live prefix (``n_vars`` /
    ``n_cons`` rows) is exactly what decode reads; the trailing pad
    width is a bucketing artifact that already differs across the
    unsharded path's own size-class buckets, so it was never a
    cross-path guarantee."""
    assert len(base) == len(other) == len(problems)
    for i, (p, b, o) in enumerate(zip(problems, base, other)):
        assert int(b.outcome) == int(o.outcome), f"{ctx} lane {i}: outcome"
        assert np.array_equal(
            np.asarray(b.installed)[: p.n_vars],
            np.asarray(o.installed)[: p.n_vars]), f"{ctx} lane {i}: model"
        assert np.array_equal(
            np.asarray(b.core)[: p.n_cons],
            np.asarray(o.core)[: p.n_cons]), f"{ctx} lane {i}: core"
        assert int(b.steps) == int(o.steps), f"{ctx} lane {i}: steps"


# -------------------------------------------------------- mesh resolution


class TestServingMesh:
    def test_env_parsing(self, monkeypatch):
        cases = {"": None, "0": None, "1": None, "off": None,
                 "none": None, "all": -1, "-1": -1, "4": 4,
                 "banana": None, "-3": None}
        for raw, want in cases.items():
            monkeypatch.setenv("DEPPY_TPU_MESH_DEVICES", raw)
            assert mesh_devices_from_env() == want, raw

    def test_serving_mesh_sizes_and_clamps(self, monkeypatch):
        monkeypatch.delenv("DEPPY_TPU_MESH_DEVICES", raising=False)
        assert serving_mesh(None) is None          # off by default
        assert serving_mesh(1) is None             # 1 device = no mesh
        assert serving_mesh(4).size == 4
        assert serving_mesh(-1).size == len(jax.devices())
        assert serving_mesh(999).size == len(jax.devices())  # clamped

    def test_scheduler_sizes_micro_batches_to_mesh(self):
        mesh = serving_mesh(8)
        s = Scheduler(backend="host", lanes_per_device=4, mesh=mesh)
        assert s.max_fill == 8 * 4
        # An explicit max_fill wins over mesh sizing.
        s2 = Scheduler(backend="host", lanes_per_device=4, mesh=mesh,
                       max_fill=16)
        assert s2.max_fill == 16


# ------------------------------------------- byte-identity + fuzz (driver)


class TestShardedDriver:
    # The first full-mesh per-device dispatch in a process compiles one
    # executable set PER DEVICE (placement is part of jit's cache key),
    # which on the forced 8-device CPU platform costs ~90s of wall on 2
    # cores.  Tier-1 keeps the 2-device scheduler-drain pins below (same
    # code path, two executables instead of eight, raw-tensor identity
    # asserted lane by lane); the driver-level fuzz/SPMD pins here run
    # under `make test-shard` (-m shard includes slow) and the 8-device
    # acceptance surface also runs end-to-end in sanity CI via
    # scripts/shard_smoke.py.
    @pytest.mark.slow
    def test_sharded_matches_unsharded_byte_identical(self):
        problems = _problems(16)
        base = driver.solve_problems(problems, max_steps=20000)
        shard = driver.solve_problems_sharded(
            problems, mesh=serving_mesh(8), max_steps=20000)
        _assert_results_identical(problems, base, shard)

    @pytest.mark.parametrize("seed0,n,ndev", [
        pytest.param(100, 8, 8, marks=pytest.mark.slow),
        pytest.param(200, 11, 4, marks=pytest.mark.slow),
        pytest.param(300, 5, 2, marks=pytest.mark.slow),
    ])
    def test_fuzz_differential_over_mesh_shapes(self, seed0, n, ndev):
        """Uneven batches, partial meshes: lane→shard assignment must
        never change a verdict, a model, a core, or a step count."""
        problems = _problems(n, length=16, seed0=seed0)
        base = driver.solve_problems(problems, max_steps=20000)
        shard = driver.solve_problems_sharded(
            problems, mesh=serving_mesh(ndev), max_steps=20000)
        _assert_results_identical(problems, base, shard,
                                  ctx=f"ndev={ndev}")

    @pytest.mark.slow
    def test_spmd_spelling_matches_unsharded(self):
        """The SPMD spelling — ONE program over the whole mesh, the
        lane axis partitioned by batched_solve_sharded's explicit
        PartitionSpec shardings — answers identically to the
        single-device path and (by transitivity) the per-device serving
        composition."""
        problems = _problems(16)
        base = driver.solve_problems(problems, max_steps=20000)
        spmd = driver.solve_problems_sharded(
            problems, mesh=serving_mesh(8), max_steps=20000, spmd=True)
        _assert_results_identical(problems, base, spmd, ctx="spmd")

    def test_single_problem_falls_back_to_unsharded(self):
        problems = _problems(2)[:1]
        res = driver.solve_problems_sharded(
            problems, mesh=serving_mesh(8), max_steps=20000)
        base = driver.solve_problems(problems, max_steps=20000)
        _assert_results_identical(problems, base, res)


# ------------------------------------------------- scheduler sharded drain


def _vars(n, seed0=0):
    """Variable-list problems for the scheduler's submit() surface."""
    half = n // 2
    return ([random_instance(length=20, seed=s)
             for s in range(seed0, seed0 + half)]
            + [random_instance(length=20, seed=s, p_mandatory=0.5,
                               p_conflict=0.5, n_conflict=4)
               for s in range(seed0, seed0 + (n - half))])


def _canon(results):
    out = []
    for r in results:
        if isinstance(r, sat.NotSatisfiable):
            out.append(("unsat", sorted(
                (ac.variable.identifier, str(ac)) for ac in r.constraints)))
        elif isinstance(r, dict):
            out.append(("sat", sorted(k for k, v in r.items() if v)))
        else:
            out.append(("incomplete", None))
    return out


class TestSchedulerShardedDrain:
    def test_sharded_drain_byte_identical_to_unsharded(self, monkeypatch):
        from deppy_tpu.sat import solver as sat_solver

        monkeypatch.setattr(sat_solver, "_ENGINE_USABLE", True)
        probs = _vars(16)

        plain = Scheduler(backend="tpu", max_wait_ms=0.0, cache_size=0)
        plain.start()
        try:
            stats_p: dict = {}
            base = plain.submit(probs, stats=stats_p)
        finally:
            plain.stop()

        meshed = Scheduler(backend="tpu", max_wait_ms=0.0, cache_size=0,
                           mesh=serving_mesh(2))
        meshed.start()
        try:
            stats_m: dict = {}
            got = meshed.submit(probs, stats=stats_m)
        finally:
            meshed.stop()

        assert _canon(base) == _canon(got)
        # Same models, same cores — and the same engine step counts:
        # sharding changes placement, never the search.
        assert stats_p["steps"] == stats_m["steps"]

    def test_poisoned_shard_degrades_only_its_slice(self, monkeypatch):
        """Chaos acceptance (ISSUE 6): a fault plan poisons device 1's
        shard dispatch.  That slice must recover through its OWN fault
        domain (host fallback after the per-device breaker trips) with
        correct answers; batchmates on device 0 complete on-device; no
        other breaker — per-device or process-wide — is charged.  (The
        full-mesh spelling — one poisoned device among 8 — runs in
        sanity CI via scripts/shard_smoke.py.)"""
        monkeypatch.setenv("DEPPY_TPU_FAULT_BACKOFF_S", "0.001")
        faults.configure_plan(faults.plan_from_spec(
            '[{"point": "driver.shard_dispatch.1", "kind": "error",'
            ' "times": -1}]'))
        problems = _problems(16)   # 16 lanes / 2 devices = 8 per shard
        mesh = serving_mesh(2)
        base = driver.solve_problems(problems, max_steps=20000)
        faults.default_breaker().reset()
        got = driver.solve_problems_sharded(problems, mesh=mesh,
                                            max_steps=20000)
        # Every lane answers, and every verdict/model/core matches the
        # unsharded oracle — the poisoned slice came back via the host
        # engine (a correctness-preserving degrade), not as an error.
        assert _canon_results(problems, base) == _canon_results(problems,
                                                                got)
        # The poisoned device's breaker took the charges…
        assert faults.device_breaker("1").blocks_device()
        # …its batchmate's breaker did not…
        br = faults.device_breakers().get("0")
        assert br is None or not br.blocks_device()
        # …and the process-wide accelerator breaker is untouched.
        assert not faults.default_breaker().blocks_device()
        # The recovery + breaker surface is observable: the per-device
        # recovery counter moved and /metrics grows a labeled line.
        snap = telemetry.default_registry().snapshot()
        assert (snap.get("deppy_shard_recoveries_total") or {}).get(
            "1", 0) >= 1
        lines = faults.render_metric_lines()
        assert any(l.startswith('deppy_breaker_state{device="1"}')
                   for l in lines), lines

    def test_open_device_breaker_host_routes_without_attempt(self):
        """A shard whose device breaker is already open never pays a
        dispatch attempt: the slice host-routes immediately (the mesh
        analog of PR 2's breaker-open fast path)."""
        for _ in range(faults.device_breaker("1").failure_threshold):
            faults.device_breaker("1").record_failure()
        assert faults.device_breaker("1").blocks_device()
        problems = _problems(16)
        base = driver.solve_problems(problems, max_steps=20000)
        got = driver.solve_problems_sharded(problems, mesh=serving_mesh(2),
                                            max_steps=20000)
        assert _canon_results(problems, base) == _canon_results(problems,
                                                                got)
        assert not faults.default_breaker().blocks_device()

    def test_open_process_breaker_host_routes_every_shard(self):
        """An open PROCESS-wide breaker is a whole-accelerator verdict:
        every shard host-routes without paying a dispatch attempt (PR
        2's breaker-open guarantee survives mesh serving), and the
        shard traffic neither charges the per-device breakers nor
        consumes the process breaker's half-open probe slot."""
        br = faults.default_breaker()
        for _ in range(br.failure_threshold):
            br.record_failure()
        assert br.blocks_device()
        problems = _problems(8)
        got = driver.solve_problems_sharded(problems, mesh=serving_mesh(2),
                                            max_steps=20000)
        snap = telemetry.default_registry().snapshot()
        # Every lane took the breaker-open host route (no attempt paid)…
        assert snap.get("deppy_fault_host_routed_total", 0) == len(problems)
        # …every lane still answers…
        assert len(got) == len(problems)
        assert all(r is not None for r in got)
        # …no device breaker was charged (no device ever dispatched),
        # and the process breaker is still open with its half-open
        # probe slot unconsumed by shard traffic.
        for key, dbr in faults.device_breakers().items():
            assert not dbr.blocks_device(), key
        assert br.blocks_device()


def _canon_results(problems, results):
    """Driver SolveResults → decoded, comparable verdicts.  Decoded
    rather than raw tensors: host-recovered lanes carry narrower padded
    core arrays than device lanes (same live values, different pad
    width), and the decode vocabulary is the real response surface the
    byte-identity claim is about."""
    return _canon(driver.decode_results(problems, results))


# ------------------------------------------------------ capability verdict


class TestBackendCapability:
    def test_clause_shard_requires_bits_impl(self, monkeypatch):
        from deppy_tpu.parallel import solve_sharded

        monkeypatch.setattr(core, "_BCP_IMPL", "gather")
        with pytest.raises(BackendCapabilityError) as ei:
            solve_sharded(encode(random_instance(length=8, seed=1)))
        assert "clause_shard" in str(ei.value)
        assert "gather" in str(ei.value)

    def test_service_renders_capability_error_as_400(self):
        """The typed error is a clean client-facing verdict at the
        service boundary, not an internal 500."""
        from deppy_tpu.service import Server

        assert issubclass(BackendCapabilityError, Exception)
        assert not issubclass(BackendCapabilityError,
                              sat.InternalSolverError)
        # The handler catches it explicitly (compile-time pin: the
        # import exists and the except clause references it).
        import inspect

        src = inspect.getsource(Server.resolve_document)
        assert "BackendCapabilityError" in src
