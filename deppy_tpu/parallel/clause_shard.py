"""Clause-sharded solve: intra-problem parallelism for giant problems.

The batch axis (mesh.py) scales *many* problems; this module scales *one*
problem past a single core — the framework's honest translation of
sequence-length scaling (SURVEY.md §5 "long-context"): a problem whose
clause planes exceed one core's VMEM/HBM budget is sharded along the
**clause row axis** over the mesh.  Every device runs the identical,
replicated solve control flow (baseline Test, guess search, DPLL leaves,
minimization, core extraction — all of :func:`deppy_tpu.engine.core
.solve_full`); only boolean-constraint propagation touches the sharded
rows, and each round combines the per-shard forced-literal masks and
conflict flags with one fused OR all-gather (:class:`core.clause_axis`).
That is the entire communication pattern — a few dozen packed words per
round over ICI, no resharding, no host round trips inside the solve.

This is SPMD by construction: control state (assignment planes, stacks,
deques) is replicated, so every device computes identical values and the
collectives are the only cross-device dependence.  Results decode exactly
like the batched path's.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..sat.constraints import Variable
from ..sat.encode import Problem, encode
from ..sat.errors import (BackendCapabilityError, Incomplete,
                          InternalSolverError, NotSatisfiable)
from ..analysis import compileguard
from ..engine import core, driver

CLAUSE_AXIS = "clause"


def clause_mesh(devices=None) -> Mesh:
    """A 1-D mesh over ``devices`` with the clause-row axis."""
    devs = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devs), (CLAUSE_AXIS,))


# ProblemTensors fields whose leading axis is the clause (C) or
# cardinality (NA) row axis — these shard; everything else replicates.
_ROW_SHARDED = {
    "clauses", "card_ids", "card_n", "card_act", "card_valid",
    "pos_bits", "neg_bits", "card_member_bits", "card_act_bits",
    "pos_bits_r", "neg_bits_r", "card_member_bits_r",
}


def _specs(axis: str) -> core.ProblemTensors:
    return core.ProblemTensors(**{
        f: (P(axis) if f in _ROW_SHARDED else P())
        for f in core.ProblemTensors._fields
    })


class _ShardDims(driver._Dims):
    """Batch dims with the row axes padded to a multiple of the mesh size
    (power-of-two meshes keep per-shard rows a power of two, which the
    halving-tree OR-reduce in round_planes relies on)."""

    def __init__(self, problems, n_devices: int):
        super().__init__(problems, 1)
        for f in ("C", "NA"):
            v = getattr(self, f)
            setattr(self, f, -(-v // n_devices) * n_devices)


@functools.lru_cache(maxsize=64)
def _sharded_fn(mesh: Mesh, V: int, NCON: int, NV: int,
                with_core: bool = True):
    """Compiled clause-sharded solve for one (mesh, space) signature —
    memoized like the driver's batched_* entry points, so same-shaped
    giant problems compile once.  Input-shape variation within a
    signature retraces via jit's own cache; callers must hold
    :class:`core.clause_axis` around invocations so those retraces pick
    up the collectives.  ``with_core=False`` compiles the deletion arm
    out (host-routed core extraction, driver.HOST_CORE_NCONS)."""
    devices = tuple(d.id for d in mesh.devices.flat)
    return jax.jit(compileguard.observe(
        "clause_shard.sharded_fn",
        jax.shard_map(
            functools.partial(core.solve_full, V=V, NCON=NCON, NV=NV,
                              with_core=with_core),
            mesh=mesh,
            in_specs=(_specs(CLAUSE_AXIS), P()),
            out_specs=core.SolveResult(
                *[P()] * len(core.SolveResult._fields)),
            check_vma=False,
        ),
        static=(devices, V, NCON, NV, with_core),
    ))


def solve_sharded(
    problem: Problem,
    mesh: Optional[Mesh] = None,
    max_steps: Optional[int] = None,
) -> core.SolveResult:
    """Solve ONE lowered problem with its clause rows sharded over the
    mesh.  Use for problems too large for a single core; for fleets of
    normal-sized problems use the batched driver."""
    if problem.errors:
        raise InternalSolverError(problem.errors)
    if core._resolved_impl() != "bits":
        # Only the bitplane round kernel carries the per-round OR
        # collective; the gather/pallas paths would propagate per-shard
        # with no cross-device combine and silently return wrong answers.
        # Typed (not a raw NotImplementedError): callers that never chose
        # an impl — the facade, the service — get a clean
        # backend-capability verdict they can render, not an internal
        # crash.
        raise BackendCapabilityError(
            "clause_shard", core._resolved_impl(),
            hint="clause-sharded solve carries its per-round OR "
            "collective only in the 'bits' BCP round kernel; unset "
            "DEPPY_TPU_BCP or select bits",
        )
    if mesh is None:
        mesh = clause_mesh()
    n_dev = mesh.devices.size
    d = _ShardDims([problem], n_dev)
    pts = driver.pad_problem(problem, d, pack=True)
    budget = driver._budget(max_steps)

    # Giant problems (which clause sharding exists for) host-route their
    # core extraction exactly like the batched driver: the deletion
    # sweep's kept-member probes are full SAT searches a serial engine
    # resolves faster (on the chip this is not yet measured).
    host_core = problem.n_cons > driver.HOST_CORE_NCONS
    with core.clause_axis(CLAUSE_AXIS):
        res = _sharded_fn(mesh, d.V, d.NCON, d.NV,
                          with_core=not host_core)(pts, budget)
    res = jax.device_get(core.SolveResult(*res))
    if host_core and int(res.outcome) == core.UNSAT:
        cores_, steps_ = driver._host_core_rows(
            [problem], [0], d, budget, np.asarray([int(res.steps)])
        )
        total = int(res.steps) + int(steps_[0])
        res = res._replace(
            core=cores_[0],
            steps=np.int64(total),
            outcome=np.int32(core.RUNNING if total > int(budget)
                             else res.outcome),
        )
    return res


def solve_one_sharded(
    variables: List[Variable],
    mesh: Optional[Mesh] = None,
    max_steps: Optional[int] = None,
) -> List[Variable]:
    """End-to-end single-problem entry with clause sharding: same contract
    as ``Solver.solve()`` — installed variables, or :class:`NotSatisfiable`
    with the minimal constraint core, or :class:`Incomplete`."""
    problem = encode(variables)
    res = solve_sharded(problem, mesh=mesh, max_steps=max_steps)
    if int(res.outcome) == core.SAT:
        return driver._decode_installed(problem, np.asarray(res.installed))
    if int(res.outcome) == core.UNSAT:
        raise driver._decode_core(problem, np.asarray(res.core))
    raise Incomplete()
