"""Group-wise checkpoint/resume for fleet-scale batch solves.

The reference has no persistence — every solve is stateless from scratch
(a fresh engine per ``NewSolver``, reference solve.go:122) and its only
failure-recovery mechanism is operational (leader election + liveness
probes, main.go:51-81).  For a framework whose unit of work is a 10k-problem
fleet batch on an accelerator, that is not enough: a process crash
mid-batch should not void an hour of completed chunks.

This module checkpoints at the natural boundary the chunked driver already
has: groups of ``group`` problems.  Each completed group's results are
written to ``<dir>/group_<i>.npz`` together with a fingerprint of the
problem batch; re-running the same batch with the same directory loads
completed groups and solves only the remainder.  The fingerprint covers
every problem's lowered tensors, so a changed batch never resumes from
stale results (the directory is then ignored for reading and rewritten).

Results round-trip exactly: ``SolveResult`` is a NamedTuple of numpy
arrays, stacked per group on save and unstacked on load.
"""

from __future__ import annotations

import hashlib
import json
import os
import zipfile
from typing import List, Optional, Sequence

import numpy as np

from .. import faults
from ..sat.encode import Problem
from . import core, driver


def batch_fingerprint(problems: Sequence[Problem]) -> str:
    """Stable content hash of a lowered problem batch (order-sensitive)."""
    h = hashlib.blake2b(digest_size=16)
    h.update(str(len(problems)).encode())
    for p in problems:
        for a in (p.clauses, p.card_ids, p.card_n, p.card_act, p.anchors,
                  p.choice_cand, p.var_choices):
            # Shape + dtype delimit each array: identical bytes under a
            # different padding (e.g. clauses [2,2] vs [1,4]) must not
            # collide, and neither may adjacent arrays' concatenation.
            h.update(repr((a.shape, str(a.dtype))).encode())
            h.update(np.ascontiguousarray(a).tobytes())
        h.update(np.int64([p.n_vars, p.n_cons]).tobytes())
    return h.hexdigest()


def _meta_path(ckpt_dir: str) -> str:
    return os.path.join(ckpt_dir, "batch.json")


def _group_path(ckpt_dir: str, i: int) -> str:
    return os.path.join(ckpt_dir, f"group_{i:05d}.npz")


def _pad_to(a: np.ndarray, shape: tuple) -> np.ndarray:
    """Zero-pad ``a`` up to ``shape`` (same rank).  Decode reads masks by
    live index (< n_vars / n_cons), so zero padding is outcome-neutral."""
    if a.shape == shape:
        return a
    out = np.zeros(shape, dtype=a.dtype)
    out[tuple(slice(0, s) for s in a.shape)] = a
    return out


def _save_group(ckpt_dir: str, i: int, results: List[core.SolveResult]) -> None:
    # fault point: a scripted crash here models the real failure this
    # module exists for — the process dying between completed groups
    # (tests/test_checkpoint.py resumes across exactly this).
    faults.inject("checkpoint.save_group")
    arrays = {}
    for f in core.SolveResult._fields:
        vals = [np.asarray(getattr(r, f)) for r in results]
        # Results within one group normally share their bucket's padded
        # dims, but the fault layer can split a failing group or route
        # part of it to the host engine, leaving mixed widths — pad to
        # the widest so the stack (and the resume load) stays exact.
        widest = tuple(max(v.shape[k] for v in vals)
                       for k in range(vals[0].ndim))
        arrays[f] = np.stack([_pad_to(v, widest) for v in vals])
    tmp = _group_path(ckpt_dir, i) + ".tmp"
    with open(tmp, "wb") as fh:
        np.savez(fh, **arrays)
        fh.flush()
        os.fsync(fh.fileno())  # data on disk before the rename points at it
    os.replace(tmp, _group_path(ckpt_dir, i))


def _load_group(ckpt_dir: str, i: int, n: int) -> Optional[List[core.SolveResult]]:
    path = _group_path(ckpt_dir, i)
    if not os.path.exists(path):
        return None
    try:
        with np.load(path) as z:
            arrays = {f: z[f] for f in core.SolveResult._fields}
    except (OSError, ValueError, KeyError, EOFError, zipfile.BadZipFile):
        return None  # torn/stale file: recompute the group
    if arrays["outcome"].shape[0] != n:
        return None
    return [
        core.SolveResult(*[arrays[f][j] for f in core.SolveResult._fields])
        for j in range(n)
    ]


def solve_problems_checkpointed(
    problems: Sequence[Problem],
    ckpt_dir: str,
    group: int = 0,
    max_steps: Optional[int] = None,
    mesh=None,
) -> List[core.SolveResult]:
    """:func:`deppy_tpu.engine.driver.solve_problems` with group-wise
    resume.  ``group`` = problems per checkpoint unit (default: the
    driver's per-dispatch lane cap, so one group ≈ one device dispatch).

    Semantics match ``solve_problems`` exactly — per-problem results in
    input order; groups are solved independently, which also bounds the
    padded shape blowup like the driver's size-class bucketing (a group
    never pads to a straggler outside it)."""
    if group <= 0:
        group = driver.MAX_LANES
    os.makedirs(ckpt_dir, exist_ok=True)
    fp = batch_fingerprint(problems)
    # max_steps is part of the key: results computed under a different
    # step budget (e.g. Incomplete at a tiny cap) must not resume.
    meta = {"fingerprint": fp, "n": len(problems), "group": group,
            "max_steps": max_steps}
    meta_ok = False
    try:
        with open(_meta_path(ckpt_dir)) as fh:
            meta_ok = json.load(fh) == meta
    except (OSError, ValueError):
        pass
    if not meta_ok:
        # Different batch (or fresh dir): drop stale groups, write meta.
        for name in os.listdir(ckpt_dir):
            if name.startswith("group_") and name.endswith(".npz"):
                os.unlink(os.path.join(ckpt_dir, name))
        tmp = _meta_path(ckpt_dir) + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(meta, fh)
        os.replace(tmp, _meta_path(ckpt_dir))

    out: List[Optional[core.SolveResult]] = [None] * len(problems)
    resumed = 0
    # ambient_deadline here (not just inside each driver call) so the
    # per-group persistence check below sees the env-configured batch
    # deadline too, not only a caller-installed scope.
    with faults.ambient_deadline() as dl:
        for gi, lo in enumerate(range(0, len(problems), group)):
            chunk = list(problems[lo: lo + group])
            cached = (_load_group(ckpt_dir, gi, len(chunk))
                      if meta_ok else None)
            if cached is None:
                cached = driver.solve_problems(chunk, max_steps=max_steps,
                                               mesh=mesh)
                # A group computed after the batch deadline expired may
                # be deadline-degraded (Incomplete with zero work done)
                # — never persist it: the meta key covers the step
                # budget but not the wall clock, and a resume without
                # the deadline must re-solve these groups, not inherit
                # their degradation.
                if dl is None or not dl.expired():
                    _save_group(ckpt_dir, gi, cached)
            else:
                resumed += len(chunk)
            out[lo: lo + len(chunk)] = cached
    if resumed:
        import sys

        print(f"[checkpoint] resumed {resumed}/{len(problems)} problems "
              f"from {ckpt_dir}", file=sys.stderr)
    return out  # type: ignore[return-value]
