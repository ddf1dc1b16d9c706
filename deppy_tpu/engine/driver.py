"""Host-side driver: pad, batch, dispatch, decode.

Bridges the symbolic layer (:class:`deppy_tpu.sat.encode.Problem`) and the
tensor engine (:mod:`deppy_tpu.engine.core`):

  * pads each lowered problem's tensors to the batch's common shapes,
    bucketing every dimension up to a power of two so the number of
    distinct compiled programs stays bounded (the padding-economics policy
    from SURVEY.md §7.3);
  * stacks problems along a leading batch axis and dispatches one jitted,
    vmapped solve for the whole batch;
  * decodes outcome masks back to installed variables, and active-constraint
    masks back to :class:`NotSatisfiable` unsat cores, exactly like the
    reference maps lits back through LitMapping
    (/root/reference/pkg/sat/lit_mapping.go:176-207).

Batch entries behind a padded batch dimension are empty problems (zero
variables) which solve trivially and are dropped on decode.
"""

from __future__ import annotations

import functools as _functools
import os
import threading as _threading
import time as _time
from typing import List, Optional, Sequence, Union

import jax
import numpy as np

from .. import config, faults, telemetry
from .. import profile as _profile
from .. import size_classes as _size_classes
from ..analysis import compileguard
from ..sat.constraints import Variable
from ..sat.encode import Problem, encode
from ..sat.errors import Incomplete, InternalSolverError, NotSatisfiable
from ..utils.platform_env import assert_env_platform
from . import core

# Library-level platform guard: importing the tensor engine is the first
# step of every device code path (Solver(backend="tpu"), BatchResolver,
# clause sharding), so a ``JAX_PLATFORMS`` set after jax was imported
# still decides which backend this process initializes.  Process entry
# points also call this via apply_platform_env(); this covers plain
# library imports.
assert_env_platform()

# Default step budget when the caller sets none: generous enough for any
# realistic catalog problem, small enough that a pathological instance
# yields Incomplete rather than an unbounded device loop (the reference
# quirk of unhonored cancellation — SURVEY.md §3.1 — done better).
DEFAULT_MAX_STEPS = 1 << 24


# ----------------------------------------------------------------- telemetry
#
# Span/counter/report instrumentation for the whole dispatch pipeline
# (ISSUE 1, SURVEY.md §5): pad/pack economics, device transfer, per-chunk
# dispatch, escalation staging, and host-fallback routing all record into
# the default telemetry registry (and into the thread's active SolveReport
# when one exists).  Everything here is a handful of perf_counter calls
# and dict updates per BATCH — nowhere near the per-lane hot path.


def _telem_record_pad(problems, total: int, d: _Dims, n_chunks: int,
                      dur_s: float) -> None:
    """Record one bucket's padding economics: live vs padded lanes, and
    live vs padded clause-matrix cells (the dominant tensor)."""
    reg = telemetry.default_registry()
    n = len(problems)
    live_cells = int(sum(p.clauses.size for p in problems))
    pad_cells = int(total) * d.C * d.K
    reg.histogram(
        "deppy_batch_fill_ratio",
        "Live problems per dispatched batch lane (1.0 = no lane padding).",
        buckets=telemetry.RATIO_BUCKETS,
    ).observe(n / total if total else 1.0)
    reg.counter("deppy_pad_cells_total",
                "Clause-matrix cells dispatched, including padding."
                ).inc(pad_cells)
    reg.counter("deppy_live_cells_total",
                "Clause-matrix cells carrying live problem data."
                ).inc(live_cells)
    reg.counter("deppy_chunks_total",
                "Device dispatch chunks issued.").inc(n_chunks)
    rep = telemetry.current_report()
    if rep is not None:
        rep.record_batch(live_lanes=n, batch_lanes=int(total),
                         live_cells=live_cells, pad_cells=pad_cells,
                         n_chunks=n_chunks)
        rep.add_wall("pad_pack", dur_s)


def _bucket(n: int, minimum: int = 1) -> int:
    """Round up to the next power of two (≥ minimum) — delegated to the
    shared size-class module so class arithmetic and live padding use
    one quantum."""
    return _size_classes.bucket(n, minimum)


def _pad2(a: np.ndarray, rows: int, cols: int, fill: int) -> np.ndarray:
    out = np.full((rows, cols), fill, dtype=np.int32)
    r, c = a.shape
    out[:r, :c] = a
    return out


def _pad1(a: np.ndarray, n: int, fill: int) -> np.ndarray:
    out = np.full((n,), fill, dtype=np.int32)
    out[: a.shape[0]] = a
    return out


class _Dims:
    """Common padded dimensions for a batch of problems."""

    def __init__(self, problems: Sequence[Problem], batch: int, batch_multiple: int = 1):
        self.C = _bucket(max((p.clauses.shape[0] for p in problems), default=1))
        self.K = _bucket(max((p.clauses.shape[1] for p in problems), default=1), 2)
        self.NA = _bucket(max((p.card_ids.shape[0] for p in problems), default=1))
        self.M = _bucket(max((p.card_ids.shape[1] for p in problems), default=1))
        self.A = _bucket(max((p.anchors.shape[0] for p in problems), default=1))
        self.NC = _bucket(max((p.choice_cand.shape[0] for p in problems), default=1))
        self.Kc = _bucket(max((p.choice_cand.shape[1] for p in problems), default=1))
        self.NV = _bucket(max((p.n_vars for p in problems), default=1))
        self.W = _bucket(max((p.var_choices.shape[1] for p in problems), default=1))
        self.NCON = _bucket(max((p.n_cons for p in problems), default=1))
        self.V = self.NV + self.NCON
        self.Wv = -(-self.V // core.WORD)  # bitplane words per variable set
        self.Wr = -(-self.NV // core.WORD)  # reduced (problem-var-only) words
        # Batch padded to a power of two AND a multiple of the mesh size so
        # the batch axis shards evenly.
        b = _bucket(batch)
        if b % batch_multiple:
            b *= batch_multiple // np.gcd(b, batch_multiple)
        self.B = b
        # Clause-bank widths (ISSUE 12) are data-dependent (max literal
        # occurrence / card membership over the batch) and only the
        # watched impl reads them — computed lazily so every other
        # dispatch skips the counting pass.
        self._problems = list(problems)
        self._Ob: Optional[int] = None
        self._Oc: Optional[int] = None

    @property
    def Ob(self) -> int:
        """Bucketed literal-occurrence width of the watched clause bank."""
        if self._Ob is None:
            from . import clause_bank

            self._Ob = _bucket(max(
                (clause_bank.max_occurrence(p.clauses)
                 for p in self._problems), default=0))
        return self._Ob

    @property
    def Oc(self) -> int:
        """Bucketed member→AtMost-row width of the watched bank."""
        if self._Oc is None:
            from . import clause_bank

            self._Oc = _bucket(max(
                (clause_bank.max_card_membership(p.card_ids)
                 for p in self._problems), default=0))
        return self._Oc


def _pack_planes(clauses: np.ndarray, Wv: int) -> tuple:
    """Signed clause matrix → (pos, neg) packed int32 bitplanes."""
    C = clauses.shape[0]
    W = core.WORD
    pos = np.zeros((C, Wv), np.uint32)
    neg = np.zeros((C, Wv), np.uint32)
    for plane, mask in ((pos, clauses > 0), (neg, clauses < 0)):
        r, c = np.nonzero(mask)
        v = np.abs(clauses[r, c]).astype(np.int64) - 1
        np.bitwise_or.at(plane, (r, v // W), np.uint32(1) << np.uint32(v % W))
    return pos.view(np.int32), neg.view(np.int32)


def _pack_index_rows(rows: np.ndarray, Wv: int) -> np.ndarray:
    """0-based index matrix (-1 pad) → packed int32 membership bitplanes."""
    W = core.WORD
    out = np.zeros((rows.shape[0], Wv), np.uint32)
    r, c = np.nonzero(rows >= 0)
    v = rows[r, c].astype(np.int64)
    np.bitwise_or.at(out, (r, v // W), np.uint32(1) << np.uint32(v % W))
    return out.view(np.int32)


def pad_problem(p: Problem, d: _Dims, pack: bool = True) -> core.ProblemTensors:
    """Pad one lowered problem to the batch dims (numpy, host-side).

    ``pack=False`` fills every bitplane field with 1-word dummies: the
    dispatch paths derive planes on device (:func:`core.derive_planes`),
    so host packing time and plane upload bytes are spent only by callers
    that ask for them (single-problem tests, the graft entry)."""
    clauses = _pad2(p.clauses, d.C, d.K, 0)
    card_ids = _pad2(p.card_ids, d.NA, d.M, -1)
    card_act = _pad1(p.card_act, d.NA, -1)
    if pack:
        pos_bits, neg_bits = _pack_planes(clauses, d.Wv)
        member_bits = _pack_index_rows(card_ids, d.Wv)
        act_bits = _pack_index_rows(card_act[:, None], d.Wv)
    else:
        pos_bits = np.zeros((d.C, 1), np.int32)
        neg_bits = np.zeros((d.C, 1), np.int32)
        member_bits = np.zeros((d.NA, 1), np.int32)
        act_bits = np.zeros((d.NA, 1), np.int32)
    # Reduced planes: drop activation-variable literals (constant TRUE in
    # the search/minimization phases, so their ¬act literals fold away).
    # Only the bits impl reads them — other impls get 1-word dummies so
    # neither packing time nor upload bytes are spent on them.
    if pack and core.phases_reduced():
        clauses_r = np.where(np.abs(clauses) <= p.n_vars, clauses, 0)
        pos_bits_r, neg_bits_r = _pack_planes(clauses_r, d.Wr)
        member_r = _pack_index_rows(card_ids, d.Wr)
    else:
        pos_bits_r = np.zeros((d.C, 1), np.int32)
        neg_bits_r = np.zeros((d.C, 1), np.int32)
        member_r = np.zeros((d.NA, 1), np.int32)
    if pack and d.Ob <= _bank_cap(d):
        # Clause banks ride every packed single-problem build (tests
        # flip impls AFTER padding via set_bcp_impl, so the bank must
        # already be there); the dispatch paths (pack=False) derive
        # them on device only when the watched impl is selected.  The
        # size-class OCC cap applies here exactly as on the device
        # path: past it every impl runs dense rounds, so building (and
        # — on the clause-sharded path — replicating) a huge bank a
        # popular literal inflated would be pure dead weight.
        from . import clause_bank

        occ_pos, occ_neg = clause_bank.occ_from_clauses_np(
            clauses, d.V, d.Ob)
        occ_pos_r, occ_neg_r = clause_bank.occ_from_clauses_np(
            clauses, d.NV, d.Ob, n_vars=p.n_vars)
        card_occ = clause_bank.card_occ_np(card_ids, d.NV, d.Oc)
    else:
        occ_pos = occ_neg = np.full((1, 1), -1, np.int32)
        occ_pos_r = occ_neg_r = np.full((1, 1), -1, np.int32)
        card_occ = np.full((1, 1), -1, np.int32)
    return core.ProblemTensors(
        clauses=clauses,
        card_ids=card_ids,
        card_n=_pad1(p.card_n, d.NA, 0),
        card_act=card_act,
        anchors=_pad1(p.anchors, d.A, -1),
        choice_cand=_pad2(p.choice_cand, d.NC, d.Kc, -1),
        var_choices=_pad2(p.var_choices, d.NV, d.W, -1),
        n_vars=np.int32(p.n_vars),
        n_cons=np.int32(p.n_cons),
        pos_bits=pos_bits,
        neg_bits=neg_bits,
        card_member_bits=member_bits,
        card_act_bits=act_bits,
        pos_bits_r=pos_bits_r,
        neg_bits_r=neg_bits_r,
        card_member_bits_r=member_r,
        card_valid=(card_act >= 0).astype(np.int32),
        occ_pos=occ_pos,
        occ_neg=occ_neg,
        occ_pos_r=occ_pos_r,
        occ_neg_r=occ_neg_r,
        card_occ=card_occ,
    )


def _pack_planes_batch(clauses: np.ndarray, Wv: int) -> tuple:
    """Batched signed clause matrices [B, C, K] → (pos, neg) packed int32
    bitplanes [B, C, Wv].  Vectorized over the whole batch: per-word
    OR-reductions instead of the scalar ``np.bitwise_or.at`` scatter."""
    mask = clauses != 0
    v = np.where(mask, np.abs(clauses) - 1, 0).astype(np.int64)
    word = v >> 5
    shifted = np.left_shift(np.uint32(1), (v & 31).astype(np.uint32))
    pos_sh = np.where(clauses > 0, shifted, np.uint32(0))
    neg_sh = np.where(clauses < 0, shifted, np.uint32(0))
    B, C, _ = clauses.shape
    pos = np.zeros((B, C, Wv), np.uint32)
    neg = np.zeros((B, C, Wv), np.uint32)
    for w in range(Wv):
        m = word == w
        pos[:, :, w] = np.bitwise_or.reduce(np.where(m, pos_sh, 0), axis=2)
        neg[:, :, w] = np.bitwise_or.reduce(np.where(m, neg_sh, 0), axis=2)
    return pos.view(np.int32), neg.view(np.int32)


def _pack_index_batch(rows: np.ndarray, Wv: int) -> np.ndarray:
    """Batched 0-based index matrices [B, R, M] (-1 pad) → packed int32
    membership bitplanes [B, R, Wv]."""
    mask = rows >= 0
    v = np.where(mask, rows, 0).astype(np.int64)
    word = v >> 5
    shifted = np.where(
        mask, np.left_shift(np.uint32(1), (v & 31).astype(np.uint32)),
        np.uint32(0),
    )
    B, R, _ = rows.shape
    out = np.zeros((B, R, Wv), np.uint32)
    for w in range(Wv):
        out[:, :, w] = np.bitwise_or.reduce(np.where(word == w, shifted, 0), axis=2)
    return out.view(np.int32)


def pad_stack(problems: Sequence[Problem], d: _Dims, total: int,
              pack: bool = True) -> core.ProblemTensors:
    """Pad and stack a whole problem list to [total, ...] batch tensors in
    one vectorized pass (trailing lanes beyond ``len(problems)`` are empty
    problems).  Equivalent to ``_stack([pad_problem(p, d) ...])`` but ~10×
    faster on fleet-scale batches — per-problem work is one slice
    assignment per field.  ``pack=False`` (what the dispatch paths use)
    skips host bit-packing entirely: plane fields come back as 1-word
    dummies and the device derives the real planes from the compact
    clause tensors (:func:`core.derive_planes`), which both removes the
    dominant host cost of a dispatch and ships fewer bytes."""
    n = len(problems)
    clauses = np.zeros((total, d.C, d.K), np.int32)
    card_ids = np.full((total, d.NA, d.M), -1, np.int32)
    card_n = np.zeros((total, d.NA), np.int32)
    card_act = np.full((total, d.NA), -1, np.int32)
    anchors = np.full((total, d.A), -1, np.int32)
    choice_cand = np.full((total, d.NC, d.Kc), -1, np.int32)
    var_choices = np.full((total, d.NV, d.W), -1, np.int32)
    n_vars = np.zeros(total, np.int32)
    n_cons = np.zeros(total, np.int32)
    for i, p in enumerate(problems):
        c = p.clauses
        clauses[i, : c.shape[0], : c.shape[1]] = c
        ci = p.card_ids
        card_ids[i, : ci.shape[0], : ci.shape[1]] = ci
        card_n[i, : p.card_n.shape[0]] = p.card_n
        card_act[i, : p.card_act.shape[0]] = p.card_act
        anchors[i, : p.anchors.shape[0]] = p.anchors
        cc = p.choice_cand
        choice_cand[i, : cc.shape[0], : cc.shape[1]] = cc
        vc = p.var_choices
        var_choices[i, : vc.shape[0], : vc.shape[1]] = vc
        n_vars[i] = p.n_vars
        n_cons[i] = p.n_cons
    if pack:
        pos_bits, neg_bits = _pack_planes_batch(clauses, d.Wv)
        member_bits = _pack_index_batch(card_ids, d.Wv)
        act_bits = _pack_index_batch(card_act[:, :, None], d.Wv)
    else:
        pos_bits = np.zeros((total, d.C, 1), np.int32)
        neg_bits = np.zeros((total, d.C, 1), np.int32)
        member_bits = np.zeros((total, d.NA, 1), np.int32)
        act_bits = np.zeros((total, d.NA, 1), np.int32)
    if pack and core.phases_reduced():
        clauses_r = np.where(
            np.abs(clauses) <= n_vars[:, None, None], clauses, 0
        )
        pos_bits_r, neg_bits_r = _pack_planes_batch(clauses_r, d.Wr)
        member_r = _pack_index_batch(card_ids, d.Wr)
    else:
        pos_bits_r = np.zeros((total, d.C, 1), np.int32)
        neg_bits_r = np.zeros((total, d.C, 1), np.int32)
        member_r = np.zeros((total, d.NA, 1), np.int32)
    if pack and d.Ob <= _bank_cap(d):
        from . import clause_bank

        occ_pos = np.full((total, d.V, d.Ob), -1, np.int32)
        occ_neg = np.full((total, d.V, d.Ob), -1, np.int32)
        occ_pos_r = np.full((total, d.NV, d.Ob), -1, np.int32)
        occ_neg_r = np.full((total, d.NV, d.Ob), -1, np.int32)
        card_occ = np.full((total, d.NV, d.Oc), -1, np.int32)
        for i, p in enumerate(problems):
            occ_pos[i], occ_neg[i] = clause_bank.occ_from_clauses_np(
                clauses[i], d.V, d.Ob)
            occ_pos_r[i], occ_neg_r[i] = clause_bank.occ_from_clauses_np(
                clauses[i], d.NV, d.Ob, n_vars=int(p.n_vars))
            card_occ[i] = clause_bank.card_occ_np(card_ids[i], d.NV, d.Oc)
    else:
        occ_pos = occ_neg = np.full((total, 1, 1), -1, np.int32)
        occ_pos_r = occ_neg_r = np.full((total, 1, 1), -1, np.int32)
        card_occ = np.full((total, 1, 1), -1, np.int32)
    return core.ProblemTensors(
        clauses=clauses,
        card_ids=card_ids,
        card_n=card_n,
        card_act=card_act,
        anchors=anchors,
        choice_cand=choice_cand,
        var_choices=var_choices,
        n_vars=n_vars,
        n_cons=n_cons,
        pos_bits=pos_bits,
        neg_bits=neg_bits,
        card_member_bits=member_bits,
        card_act_bits=act_bits,
        pos_bits_r=pos_bits_r,
        neg_bits_r=neg_bits_r,
        card_member_bits_r=member_r,
        card_valid=(card_act >= 0).astype(np.int32),
        occ_pos=occ_pos,
        occ_neg=occ_neg,
        occ_pos_r=occ_pos_r,
        occ_neg_r=occ_neg_r,
        card_occ=card_occ,
    )


# Compact fields a dispatch uploads; every bitplane field is derived from
# them on device (core.derive_planes), so no plane bytes ever cross
# host→device and no host time is spent packing.
_COMPACT_FIELDS = (
    "clauses", "card_ids", "card_n", "card_act", "anchors", "choice_cand",
    "var_choices", "n_vars", "n_cons", "card_valid",
)


@_functools.lru_cache(maxsize=128)
def _planes_fn(Wv: int, Wr: int, red: bool, full: bool):
    return jax.jit(compileguard.observe(
        "driver.planes_fn",
        _functools.partial(core.derive_planes, Wv=Wv, Wr=Wr, red=red,
                           full=full),
        static=(Wv, Wr, red, full),
    ))


# Watched-bank occurrence-width cap (0 = the dispatch's size-class OCC
# cap from the shared ladder): a batch whose max per-literal clause
# count exceeds the cap would pay an occ table of V x Ob cells mostly
# for one popular literal — those dispatches ship dummy banks and the
# compiled program statically falls back to the dense rounds.
BANK_OCC_CAP = int(config.env_raw("DEPPY_TPU_BANK_OCC_CAP", "0"))


@_functools.lru_cache(maxsize=128)
def _bank_fn(V: int, NV: int, Ob: int, Oc: int, red: bool, full: bool):
    from . import clause_bank

    return jax.jit(compileguard.observe(
        "driver.bank_fn",
        _functools.partial(clause_bank.derive_banks, V=V, NV=NV, Ob=Ob,
                           Oc=Oc, red=red, full=full),
        static=(V, NV, Ob, Oc, red, full),
    ))


def _bank_cap(d: "_Dims") -> int:
    if BANK_OCC_CAP > 0:
        return BANK_OCC_CAP
    name = _size_classes.class_of_cost((d.C + 2 * d.NV) * d.Wv)
    return _size_classes.occ_cap(name)


def _derive_banks(pts: core.ProblemTensors, d: "_Dims", red: bool,
                  full: bool) -> core.ProblemTensors:
    """Replace the dummy clause-bank fields with device-derived banks
    (watched impl only; reads the chunk's device-resident compact
    tensors).  A batch whose occurrence width exceeds its cap keeps the
    dummies — the watched program detects them statically and runs the
    dense rounds instead."""
    if d.Ob > _bank_cap(d):
        return pts
    occ_pos, occ_neg, occ_pos_r, occ_neg_r, card_occ = _bank_fn(
        d.V, d.NV, d.Ob, d.Oc, red, full
    )(pts.clauses, pts.card_ids, pts.n_vars)
    return pts._replace(occ_pos=occ_pos, occ_neg=occ_neg,
                        occ_pos_r=occ_pos_r, occ_neg_r=occ_neg_r,
                        card_occ=card_occ)


def _derive_planes(pts: core.ProblemTensors, d: _Dims,
                   full: Optional[bool] = None,
                   red: Optional[bool] = None) -> core.ProblemTensors:
    """Replace the (dummy) plane fields with device-derived planes.

    ``full=None`` materializes the full-space planes only when the
    selected impl's search/minimization phases read them — under the
    default bits impl those run in the reduced space, so SAT-dominated
    chunks never hold full planes resident; the unsat-core dispatches ask
    for ``full=True`` explicitly (:func:`_derive_full`).

    The gather impl never reads plane *contents* (its BCP walks the
    compact clause matrices), but the packed DPLL state is still sized by
    ``pos_bits.shape[-1]`` — it gets single-row zero planes carrying only
    that width."""
    if core._resolved_impl() == "gather":
        B = np.shape(pts.clauses)[0]
        z = np.zeros((B, 1, d.Wv), np.int32)
        return pts._replace(
            pos_bits=z, neg_bits=z, card_member_bits=z, card_act_bits=z,
        )
    if full is None:
        full = not core.phases_reduced()
    if red is None:
        red = core.phases_reduced()
    pos, neg, mem, act, pos_r, neg_r, mem_r = _planes_fn(
        d.Wv, d.Wr, red, full
    )(pts.clauses, pts.card_ids, pts.card_act, pts.n_vars)
    pts = pts._replace(
        pos_bits=pos, neg_bits=neg, card_member_bits=mem, card_act_bits=act,
        pos_bits_r=pos_r, neg_bits_r=neg_r, card_member_bits_r=mem_r,
    )
    if core._resolved_impl() == "watched":
        pts = _derive_banks(pts, d, red, full)
    return pts


def _derive_full(pts: core.ProblemTensors, d: _Dims) -> core.ProblemTensors:
    """Add full-space planes to an already-resident chunk (unsat-core
    phase inputs; reads the chunk's device-resident compact tensors, so
    nothing re-crosses the host boundary)."""
    pos, neg, mem, act, _, _, _ = _planes_fn(d.Wv, d.Wr, False, True)(
        pts.clauses, pts.card_ids, pts.card_act, pts.n_vars
    )
    pts = pts._replace(
        pos_bits=pos, neg_bits=neg, card_member_bits=mem, card_act_bits=act,
    )
    if core._resolved_impl() == "watched" and d.Ob <= _bank_cap(d):
        # Full-space banks only — the chunk's reduced banks stay.
        occ_pos, occ_neg, _, _, card_occ = _bank_fn(
            d.V, d.NV, d.Ob, d.Oc, False, True
        )(pts.clauses, pts.card_ids, pts.n_vars)
        pts = pts._replace(occ_pos=occ_pos, occ_neg=occ_neg,
                           card_occ=card_occ)
    return pts


_EMPTY_PROBLEM: Optional[Problem] = None


def _empty_problem() -> Problem:
    global _EMPTY_PROBLEM
    if _EMPTY_PROBLEM is None:
        _EMPTY_PROBLEM = encode([])
    return _EMPTY_PROBLEM


def _stack(pts: Sequence[core.ProblemTensors]) -> core.ProblemTensors:
    return core.ProblemTensors(
        *[np.stack([getattr(p, f) for p in pts]) for f in core.ProblemTensors._fields]
    )


def _budget(max_steps: Optional[int]) -> np.int32:
    return np.int32(min(max_steps if max_steps is not None else DEFAULT_MAX_STEPS,
                        np.iinfo(np.int32).max - 1))


def _to_device(tree, mesh):
    if mesh is None:
        return tree
    from ..parallel.mesh import shard_batch

    return shard_batch(mesh, tree)


def _put_compact(pts: core.ProblemTensors) -> core.ProblemTensors:
    """device_put the compact fields; plane dummies stay host-side."""
    return core.ProblemTensors(**{
        f: (jax.device_put(getattr(pts, f)) if f in _COMPACT_FIELDS
            else getattr(pts, f))
        for f in core.ProblemTensors._fields
    })


def _put_chunk(pts_chunk: core.ProblemTensors, mesh, d: _Dims,
               full: Optional[bool] = None,
               red: Optional[bool] = None) -> core.ProblemTensors:
    """Upload one chunk's compact tensors explicitly (so later phases
    reuse the device-resident buffers instead of re-transferring) and
    derive its bitplanes on device.  Under a mesh the compact fields are
    sharded over the batch axis first; the derived planes inherit that
    sharding (elementwise build)."""
    if mesh is not None:
        return _derive_planes(_to_device(pts_chunk, mesh), d, full, red)
    return _derive_planes(_put_compact(pts_chunk), d, full, red)


def _pad_group(k: int, mesh) -> int:
    """Padded batch size for a compacted phase group: power of two and a
    multiple of the mesh size."""
    b = _bucket(k)
    m = mesh.size if mesh is not None else 1
    if b % m:
        b *= m // np.gcd(b, m)
    return b


def _gather_rows(pts: core.ProblemTensors, idx: np.ndarray, B: int,
                 empty_row: core.ProblemTensors) -> core.ProblemTensors:
    """Compact batch rows ``idx`` out of a stacked pytree, padding to ``B``
    lanes with the empty problem."""
    pad = B - idx.size
    fields = []
    for f in core.ProblemTensors._fields:
        a = getattr(pts, f)[idx]
        e = getattr(empty_row, f)
        if pad:
            a = np.concatenate(
                [a, np.broadcast_to(e[None], (pad,) + e.shape).copy()]
            )
        fields.append(a)
    return core.ProblemTensors(*fields)


def _pad_rows(a: np.ndarray, B: int, fill=0) -> np.ndarray:
    pad = B - a.shape[0]
    if not pad:
        return a
    return np.concatenate(
        [a, np.full((pad,) + a.shape[1:], fill, dtype=a.dtype)]
    )


# Core extraction for problems above this many applied constraints routes
# to the host spec engine instead of the device deletion loop (monolith
# and compacted-split paths; the en-gated UNSAT-heavy fleet path stays on
# device, where batch parallelism amortizes the sweep).  Two measured
# reasons: the sweep's cost is dominated by kept-member probes — full SAT
# searches — which the serial host engine resolves faster than a lockstep
# device program at giant sizes (2.1s vs 7.7s at 1.7k constraints, CPU
# XLA), and it bounds the length of one device program.  The threshold
# is not yet measured on the chip.
# Results are bit-identical: HostEngine.unsat_core_mask IS the spec the
# device loop reproduces.
HOST_CORE_NCONS = int(config.env_raw("DEPPY_TPU_HOST_CORE_NCONS", "768"))


# Lane width of one speculative-probe dispatch (stage 1 below).  Bounded
# like MAX_LANES; not yet measured on the chip.
PROBE_LANES = int(config.env_raw("DEPPY_TPU_PROBE_LANES", "512"))

# Speculative-core policy.  Measured on CPU XLA it LOSES to the host
# spec sweep (27.6s vs 2.1s on the 1.7k-constraint giant catalog): the
# vmapped probe fixpoint runs max-over-lanes propagation rounds, and one
# deep-chain lane drags 512 lanes × full clause planes through ~dozens
# of rounds on one core.  The accelerator bet is bandwidth — the same
# traffic is a few hundred MB of HBM reads — but that bet has no chip
# measurement.  So "auto" resolves to OFF unless the measured-defaults
# registry records a win for the running backend.  "1"/"0" force it
# on/off (tests force "1" on CPU).
SPEC_CORE = config.env_raw("DEPPY_TPU_SPEC_CORE", "auto")

# Per-dispatch step budget for the speculative sweep's SEARCH stages
# (stage-2 DPLL lanes and the certifying probe).  The caller's remaining
# budget can be millions of steps, and a 512-lane lockstep program
# running a deep SAT search that long would hold the device for
# minutes in one program.  Exceeding the cap is harmless
# for correctness: capped-out lanes read as RUNNING and the sweep
# returns None, falling back to the host spec sweep with the steps
# spent charged against the budget.
SPEC_CORE_CAP = int(config.env_raw("DEPPY_TPU_SPEC_CORE_CAP", str(1 << 15)))


def _spec_core_enabled() -> bool:
    if SPEC_CORE == "1":
        return True
    if SPEC_CORE == "auto":
        # Measured default per backend: the revalidation ladder's stage
        # H records the full-scale A/B verdict ('on' only when the
        # speculative sweep agreed with the host sweep AND won on time)
        # in the measured-defaults registry; with no measured row the
        # conservative answer stays OFF (see SPEC_CORE above).
        return core.measured_default("spec_core") == "on"
    return False


def _speculative_core_mask(problem, remaining: int):
    """Deletion-sweep shortcut for ONE giant problem: run all n_cons
    single-drop probes as vmap lanes of a batched device program instead
    of n_cons sequential host solves, then certify the result with one
    probe.  Returns (core_mask[n_cons] or None, steps_spent) — on None
    the caller falls back to the host spec sweep (with the leftover
    budget), so correctness never depends on this path succeeding.

    Exactness (trust-but-verify): let K = {j : SAT without j} over the
    INITIAL full active set.  SAT(all\\{j}) implies SAT of every subset,
    so the spec's in-order sweep keeps each j in K at its turn, whatever
    was dropped before — K is a subset of the spec's final core.  If the
    verification probe shows K itself UNSAT, then at every j outside K
    the spec's remaining active set contains K, hence stays UNSAT without
    j, hence the spec drops j — its final core is exactly K.  If K probes
    SAT (overlapping/disjoint cores: order decides), this shortcut proves
    nothing and returns None.  Probes here and in the spec agree
    literally: same base assignment, anchors not assumed
    (core.probe_phase is core_phase's own trial probe).

    Steps: 1 per stage-1 fixpoint probe (the host's near-free probes also
    count ~1) plus the DPLL steps of stage-2 and verification lanes."""
    n = int(problem.n_cons)
    if n == 0 or remaining <= 0:
        return None, 0
    d = _Dims([problem], 1)
    pts1 = _put_compact(pad_stack([problem], d, 1, pack=False))
    pts1 = _derive_planes(pts1, d, full=True, red=False)
    pt = jax.tree_util.tree_map(lambda a: a[0], pts1)
    steps = 0

    # Stage 1: one propagation fixpoint per single-drop probe; a conflict
    # proves that probe UNSAT with zero search (the common case on an
    # overconstrained catalog).
    fp = core.batched_probe_fixpoint(d.V, d.NCON)
    P = min(PROBE_LANES, _bucket(n))
    conflicts = []
    for lo in range(0, n, P):
        drop = np.arange(lo, lo + P, dtype=np.int32)  # tail lanes: j >= n
        conflicts.append(fp(pt, drop))
    conflict = np.concatenate(jax.device_get(conflicts))[:n]
    steps += n

    # Stage 2: finish undetermined probes (core members' SAT probes plus
    # any UNSAT that needs actual search) with full DPLL lanes.
    pend = np.nonzero(~conflict)[0]
    status = np.full(n, core.UNSAT, np.int32)
    if pend.size:
        if pend.size > max(n // 2, PROBE_LANES):
            return None, steps  # propagation settled little: wrong case
        pb = core.batched_probe(d.V, d.NCON, d.NV)
        Q = min(_bucket(min(pend.size, PROBE_LANES)), PROBE_LANES)
        idx32 = np.arange(d.NCON, dtype=np.int32)
        for lo in range(0, pend.size, Q):
            rows = pend[lo: lo + Q]
            trials = (idx32[None, :] < n) & (idx32[None, :] != rows[:, None])
            # Pad lanes probe the EMPTY active set (immediately SAT) — an
            # all-active pad would re-prove the whole problem UNSAT under
            # lockstep, stalling the real lanes.
            trials = np.concatenate(
                [trials, np.zeros((Q - len(rows), d.NCON), bool)])
            st, sp = jax.device_get(
                pb(pt, trials, np.int32(min(remaining, SPEC_CORE_CAP))))
            status[rows] = st[: len(rows)]
            steps += int(sp[: len(rows)].sum())
            if steps > remaining:
                # Budget already blown: don't dispatch chunks whose
                # results the post-loop check would discard anyway.
                return None, steps
        if (status[pend] == core.RUNNING).any():
            # Budget pressure: let the spec sweep own the Incomplete call.
            return None, steps
    else:
        Q = 1

    keep = status == core.SAT
    if not keep.any():
        return None, steps  # every single drop stays UNSAT: order decides

    # Verification: K UNSAT ⇒ the spec sweep's core is exactly K.  Padded
    # to stage 2's lane width so the same compiled program is reused (pad
    # lanes probe the empty set, like stage 2's).
    pb = core.batched_probe(d.V, d.NCON, d.NV)
    vt = np.zeros((Q, d.NCON), bool)
    vt[0, :n] = keep
    st, sp = jax.device_get(
        pb(pt, vt, np.int32(min(remaining, SPEC_CORE_CAP))))
    steps += int(sp[0])
    if int(st[0]) != core.UNSAT or steps > remaining:
        return None, steps
    return keep, steps


def _host_core_rows(problems, idx, d: _Dims, budget, spent,
                    allow_device: bool = False) -> tuple:
    """Host-engine core extraction for the given batch rows.  Returns
    (cores [len(idx), NCON] bool, steps [len(idx)]) — steps to ADD to the
    lane's device count.  Each lane's engine gets only the budget left
    after its device solve (``spent``), so the combined count trips the
    caller's ``steps > budget`` Incomplete check exactly like the device
    core phase, which continues counting from the search's total against
    the same budget — the routing stays outcome-invisible under tight
    budgets, not just generous ones.

    This function is the single source of the routing's steps/outcome
    convention (remaining-budget cap, one-tick-over on exhaustion); its
    three callers — _solve_monolith, _solve_split, and
    parallel.clause_shard.solve_sharded — each add the returned steps to
    the lane's device count and flip the lane to RUNNING when the total
    exceeds the budget.  Change all three together.

    ``allow_device`` (only the monolith caller, single-device runs — the
    split path keeps the host sweep that overlaps its in-flight device
    dispatches) first tries :func:`_speculative_core_mask` — the whole
    sweep as one batched device program plus a certifying probe,
    bit-identical when it succeeds — and falls back to the host spec
    sweep on any ambiguity, with the speculative attempt's steps charged
    against the budget."""
    from ..sat.host import HostEngine

    # The "silent host fallback" made loud: every row routed here counts.
    reg = telemetry.default_registry()
    reg.counter(
        "deppy_host_fallback_rows_total",
        "UNSAT rows whose core extraction routed to the host spec engine.",
    ).inc(len(idx))
    _rep = telemetry.current_report()
    if _rep is not None:
        _rep.host_fallback_rows += len(idx)
    with reg.span("driver.core_stage", lanes=len(idx), host=True):
        cores = np.zeros((len(idx), d.NCON), bool)
        steps = np.zeros(len(idx), np.int64)
        for r, i in enumerate(idx):
            remaining = int(budget) - int(spent[r])
            if remaining <= 0:
                # Already over: one tick keeps the lane RUNNING.
                steps[r] = 1
                continue
            spec_steps = 0
            if allow_device and _spec_core_enabled():
                mask, spec_steps = _speculative_core_mask(problems[i],
                                                          remaining)
                if mask is not None:
                    cores[r, : problems[i].n_cons] = mask
                    steps[r] = spec_steps
                    continue
                if spec_steps >= remaining:
                    steps[r] = remaining + 1
                    continue
            eng = HostEngine(problems[i], max_steps=remaining - spec_steps)
            try:
                cores[r, : problems[i].n_cons] = eng.unsat_core_mask()
                steps[r] = spec_steps + eng.steps
            except Incomplete:
                # Budget exhausted mid-sweep: mirror the device contract
                # — steps past the budget mark the lane Incomplete on
                # decode.
                steps[r] = remaining + 1
        return cores, steps


def _profile_dispatch(t0, problems, d: _Dims, steps: np.ndarray,
                      live: int, total: int, chunk: int) -> None:
    """Trip-ledger hook shared by the dispatch impls (ISSUE 11): runs
    only for dispatches :func:`profile.dispatch_t0` sampled, strictly
    AFTER the result fetch (host numpy in hand — never inside traced
    code).  ``steps`` are the dispatch's final per-lane counts, live
    lanes first; ``chunk`` is the lockstep program width."""
    cost = max(_cost_proxy(p) for p in problems)
    _profile.record_device_dispatch(
        t0, steps=steps, live=live, chunk=chunk,
        size_class=_bucket(cost),
        size_class_name=_size_classes.class_of_cost(cost),
        pad_cells=int(total) * d.C * d.K,
        live_cells=int(sum(p.clauses.size for p in problems)))


def padded_class(problems) -> str:
    """The ladder class of a dispatch group's PADDED batch dims — the
    same classification :func:`_bank_cap` applies to the same dispatch
    (cost over the bucketed C/NV/NCON maxima), and a function of
    exactly the dims that key jit's shape cache.  The max of
    per-problem cost proxies is NOT such a function (a wide-clause
    problem and a wide-var problem can trade maxima), so per-class
    impl routing must classify here, not there."""
    C = _size_classes.bucket(max((p.clauses.shape[0]
                                  for p in problems), default=1))
    NV = _size_classes.bucket(max((p.n_vars for p in problems),
                                  default=1))
    NCON = _size_classes.bucket(max((p.n_cons for p in problems),
                                    default=1))
    Wv = -(-(NV + NCON) // _size_classes.WORD)
    return _size_classes.class_of_cost((C + 2 * NV) * Wv)


def _class_impl_scoped(fn):
    """Scope a dispatch-group impl to its ladder class's resolved BCP
    impl (ISSUE 13 satellite: the measured-defaults ``bcp`` row is
    keyed per size class, so deep-chain classes run ``watched`` while
    the mixed fleet keeps ``bits``).  The class comes from
    :func:`padded_class` — a function of the padded dims that key the
    compiled programs, so two dispatches reaching the same program
    always resolve the same impl.  With the global knob set, or no
    per-class row measured, the scope resolves to exactly what the
    global resolution would — byte-identical dispatch."""

    @_functools.wraps(fn)
    def wrapped(problems, budget, mesh, trace_cap, **kw):
        if not problems or core._BCP_IMPL != "auto":
            return fn(problems, budget, mesh, trace_cap, **kw)
        with core.impl_scope(
                core.resolved_impl_for(padded_class(problems))):
            return fn(problems, budget, mesh, trace_cap, **kw)

    return wrapped


@_class_impl_scoped
def _solve_monolith(problems, budget, mesh, trace_cap,
                    _spmd_entry: bool = False) -> List[core.SolveResult]:
    """Single-dispatch path (one jitted program, all phases lane-gated):
    the right trade for a batch of one, where phase compaction buys
    nothing and one compile beats three.  ``_spmd_entry`` swaps the
    jitted program for :func:`batched_solve_sharded` — same vmapped
    solve, explicit PartitionSpec shardings over ``mesh`` — the SPMD
    spelling of the mesh entry (:func:`_solve_spmd`)."""
    prof_t0 = _profile.dispatch_t0()
    n = len(problems)
    d = _Dims(problems, max(n, 1), batch_multiple=mesh.size if mesh is not None else 1)
    host_core = any(p.n_cons > HOST_CORE_NCONS for p in problems)
    reg = telemetry.default_registry()
    rep = telemetry.current_report()
    # The single program runs every device phase, so both plane spaces
    # materialize — except under host_core, where the deletion arm (the
    # only reader of the full-space planes under the bits impl) is
    # compiled out and the default derivation suffices.  _put_chunk
    # device_puts the compact tensors first so they cross host→device
    # exactly once.
    with reg.span("driver.pad_pack", problems=n, lanes=int(d.B)) as sp:
        pts_np = pad_stack(problems, d, d.B, pack=False)
    _telem_record_pad(problems, d.B, d, n_chunks=1, dur_s=sp.dur_s)
    with reg.span("driver.device_put", lanes=int(d.B)) as sp:
        faults.inject("driver.device_put")
        pts = _put_chunk(pts_np, mesh, d,
                         full=True if not host_core else None)
    if rep is not None:
        rep.add_wall("device_put", sp.dur_s)
    with reg.span("driver.launch", lanes=int(d.B)):
        if _spmd_entry:
            fn = batched_solve_sharded(mesh, d.V, d.NCON, d.NV, trace_cap,
                                       with_core=not host_core)
        else:
            fn = core.batched_solve(d.V, d.NCON, d.NV, trace_cap,
                                    with_core=not host_core)
        res = fn(pts, budget)
    # One batched fetch for the whole result tree: each individual
    # device→host transfer pays its own round trip, so per-field
    # np.asarray would cost 6 of them.
    with reg.span("driver.fetch", lanes=int(d.B)):
        res = jax.device_get(res)
    outcome = np.asarray(res.outcome)
    installed = np.asarray(res.installed)
    cores = np.asarray(res.core)
    steps = np.asarray(res.steps).astype(np.int64)
    trace_stack = np.asarray(res.trace_stack)
    trace_n = np.asarray(res.trace_n)
    # Ledger steps snapshot BEFORE host-core patching: the trip model
    # is about lockstep device while-trips, and folding the host spec
    # engine's core-sweep iterations into a lane's count would inflate
    # trips with work the device loop never executed (biasing the
    # us/trip regression the profiler exists to produce).
    prof_steps = steps.copy() if (prof_t0 is not None and host_core) \
        else steps
    if host_core:
        outcome, cores, steps = _host_core_patch(
            problems, d, budget, outcome, cores, steps,
            allow_device=mesh is None)
    if prof_t0 is not None:
        _profile_dispatch(prof_t0, problems, d, prof_steps, live=n,
                          total=int(d.B), chunk=int(d.B))
    return [
        core.SolveResult(outcome[i], installed[i], cores[i], steps[i],
                         trace_stack[i], trace_n[i])
        for i in range(n)
    ]


def _host_core_patch(problems, d: _Dims, budget, outcome, cores, steps,
                     allow_device: bool = False):
    """Host-route core extraction for a fetched single-program result's
    UNSAT rows (the ``with_core=False`` compositions: monolith and the
    mesh-serving shard dispatch) — same steps/outcome convention as
    :func:`_host_core_rows`.  Returns (outcome, cores, steps); inputs
    are host numpy, ``cores`` is copied before patching."""
    unsat_idx = np.nonzero(outcome[: len(problems)] == core.UNSAT)[0]
    if unsat_idx.size:
        hc, hs = _host_core_rows(problems, unsat_idx, d, budget,
                                 steps[unsat_idx],
                                 allow_device=allow_device)
        cores = cores.copy()
        cores[unsat_idx] = hc
        steps[unsat_idx] += hs
        outcome = np.where(steps > int(budget), core.RUNNING, outcome)
    return outcome, cores, steps


# Per-dispatch lane cap (power of two): smaller dispatches bound
# max-over-lanes lockstep waste while async dispatch keeps the device busy
# across chunks, and one batched fetch per phase still pays a single round
# trip regardless of chunk count.  The value is not yet measured on the
# chip.
MAX_LANES = int(config.env_raw("DEPPY_TPU_MAX_LANES", "512"))


def _chunk_slices(total: int, ch: int) -> List[slice]:
    return [slice(i, i + ch) for i in range(0, total, ch)]


def _rows(pts: core.ProblemTensors, sl: slice) -> core.ProblemTensors:
    return core.ProblemTensors(
        *[getattr(pts, f)[sl] for f in core.ProblemTensors._fields]
    )


@_class_impl_scoped
def _solve_split(problems, budget, mesh, trace_cap) -> List[core.SolveResult]:
    """Chunked three-phase path: search over the batch in ≤ MAX_LANES
    dispatches, then minimization on compacted SAT-lane chunks and core
    extraction on compacted UNSAT-lane chunks.

    Under ``vmap`` every ``while_loop`` runs max-over-lanes iterations, so
    in the single-program composition a batch's few UNSAT lanes serialize
    every lane through the O(n_cons) deletion loop and SAT lanes pay for
    minimization they may not need; compaction confines each phase's cost
    to the lanes that need it (SURVEY.md §7.3 item 4's divergence
    mitigation).  All chunks of a phase dispatch asynchronously (device
    work pipelines) and their results come back in one batched fetch."""
    prof_t0 = _profile.dispatch_t0()
    prof_steps = None  # device-only ledger snapshot (set on host route)
    n = len(problems)
    # MAX_LANES caps every dispatch, mesh or not: sharding divides lanes
    # across devices but each worker still executes its shard of one
    # program.
    ch_cap = min(max(n, 1), MAX_LANES)
    d = _Dims(problems, ch_cap, batch_multiple=mesh.size if mesh is not None else 1)
    CH = d.B
    n_chunks = max(1, -(-n // CH))
    total = n_chunks * CH
    reg = telemetry.default_registry()
    rep = telemetry.current_report()
    empty_row = pad_problem(_empty_problem(), d, pack=False)
    with reg.span("driver.pad_pack", problems=n, lanes=total,
                  chunks=n_chunks) as sp:
        pts_np = pad_stack(problems, d, total, pack=False)
    _telem_record_pad(problems, total, d, n_chunks=n_chunks, dur_s=sp.dur_s)
    en = np.arange(total) < n
    slices = _chunk_slices(total, CH)

    # Compact problem tensors go to the device in ONE transfer for the
    # whole batch, then chunks are sliced on device: every device_put
    # call pays a full round trip, so per-chunk uploads
    # cost n_chunks round trips (measured 473ms of a 1.2s dispatch at
    # 8 chunks) where one batched upload pays one.  Planes are derived
    # per chunk on device and everything stays resident: phase 2 reuses
    # the buffers directly, so nothing is re-uploaded.  Under a mesh the
    # per-chunk path shards each chunk's batch axis instead (a single
    # upload would fix the whole batch onto one device).
    with reg.span("driver.device_put", lanes=total, chunks=n_chunks) as sp:
        faults.inject("driver.device_put")
        if mesh is None:
            pts_all = _put_compact(pts_np)
            pts_dev = [_derive_planes(_rows(pts_all, sl), d)
                       for sl in slices]
            # The chunk slices are independent buffers; drop the
            # full-batch copy so it doesn't hold HBM alongside them for
            # the whole solve.
            del pts_all
        else:
            pts_dev = [_put_chunk(_rows(pts_np, sl), mesh, d)
                       for sl in slices]
        en_dev = [_to_device(en[sl], mesh) for sl in slices]
    if rep is not None:
        rep.add_wall("device_put", sp.dur_s)

    with reg.span("driver.launch", lanes=total, chunks=n_chunks):
        fn_a = core.batched_search(d.V, d.NCON, d.NV, trace_cap)
        outs = [fn_a(p, budget, e) for p, e in zip(pts_dev, en_dev)]

        # Phase 2 dispatches immediately on the same device-resident
        # chunks, gated per lane by the phase-1 result — no host round
        # trip in between.
        fn_b = core.batched_minimize_gated(d.V, d.NCON, d.NV)
        res_b = [
            fn_b(p, o[0], o[2], o[1], budget, o[3], e)
            for p, o, e in zip(pts_dev, outs, en_dev)
        ]

    # One small fetch decides the phase-3 strategy (results + steps only).
    with reg.span("driver.fetch", lanes=total, chunks=n_chunks):
        small = jax.device_get([(o[0], o[3], o[5]) for o in outs])
    result = np.concatenate([s[0] for s in small])
    steps = np.concatenate([s[1] for s in small]).astype(np.int64)
    trace_n = np.concatenate([s[2] for s in small])

    installed = np.zeros((total, d.NV), bool)
    min_found = np.zeros(total, bool)
    cores = np.zeros((total, d.NCON), bool)

    unsat_idx = np.nonzero(en & (result == core.UNSAT))[0]
    sat_any = bool((en & (result == core.SAT)).any())

    res_c: list = []
    core_gated = unsat_idx.size > total // 2
    if unsat_idx.size and core_gated:
        # UNSAT-heavy batch: compaction would re-upload nearly every row —
        # run the deletion loop en-gated on the resident chunks instead.
        # Under the bits impl the resident chunks carry only reduced
        # planes; the core phase probes with activations disabled, so its
        # full-space planes are derived here from the resident compact
        # tensors (no host round trip).
        with reg.span("driver.launch", lanes=total, chunks=n_chunks):
            fn_cg = core.batched_core_gated(d.V, d.NCON, d.NV)
            red = core.phases_reduced()
            # Derive per chunk inside the loop so only one chunk's full
            # planes are live at a time (they free once its dispatch
            # retires).
            res_c = [
                fn_cg(_derive_full(p, d) if red else p, o[0], budget,
                      o[3], e)
                for p, o, e in zip(pts_dev, outs, en_dev)
            ]
    elif unsat_idx.size:
        # Few UNSAT lanes: giant problems route to the host spec engine
        # (HOST_CORE_NCONS — kept-member probes are full SAT searches the
        # serial host resolves faster); the rest compact into (usually)
        # one small
        # device dispatch — only those rows transfer again (and only
        # their compact tensors — the core phase's full-space planes are
        # derived on device).
        host_idx = unsat_idx[
            [problems[i].n_cons > HOST_CORE_NCONS for i in unsat_idx]
        ]
        dev_idx = unsat_idx[
            [problems[i].n_cons <= HOST_CORE_NCONS for i in unsat_idx]
        ]
        b = 0
        if dev_idx.size:
            b = min(_pad_group(dev_idx.size, mesh), CH)
            with reg.span("driver.core_stage", lanes=int(dev_idx.size)):
                staged = [(
                    # The core phase reads only the full-space planes:
                    # skip the reduced build on these re-gathered rows.
                    _put_chunk(_gather_rows(pts_np, idx, b, empty_row),
                               mesh, d, full=True, red=False),
                    _to_device(_pad_rows(steps[idx], b), mesh),
                    _to_device(np.arange(b) < idx.size, mesh),
                ) for idx in [dev_idx[i: i + b]
                              for i in range(0, dev_idx.size, b)]]
            with reg.span("driver.launch", lanes=b * len(staged),
                          chunks=len(staged)):
                fn_c = core.batched_core(d.V, d.NCON, d.NV)
                res_c = [fn_c(pts_c, budget, steps_c, en_c)
                         for pts_c, steps_c, en_c in staged]
        if host_idx.size:
            # Runs on the host CPU while the device chews on the phase-2/3
            # dispatches above — the final fetch below synchronizes both.
            # allow_device stays False here: these rows overlap with the
            # in-flight phase-2/3 dispatches (the comment below), and a
            # speculative device attempt would queue behind them and
            # block — serializing exactly what this path parallelizes.
            # The monolith path, where the device is idle by core time,
            # is where the speculative probes run.
            host_cores, host_steps = _host_core_rows(
                problems, host_idx, d, budget, steps[host_idx]
            )

    # Final batched fetch: all phase-2 and phase-3 results (and trace
    # buffers if compiled in) in one round trip.
    fetch = {"b": res_b if sat_any else [], "c": res_c}
    if trace_cap > 0:
        fetch["tr"] = [o[4] for o in outs]
    with reg.span("driver.fetch", lanes=total, chunks=n_chunks):
        fetched = jax.device_get(fetch)

    if sat_any:
        inst_c = np.concatenate([r[0] for r in fetched["b"]])
        mf_c = np.concatenate([r[1] for r in fetched["b"]])
        st_c = np.concatenate([r[2] for r in fetched["b"]])
        sat_mask = en & (result == core.SAT)
        installed[sat_mask] = inst_c[sat_mask]
        min_found[sat_mask] = mf_c[sat_mask]
        steps[sat_mask] = st_c[sat_mask]
    if unsat_idx.size:
        if core_gated:
            core_c = np.concatenate([r[0] for r in fetched["c"]])
            st_c = np.concatenate([r[1] for r in fetched["c"]])
            cores[unsat_idx] = core_c[unsat_idx]
            steps[unsat_idx] = st_c[unsat_idx]
        else:
            if dev_idx.size:
                core_c = np.concatenate([r[0] for r in fetched["c"]])
                st_c = np.concatenate([r[1] for r in fetched["c"]])
                ks = [min(b, dev_idx.size - j)
                      for j in range(0, dev_idx.size, b)]
                keep = np.concatenate([np.arange(b) < k for k in ks])
                cores[dev_idx] = core_c[keep]
                steps[dev_idx] = st_c[keep]
            if host_idx.size:
                cores[host_idx] = host_cores
                if prof_t0 is not None:
                    # Device-only snapshot for the trip ledger (see
                    # _solve_monolith): host spec-engine core steps are
                    # not lockstep trips.
                    prof_steps = steps.copy()
                steps[host_idx] = steps[host_idx].astype(np.int64) + host_steps
    if trace_cap > 0:
        trace_stack = np.concatenate(fetched["tr"])
    else:
        trace_stack = np.zeros((total, 0, 0), np.int32)

    incomplete = (
        (steps > int(budget))
        | (result == core.RUNNING)
        | ((result == core.SAT) & ~min_found)
    )
    outcome = np.where(incomplete, core.RUNNING, result).astype(np.int32)
    if prof_t0 is not None:
        _profile_dispatch(prof_t0, problems, d,
                          prof_steps if prof_steps is not None else steps,
                          live=n, total=total, chunk=CH)
    return [
        core.SolveResult(outcome[i], installed[i], cores[i], steps[i],
                         trace_stack[i], trace_n[i])
        for i in range(n)
    ]


# Size-class bucketing (SURVEY.md §7.3 items 4-5): a heterogeneous fleet
# batch is partitioned into size classes so one large straggler doesn't
# inflate every lane's padded planes.  The class boundaries come from the
# SHARED ladder (deppy_tpu.size_classes — the same table the
# block-contract lint tier evaluates), so a 64-clause problem lands in
# `xs` and never shares dims with an `l` problem, whatever the cost
# distribution between them looks like.  The pre-ISSUE-12 splitter cut
# only at >= SPLIT_RATIO jumps between ADJACENT sorted costs — on a
# smooth distribution no adjacent jump ever reaches the ratio even when
# the extremes span 64x, which is exactly how a 64-clause problem ended
# up paying a 4096-clause pad (`block-pad-waste`, ROADMAP item 1).  That
# splitter is kept behind DEPPY_TPU_SIZE_LADDER=off for A/B (and
# MAX_BUCKETS keeps its pre-ladder value so that arm reproduces the
# replaced partitioner exactly; under the ladder it caps the jump
# splits WITHIN each class).  Buckets below MIN_BUCKET problems aren't
# worth a separate dispatch and merge with their neighbor.
MAX_BUCKETS = 4
MIN_BUCKET = 16
# Only split at a size-class boundary when the padded per-lane cost ratio
# across it is at least this factor (shared with the lint contracts).
SPLIT_RATIO = _size_classes.SPLIT_RATIO

# Ladder-vs-legacy partitioner selection ('on' = the shared size-class
# ladder; 'off' = the adjacent-jump splitter, kept for A/B).
_SIZE_LADDER = config.env_raw("DEPPY_TPU_SIZE_LADDER", "on")


def _cost_proxy(p: Problem) -> int:
    """Padded per-lane cost proxy (shared model:
    :func:`deppy_tpu.size_classes.cost_proxy`): clause-plane area
    dominates BCP; the var count drives DPLL snapshot size and
    iteration count."""
    return _size_classes.cost_proxy(p.clauses.shape[0], p.n_vars,
                                    p.n_cons)


def _merge_small(buckets: List[List[int]]) -> List[List[int]]:
    """Merge under-MIN_BUCKET buckets into the previous (smaller-class)
    neighbor: a dedicated dispatch for a handful of lanes wastes more
    than the neighbor's re-pad."""
    merged: List[List[int]] = []
    for idxs in buckets:
        if merged and (len(idxs) < MIN_BUCKET
                       or len(merged[-1]) < MIN_BUCKET):
            merged[-1].extend(idxs)
        else:
            merged.append(idxs)
    return merged


def _jump_splits(costs: np.ndarray, order: np.ndarray,
                 max_buckets: int) -> List[List[int]]:
    """Cut a sorted cost run at its largest adjacent-cost jumps (up to
    ``max_buckets - 1`` of them, each >= SPLIT_RATIO)."""
    n = order.size
    sc = costs[order]
    ratios = sc[1:] / np.maximum(sc[:-1], 1)
    cand = np.nonzero(ratios >= SPLIT_RATIO)[0]
    cand = cand[np.argsort(ratios[cand])[::-1][: max_buckets - 1]]
    splits = sorted(int(i) + 1 for i in cand)
    bounds = [0] + splits + [n]
    return [order[lo:hi].tolist()
            for lo, hi in zip(bounds[:-1], bounds[1:])]


def _partition_legacy(costs: np.ndarray, order: np.ndarray,
                      n: int) -> List[List[int]]:
    """Pre-ladder splitter: adjacent-cost jumps only — blind to a
    smooth distribution whose extremes span a class boundary."""
    return _merge_small(_jump_splits(costs, order, MAX_BUCKETS))


def partition_buckets(problems: Sequence[Problem]) -> List[List[int]]:
    """Partition problem indices into size-class buckets: first along
    the shared ladder's class boundaries (a 64-clause problem never
    shares dims with a 4096-clause one, however smooth the cost
    distribution), then at >= SPLIT_RATIO adjacent-cost jumps WITHIN
    each class (a class can still span a big jump — e.g. 24-var and
    96-var problems both landing in `xs`).  Strictly finer than the
    legacy jump-only splitter before the small-bucket merge.  Returns
    index lists; a homogeneous batch comes back as one bucket."""
    n = len(problems)
    if n < 2 * MIN_BUCKET:
        return [list(range(n))]
    costs = np.array([_cost_proxy(p) for p in problems], dtype=np.int64)
    order = np.argsort(costs, kind="stable")
    if _SIZE_LADDER == "off":
        return _partition_legacy(costs, order, n)
    buckets: List[List[int]] = []
    run: List[int] = []
    cur: Optional[str] = None
    for i in order.tolist():
        name = _size_classes.class_of_cost(int(costs[i]))
        if name != cur and run:
            buckets += _jump_splits(costs, np.array(run), MAX_BUCKETS)
            run = []
        cur = name
        run.append(i)
    if run:
        buckets += _jump_splits(costs, np.array(run), MAX_BUCKETS)
    return _merge_small(buckets)


# Progressive budget escalation (SURVEY.md §7.3 item 4's "compaction of
# unfinished problems"): under vmap every lane pays the slowest lane's
# while_loop trip count, and real catalog batches are heavy-tailed
# (config-2 distribution: median 47 steps, p99 213, max 338).  Stage 1
# runs every lane with this small step budget; the few lanes still
# unfinished re-dispatch compacted at the full budget.  0 disables.
# Default OFF: on CPU XLA the re-dispatch overhead loses 4-13% at every
# stage-1 size tried (64/96/128/256 on the 1024-problem config-2 batch) —
# the bet only pays where per-iteration cost grows with lane width, so it
# stays an opt-in to A/B on real TPU before becoming a default.
STAGE1_STEPS = int(config.env_raw("DEPPY_TPU_STAGE1_STEPS", "0"))
# Escalation only pays when stage 1 resolves the vast majority; if more
# than this fraction straggle, the batch is uniformly hard and the whole
# batch re-runs at full budget (stage 1 was mis-sized, bounded waste).
STAGE1_MAX_STRAGGLERS = 0.25
# Batches below this size aren't worth a two-stage dance.
STAGE1_MIN_BATCH = 64


def _record_escalation(stage: int, stragglers: int = 0) -> None:
    """Record the escalation stage a dispatch group reached: 0 = single
    stage (escalation disabled or not profitable), 1 = stage-1 budget
    resolved every lane, 2 = stage-2 (compacted redo or full rerun)."""
    telemetry.default_registry().counter(
        "deppy_escalation_total",
        "Dispatch groups by the budget-escalation stage reached.",
        labelname="stage",
    ).inc(1, label=str(stage))
    rep = telemetry.current_report()
    if rep is not None:
        rep.note_escalation(stage)


# ------------------------------------------------------------- fault domain
#
# ISSUE 2 tentpole: the dispatch path must survive a dying accelerator.
# Every dispatch-group impl call (_solve_monolith / _solve_split, via
# _solve_escalating) runs under _recovering(), which owns the policy:
# retry with backoff, split a group that keeps failing, route to the
# host engine as the last line, and feed the accelerator circuit
# breaker.  The fault-injection harness (faults.inject) scripts device
# failures at the named points so all of this runs in CI on CPU.


def _fault_results_host(problems, budget, reason: str) -> List[core.SolveResult]:
    """Solve one dispatch group entirely on the host engine (fault-path
    fallback: the device dispatch failed or the breaker is open).

    Lanes run through the shared hostpool entry (ISSUE 5) — concurrent
    across the host worker pool when one is available, inline otherwise,
    bit-identical either way — so breaker-open serving scales with the
    host's cores instead of collapsing to one.  Results are
    device-shaped — installed/core masks padded to the group's bucketed
    dims so checkpoint stacking and decode see exactly what a device
    dispatch would have produced; the step budget carries over, so
    budget-exhausted lanes still read Incomplete, and lanes not started
    before the batch deadline expires degrade (one counted event for
    the group, matching the driver's per-group accounting)."""
    from .. import hostpool

    faults.inject("driver.host_fallback")
    reg = telemetry.default_registry()
    faults.fault_counter("deppy_fault_host_routed_total").inc(len(problems))
    reg.event("fault", fault="host_fallback", reason=reason,
              problems=len(problems))
    rep = telemetry.current_report()
    if rep is not None:
        rep.fault_host_routed += len(problems)
    d = _Dims(problems, max(len(problems), 1))
    out: List[core.SolveResult] = []
    dl = faults.current_deadline()
    prof_t0 = _profile.dispatch_t0("hostpool")
    with reg.span("driver.fault_host_fallback", problems=len(problems),
                  reason=reason):
        lanes = hostpool.solve_host_problems(
            problems, max_steps=int(budget),
            deadlines=[dl] * len(problems))
        if prof_t0 is not None:
            # Per-backend cost attribution (ISSUE 11): breaker-open /
            # fault-routed groups account under "hostpool".
            _profile.record_backend_flush(
                "hostpool", len(problems),
                int(sum(r.steps for r in lanes)),
                _time.perf_counter() - prof_t0)
        n_degraded = sum(1 for r in lanes if r.degraded)
        if n_degraded:
            faults.note_deadline_exceeded("driver.host_fallback",
                                          n_degraded)
        for p, lane in zip(problems, lanes):
            installed = np.zeros(d.NV, bool)
            cmask = np.zeros(d.NCON, bool)
            if lane.outcome == "sat":
                installed[lane.installed_idx] = True
                outcome = core.SAT
            elif lane.outcome == "unsat":
                cmask[lane.core_idx] = True
                outcome = core.UNSAT
            else:
                outcome = core.RUNNING
            out.append(core.SolveResult(
                np.int32(outcome), installed, cmask,
                np.int64(lane.steps), np.zeros((0, 0), np.int32),
                np.int32(lane.backtracks)))
    return out


def _deadline_results(problems) -> List[core.SolveResult]:
    """Incomplete results for a group whose batch deadline expired before
    it could dispatch — completed batchmates keep their answers, these
    lanes report exactly what a budget-exhausted solve would."""
    d = _Dims(problems, max(len(problems), 1))
    return [
        core.SolveResult(np.int32(core.RUNNING), np.zeros(d.NV, bool),
                         np.zeros(d.NCON, bool), np.int64(0),
                         np.zeros((0, 0), np.int32), np.int32(0))
        for _ in problems
    ]


def _recovering(impl, breaker=None, point: str = "driver.dispatch",
                on_fault=None):
    """Wrap a dispatch-group impl with the fault-domain policy.

    Order of recovery for a failing group: (1) retry up to
    ``RetryPolicy.max_attempts`` with exponential backoff + jitter,
    (2) split the group in half and recurse (a single poison problem
    isolates in log2 steps while its groupmates stay on device),
    (3) host-engine fallback.  Semantic outcomes (NotSatisfiable /
    Incomplete / InternalSolverError) and admission errors pass
    through untouched — only unexpected failures are device faults.

    The breaker sees every failure and success; once open, groups route
    straight to the host engine without paying an attempt, until the
    cooldown's half-open probe dispatch.  ``breaker`` defaults to the
    process-wide accelerator breaker; the mesh-serving path passes a
    per-device breaker and its shard's fault point
    (``driver.shard_dispatch.N``) so a poisoned shard charges — and
    trips — only its own device (ISSUE 6).  ``on_fault`` (optional) is
    called whenever the group leaves the clean path — a dispatch
    failure or a breaker-open host route — possibly more than once per
    call (retries, split halves); callers wanting once-per-group
    semantics dedup themselves (the shard recovery counter does)."""

    def run(problems, budget, mesh, trace_cap):
        policy = faults.RetryPolicy.from_env()
        nonlocal breaker
        if breaker is None:
            breaker = faults.default_breaker()
        reg = telemetry.default_registry()
        dl = faults.current_deadline()
        if dl is not None and dl.expired():
            faults.note_deadline_exceeded(point, len(problems))
            return _deadline_results(problems)
        if not breaker.allow():
            if on_fault is not None:
                on_fault()
            return _fault_results_host(problems, budget,
                                       reason="breaker_open")
        attempt = 0
        while True:
            t0 = _time.monotonic()
            try:
                faults.inject(point)
                results = impl(problems, budget, mesh, trace_cap)
            except (InternalSolverError, NotSatisfiable, Incomplete,
                    faults.DeadlineExceeded):
                # Not a device verdict: if this attempt was the breaker's
                # half-open probe, hand the slot back so the next
                # dispatch can probe (a leaked slot would silently deny
                # the device forever).
                breaker.abandon_probe()
                raise
            except Exception as e:
                attempt += 1
                if on_fault is not None:
                    on_fault()
                breaker.record_failure()
                faults.fault_counter("deppy_fault_failures_total").inc()
                reg.event("fault", fault="dispatch_failed",
                          error=type(e).__name__, attempt=attempt,
                          problems=len(problems), breaker=breaker.state())
                if dl is not None and dl.expired():
                    faults.note_deadline_exceeded(point, len(problems))
                    return _deadline_results(problems)
                if attempt < policy.max_attempts and not breaker.blocks_device():
                    faults.fault_counter("deppy_fault_retries").inc()
                    back = policy.backoff_s(attempt)
                    if dl is not None:
                        back = min(back, max(dl.remaining(), 0.0))
                    if back > 0:
                        _time.sleep(back)
                    continue
                if (len(problems) > 1 and policy.split_failed_groups
                        and not breaker.blocks_device()):
                    reg.event("fault", fault="group_split",
                              problems=len(problems))
                    mid = (len(problems) + 1) // 2
                    return (run(list(problems[:mid]), budget, mesh, trace_cap)
                            + run(list(problems[mid:]), budget, mesh,
                                  trace_cap))
                return _fault_results_host(problems, budget,
                                           reason=type(e).__name__)
            else:
                dur = _time.monotonic() - t0
                if (policy.chunk_deadline_s > 0
                        and dur > policy.chunk_deadline_s):
                    # A dispatch that ran this long holds the device
                    # past the policy's bound: keep the
                    # valid result, but count it and charge the breaker
                    # so a streak of them trips to host-only.
                    faults.note_deadline_exceeded("driver.chunk",
                                                  len(problems))
                    breaker.record_failure()
                else:
                    breaker.record_success()
                return results

    return run


def _solve_escalating(impl, problems, budget, mesh, trace_cap,
                      breaker=None, point: str = "driver.dispatch",
                      on_fault=None):
    """Run ``impl`` in two budget stages when profitable; transparent
    fallbacks otherwise.  Tracing disables escalation (stage-2 re-runs
    would re-record trace buffers from scratch).  Every impl call is
    wrapped by the fault-domain recovery policy (:func:`_recovering`);
    ``breaker``/``point``/``on_fault`` pass through to it so the
    mesh-serving path runs this same pipeline under a per-device fault
    domain (ISSUE 6)."""
    impl = _recovering(impl, breaker=breaker, point=point,
                       on_fault=on_fault)
    reg = telemetry.default_registry()
    if (
        STAGE1_STEPS <= 0
        or trace_cap > 0
        or len(problems) < STAGE1_MIN_BATCH
        or int(budget) < 8 * STAGE1_STEPS
        # Giant problems host-route their core extraction, and a stage-1
        # budget is too small for that serial sweep to finish — it would
        # run (on the critical path), exhaust, and be redone in stage 2.
        or any(p.n_cons > HOST_CORE_NCONS for p in problems)
    ):
        with reg.span("driver.escalation", problems=len(problems),
                      stage=0):
            results = impl(problems, budget, mesh, trace_cap)
        _record_escalation(0)
        return results
    with reg.span("driver.escalation", problems=len(problems)) as sp:
        results = impl(problems, np.int32(STAGE1_STEPS), mesh, 0)
        stragglers = [
            i for i, r in enumerate(results) if r.outcome == core.RUNNING
        ]
        sp.set(stragglers=len(stragglers))
        if not stragglers:
            sp["stage"] = 1
            _record_escalation(1)
            return results
        sp["stage"] = 2
        _record_escalation(2, stragglers=len(stragglers))
        dl = faults.current_deadline()
        if dl is not None and dl.expired():
            # The batch deadline expired during stage 1: the redo would
            # only hit the recovery wrapper's expired-deadline fast path
            # again (degrading the same lanes and double-counting
            # deppy_deadline_exceeded) — the stage-1 results already
            # carry the right Incomplete verdicts.
            return results
        if len(stragglers) > STAGE1_MAX_STRAGGLERS * len(problems):
            redo = impl(problems, budget, mesh, trace_cap)
            # A lane the redo left undecided (fault/deadline degradation
            # inside the recovery wrapper) keeps its stage-1 decision:
            # completed lanes must never be un-solved by a redo that was
            # only ever about the stragglers.
            return [
                r1 if (int(r2.outcome) == core.RUNNING
                       and int(r1.outcome) != core.RUNNING) else r2
                for r1, r2 in zip(results, redo)
            ]
        sub = impl([problems[i] for i in stragglers], budget, mesh, 0)
        for i, r in zip(stragglers, sub):
            # Each lane reports the steps of the run that produced its
            # result (stage-1 work on a redone straggler is not added:
            # both redo branches then agree, and a lane can never report
            # steps > budget alongside a decided outcome — same
            # invariant as single-stage).
            results[i] = r
        return results


# ------------------------------------------------------------- mesh serving
#
# ISSUE 6 tentpole: the scheduler's coalesced micro-batches shard their
# lane axis across a device mesh instead of landing on one chip.  The
# shape of the machinery:
#
#   * batched_solve_sharded — the batch-axis sharded dispatch: the
#     single-program batched solve jitted with explicit PartitionSpec
#     shardings on the lane axis, memoized per (mesh, signature) exactly
#     like parallel.clause_shard._sharded_fn.  This is the SPMD
#     spelling: one program, the whole mesh, one fault domain
#     (solve_problems_sharded(spmd=True); the bench scaling row and the
#     multichip dry run measure it against the serving composition);
#   * solve_problems_sharded — the serving entry: slice the batch into
#     per-device shards and drain each device's shards on its own
#     worker thread through the FULL phased pipeline (size-class
#     bucketing → compacted three-phase dispatch → budget escalation —
#     the same composition the single-device path serves with, so the
#     mesh pays no composition tax), with EACH shard under its own
#     fault domain — retry/split/host-fallback via the PR 2 _recovering
#     machinery for that slice only, charging a per-device breaker
#     (deppy_breaker_state{device=...}) so one bad chip degrades one
#     shard of the mesh, not the process.
#
# One program per device rather than one SPMD program over the mesh for
# the *serving* path: problems are independent (zero collectives either
# way — XLA would partition the SPMD program into the same per-device
# work), but separate programs make the fault blast radius one shard,
# which is the entire point of per-shard fault domains — and the
# per-device spelling keeps the phased/compacted composition, where the
# SPMD monolith lane-gates every phase (an UNSAT lane serializes its
# whole dispatch through the deletion loop).


@_functools.lru_cache(maxsize=32)
def batched_solve_sharded(mesh, V: int, NCON: int, NV: int,
                          trace_cap: int = 0, with_core: bool = True):
    """Batch-axis sharded dispatch entry (ISSUE 6): the vmapped
    single-program solve jitted with every ``ProblemTensors`` leaf
    sharded on its leading (lane) axis over the mesh's ``batch`` axis
    (``PartitionSpec``; SNIPPETS.md [1]-[3]), budget replicated, outputs
    lane-sharded.  Memoized per (mesh, space signature); input-shape
    variation within a signature retraces via jit's own cache."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.mesh import BATCH_AXIS

    s_lane = NamedSharding(mesh, PartitionSpec(BATCH_AXIS))
    s_repl = NamedSharding(mesh, PartitionSpec())
    vfn = jax.vmap(
        _functools.partial(core.solve_full, V=V, NCON=NCON, NV=NV,
                           T=trace_cap, with_core=with_core),
        in_axes=(0, None),
    )
    in_sh = (
        core.ProblemTensors(
            *([s_lane] * len(core.ProblemTensors._fields))),
        s_repl,
    )
    out_sh = core.SolveResult(
        *([s_lane] * len(core.SolveResult._fields)))
    devices = tuple(d.id for d in mesh.devices.flat)
    return jax.jit(
        compileguard.observe(
            "driver.batched_solve_sharded", vfn,
            static=(devices, V, NCON, NV, trace_cap, with_core)),
        in_shardings=in_sh, out_shardings=out_sh)


@_functools.lru_cache(maxsize=64)
def _device_submesh(device):
    """One-device 1-D batch mesh (memoized so the pjit entry's
    per-(mesh, signature) cache hits across dispatches)."""
    from ..parallel.mesh import default_mesh

    return default_mesh([device])


def _shard_slices(n: int, n_dev: int) -> List[List[int]]:
    """Contiguous lane slices for a sharded dispatch: ``ceil(n/n_dev)``
    lanes per shard, capped at MAX_LANES; shard *i* runs on device
    ``i % n_dev``, so batches past ``n_dev × MAX_LANES`` wrap round-robin
    and every device stays busy."""
    per = min(-(-n // n_dev), MAX_LANES)
    return [list(range(lo, min(lo + per, n)))
            for lo in range(0, n, per)]


def _solve_spmd(problems, budget, mesh, trace_cap) -> List[core.SolveResult]:
    """SPMD spelling of the mesh entry: ONE program over the whole mesh,
    the lane axis partitioned by :func:`batched_solve_sharded`'s explicit
    shardings.  Single fault domain — the bench scaling record and the
    multichip dry run measure it against the per-device serving
    composition (:func:`_solve_sharded_inner`)."""
    return _solve_monolith(problems, budget, mesh, trace_cap,
                           _spmd_entry=True)


def _shard_pipeline(problems, budget, submesh, trace_cap, breaker, point,
                    on_fault) -> List[core.SolveResult]:
    """One shard slice through the FULL single-device composition —
    size-class bucketing, compacted three-phase dispatch, budget
    escalation (the same pipeline :func:`_solve_problems_inner` runs) —
    under the shard's per-device fault domain.  This is why the mesh
    path pays no composition tax over single-device serving: the old
    monolith-per-shard spelling lane-gated every phase, serializing a
    shard's SAT lanes through its UNSAT lanes' deletion loops."""
    n = len(problems)
    impl = _solve_split if n > 1 else _solve_monolith
    buckets = partition_buckets(problems) if n > 1 else [list(range(n))]
    if len(buckets) == 1:
        return _solve_escalating(impl, list(problems), budget, submesh,
                                 trace_cap, breaker=breaker, point=point,
                                 on_fault=on_fault)
    out: List[Optional[core.SolveResult]] = [None] * n
    for idxs in buckets:
        sub = _solve_escalating(impl, [problems[i] for i in idxs], budget,
                                submesh, trace_cap, breaker=breaker,
                                point=point, on_fault=on_fault)
        for i, r in zip(idxs, sub):
            out[i] = r
    return out  # type: ignore[return-value]


def _solve_sharded_inner(problems, budget, mesh,
                         trace_cap: int) -> List[core.SolveResult]:
    n = len(problems)
    devices = list(mesh.devices.flat)
    n_dev = len(devices)
    reg = telemetry.default_registry()
    rep = telemetry.current_report()
    dl = faults.current_deadline()
    if dl is not None and dl.expired():
        faults.note_deadline_exceeded("driver.mesh_dispatch", n)
        return _deadline_results(problems)
    slices = _shard_slices(n, n_dev)
    c_disp = reg.counter(
        "deppy_shard_dispatches_total",
        "Mesh-serving shard dispatches, by device.", labelname="device")
    c_rec = reg.counter(
        "deppy_shard_recoveries_total",
        "Shard slices that entered per-device fault recovery "
        "(retry / split / host fallback).", labelname="device")
    results: List[Optional[core.SolveResult]] = [None] * n
    shard_reports: List[Optional[telemetry.SolveReport]] = \
        [None] * len(slices)
    shard_spans: List[Optional[tuple]] = [None] * len(slices)
    errors: List[BaseException] = []

    def drain_device(di: int) -> None:
        # One worker per device (a device runs one program at a time, so
        # more threads per device buy nothing): drains this device's
        # round-robin share of the slices serially, each through the
        # full phased pipeline under the device's own fault domain.  The
        # report and batch deadline both travel on thread-locals, so the
        # worker re-installs the parent's deadline and fills its own
        # report for the parent to merge after the join — sharing the
        # parent's report would race its unlocked counters.
        dev = devices[di]
        dev_key = str(getattr(dev, "id", di))
        # The device's own breaker gated on the process-wide one: an
        # open accelerator verdict host-routes every shard without an
        # attempt (PR 2's guarantee), while failures charge only this
        # device so one bad chip trips one shard of the mesh.
        br = faults.GatedDeviceBreaker(faults.device_breaker(dev_key),
                                       faults.default_breaker())
        submesh = _device_submesh(dev)
        for si in range(di, len(slices), n_dev):
            idxs = slices[si]
            sub = [problems[i] for i in idxs]
            c_disp.inc(label=dev_key)
            fired = [False]

            def on_fault(fired=fired):
                # Once per slice, however many retries / split halves /
                # breaker-open host routes the recovery walk takes.
                if not fired[0]:
                    fired[0] = True
                    c_rec.inc(label=dev_key)

            srep, owns = telemetry.begin_report(backend="tpu")
            t1 = _time.perf_counter()
            try:
                with faults.deadline_scope(dl):
                    out = _shard_pipeline(
                        sub, budget, submesh, trace_cap, breaker=br,
                        point=f"driver.shard_dispatch.{di}",
                        on_fault=on_fault)
            except BaseException as e:  # re-raised on the parent thread
                errors.append(e)
                return
            finally:
                telemetry.detach_report(srep, owns)
                if owns:
                    shard_reports[si] = srep
            shard_spans[si] = (dev_key, len(idxs),
                               _time.perf_counter() - t1)
            for i, r in zip(idxs, out):
                results[i] = r

    workers = [
        _threading.Thread(target=drain_device, args=(di,),
                          name=f"deppy-shard-{di}", daemon=True)
        for di in range(min(n_dev, len(slices)))
    ]
    with reg.span("driver.mesh_dispatch", problems=n, shards=len(slices),
                  devices=n_dev):
        for t in workers:
            t.start()
        for t in workers:
            t.join()
    # Spans and report merge land on the parent thread: record_span here
    # stamps the submitting request's trace context (workers have none),
    # and the merged report keeps one report event per batch.
    for entry in shard_spans:
        if entry is not None:
            dev_key, lanes, dur = entry
            reg.record_span("driver.shard_solve", dur, device=dev_key,
                            lanes=lanes)
    if rep is not None:
        for srep in shard_reports:
            if srep is not None:
                rep.merge(srep)
    if errors:
        # Semantic outcomes (InternalSolverError et al.) pass through
        # _recovering untouched; surface the first one exactly as the
        # unsharded path would.
        raise errors[0]
    return results  # type: ignore[return-value]


def solve_problems_sharded(
    problems: Sequence[Problem],
    mesh=None,
    max_steps: Optional[int] = None,
    trace_cap: int = 0,
    spmd: bool = False,
) -> List[core.SolveResult]:
    """Mesh-serving batch entry (ISSUE 6): shard one coalesced
    micro-batch's lane axis across ``mesh``'s devices — one worker
    thread per device draining its shards through the full phased
    pipeline, per-shard fault domains (see
    :func:`_solve_sharded_inner`).  Byte-identical results to
    :func:`solve_problems` on the same batch — problems are independent
    and sharding only changes placement — which the shard test suite
    pins.  Falls back to :func:`solve_problems` when the mesh is absent
    or single-device or the batch has a single problem.

    ``spmd=True`` instead dispatches the whole batch as ONE program
    whose lane axis is partitioned over the mesh by explicit
    ``PartitionSpec`` shardings (:func:`batched_solve_sharded`) under a
    single fault domain — same answers, whole-mesh blast radius; the
    bench scaling record measures both spellings."""
    if (mesh is None or getattr(mesh, "size", 1) < 2
            or len(problems) < 2):
        return solve_problems(problems, max_steps=max_steps,
                              trace_cap=trace_cap)
    for p in problems:
        if p.errors:
            raise InternalSolverError(p.errors)
    rep, owns = telemetry.begin_report(backend="tpu",
                                       n_problems=len(problems))
    reg = telemetry.default_registry()
    t0 = _time.perf_counter()
    try:
        with faults.ambient_deadline(), \
                reg.span("driver.solve", problems=len(problems),
                         devices=int(mesh.size)):
            if spmd:
                results = _recovering(_solve_spmd)(
                    list(problems), _budget(max_steps), mesh, trace_cap)
            else:
                results = _solve_sharded_inner(
                    problems, _budget(max_steps), mesh, trace_cap)
        for r in results:
            o = int(r.outcome)
            key = ("sat" if o == core.SAT
                   else "unsat" if o == core.UNSAT else "incomplete")
            rep.count_outcome(key)
            rep.steps += int(r.steps)
            rep.backtracks += int(r.trace_n)
        reg.histogram(
            "deppy_solve_seconds",
            "Wall-clock seconds per driver solve call (pad through "
            "decode).",
        ).observe(_time.perf_counter() - t0)
    finally:
        rep.add_wall("solve", _time.perf_counter() - t0)
        if owns:
            telemetry.end_report(rep, owns)
    return results


def solve_problems(
    problems: Sequence[Problem],
    max_steps: Optional[int] = None,
    mesh=None,
    trace_cap: int = 0,
    split_phases: Optional[bool] = None,
    bucketing: bool = True,
) -> List[core.SolveResult]:
    """Solve lowered problems as device batches; per-problem results with
    host numpy arrays.  With ``mesh`` (a 1-D ``jax.sharding.Mesh`` from
    :mod:`deppy_tpu.parallel`), each dispatch's batch axis is sharded over
    the mesh's devices and XLA partitions the solve — the fleet-scale path.
    ``trace_cap`` > 0 compiles in backtrack tracing with that buffer depth
    (see :class:`core.SolveResult`).

    ``split_phases`` (default: automatic — on for real batches, off for a
    batch of one) dispatches search / minimization / core extraction as
    separate compacted batches; ``bucketing`` partitions heterogeneous
    batches into size classes first.

    Telemetry: the whole call runs under a ``driver.solve`` span, and the
    thread's active :class:`deppy_tpu.telemetry.SolveReport` (created
    here when none is active — nested calls, e.g. checkpoint groups,
    merge into the enclosing one) accumulates padding economics,
    per-stage wall clock, escalation staging, and outcome counters;
    retrieve it afterwards via :func:`deppy_tpu.telemetry.last_report`."""
    for p in problems:
        if p.errors:
            raise InternalSolverError(p.errors)
    rep, owns = telemetry.begin_report(backend="tpu",
                                       n_problems=len(problems))
    reg = telemetry.default_registry()
    t0 = _time.perf_counter()
    try:
        # Ambient batch deadline: the caller's deadline_scope when one is
        # active (service request / CLI --deadline), else
        # DEPPY_TPU_BATCH_DEADLINE_S from the environment.  Expiry never
        # aborts the batch — groups past the deadline decode Incomplete.
        with faults.ambient_deadline(), \
                reg.span("driver.solve", problems=len(problems)):
            results = _solve_problems_inner(
                problems, max_steps, mesh, trace_cap, split_phases,
                bucketing,
            )
        for r in results:
            o = int(r.outcome)
            key = ("sat" if o == core.SAT
                   else "unsat" if o == core.UNSAT else "incomplete")
            rep.count_outcome(key)
            rep.steps += int(r.steps)
            rep.backtracks += int(r.trace_n)
        reg.histogram(
            "deppy_solve_seconds",
            "Wall-clock seconds per driver solve call (pad through "
            "decode).",
        ).observe(_time.perf_counter() - t0)
    finally:
        rep.add_wall("solve", _time.perf_counter() - t0)
        if owns:
            telemetry.end_report(rep, owns)
    return results


def _solve_problems_inner(problems, max_steps, mesh, trace_cap,
                          split_phases, bucketing):
    n = len(problems)
    budget = _budget(max_steps)
    if split_phases is None:
        split_phases = n > 1
    impl = _solve_split if split_phases else _solve_monolith
    buckets = partition_buckets(problems) if (bucketing and n > 1) else [list(range(n))]
    if len(buckets) == 1:
        return _solve_escalating(impl, list(problems), budget, mesh,
                                 trace_cap)
    results: List[Optional[core.SolveResult]] = [None] * n
    for idxs in buckets:
        sub = _solve_escalating(impl, [problems[i] for i in idxs], budget,
                                mesh, trace_cap)
        for i, r in zip(idxs, sub):
            results[i] = r
    return results  # type: ignore[return-value]


def _decode_installed(p: Problem, installed: np.ndarray) -> List[Variable]:
    return [p.variables[i] for i in range(p.n_vars) if installed[i]]


def _decode_core(p: Problem, active: np.ndarray) -> NotSatisfiable:
    return NotSatisfiable([p.applied[j] for j in range(p.n_cons) if active[j]])


# Trace-buffer depth compiled in when a tracer is attached.  Deep enough
# for any realistic catalog search; pass ``trace_cap`` to
# :func:`solve_one` (or ``Solver(trace_cap=...)``) for pathological cases.
# Truncation warns and is visible as stats["backtracks"] > trace calls.
DEFAULT_TRACE_CAP = 256


class _LazyReplayPosition:
    """``SearchPosition`` whose conflict set is reconstructed on demand.

    The assumption stack comes straight off the device trace buffer; the
    conflict list requires a host-engine replay, so it is computed only
    when a tracer actually calls ``conflicts()``.  Stats-only tracers
    (e.g. ``StatsTracer``) therefore cost zero host solves — the tracer
    contract only promises the position, not an eager materialization
    (reference tracer.go:13-15)."""

    def __init__(self, variables, compute_conflicts):
        self._variables = variables
        self._compute = compute_conflicts
        self._conflicts = None

    def variables(self):
        return self._variables

    def conflicts(self):
        if self._conflicts is None:
            self._conflicts = self._compute()
        return self._conflicts


def _replay_trace(problem: Problem, res: core.SolveResult, tracer) -> None:
    """Decode the device trace buffer into host ``Tracer.trace`` calls.

    Each recorded row is the guess-variable stack at one backtrack.  The
    conflict set is reconstructed — lazily, on first ``conflicts()``
    access — by replaying one host-engine Test under those assumptions
    (the host engine is the semantic spec; BCP is confluent, so the
    replayed fixpoint — and its conflict attribution — matches the
    device's).  A backtrack caused by an exhausted leaf DPLL rather than
    a propagation conflict replays without conflict and reports an empty
    conflict list, where the host engine surfaces its DPLL's final
    internal conflict — the assumption stacks agree exactly, the conflict
    annotation is best-effort (reference gini would compute a
    failed-assumption core here, lit_mapping.go:198-207)."""
    total = int(res.trace_n)
    rows = min(total, res.trace_stack.shape[0])
    if rows == 0:
        return
    if total > rows:
        import warnings

        warnings.warn(
            f"search backtracked {total} times but the trace buffer holds "
            f"{rows}; trailing events are dropped — raise trace_cap "
            f"(solve_one) to capture them",
            RuntimeWarning,
            stacklevel=3,
        )
    eng_box: list = []

    def _conflicts_for(gv):
        def compute():
            from ..sat.host import UNSAT as HOST_UNSAT
            from ..sat.host import HostEngine

            if not eng_box:
                eng_box.append(HostEngine(problem))
            eng = eng_box[0]
            outcome, _ = eng._test(guessed=tuple(gv))
            return list(eng.last_conflicts) if outcome == HOST_UNSAT else []

        return compute

    for i in range(rows):
        gv = [int(v) for v in res.trace_stack[i] if v >= 0]
        tracer.trace(
            _LazyReplayPosition(
                [problem.variables[v] for v in gv], _conflicts_for(gv)
            )
        )


def solve_one(
    problem: Problem,
    max_steps: Optional[int] = None,
    stats: Optional[dict] = None,
    tracer=None,
    trace_cap: Optional[int] = None,
) -> List[Variable]:
    """Single-problem entry used by :class:`deppy_tpu.sat.solver.Solver`
    (batch of one).  Same error contract as the host engine.  A ``stats``
    dict, when given, receives ``{"steps": N}`` — the engine iteration count
    (SURVEY.md §5 observability).  A ``tracer`` receives one ``trace`` call
    per search backtrack, like the host engine (reference tracer.go:13-15);
    ``trace_cap`` sizes the device-side event buffer (default
    ``DEFAULT_TRACE_CAP``; a warning fires if the search overflows it)."""
    if trace_cap is None:
        trace_cap = DEFAULT_TRACE_CAP if tracer is not None else 0
    (res,) = solve_problems([problem], max_steps=max_steps,
                            trace_cap=trace_cap)
    if stats is not None:
        stats["steps"] = int(res.steps)
        stats["backtracks"] = int(res.trace_n)
        stats["report"] = telemetry.last_report()
    if tracer is not None:
        _replay_trace(problem, res, tracer)
    if res.outcome == core.SAT:
        return _decode_installed(problem, res.installed)
    if res.outcome == core.UNSAT:
        raise _decode_core(problem, res.core)
    raise Incomplete()


def solve_batch(
    problem_vars: Sequence[Sequence[Variable]],
    max_steps: Optional[int] = None,
    mesh=None,
    stats: Optional[dict] = None,
    checkpoint_dir: Optional[str] = None,
):
    """Batch entry used by :class:`deppy_tpu.resolution.facade.BatchResolver`:
    N independent variable lists → per-problem result: a ``Solution`` dict,
    the problem's :class:`NotSatisfiable` error, or an :class:`Incomplete`
    marker when that problem exhausted the step budget (problems are
    independent, so one straggler never voids its batchmates' answers).  A
    ``stats`` dict, when given, receives ``{"steps": N}`` summed over the
    batch.  ``checkpoint_dir`` enables group-wise resume for fleet-scale
    batches (see :mod:`deppy_tpu.engine.checkpoint`)."""
    problems = [encode(vs) for vs in problem_vars]
    # Own the SolveReport across the whole batch so a checkpointed run's
    # per-group driver calls merge into one report instead of each
    # publishing their own.
    rep, owns = telemetry.begin_report(backend="tpu")
    try:
        if checkpoint_dir is not None:
            from .checkpoint import solve_problems_checkpointed

            results = solve_problems_checkpointed(
                problems, checkpoint_dir, max_steps=max_steps, mesh=mesh
            )
        else:
            results = solve_problems(problems, max_steps=max_steps,
                                     mesh=mesh)
    finally:
        telemetry.end_report(rep, owns)
    if stats is not None:
        stats["steps"] = int(sum(int(r.steps) for r in results))
        stats["report"] = telemetry.last_report()
    return decode_results(problems, results)


def decode_results(
    problems: Sequence[Problem], results: Sequence[core.SolveResult]
) -> List[Union[dict, NotSatisfiable, Incomplete]]:
    """Decode per-problem :class:`core.SolveResult`\\ s back to the
    facade vocabulary: a Solution dict (every entity id → selected?),
    the problem's :class:`NotSatisfiable` core, or an
    :class:`Incomplete` marker.  Shared by :func:`solve_batch` and the
    request scheduler (:mod:`deppy_tpu.sched`), which dispatches
    pre-encoded problems and decodes per lane — the two paths cannot
    drift."""
    out: List[Union[dict, NotSatisfiable, Incomplete]] = []
    # Spanned (ISSUE 4): decode is the last leg of a request's timing
    # breakdown (queue-wait → dispatch → solve → decode), and the trace
    # tree should show it like every other stage.
    with telemetry.default_registry().span("driver.decode",
                                           problems=len(problems)):
        for p, res in zip(problems, results):
            if res.outcome == core.SAT:
                solution = {v.identifier: False for v in p.variables}
                for v in _decode_installed(p, res.installed):
                    solution[v.identifier] = True
                out.append(solution)
            elif res.outcome == core.UNSAT:
                out.append(_decode_core(p, res.core))
            else:
                out.append(Incomplete())
    return out


def warm_screen(problems: Sequence[Problem], models, cones) -> np.ndarray:
    """Batched warm-prefix screen (ISSUE 10): the device lane variant of
    the incremental tier.  Each lane's assignment is initialized from
    its cached ``model`` (bool[n_vars]) with the ``cone`` variables left
    open, and one lockstep :func:`core.batched_warm_check` pass per
    ≤ MAX_LANES chunk (a mesh-sized warm flush can carry thousands of
    lanes) flags lanes whose warm prefix already conflicts
    — those cold-solve without paying a host warm attempt.  Returns
    bool[n].  Router only: results never depend on this screen, so it
    shares no identity obligations with the solve paths."""
    n = len(problems)
    ch_cap = min(max(n, 1), MAX_LANES)
    d = _Dims(problems, ch_cap)
    CH = d.B
    total = max(1, -(-n // CH)) * CH
    pts = pad_stack(problems, d, total, pack=False)
    assign = np.zeros((total, d.NV), np.int32)
    for i, (m, c) in enumerate(zip(models, cones)):
        a = np.where(np.asarray(m, dtype=bool), 1, -1).astype(np.int32)
        a[np.asarray(c, dtype=bool)] = 0
        assign[i, : a.shape[0]] = a
    fn = core.batched_warm_check(d.V, d.NCON, d.NV)
    with telemetry.default_registry().span("driver.warm_screen",
                                           lanes=n):
        outs = [fn(_rows(pts, sl), assign[sl])
                for sl in _chunk_slices(total, CH)]
        ok = np.concatenate([np.asarray(o) for o in jax.device_get(outs)])
    return ok[:n]
