"""Per-problem tensor solve: the reference algorithm as pure JAX.

This module re-implements, with dense fixed-shape state, exactly the
algorithm the host reference engine (:mod:`deppy_tpu.sat.host`) specifies —
which in turn mirrors /root/reference/pkg/sat/solve.go:53-119 and
search.go:34-203:

  * :func:`bcp` / :func:`planes_fixpoint` — boolean-constraint propagation
    to fixpoint.  Clauses and assignments live as packed int32 bitplanes;
    one round evaluates every clause and cardinality row simultaneously
    with bitwise algebra (the TPU-native formulation of watched-literal
    propagation; a [C, K] gather variant remains selectable).
  * :func:`dpll` — complete search under assumptions (the analog of gini
    ``Solve()``): chronological DPLL on a fixed-size decision stack,
    deciding the lowest-index unassigned variable false-first.  A trail of
    per-level plane snapshots makes each iteration propagate only its new
    decision literal from the previous fixpoint, and backtracking a pure
    snapshot restore.
  * :func:`search` — the preference-ordered guess search (search.go:34-203):
    the choice deque and guess stack become fixed-capacity circular-buffer /
    stack tensors.  The four reference loop arms run as lane-gated masked
    selects (not ``lax.switch``, which lowers to select under ``vmap`` and
    would execute every arm for every lane), with guess-trail snapshots so
    pops re-Test for free.
  * :func:`solve_full` — the whole pipeline including extras-only
    cardinality minimization (solve.go:86-113) and deletion-based
    unsat-core minimization (the engine-agnostic analog of gini ``Why``,
    lit_mapping.go:198-207).  Phases are lane-gated: each takes an
    ``enabled`` flag that makes its ``while_loop`` trip zero times on lanes
    that don't need it, because under ``vmap`` a ``lax.cond`` would run
    both branches for every lane anyway.

Everything here is shape-static and batchable with ``jax.vmap``; no Python
control flow depends on traced values.  The batch axis and device-mesh
sharding live in :mod:`deppy_tpu.engine.driver` and
:mod:`deppy_tpu.parallel`.
"""

from __future__ import annotations

import functools
import json
import os
import threading
from contextlib import contextmanager
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import config
from ..analysis import compileguard

# Assignment values (same convention as the host engine).
TRUE = 1
FALSE = -1
UNASSIGNED = 0

# Outcomes (reference solve.go:43-47).  RUNNING doubles as UNKNOWN.
SAT = 1
UNSAT = -1
RUNNING = 0

# Bits per bitplane word.  Bitplanes encode clause/assignment sets as packed
# int32 words (logical-shift arithmetic throughout — Mosaic has no unsigned
# reductions), turning BCP's per-literal gather into dense bitwise algebra:
# the TPU-native formulation of watched-literal propagation.
WORD = 32


class ProblemTensors(NamedTuple):
    """One lowered problem, padded to the batch's common shapes.

    Produced by :func:`deppy_tpu.engine.driver.pad_problem` from
    :class:`deppy_tpu.sat.encode.Problem`.  Conventions: clause literals are
    signed 1-based with 0 padding; every other index tensor is 0-based with
    -1 padding.  ``n_vars``/``n_cons`` are the problem's true sizes inside
    the padding.
    """

    clauses: jax.Array      # i32[C, K]
    card_ids: jax.Array     # i32[NA, M]
    card_n: jax.Array       # i32[NA]
    card_act: jax.Array     # i32[NA]  (-1 on padded rows)
    anchors: jax.Array      # i32[A]   (-1 padded)
    choice_cand: jax.Array  # i32[NC, Kc]
    var_choices: jax.Array  # i32[NV, W]
    n_vars: jax.Array       # i32 scalar
    n_cons: jax.Array       # i32 scalar
    # Bitplane mirrors of the clause matrix and cardinality rows (packed
    # int32, Wv = ceil(V/32) words): the "bits"/"pallas" BCP paths evaluate
    # every clause with bitwise algebra instead of a [C, K] gather.
    pos_bits: jax.Array         # i32[C, Wv]  positive-literal membership
    neg_bits: jax.Array         # i32[C, Wv]  negative-literal membership
    card_member_bits: jax.Array  # i32[NA, Wv] AtMost member sets
    card_act_bits: jax.Array    # i32[NA, Wv] one-hot activation var (0 = pad)
    # Reduced-space planes (packed over the problem-var region only,
    # Wr = ceil(NV/32) words): the search and minimization phases never
    # disable constraint activations — every activation variable is
    # constant TRUE there — so each clause's ¬activation literal is
    # constant-false and folds away.  Dropping the activation columns
    # shrinks every propagation round's plane traffic by V/NV (often
    # 2-3×, the activation region usually outnumbering real variables).
    # Only the unsat-core phase, which probes with activation subsets
    # disabled, needs the full-space planes above.
    pos_bits_r: jax.Array       # i32[C, Wr]
    neg_bits_r: jax.Array       # i32[C, Wr]
    card_member_bits_r: jax.Array  # i32[NA, Wr]
    card_valid: jax.Array       # i32[NA]  1 on real AtMost rows, 0 on pads
    # Compressed clause banks (ISSUE 12): literal→clause adjacency for
    # the implication-driven "watched" BCP impl — occ_pos/occ_neg list
    # the clause rows containing +v/-v (i32[V, Ob], -1 padded; _r =
    # the reduced problem-var space), card_occ the AtMost rows each
    # member variable sits in (i32[NV, Oc]).  Every other impl (and a
    # batch whose occurrence width exceeds its size class's OCC cap)
    # ships 1-row dummies; see deppy_tpu.engine.clause_bank.
    occ_pos: jax.Array          # i32[V, Ob]
    occ_neg: jax.Array          # i32[V, Ob]
    occ_pos_r: jax.Array        # i32[NV, Ob]
    occ_neg_r: jax.Array        # i32[NV, Ob]
    card_occ: jax.Array         # i32[NV, Oc]


class SolveResult(NamedTuple):
    outcome: jax.Array     # i32: SAT / UNSAT / RUNNING (= incomplete)
    installed: jax.Array   # bool[NV] (problem-var region, every impl/mode)
    core: jax.Array        # bool[NCON] active applied constraints (UNSAT only)
    steps: jax.Array       # i32 step counter (tests + DPLL iterations)
    # Backtrack trace (tracer.go:13-15): row i = the guess-variable stack
    # (-1 padded) at the i-th search backtrack.  Shape [T, GS]; T is the
    # static trace capacity (0 = tracing off).  ``trace_n`` counts ALL
    # backtracks, so trace_n > T means the buffer truncated.
    trace_stack: jax.Array  # i32[T, GS]
    trace_n: jax.Array      # i32


# --------------------------------------------------------------------------
# assignment construction


def _base_assignment(pt: ProblemTensors, V: int, NCON: int,
                     act_enabled: jax.Array | None = None) -> jax.Array:
    """All problem vars unassigned; activation vars true (the analog of
    ``AssumeConstraints``, reference lit_mapping.go:136-140) unless an
    explicit ``act_enabled: bool[NCON]`` subset is given (unsat-core mode);
    padding slots pinned false so they never read as unassigned."""
    idx = jnp.arange(V, dtype=jnp.int32)
    in_act = (idx >= pt.n_vars) & (idx < pt.n_vars + pt.n_cons)
    if act_enabled is None:
        act_val = jnp.int32(TRUE)
    else:
        j = jnp.clip(idx - pt.n_vars, 0, NCON - 1)
        act_val = jnp.where(act_enabled[j], TRUE, UNASSIGNED).astype(jnp.int32)
    return jnp.where(
        idx < pt.n_vars,
        jnp.int32(UNASSIGNED),
        jnp.where(in_act, act_val, jnp.int32(FALSE)),
    )


def _base_assignment_red(pt: ProblemTensors, NV: int) -> jax.Array:
    """Reduced-space base assignment: no activation region exists (all
    activations are constant TRUE and folded into the reduced planes);
    padding slots beyond ``n_vars`` are pinned false."""
    idx = jnp.arange(NV, dtype=jnp.int32)
    return jnp.where(idx < pt.n_vars, jnp.int32(UNASSIGNED), jnp.int32(FALSE))


def _apply_anchors(pt: ProblemTensors, assign: jax.Array, V: int) -> jax.Array:
    """Assume every anchor (Mandatory variable) true (solve.go:67-75)."""
    tgt = jnp.where(pt.anchors >= 0, pt.anchors, V)
    return assign.at[tgt].set(TRUE, mode="drop")


def _anchor_mask(pt: ProblemTensors, V: int) -> jax.Array:
    tgt = jnp.where(pt.anchors >= 0, pt.anchors, V)
    return jnp.zeros(V, bool).at[tgt].set(True, mode="drop")


# --------------------------------------------------------------------------
# bitplane algebra (shared by the jnp "bits" path and the Pallas kernel)


def _srl(x: jax.Array, n) -> jax.Array:
    """Logical right shift on int32 (sign bit is data, not sign)."""
    return lax.shift_right_logical(x, n)


def popcount32(v: jax.Array) -> jax.Array:
    """Per-word SWAR popcount on int32 bitplanes (no unsigned types:
    Mosaic cannot reduce unsigned ints; logical shifts keep this exact)."""
    v = v - (_srl(v, 1) & 0x55555555)
    v = (v & 0x33333333) + (_srl(v, 2) & 0x33333333)
    v = (v + _srl(v, 4)) & 0x0F0F0F0F
    return (v + _srl(v, 8) + _srl(v, 16) + _srl(v, 24)) & 0x3F


def or_reduce_rows(x: jax.Array) -> jax.Array:
    """Bitwise-OR reduce over axis 0 → shape [1, ...].  Static halving tree
    (works inside Pallas kernels, where ufunc or-reductions don't lower);
    rows are zero-padded to a power of two first."""
    n = x.shape[0]
    p = 1
    while p < n:
        p <<= 1
    if p != n:
        x = jnp.concatenate(
            [x, jnp.zeros((p - n,) + x.shape[1:], x.dtype)], axis=0
        )
    while p > 1:
        x = x[: p // 2] | x[p // 2 :]
        p //= 2
    return x


def _pow2_pad(x: jax.Array, axis: int, fill) -> tuple:
    """Pad ``axis`` with ``fill`` up to the next power of two; returns
    (padded, padded length)."""
    n = x.shape[axis]
    p = 1
    while p < n:
        p <<= 1
    if p != n:
        shape = list(x.shape)
        shape[axis] = p - n
        x = jnp.concatenate(
            [x, jnp.full(shape, fill, x.dtype)], axis=axis
        )
    return x, p


def _tree_fold(x: jax.Array, axis: int, combine, fill) -> jax.Array:
    """Static halving-tree reduction along ``axis`` (keepdims).  The
    installed Mosaic lowering rejects every *integer* ``reduce_*``
    primitive ("Reductions over integers not implemented", jax 0.4.x),
    while adds/mins and slices always lower — so the kernels reduce by
    tree instead.  Bit-exact vs the reduction primitives: int32 add and
    min are associative."""
    x, p = _pow2_pad(x, axis, fill)
    while p > 1:
        h = p // 2
        x = combine(lax.slice_in_dim(x, 0, h, axis=axis),
                    lax.slice_in_dim(x, h, p, axis=axis))
        p = h
    return x


def tree_sum(x: jax.Array, axis: "int | None" = None,
             keepdims: bool = False) -> jax.Array:
    """Mosaic-safe integer sum (see :func:`_tree_fold`).  ``axis=None``
    reduces every axis to a scalar.  Bools count as int32."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int32)
    if axis is None:
        for ax in range(x.ndim):
            x = _tree_fold(x, ax, lax.add, 0)
        return jnp.squeeze(x)
    axis = axis % x.ndim
    x = _tree_fold(x, axis, lax.add, 0)
    return x if keepdims else jnp.squeeze(x, axis=axis)


def tree_min(x: jax.Array, axis: "int | None" = None,
             keepdims: bool = False) -> jax.Array:
    """Mosaic-safe integer min (see :func:`_tree_fold`)."""
    fill = jnp.iinfo(x.dtype).max
    if axis is None:
        for ax in range(x.ndim):
            x = _tree_fold(x, ax, lax.min, fill)
        return jnp.squeeze(x)
    axis = axis % x.ndim
    x = _tree_fold(x, axis, lax.min, fill)
    return x if keepdims else jnp.squeeze(x, axis=axis)


def tree_max(x: jax.Array, axis: "int | None" = None,
             keepdims: bool = False) -> jax.Array:
    """Mosaic-safe integer max (see :func:`_tree_fold`)."""
    fill = jnp.iinfo(x.dtype).min
    if axis is None:
        for ax in range(x.ndim):
            x = _tree_fold(x, ax, lax.max, fill)
        return jnp.squeeze(x)
    axis = axis % x.ndim
    x = _tree_fold(x, axis, lax.max, fill)
    return x if keepdims else jnp.squeeze(x, axis=axis)


def pack_mask(mask: jax.Array, Wv: int) -> jax.Array:
    """bool[V] → packed i32[1, Wv] bitplane.  Distinct bit positions make the
    int32 sum carry-free, i.e. an OR."""
    V = mask.shape[0]
    pad = Wv * WORD - V
    m = mask
    if pad:
        m = jnp.concatenate([m, jnp.zeros(pad, bool)])
    m = m.reshape(Wv, WORD).astype(jnp.int32)
    shifts = jnp.arange(WORD, dtype=jnp.int32)[None, :]
    return tree_sum(m << shifts, axis=1)[None, :]


def unpack_mask(words: jax.Array, V: int) -> jax.Array:
    """packed i32[1, Wv] → bool[V]."""
    shifts = jnp.arange(WORD, dtype=jnp.int32)[None, :]
    bits = (_srl(words.reshape(-1, 1), shifts) & 1).astype(bool)
    return bits.reshape(-1)[:V]


# Mesh axis for clause-sharded propagation (intra-problem parallelism,
# SURVEY.md §2.7 axis 3 / §5's beyond-one-core scaling): when set, each
# device holds a row shard of the clause/cardinality planes and every
# propagation round combines the per-shard unit/conflict partials with an
# OR collective.  Trace-time state (like _BCP_IMPL) so the whole solve
# stack runs unmodified inside ``shard_map`` — control flow is replicated,
# only the clause row axis is distributed.  Thread-local: a retrace of an
# unsharded program on another thread while one thread holds the context
# must not capture the collectives (an unbound axis name outside
# shard_map is a trace error).
_AXIS_STATE = threading.local()


def _clause_axis_name() -> "str | None":
    return getattr(_AXIS_STATE, "name", None)


class clause_axis:
    """Context manager: trace the enclosed programs with clause-row
    collectives over ``name`` (a mesh axis inside ``shard_map``)."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._prev = _clause_axis_name()
        _AXIS_STATE.name = self.name
        return self

    def __exit__(self, *exc):
        _AXIS_STATE.name = self._prev
        return False


def _axis_or_fused(wpos: jax.Array, wneg: jax.Array, conflict: jax.Array,
                   axis_name: str) -> tuple:
    """Combine a round's shard partials in ONE collective: the forced
    masks and the conflict flag concatenate into a single [1, 2Wv+1]
    buffer, one all-gather crosses ICI, and the OR-fold splits back out
    (conflict OR == any)."""
    Wv = wpos.shape[1]
    buf = jnp.concatenate(
        [wpos, wneg, conflict.astype(jnp.int32).reshape(1, 1)], axis=1
    )
    g = lax.all_gather(buf, axis_name)  # [D, 1, 2Wv+1]
    out = g[0]
    for i in range(1, g.shape[0]):
        out = out | g[i]
    return out[:, :Wv], out[:, Wv: 2 * Wv], out[0, 2 * Wv] != 0


def round_planes(pos, neg, mem, card_active, card_n2, min_bits, min_w, t, f):
    """One propagation round on bitplanes — the exact bitwise translation of
    :func:`bcp_round` (itself the dense analog of gini's watched-literal BCP).
    Shapes: pos/neg i32[C, Wv]; mem i32[NA, Wv]; card_active bool[NA, 1];
    card_n2 i32[NA, 1]; min_bits/t/f i32[1, Wv]; min_w i32 scalar.  Returns
    (conflict, new_t, new_f, changed).  Runs unchanged under jit and inside
    the Pallas kernel (:mod:`deppy_tpu.engine.pallas_bcp`).

    ``card_active`` is precomputed by the caller: activation variables are
    assumptions — propagation never flips one (a clause forcing ¬act on a
    true act is a conflict, not a flip) — so row activity is invariant
    across a fixpoint and need not be re-derived every round.

    Under :class:`clause_axis`, ``pos``/``neg``/``mem`` rows are one mesh
    shard of the problem's clause set and ``t``/``f``/``min_bits`` are
    replicated: the per-shard forced-literal masks and conflict flags
    combine with one fused OR all-gather per round — the only cross-device
    traffic of a clause-sharded solve, a few dozen words per round over
    ICI."""
    a = t | f
    sat = (((pos & t) | (neg & f)) != 0).any(axis=1, keepdims=True)   # [C,1]
    upos = pos & ~a
    uneg = neg & ~a
    n_un = tree_sum(popcount32(upos), axis=1, keepdims=True) + tree_sum(
        popcount32(uneg), axis=1, keepdims=True
    )                                                                  # [C,1]
    valid = ((pos | neg) != 0).any(axis=1, keepdims=True)
    dead = valid & ~sat & (n_un == 0)
    unit = valid & ~sat & (n_un == 1)
    wpos = or_reduce_rows(jnp.where(unit, upos, 0))                    # [1,Wv]
    wneg = or_reduce_rows(jnp.where(unit, uneg, 0))

    # AtMost rows: count true / unassigned members; > n conflicts, == n
    # forces the rest false.
    active = card_active                                               # [NA,1]
    trues = tree_sum(popcount32(mem & t), axis=1, keepdims=True)
    unk = tree_sum(popcount32(mem & ~a), axis=1, keepdims=True)
    over = active & (trues > card_n2)
    full = active & (trues == card_n2) & (unk > 0)
    wneg = wneg | or_reduce_rows(jnp.where(full, mem & ~a, 0))

    # Dynamic "at most w of the extras" bound for the minimization loop.
    # (min_bits/t are replicated under clause sharding — no collective.)
    mtrues = tree_sum(popcount32(min_bits & t))
    min_over = mtrues > min_w
    wneg = jnp.where(mtrues == min_w, wneg | (min_bits & ~a), wneg)

    row_conflict = dead.any() | over.any()
    axis = _clause_axis_name()
    if axis is not None:
        # Combine shard partials: forced-literal masks OR together (the
        # replicated min-bound contribution is idempotent under OR), row
        # conflicts any-reduce — all in one fused all-gather.
        wpos, wneg, row_conflict = _axis_or_fused(
            wpos, wneg, row_conflict, axis
        )
    conflict = row_conflict | min_over | ((wpos & wneg) != 0).any()
    new_t = t | (wpos & ~a)
    new_f = f | (wneg & ~a)
    changed = ((new_t != t) | (new_f != f)).any() & ~conflict
    return conflict, new_t, new_f, changed


# --------------------------------------------------------------------------
# BCP


def bcp_round(pt: ProblemTensors, assign: jax.Array,
              min_mask: jax.Array, min_w: jax.Array
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One propagation round: evaluate every clause and cardinality row,
    derive implied literals, detect conflicts.  Returns
    (conflict, new_assign, changed).  This is the hot op the Pallas kernel
    (:mod:`deppy_tpu.engine.pallas_bcp`) specializes."""
    V = assign.shape[0]
    cls_mask = pt.clauses != 0
    cls_var = jnp.where(cls_mask, jnp.abs(pt.clauses) - 1, 0)
    cls_sign = jnp.sign(pt.clauses)
    cls_valid = cls_mask.any(axis=1)

    vals = assign[cls_var] * cls_sign
    vals = jnp.where(cls_mask, vals, jnp.int32(FALSE))
    satc = (vals == TRUE).any(axis=1)
    n_un = (vals == UNASSIGNED).sum(axis=1)
    dead = cls_valid & ~satc & (n_un == 0)
    unit = cls_valid & ~satc & (n_un == 1)
    ucol = jnp.argmax(vals == UNASSIGNED, axis=1)
    uvar = jnp.take_along_axis(cls_var, ucol[:, None], axis=1)[:, 0]
    usign = jnp.take_along_axis(cls_sign, ucol[:, None], axis=1)[:, 0]
    wpos = jnp.zeros(V, jnp.int32).at[uvar].max((unit & (usign > 0)).astype(jnp.int32))
    wneg = jnp.zeros(V, jnp.int32).at[uvar].max((unit & (usign < 0)).astype(jnp.int32))

    # Native cardinality rows (AtMost): count true members; > n is a
    # conflict, == n forces every unassigned member false — the
    # arc-consistency equivalent of gini's CardSort network.
    card_mask = pt.card_ids >= 0
    card_var = jnp.where(card_mask, pt.card_ids, 0)
    card_valid = pt.card_act >= 0
    act_idx = jnp.where(card_valid, pt.card_act, 0)
    mvals = assign[card_var]
    trues = ((mvals == TRUE) & card_mask).sum(axis=1)
    unk = ((mvals == UNASSIGNED) & card_mask).sum(axis=1)
    active = card_valid & (assign[act_idx] == TRUE)
    over = active & (trues > pt.card_n)
    full = active & (trues == pt.card_n) & (unk > 0)
    force = full[:, None] & card_mask & (mvals == UNASSIGNED)
    wneg = wneg.at[card_var].max(force.astype(jnp.int32))

    # Dynamic "at most w of the extras" side-constraint used by the
    # minimization loop (the native replacement for CardinalityConstrainer
    # + Leq(w), solve.go:100-110).
    mtrues = ((assign == TRUE) & min_mask).sum()
    min_over = mtrues > min_w
    min_force = (mtrues == min_w) & (assign == UNASSIGNED) & min_mask
    wneg = jnp.maximum(wneg, min_force.astype(jnp.int32))

    conflict = dead.any() | over.any() | min_over | ((wpos == 1) & (wneg == 1)).any()
    unas = assign == UNASSIGNED
    new = jnp.where(
        unas & (wpos == 1),
        jnp.int32(TRUE),
        jnp.where(unas & (wneg == 1), jnp.int32(FALSE), assign),
    )
    changed = (new != assign).any() & ~conflict
    return conflict, new, changed


# BCP implementation selection: "gather" = the [C, K] literal-gather round
# above; "bits" = jnp bitplane algebra; "pallas" = the fused fixpoint kernel
# holding the planes in VMEM across rounds; "watched" = the compressed
# clause-bank implication-driven path (engine/clause_bank.py — visits
# only the clauses adjacent to a newly-falsified literal instead of
# scanning every row per round).  "auto" = the measured-defaults
# registry's "bcp" row for this backend when one exists, else "bits":
# measured on a real v5-lite chip (256-problem random-catalog batch),
# bits is 18.7× faster than gather (368/s vs 19.7/s) and 1.8× faster
# than the Pallas kernel — under vmap, XLA vectorizes the batch axis of
# the bitplane algebra across VPU lanes, while a vmapped pallas_call
# serializes problems into grid steps.  The kernel pays off only for
# single very large problems (clause planes near VMEM capacity), so it
# stays opt-in; "watched" likewise defaults off until a measured A/B
# row lands (scripts/tpu_ab.py carries the variant).  Measured on this
# box (CPU XLA, r12): watched wins 7x on deep-implication-chain batches
# (1855/s vs 260/s, 96 lanes x depths 48-192) and loses ~10% on the
# mixed random-catalog fleet — benchmarks/results/bcp_rewrite_r12.json.
_BCP_IMPL = config.env_raw("DEPPY_TPU_BCP", "auto")

_BCP_IMPLS = ("auto", "gather", "bits", "pallas", "blockwise", "watched")

# Propagation rounds applied per fixpoint while_loop trip (the "bits"
# path only).  >1 trades redundant work on converged lanes for fewer
# loop trips — a bet on per-trip scheduling overhead, i.e. a TPU knob;
# exit states are bit-identical at any setting (see planes_fixpoint).
# Measured on CPU XLA it LOSES outright (deep-chain config: 7552/s at
# 1 vs 6563/s at 2 vs 2631/s at 3; random catalog the same shape) —
# per-trip overhead is negligible there and the redundant gated round
# dominates.  Default 1; A/B on a real TPU before ever raising it.
_BCP_UNROLL = max(1, int(config.env_raw("DEPPY_TPU_BCP_UNROLL", "1")))

# Decision steps applied per dpll while_loop trip — the decision-level
# twin of _BCP_UNROLL, one level up the trip hierarchy (search trips =
# episodes × decisions × propagation rounds; this attacks the middle
# factor).  The dpll body is fully lane-gated on a ``live`` predicate
# (status RUNNING and in budget), so K-fold body repetition inside one
# trip is exit-state- and step-count-identical at any K: a finished or
# budget-exhausted lane's extra applications are no-ops.  Same bet
# shape as _BCP_UNROLL — redundant gated work for fewer ~175µs trips —
# and same policy: default 1 everywhere until a real-chip A/B row
# exists (scripts/tpu_ab.py carries dpll-unroll variants).
_DPLL_UNROLL = max(1, int(config.env_raw("DEPPY_TPU_DPLL_UNROLL", "1")))

# Episode-control steps (guess-stack pushes/pops) applied per control
# while_loop trip — the outermost factor of the trip product.  Same
# gated-repeat construction and same identity contract as _DPLL_UNROLL
# (the control body's arms are selected under a ``live`` predicate);
# default 1 until an on-chip A/B row exists.
_CTL_UNROLL = max(1, int(config.env_raw("DEPPY_TPU_CTL_UNROLL", "1")))


def _batch_planes(clauses: jax.Array, W: int) -> Tuple[jax.Array, jax.Array]:
    """Batched signed clause matrices [B, C, K] → (pos, neg) packed int32
    bitplanes [B, C, W].  The device-side equivalent of the driver's numpy
    packing.  O(K) emitted ops (K is small and static): each literal
    column scatters into its word via a one-hot compare over the word
    axis, OR-folded into the accumulators — compile size stays flat as W
    grows (the near-VMEM single-problem case has W in the hundreds)."""
    B, C, K = clauses.shape
    w_idx = jnp.arange(W, dtype=jnp.int32)
    acc_p = jnp.zeros((B, C, W), jnp.int32)
    acc_n = jnp.zeros((B, C, W), jnp.int32)
    for k in range(K):
        lit = clauses[..., k]
        v = jnp.where(lit != 0, jnp.abs(lit) - 1, 0)
        onehot = _srl(v, 5)[..., None] == w_idx
        bit = jnp.left_shift(jnp.int32(1), v & 31)[..., None]
        acc_p = acc_p | jnp.where(onehot & (lit > 0)[..., None], bit, 0)
        acc_n = acc_n | jnp.where(onehot & (lit < 0)[..., None], bit, 0)
    return acc_p, acc_n


def _batch_index_planes(rows: jax.Array, W: int) -> jax.Array:
    """Batched 0-based index matrices [B, R, M] (-1 pad) → packed int32
    membership bitplanes [B, R, W].  Same O(M)-op structure as
    :func:`_batch_planes`."""
    B, R, M = rows.shape
    w_idx = jnp.arange(W, dtype=jnp.int32)
    acc = jnp.zeros((B, R, W), jnp.int32)
    for m in range(M):
        v0 = rows[..., m]
        valid = v0 >= 0
        v = jnp.where(valid, v0, 0)
        onehot = _srl(v, 5)[..., None] == w_idx
        bit = jnp.left_shift(jnp.int32(1), v & 31)[..., None]
        acc = acc | jnp.where(onehot & valid[..., None], bit, 0)
    return acc


def derive_planes(clauses: jax.Array, card_ids: jax.Array,
                  card_act: jax.Array, n_vars: jax.Array,
                  *, Wv: int, Wr: int, red: bool, full: bool = True
                  ) -> Tuple[jax.Array, ...]:
    """Compute packed-bitplane fields of :class:`ProblemTensors` from the
    compact clause/cardinality tensors, on device and batched.

    Returns (pos_bits, neg_bits, card_member_bits, card_act_bits,
    pos_bits_r, neg_bits_r, card_member_bits_r).  The driver calls this
    once per uploaded chunk (jitted, cached per shape): dispatches ship
    only the compact [B, C, K] literal matrices and the device builds the
    plane variants in a few fused passes instead of a host numpy loop.

    ``red``/``full`` select which spaces materialize (the other side comes
    back as 1-word dummies): the bits impl's search/minimization phases
    read only the reduced problem-var space, so SAT-dominated batches
    never hold full-space planes resident — only a dispatch that will run
    the unsat-core phase (which probes with activations disabled) asks for
    ``full=True``."""
    B, C, _ = clauses.shape
    NA = card_ids.shape[1]
    if full:
        pos, neg = _batch_planes(clauses, Wv)
        member = _batch_index_planes(card_ids, Wv)
        act_bits = _batch_index_planes(card_act[:, :, None], Wv)
    else:
        pos = jnp.zeros((B, C, 1), jnp.int32)
        neg = jnp.zeros((B, C, 1), jnp.int32)
        member = jnp.zeros((B, NA, 1), jnp.int32)
        act_bits = jnp.zeros((B, NA, 1), jnp.int32)
    if red:
        cl_r = jnp.where(jnp.abs(clauses) <= n_vars[:, None, None], clauses, 0)
        pos_r, neg_r = _batch_planes(cl_r, Wr)
        mem_r = _batch_index_planes(card_ids, Wr)
    else:
        pos_r = jnp.zeros((B, C, 1), jnp.int32)
        neg_r = jnp.zeros((B, C, 1), jnp.int32)
        mem_r = jnp.zeros((B, NA, 1), jnp.int32)
    return pos, neg, member, act_bits, pos_r, neg_r, mem_r


def clear_batched_caches() -> None:
    """Drop every cached batched_* entry-point wrapper (and with them
    their compiled executables).  Shared by :func:`set_bcp_impl` and
    :func:`deppy_tpu.engine.clear_compile_caches` — add new cached entry
    points here so both invalidation paths stay complete."""
    batched_solve.cache_clear()
    batched_search.cache_clear()
    batched_core.cache_clear()
    batched_minimize_gated.cache_clear()
    batched_core_gated.cache_clear()
    # A deliberate drop means the recompiles that follow are expected:
    # zero the compile-guard ledger so they don't read as a storm.
    compileguard.reset_counts()


def pallas_interpret() -> bool:
    """Whether the Pallas kernels run in interpret mode: on the CPU
    backend only (the test suite's differential runs).  On TPU they are
    compiled by Mosaic; any other backend has no kernel path and raises
    rather than interpreting on the accelerator."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    from ..sat.errors import BackendCapabilityError

    raise BackendCapabilityError(
        "pallas", backend,
        hint="Pallas kernels compile for TPU and interpret on CPU only")


def set_bcp_impl(name: str) -> None:
    """Select the BCP implementation ('auto'|'gather'|'bits'|'pallas'|
    'blockwise'|'watched') and invalidate compiled solves."""
    global _BCP_IMPL
    if name not in _BCP_IMPLS:
        raise ValueError(f"unknown BCP impl {name!r}")
    _BCP_IMPL = name
    clear_batched_caches()


# Phase-1 search substrate: "xla" = the vmapped lockstep program in this
# module; "fused" = the whole phase in ONE Pallas kernel per problem
# (engine/pallas_search.py) — the escalation against the XLA search's
# per-while-trip scheduling overhead (round-3 verdict #1).
# "auto" = "xla" unless a MEASURED default exists for the current
# backend (measured_defaults.json — written by the revalidation
# ladder's stage F3 only after a same-run Mosaic smoke pass + paired
# A/B win + full headline bench under the knob; every device bet in
# this tree defaults off until such a measured row exists).  The env
# knob and set_search_impl always override.
_SEARCH_IMPL = config.env_raw("DEPPY_TPU_SEARCH", "auto")

# Measured-default registry: {backend: {"search": "fused"|"xla", ...}}.
# Package-local so an installed wheel carries its measured defaults;
# DEPPY_TPU_MEASURED_DEFAULTS overrides the path (tests, the ladder).
_MEASURED_DEFAULTS_PATH = config.env_raw(
    "DEPPY_TPU_MEASURED_DEFAULTS",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "measured_defaults.json"))
_MEASURED_DEFAULTS: Optional[dict] = None


def measured_default(key: str) -> Optional[str]:
    """The measured default recorded for ``key`` on the current backend
    (None when no measured row exists).  Keys in use: ``search``
    (phase-substrate: 'fused'|'xla'), ``spec_core`` ('on'|'off'), and
    ``bcp`` (propagation impl, e.g. 'watched'|'bits')."""
    global _MEASURED_DEFAULTS
    # Reachable at trace time via _resolved_impl (the auto impl route):
    # the registry read is memoized into module state whose only write
    # path (reload_measured_defaults) drops every compiled program, so
    # a traced program can never go stale against it — the exact
    # contract the compile-surface/trace-purity rules exist to enforce.
    # deppy: lint-ok[compile-surface] memoized; reload_measured_defaults invalidates the jit caches
    if _MEASURED_DEFAULTS is None:
        try:
            # deppy: lint-ok[trace-purity] one memoized registry read; re-traces reuse the cached dict
            with open(_MEASURED_DEFAULTS_PATH) as f:
                loaded = json.load(f)
            _MEASURED_DEFAULTS = loaded if isinstance(loaded, dict) else {}
        except (OSError, ValueError):
            _MEASURED_DEFAULTS = {}
    # deppy: lint-ok[compile-surface] memoized; reload_measured_defaults invalidates the jit caches
    entry = _MEASURED_DEFAULTS.get(jax.default_backend())
    val = entry.get(key) if isinstance(entry, dict) else None
    return val if isinstance(val, str) else None


def _measured_default_search() -> Optional[str]:
    impl = measured_default("search")
    return impl if impl in ("fused", "xla") else None


def reload_measured_defaults() -> None:
    """Drop the cached measured-default registry (tests; the ladder
    after writing a new row) and invalidate compiled solves."""
    global _MEASURED_DEFAULTS
    _MEASURED_DEFAULTS = None
    clear_batched_caches()


def set_search_impl(name: str) -> None:
    """Select the phase-1 search substrate ('auto'|'xla'|'fused') and
    invalidate compiled solves."""
    global _SEARCH_IMPL
    if name not in ("auto", "xla", "fused"):
        raise ValueError(f"unknown search impl {name!r}")
    _SEARCH_IMPL = name
    clear_batched_caches()


def _resolved_search_impl() -> str:
    if _SEARCH_IMPL == "auto":
        return _measured_default_search() or "xla"
    return _SEARCH_IMPL


def _fused_routable(pts, arr) -> bool:
    """The one dispatch rule shared by every fused-kernel factory
    (batched_search / batched_minimize_gated / batched_core /
    batched_core_gated): mesh-sharded batches stay on the XLA programs
    (a pallas_call over a multi-device batch would need shard_map
    plumbing the fused path doesn't have), and the batch's static shapes
    must fit the kernel's unroll caps.  ``arr`` is the tensor whose
    sharding decides (the planes the phase actually reads)."""
    from . import pallas_search

    sharding = getattr(arr, "sharding", None)
    multi = sharding is not None and len(sharding.device_set) > 1
    return not multi and pallas_search.fused_supported(pts)


def _has_full_planes(pts, V: int) -> bool:
    """Whether this batch carries REAL full-space bit planes.  Under the
    gather impl (``phases_reduced()`` False and no bits planes anywhere)
    the driver ships 1-row placeholders — the XLA core phase walks
    ``pt.clauses`` directly and never reads them, but the fused deletion
    kernel inlines bits algebra and MUST see the real planes (caught by
    the gather+fused knob-combination test: a placeholder makes every
    probe misbehave and the core comes back unminimized).  Checks BOTH
    placeholder conventions: the 1-row gather dummy (row count) and the
    1-word pack=False dummy (word width vs the V the planes must
    cover)."""
    rows_ok = pts.pos_bits.shape[-2] == pts.clauses.shape[-2]
    width_ok = pts.pos_bits.shape[-1] == -(-V // WORD)
    return rows_ok and width_ok


# Per-size-class impl override (ISSUE 13 satellite): the driver scopes
# each dispatch to its ladder class's measured `bcp.<class>` row so
# deep-chain classes can run `watched` while the mixed fleet keeps
# `bits` — closing PR 12's "~10% loss on the mixed fleet" compromise.
# Thread-local (mesh shard workers dispatch concurrently).  Safe
# against stale compiled programs because the driver classifies each
# dispatch by its PADDED batch dims (driver.padded_class: cost over
# the bucketed C/NV/NCON maxima — a function of exactly the dims that
# key jit's shape cache), so two dispatches reaching the same
# compiled program always resolve the same class, hence the same
# impl.  Only
# the reduced-space impls (bits/watched) are honored per class —
# a per-class `gather` row would flip ``phases_reduced()`` under a
# factory wrapper whose ``red`` was baked at a shape key that does not
# include C.
_IMPL_TLS = threading.local()
_CLASS_ROUTABLE = ("bits", "watched")


@contextmanager
def impl_scope(impl: "Optional[str]"):
    """Scope the resolved BCP impl for one dispatch (driver use only).
    ``None`` is a no-op scope — the global resolution applies."""
    prev = getattr(_IMPL_TLS, "impl", None)
    _IMPL_TLS.impl = impl
    try:
        yield
    finally:
        _IMPL_TLS.impl = prev


def resolved_impl_for(class_name: "Optional[str]") -> str:
    """The BCP impl a dispatch of ladder class ``class_name`` should
    run: the explicit global knob when set, else the measured
    ``bcp.<class>`` row, else the global ``bcp`` row, else bits."""
    if _BCP_IMPL != "auto":
        return _BCP_IMPL
    if class_name is not None:
        measured = measured_default(f"bcp.{class_name}")
        if measured in _CLASS_ROUTABLE:
            return measured
    measured = measured_default("bcp")
    if measured in _BCP_IMPLS and measured != "auto":
        return measured
    return "bits"


def _resolved_impl() -> str:
    # deppy: lint-ok[compile-surface] trace-time impl dispatch by design: set_bcp_impl's write invalidates every compiled program via clear_batched_caches
    impl = _BCP_IMPL
    if impl != "auto":
        return impl
    # Per-dispatch class scope (impl_scope) wins over the global row —
    # the driver only installs one when the global knob is "auto", and
    # the class↔shape argument above keeps traced programs consistent.
    override = getattr(_IMPL_TLS, "impl", None)
    if override is not None:
        return override
    # Measured-defaults route (ISSUE 12 policy: engine bets become
    # defaults only behind a same-backend A/B row, never by fiat).
    measured = measured_default("bcp")
    if measured in _BCP_IMPLS and measured != "auto":
        return measured
    return "bits"


def _bcp_gather(pt: ProblemTensors, assign: jax.Array,
                min_mask: jax.Array, min_w: jax.Array, enabled: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    def cond(state):
        conflict, _, changed = state
        return ~conflict & changed

    def body(state):
        _, a, _ = state
        return bcp_round(pt, a, min_mask, min_w)

    state = (jnp.bool_(False), assign, enabled)
    conflict, assign, _ = lax.while_loop(cond, body, state)
    return conflict, assign


def bcp(pt: ProblemTensors, assign: jax.Array,
        min_mask: jax.Array, min_w: jax.Array,
        enabled: "jax.Array | bool" = True) -> Tuple[jax.Array, jax.Array]:
    """Propagate to fixpoint (the analog of gini ``Test`` propagation;
    host reference: HostEngine._bcp).  Returns (conflict, assignment).
    Dispatches to the implementation chosen by :func:`set_bcp_impl` /
    ``DEPPY_TPU_BCP``.

    ``enabled`` seeds the fixpoint loop's ``changed`` flag: a disabled lane
    runs **zero** rounds.  This is the lane-gating idiom used throughout
    the engine — under ``vmap``, ``lax.cond``/``lax.switch`` lower to
    select (every branch executes for every lane), so skipping work must be
    expressed as a ``while_loop`` whose condition is immediately false for
    inactive lanes."""
    impl = _resolved_impl()
    if impl == "gather":
        return _bcp_gather(pt, assign, min_mask, min_w, enabled)
    V = assign.shape[0]
    Wv = pt.pos_bits.shape[1]
    t = pack_mask(assign == TRUE, Wv)
    f = pack_mask(assign == FALSE, Wv)
    conflict, t, f = planes_fixpoint(
        pt, t, f, pack_mask(min_mask, Wv), min_w, enabled, V
    )
    return conflict, planes_to_assign(t, f, V)


def planes_fixpoint(pt: ProblemTensors, t: jax.Array, f: jax.Array,
                    min_bits: jax.Array, min_w: jax.Array,
                    enabled: jax.Array, V: int, red: bool = False
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fixpoint directly on packed (t, f) planes — the incremental engine
    primitive: starting from a previous fixpoint plus newly set literals,
    propagation converges in the few rounds the *new* implications need
    (BCP is monotone and confluent, so the result equals a from-scratch
    run).  Returns (conflict, t, f).  Dispatches on the selected impl; the
    gather path unpacks to assignment form and back.

    ``red`` (static) selects the reduced problem-var-only plane space (see
    ProblemTensors.pos_bits_r): activations are constant TRUE there, so row
    activity is just row validity.  Only the "bits" impl supports it."""
    impl = _resolved_impl()
    card_n2 = pt.card_n[:, None]
    # Incremental starts can assert a literal whose negation is already
    # set (e.g. guessing a candidate that propagation forced false): that
    # t∧f overlap IS the conflict, and it must be caught here — a clause
    # containing the overlapped variable reads as satisfied to the round
    # kernel, masking it.  From-scratch starts never overlap.
    pre_conflict = enabled & ((t & f) != 0).any()
    run = enabled & ~pre_conflict
    if impl == "gather":
        assert not red, "reduced planes are a bits-impl path"
        assign = planes_to_assign(t, f, V)
        conflict, assign = _bcp_gather(
            pt, assign, unpack_mask(min_bits, V), min_w, run
        )
        Wv = t.shape[1]
        return (conflict | pre_conflict,
                pack_mask(assign == TRUE, Wv), pack_mask(assign == FALSE, Wv))
    if red:
        assert impl in ("bits", "watched"), \
            "reduced planes are a bits/watched-impl path"
        pos, neg, mem = pt.pos_bits_r, pt.neg_bits_r, pt.card_member_bits_r
        card_active = (pt.card_valid != 0)[:, None]
    else:
        pos, neg, mem = pt.pos_bits, pt.neg_bits, pt.card_member_bits
        # Activation bits never flip inside a fixpoint (see round_planes),
        # so row activity is computed once from the entry state.
        card_active = ((pt.card_act_bits & t) != 0).any(axis=1, keepdims=True)
    if impl == "watched" and _clause_axis_name() is None:
        # Implication-driven propagation over the compressed clause
        # bank (ISSUE 12).  A dummy bank — the driver ships one when
        # the batch's occurrence width exceeds its size class's OCC cap
        # — statically falls through to the dense rounds below.  Under
        # clause sharding the bank rows would straddle shards, so the
        # sharded program stays on the dense rounds (which carry the
        # per-round collective).
        from . import clause_bank

        occ_p = pt.occ_pos_r if red else pt.occ_pos
        occ_n = pt.occ_neg_r if red else pt.occ_neg
        if clause_bank.bank_ready(occ_p):
            conflict, t, f = clause_bank.watched_fixpoint(
                pt.clauses, pt.n_vars, occ_p, occ_n, pt.card_occ,
                pos, neg, mem, card_active, card_n2, min_bits,
                min_w, t, f, run, red,
            )
            return conflict | pre_conflict, t, f
    if impl == "pallas":
        from . import pallas_bcp

        conflict, t, f = pallas_bcp.bcp_fixpoint(
            pos, neg, mem, card_active, card_n2, min_bits, min_w, t, f, run,
        )
        return conflict | pre_conflict, t, f
    if impl == "blockwise":
        from . import pallas_blockwise

        conflict, t, f = pallas_blockwise.bcp_fixpoint(
            pos, neg, mem, card_active, card_n2, min_bits, min_w, t, f, run,
        )
        return conflict | pre_conflict, t, f

    def cond(state):
        conflict, _, _, changed = state
        return ~conflict & changed

    def body(state):
        _, t, f, _ = state
        c, t, f, ch = round_planes(
            pos, neg, mem, card_active, card_n2, min_bits, min_w, t, f,
        )
        # Optional unroll: more propagation rounds per loop trip (deep
        # implication chains advance one link per round, and each
        # while_loop trip has fixed scheduling overhead — a TPU lever).
        # Exit state stays bit-identical to the 1-round loop: extra
        # applications are gated on the trip's flags so a conflicted or
        # converged state passes through unchanged (confluence would
        # make any interleaving equivalent anyway; gating keeps even the
        # intermediate states aligned).
        for _ in range(_BCP_UNROLL - 1):
            c2, t2, f2, ch2 = round_planes(
                pos, neg, mem, card_active, card_n2, min_bits, min_w, t, f,
            )
            keep = ~c & ch
            t = jnp.where(keep, t2, t)
            f = jnp.where(keep, f2, f)
            ch = jnp.where(keep, ch2, ch)
            c = c | (keep & c2)
        return c, t, f, ch

    conflict, t, f, _ = lax.while_loop(cond, body, (jnp.bool_(False), t, f, run))
    return conflict | pre_conflict, t, f


def planes_to_assign(t: jax.Array, f: jax.Array, V: int) -> jax.Array:
    """(t, f) planes → int32 assignment vector."""
    tb = unpack_mask(t, V)
    fb = unpack_mask(f, V)
    return jnp.where(
        tb, jnp.int32(TRUE), jnp.where(fb, jnp.int32(FALSE), jnp.int32(UNASSIGNED))
    )


def set_plane_bit(plane: jax.Array, var: jax.Array, on: jax.Array) -> jax.Array:
    """Set bit ``var`` in a packed [1, Wv] plane when ``on`` (no-op
    otherwise).  ``var`` is a traced index."""
    word = var // WORD
    bit = jnp.int32(1) << (var % WORD)
    cur = plane[0, word]
    return plane.at[0, word].set(jnp.where(on, cur | bit, cur))


# --------------------------------------------------------------------------
# Test


def test_outcome(conflict: jax.Array, t: jax.Array, f: jax.Array,
                 pvb: jax.Array) -> jax.Array:
    """Outcome of a propagated plane state — the analog of gini ``Test``'s
    result (solve.go:79, search.go:76): UNSAT on conflict, SAT only when
    propagation alone totalizes the problem-var region (``pvb`` = packed
    problem-var mask), else RUNNING.  The single definition shared by the
    baseline Test, the search's push Test, and dpll's totality check."""
    all_assigned = ((pvb & ~(t | f)) == 0).all()
    return jnp.where(
        conflict, jnp.int32(UNSAT),
        jnp.where(all_assigned, jnp.int32(SAT), jnp.int32(RUNNING)),
    )


# --------------------------------------------------------------------------
# DPLL


def dpll(pt: ProblemTensors, t_init: jax.Array, f_init: jax.Array,
         min_bits: jax.Array, min_w: jax.Array, budget: jax.Array,
         steps: jax.Array, NV: int, V: int,
         enabled: "jax.Array | bool" = True, red: bool = False
         ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Complete search under the fixed partial assignment given as packed
    ``(t_init, f_init)`` planes — the analog of gini ``Solve()``
    (search.go:168, solve.go:107) and of HostEngine._dpll: false-first
    decisions on the lowest-index unassigned problem variable,
    chronological backtracking that flips the deepest unflipped decision.

    Trail-style snapshots: ``snap[k]`` holds the packed-plane fixpoint
    after ``k`` decisions, so each iteration propagates only the *new*
    decision literal from the previous fixpoint (BCP is monotone and
    confluent — the incremental fixpoint equals the from-scratch one), and
    backtracking restores a snapshot instead of re-propagating the whole
    stack.  The decision order, phases, and discovered model are identical
    to the rebuild-from-scratch formulation.  All inputs and the returned
    model stay in packed plane form — no [V]-length unpack anywhere on the
    iteration path.  Returns (status, model_t, model_f, steps).

    A disabled lane runs zero iterations and returns status RUNNING; the
    caller must discard it (see :func:`bcp` for the lane-gating idiom)."""
    Wv = (pt.pos_bits_r if red else pt.pos_bits).shape[1]
    lvl = jnp.arange(NV, dtype=jnp.int32)
    pvb = pack_mask(jnp.arange(V, dtype=jnp.int32) < pt.n_vars, Wv)

    conflict0, t0, f0 = planes_fixpoint(
        pt, t_init, f_init, min_bits, min_w, enabled, V, red
    )
    status0 = jnp.where(conflict0, jnp.int32(UNSAT), jnp.int32(RUNNING))
    snap_t0 = jnp.zeros((NV + 1, Wv), jnp.int32).at[0].set(t0[0])
    snap_f0 = jnp.zeros((NV + 1, Wv), jnp.int32).at[0].set(f0[0])

    def body(st):
        (dec_var, dec_phase, sp, flip, status, m_t, m_f,
         snap_t, snap_f, steps) = st
        t = snap_t[jnp.clip(sp, 0, NV)][None, :]
        f = snap_f[jnp.clip(sp, 0, NV)][None, :]

        # SAT when the problem-var region is totalized at the current level
        # (a pending flip always has its own variable unassigned, so this
        # can only fire on the decide path).  First-unassigned comes from
        # packed bit algebra: lowest set bit of the first nonzero word.
        un_words = (pvb & ~(t | f))[0]
        nz = un_words != 0
        has_un = nz.any()
        wi = jnp.argmax(nz).astype(jnp.int32)
        word = un_words[wi]
        lsb = word & -word
        first_un = wi * WORD + popcount32(lsb - 1)
        # ``live`` restates the while cond inside the body: under
        # _DPLL_UNROLL > 1 repeated applications run WITHOUT a cond
        # check between them, and a lane that finished or exhausted its
        # budget mid-trip must be inert — including for the SAT check,
        # which would otherwise overwrite a budget-exhausted RUNNING
        # verdict.  At unroll 1 this is exactly what cond guaranteed.
        live = (status == RUNNING) & (steps <= budget)
        sat_now = live & ~flip & ~has_un
        status = jnp.where(sat_now, jnp.int32(SAT), status)
        m_t = jnp.where(sat_now, t, m_t)
        m_f = jnp.where(sat_now, f, m_f)

        do_step = live & (status == RUNNING)
        # The decision applied this iteration: a pending flip re-tries the
        # level's variable true, otherwise decide first-unassigned false.
        var = jnp.where(flip, dec_var[jnp.clip(sp, 0, NV - 1)], first_un)
        neg_phase = ~flip  # fresh decisions are false-first
        dv_idx = jnp.where(do_step & ~flip, jnp.clip(sp, 0, NV - 1), NV)
        dec_var = dec_var.at[dv_idx].set(var, mode="drop")
        dec_phase = dec_phase.at[dv_idx].set(FALSE, mode="drop")
        # A flip consumes the level's second phase.
        fl_idx = jnp.where(do_step & flip, jnp.clip(sp, 0, NV - 1), NV)
        dec_phase = dec_phase.at[fl_idx].set(TRUE, mode="drop")

        t2 = set_plane_bit(t, var, do_step & ~neg_phase)
        f2 = set_plane_bit(f, var, do_step & neg_phase)
        conflict, t3, f3 = planes_fixpoint(
            pt, t2, f2, min_bits, min_w, do_step, V, red
        )

        ok = do_step & ~conflict
        sidx = jnp.where(ok, jnp.clip(sp + 1, 0, NV), NV + 1)
        snap_t = snap_t.at[sidx].set(t3[0], mode="drop")
        snap_f = snap_f.at[sidx].set(f3[0], mode="drop")

        # SAT the moment a propagation totalizes the problem vars — in the
        # same iteration, so a solve on the last in-budget step still
        # reports its model.
        tot = ok & (((pvb & ~(t3 | f3)) == 0).all())
        status = jnp.where(tot, jnp.int32(SAT), status)
        m_t = jnp.where(tot, t3, m_t)
        m_f = jnp.where(tot, f3, m_f)

        # Chronological backtrack: deepest level still on its false phase.
        cand = (lvl <= sp) & (dec_phase == FALSE)
        l = jnp.max(jnp.where(cand, lvl, -1))
        no_bt = l < 0
        bt = do_step & conflict & ~no_bt
        status = jnp.where(do_step & conflict & no_bt, jnp.int32(UNSAT), status)
        sp = jnp.where(ok, sp + 1, jnp.where(bt, l, sp))
        flip = jnp.where(ok, jnp.bool_(False), jnp.where(bt, jnp.bool_(True), flip))
        steps = steps + do_step.astype(jnp.int32)
        return (dec_var, dec_phase, sp, flip, status, m_t, m_f,
                snap_t, snap_f, steps)

    def cond(st):
        _, _, _, _, status, _, _, _, _, steps = st
        return enabled & (status == RUNNING) & (steps <= budget)

    def trip(st):
        st = body(st)
        for _ in range(_DPLL_UNROLL - 1):
            st = body(st)  # gated repeats: no-ops on finished lanes
        return st

    st = (
        jnp.zeros(NV, jnp.int32),
        jnp.zeros(NV, jnp.int32),
        jnp.int32(0),
        jnp.bool_(False),
        status0,
        t0, f0,
        snap_t0, snap_f0,
        steps,
    )
    (_, _, _, _, status, m_t, m_f, _, _, steps) = lax.while_loop(cond, trip, st)
    return status, m_t, m_f, steps


# --------------------------------------------------------------------------
# preference-ordered guess search


def search(pt: ProblemTensors, t0: jax.Array, f0: jax.Array,
           outcome0: jax.Array, budget: jax.Array, steps: jax.Array,
           V: int, NCON: int, NV: int, T: int = 0,
           enabled: "jax.Array | bool" = True, red: bool = False
           ) -> Tuple[jax.Array, ...]:
    """The reference guess search (search.go:158-203; host: _search).

    Fixed-shape translation: the choice deque is a circular buffer of
    (choice row, candidate index) pairs with capacity NC+1 (each choice row
    lives in at most one place at a time — deque or guess stack); the guess
    stack holds (choice, index, var, children).  One loop iteration executes
    exactly one arm of the reference loop, in the reference's precedence
    order:

      0. deque empty, outcome unknown  → full DPLL solve  (search.go:167-169)
      1. outcome unsat                 → backtrack / give up (:172-179)
      2. deque empty, outcome sat      → done              (:182-184)
      3. otherwise                     → push next guess   (:187, :34-77)

    Two engine-level optimizations over a literal translation, both
    outcome-preserving:

    * **No branch dispatch** — under ``vmap``, ``lax.switch`` lowers to
      select and would execute a full DPLL plus propagation on every
      iteration of every lane; instead all four arms' bookkeeping runs as
      masked selects with exactly one lane-gated DPLL and at most one
      lane-gated propagation fixpoint per iteration.
    * **Guess-trail snapshots** — the packed-plane fixpoint and Test
      outcome after each guess are stacked; a push propagates only its new
      literal from the previous fixpoint (incremental BCP — monotone, so
      identical to from-scratch), and a pop is a pure snapshot restore with
      **zero** propagation, where the reference re-runs ``Test``
      (search.go:84) and the naive translation re-propagated everything.

    ``t0``/``f0``/``outcome0`` are the baseline fixpoint planes and Test
    outcome under anchors + activations alone (solve.go:74-79).

    ``T`` is the static trace capacity: when positive, every backtrack
    entry (the moment the reference calls ``Tracer.Trace``,
    search.go:172-173) appends the current guess-variable stack to a
    [T, GS] buffer; events past T are counted but not stored.  ``T = 0``
    keeps tracing fully out of the compiled program.

    Returns (result, guessed_mask, model, steps, trace_stack, trace_n)."""
    NC, Kc = pt.choice_cand.shape
    DQ = NC + 1
    GS = NC + 1
    Wv = (pt.pos_bits_r if red else pt.pos_bits).shape[1]
    dq_pos = jnp.arange(DQ, dtype=jnp.int32)
    pvb = pack_mask(jnp.arange(V, dtype=jnp.int32) < pt.n_vars, Wv)
    no_min_bits = jnp.zeros((1, Wv), jnp.int32)

    na = (pt.anchors >= 0).sum().astype(jnp.int32)
    # Anchor choice rows are rows 0..na-1 of the choice table, seeded in
    # input order (search.go:159-161).
    dq_c0 = jnp.where(dq_pos < na, dq_pos, 0)
    dq_i0 = jnp.zeros(DQ, jnp.int32)
    # Guess-trail snapshots: level k = fixpoint + outcome after k guesses.
    snap_t0 = jnp.zeros((GS + 1, Wv), jnp.int32).at[0].set(t0[0])
    snap_f0 = jnp.zeros((GS + 1, Wv), jnp.int32).at[0].set(f0[0])
    out_st0 = jnp.zeros(GS + 1, jnp.int32).at[0].set(outcome0)

    def body(st):
        (dq_c, dq_i, head, cnt, g_c, g_i, g_v, g_ch, gsp,
         snap_t, snap_f, out_st, result, m_t, m_f, assumed, done, need_leaf,
         steps, tr_stack, tr_n) = st

        # Arm selection (mutually exclusive; reference precedence order).
        # ``live`` restates ctl_cond inside the body: under
        # _CTL_UNROLL > 1 repeated applications run without a cond check
        # between them, and a parked (need_leaf), done, or
        # budget-exhausted lane must take NO arm — every write below is
        # gated through an arm flag, so a non-live application is inert.
        # At unroll 1 this is exactly what ctl_cond guaranteed.
        live = ~done & ~need_leaf & (steps <= budget)
        is_leaf = live & (cnt == 0) & (result == RUNNING)
        is_bt = live & ~is_leaf & (result == UNSAT)
        is_done = live & ~is_leaf & ~is_bt & (cnt == 0)
        is_push = live & ~is_leaf & ~is_bt & ~is_done

        # Trace: the reference fires Tracer.Trace at every backtrack entry
        # (search.go:172-173) with the pre-pop guess stack.
        if T > 0:
            row = jnp.where(
                jnp.arange(GS, dtype=jnp.int32) < gsp, g_v, jnp.int32(-1)
            )
            tidx = jnp.where(is_bt & (tr_n < T), jnp.clip(tr_n, 0, T - 1), T)
            tr_stack = tr_stack.at[tidx].set(row, mode="drop")
        tr_n = tr_n + is_bt.astype(jnp.int32)

        cur_t = snap_t[jnp.clip(gsp, 0, GS)][None, :]
        cur_f = snap_f[jnp.clip(gsp, 0, GS)][None, :]

        # --- arm 0: leaf DPLL request (search.go:167-169) ---------------
        # The full solve is NOT embedded here: the lane freezes (the
        # control loop's cond excludes need_leaf lanes) and one lane-gated
        # dpll per episode runs after the control loop drains — so control
        # iterations don't pay the dpll prologue/snapshot machinery, and
        # concurrent leaf lanes share a single dpll invocation.
        need_leaf = need_leaf | is_leaf

        # --- arm 1: backtrack bookkeeping (PopGuess, search.go:79-98) ---
        give_up = is_bt & (gsp == 0)
        bt = is_bt & ~give_up
        gsp2 = gsp - 1
        gc = g_c[jnp.clip(gsp2, 0)]
        gi = g_i[jnp.clip(gsp2, 0)]
        gv = g_v[jnp.clip(gsp2, 0)]
        gch = g_ch[jnp.clip(gsp2, 0)]
        head_bt = jnp.mod(head - 1, DQ)  # requeue popped choice at the front

        # --- arm 3: push bookkeeping (PushGuess, search.go:34-77) -------
        cid = dq_c[jnp.clip(head, 0, DQ - 1)]
        idx = dq_i[jnp.clip(head, 0, DQ - 1)]
        head_push = jnp.mod(head + 1, DQ)
        cands = pt.choice_cand[jnp.clip(cid, 0, NC - 1)]   # i32[Kc]
        ncand = (cands >= 0).sum()
        cand_var = cands[jnp.clip(idx, 0, Kc - 1)]
        var = jnp.where(idx < ncand, cand_var, -1)
        already = ((cands >= 0) & assumed[jnp.clip(cands, 0)]).any()
        var = jnp.where(already, jnp.int32(-1), var)
        ch_row = pt.var_choices[jnp.clip(var, 0)]          # i32[W]
        valid_ch = is_push & (var >= 0) & (ch_row >= 0)
        nch = valid_ch.sum().astype(jnp.int32)
        offs = jnp.cumsum(valid_ch.astype(jnp.int32)) - valid_ch.astype(jnp.int32)
        pos = jnp.mod(head_push + (cnt - 1) + offs, DQ)

        # --- merged state updates (each write gated by its arm) ---------
        head = jnp.where(bt, head_bt, jnp.where(is_push, head_push, head))
        cnt = jnp.where(bt, cnt - gch + 1,
                        jnp.where(is_push, cnt - 1 + nch, cnt))
        # Backtrack: requeue the popped choice, its candidate index
        # advanced past a real guess (children died with the pop — the
        # cnt shrink above removes them from the live window).
        dq_c = dq_c.at[jnp.where(bt, head_bt, DQ)].set(gc, mode="drop")
        dq_i = dq_i.at[jnp.where(bt, head_bt, DQ)].set(
            gi + (gv >= 0).astype(jnp.int32), mode="drop")
        # Push: enqueue the guessed variable's dependency choices.
        tgt = jnp.where(valid_ch, pos, DQ)
        dq_c = dq_c.at[tgt].set(ch_row, mode="drop")
        dq_i = dq_i.at[tgt].set(0, mode="drop")
        # Push always records a guess entry, null (var == -1) or not.
        g_idx = jnp.where(is_push, jnp.clip(gsp, 0, GS - 1), GS)
        g_c = g_c.at[g_idx].set(cid, mode="drop")
        g_i = g_i.at[g_idx].set(idx, mode="drop")
        g_v = g_v.at[g_idx].set(var, mode="drop")
        g_ch = g_ch.at[g_idx].set(nch, mode="drop")

        assumed = assumed.at[jnp.where(bt & (gv >= 0), jnp.clip(gv, 0), V)
                             ].set(False, mode="drop")
        assumed = assumed.at[jnp.where(is_push & (var >= 0), jnp.clip(var, 0), V)
                             ].set(True, mode="drop")

        # Push with a real variable: propagate just the new literal from
        # the current fixpoint (lane-gated).  A null push copies the level.
        push_test = is_push & (var >= 0)
        t2 = set_plane_bit(cur_t, jnp.clip(var, 0), push_test)
        conflict, t3, f3 = planes_fixpoint(
            pt, t2, cur_f, no_min_bits, jnp.int32(0), push_test, V, red
        )
        push_out = test_outcome(conflict, t3, f3, pvb)
        sidx = jnp.where(is_push, jnp.clip(gsp + 1, 0, GS), GS + 1)
        snap_t = snap_t.at[sidx].set(
            jnp.where(push_test, t3[0], cur_t[0]), mode="drop")
        snap_f = snap_f.at[sidx].set(
            jnp.where(push_test, f3[0], cur_f[0]), mode="drop")
        out_st = out_st.at[sidx].set(
            jnp.where(push_test, push_out, out_st[jnp.clip(gsp, 0, GS)]),
            mode="drop")
        gsp = jnp.where(bt, gsp2, jnp.where(is_push, gsp + 1, gsp))

        # Pop of a real guess re-Tests (search.go:84) — with snapshots the
        # outcome was already recorded at the restored level: zero
        # propagation.  Popping or pushing a null guess leaves the prior
        # outcome standing (search.go:55-60; a standing UNSAT keeps the pop
        # loop going).
        pop_restore = bt & (gv >= 0)
        pop_out = out_st[jnp.clip(gsp2, 0, GS)]
        result = jnp.where(pop_restore, pop_out,
                           jnp.where(push_test, push_out, result))
        pop_sat = pop_restore & (pop_out == SAT)
        m_t = jnp.where(pop_sat, snap_t[jnp.clip(gsp2, 0, GS)][None, :], m_t)
        m_f = jnp.where(pop_sat, snap_f[jnp.clip(gsp2, 0, GS)][None, :], m_f)
        push_sat = push_test & (push_out == SAT)
        m_t = jnp.where(push_sat, t3, m_t)
        m_f = jnp.where(push_sat, f3, m_f)

        done = done | give_up | is_done
        steps = steps + (bt | is_push).astype(jnp.int32)
        return (dq_c, dq_i, head, cnt, g_c, g_i, g_v, g_ch, gsp,
                snap_t, snap_f, out_st, result, m_t, m_f, assumed, done,
                need_leaf, steps, tr_stack, tr_n)

    def ctl_cond(st):
        done = st[16]
        need_leaf = st[17]
        steps = st[18]
        return enabled & ~done & ~need_leaf & (steps <= budget)

    def episode_body(st):
        # Drain control arms until every live lane is done or parked at a
        # leaf, then run one lane-gated dpll for all parked lanes.
        def ctl_trip(s):
            s = body(s)
            for _ in range(_CTL_UNROLL - 1):
                s = body(s)  # gated repeats: no-ops on non-live lanes
            return s

        st = lax.while_loop(ctl_cond, ctl_trip, st)
        (dq_c, dq_i, head, cnt, g_c, g_i, g_v, g_ch, gsp,
         snap_t, snap_f, out_st, result, m_t, m_f, assumed, done, need_leaf,
         steps, tr_stack, tr_n) = st
        cur_t = snap_t[jnp.clip(gsp, 0, GS)][None, :]
        cur_f = snap_f[jnp.clip(gsp, 0, GS)][None, :]
        leaf_status, leaf_t, leaf_f, steps = dpll(
            pt, cur_t, cur_f, no_min_bits, jnp.int32(0), budget, steps,
            NV, V, enabled=need_leaf, red=red,
        )
        result = jnp.where(need_leaf, leaf_status, result)
        leaf_sat = need_leaf & (leaf_status == SAT)
        m_t = jnp.where(leaf_sat, leaf_t, m_t)
        m_f = jnp.where(leaf_sat, leaf_f, m_f)
        # Budget exhaustion leaves status RUNNING; the episode cond exits.
        need_leaf = jnp.bool_(False)
        return (dq_c, dq_i, head, cnt, g_c, g_i, g_v, g_ch, gsp,
                snap_t, snap_f, out_st, result, m_t, m_f, assumed, done,
                need_leaf, steps, tr_stack, tr_n)

    def episode_cond(st):
        done = st[16]
        steps = st[18]
        return enabled & ~done & (steps <= budget)

    st = (
        dq_c0, dq_i0, jnp.int32(0), na,
        jnp.zeros(GS, jnp.int32), jnp.zeros(GS, jnp.int32),
        jnp.zeros(GS, jnp.int32), jnp.zeros(GS, jnp.int32), jnp.int32(0),
        snap_t0, snap_f0, out_st0,
        jnp.int32(RUNNING), jnp.zeros((1, Wv), jnp.int32),
        jnp.zeros((1, Wv), jnp.int32), jnp.zeros(V, bool),
        jnp.bool_(False), jnp.bool_(False), steps,
        jnp.full((T, GS), -1, jnp.int32), jnp.int32(0),
    )
    st = lax.while_loop(episode_cond, episode_body, st)
    (_, _, _, _, _, _, _, _, _, _, _, _,
     result, m_t, m_f, assumed, done, _, steps, tr_stack, tr_n) = st
    result = jnp.where(done, result, jnp.int32(RUNNING))
    model = planes_to_assign(m_t, m_f, V)
    return result, assumed, model, steps, tr_stack, tr_n


# --------------------------------------------------------------------------
# full pipeline


def search_phase(pt: ProblemTensors, budget: jax.Array,
                 en: "jax.Array | bool" = True,
                 *, V: int, NCON: int, NV: int, T: int = 0, red: bool = False
                 ) -> Tuple[jax.Array, ...]:
    """Phase 1: baseline Test + preference-ordered guess search
    (solve.go:53-85).  Returns (result, guessed, model, steps, tr_stack,
    tr_n).  ``en`` gates the whole phase (padding lanes of a compacted
    batch run zero propagation rounds and report RUNNING).

    With ``red`` (static), ``V`` is the reduced problem-var space width
    (== NV) and all planes/outputs live in that space — activations are
    constant TRUE during search, so their columns are folded away."""
    idxV = jnp.arange(V, dtype=jnp.int32)
    pv_mask = idxV < pt.n_vars
    steps0 = jnp.int32(1)
    Wv = (pt.pos_bits_r if red else pt.pos_bits).shape[1]
    pvb = pack_mask(pv_mask, Wv)
    no_min_bits = jnp.zeros((1, Wv), jnp.int32)

    # Baseline Test under anchors + activations (solve.go:74-79), computed
    # as planes so the search can snapshot from it.
    if red:
        base = _base_assignment_red(pt, V)
    else:
        base = _base_assignment(pt, V, NCON)
    base = _apply_anchors(pt, base, V)
    t0 = pack_mask(base == TRUE, Wv)
    f0 = pack_mask(base == FALSE, Wv)
    conflict0, t0, f0 = planes_fixpoint(
        pt, t0, f0, no_min_bits, jnp.int32(0), en, V, red,
    )
    outcome0 = test_outcome(conflict0, t0, f0, pvb)
    a0 = planes_to_assign(t0, f0, V)

    # ---- guess search when the baseline Test is undetermined ----
    need_search = en & (outcome0 == RUNNING)
    s_result, s_guessed, s_model, steps, tr_stack, tr_n = search(
        pt, t0, f0, outcome0, budget, steps0, V, NCON, NV, T,
        enabled=need_search, red=red,
    )
    result = jnp.where(need_search, s_result, outcome0)
    # Baseline already decided: the anchors play the guess-set role for
    # minimization (solve.go:77-83).
    guessed = jnp.where(need_search, s_guessed, _anchor_mask(pt, V))
    model = jnp.where(need_search, s_model, a0)
    result = jnp.where(en, result, jnp.int32(RUNNING))
    return result, guessed, model, steps, tr_stack, tr_n


def minimize_phase(pt: ProblemTensors, model: jax.Array, guessed: jax.Array,
                   budget: jax.Array, steps: jax.Array,
                   en: "jax.Array | bool" = True,
                   *, V: int, NCON: int, NV: int, red: bool = False
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Phase 2 (SAT lanes): extras-only cardinality minimization
    (solve.go:86-113).  Returns (installed [NV], min_found, steps).

    The reference probes w = 0, 1, 2, … and stops at the first SAT
    (solve.go:105-110).  Satisfiability is monotone in w, so binary
    search over [0, n_extras] finds the same minimal w in O(log) solves.
    Caveat: the probe sequence (and so the steps consumed) differs from
    the host engine's linear scan — under a tight ``max_steps`` budget
    the two backends can disagree on complete-vs-incomplete for the same
    problem.  Outcome parity is only guaranteed with sufficient budget
    (pinned by tests/test_differential.py::test_minimization_budget_parity).

    ``red``/``V`` as in :func:`search_phase`; ``model``/``guessed`` are in
    the same space as that phase's outputs."""
    idxV = jnp.arange(V, dtype=jnp.int32)
    pv_mask = idxV < pt.n_vars
    Wv = (pt.pos_bits_r if red else pt.pos_bits).shape[1]
    extras = (model == TRUE) & ~guessed & pv_mask
    excluded = (model != TRUE) & ~guessed & pv_mask
    if red:
        m_init = _base_assignment_red(pt, V)
    else:
        m_init = _base_assignment(pt, V, NCON)
    m_init = _apply_anchors(pt, m_init, V)
    m_init = jnp.where(guessed, jnp.int32(TRUE), m_init)
    m_init = jnp.where(excluded, jnp.int32(FALSE), m_init)
    n_extras = jnp.where(en, extras.sum(), 0)
    # Pack the probe's fixed partial assignment and the extras set once —
    # every minimization probe starts from the same planes.
    m_init_t = pack_mask(m_init == TRUE, Wv)
    m_init_f = pack_mask(m_init == FALSE, Wv)
    extras_bits = pack_mask(extras, Wv)

    def mcond(c):
        lo, hi, _, _, _, steps = c
        return en & (lo < hi) & (steps <= budget)

    def mbody(c):
        lo, hi, best_w, m2_t, found, steps = c
        w = (lo + hi) // 2
        status, mt, _, steps = dpll(
            pt, m_init_t, m_init_f, extras_bits, w, budget, steps, NV, V,
            enabled=en, red=red,
        )
        sat_w = status == SAT
        # SAT at w: the minimum is ≤ w — keep this probe's model and shrink
        # hi.  UNSAT at w: the minimum is > w.  Budget exhaustion (RUNNING)
        # changes nothing; the steps guard exits.
        best_w = jnp.where(sat_w, w, best_w)
        m2_t = jnp.where(sat_w, mt, m2_t)
        found = found | sat_w
        lo = jnp.where(sat_w, lo, jnp.where(status == UNSAT, w + 1, hi))
        hi = jnp.where(sat_w, w, hi)
        return lo, hi, best_w, m2_t, found, steps

    # Invariant: UNSAT strictly below lo, SAT at hi (the search/baseline
    # model witnesses w = n_extras).  At exit lo == hi == minimal w.
    _, m_hi, best_w, m2_t, m_found, steps = lax.while_loop(
        mcond, mbody,
        (jnp.int32(0), n_extras, jnp.int32(-1), pack_mask(model == TRUE, Wv),
         jnp.bool_(False), steps),
    )
    # The reported model must come from a probe at the minimal w itself —
    # the reference returns the w-bounded dpll model, which can differ from
    # the search witness even at equal cardinality (solve.go:108).  Probe
    # once more if the last SAT probe wasn't at the final bound.  With zero
    # extras the probe is skipped entirely: every variable is fixed by the
    # guess/excluded partition, so propagation could only rederive the
    # search model itself (the reference's single w=0 probe returns exactly
    # that model; skipping it changes the step count but never the answer).
    need_final = en & (best_w != m_hi) & (n_extras > 0)
    f_status, f_t, _, steps = dpll(
        pt, m_init_t, m_init_f, extras_bits, m_hi, budget, steps, NV, V,
        enabled=need_final, red=red,
    )
    m2_t = jnp.where(need_final & (f_status == SAT), f_t, m2_t)
    min_found = (
        jnp.where(need_final, f_status == SAT, m_found)
        | (en & (n_extras == 0))
    )
    # Uniform [NV] output in both spaces (full space's activation/padding
    # tail can never be "installed").
    installed = (unpack_mask(m2_t, V) & pv_mask & min_found & en)[:NV]
    return installed, min_found, steps


# Deletion probes are batched into chunks of this width: one probe tries
# removing a whole chunk, and only a chunk that cannot be dropped wholesale
# is probed member by member.  Cores are small in practice (the reference
# tests pin 2-4 constraints), so most chunks drop in a single probe —
# ~n/G + k·(G+1) DPLLs instead of n.  8 is the measured optimum on the
# UNSAT-heavy pinned-tenant fleet (CPU XLA, 512 problems): 4 is -9%,
# 16 is -13%.
CORE_CHUNK = 8


def core_phase(pt: ProblemTensors, budget: jax.Array, steps: jax.Array,
               en: "jax.Array | bool" = True,
               *, V: int, NCON: int, NV: int
               ) -> Tuple[jax.Array, jax.Array]:
    """Phase 3 (UNSAT lanes): deletion-based unsat-core minimization.
    Returns (core, steps).

    Start from all applied constraints active and drop any whose removal
    keeps the remainder unsatisfiable (host: _unsat_core; the analog of
    gini's failed-assumption Why, lit_mapping.go:198-207).  Probes run
    chunk-first: satisfiability is monotone in the active set — if the
    remainder without a whole chunk is still UNSAT, sequential deletion
    would have dropped every chunk member too — so a chunk-level UNSAT
    probe replaces ``CORE_CHUNK`` member probes while provably producing
    the *identical* core as the host spec's one-at-a-time loop; only a
    chunk whose removal makes the remainder satisfiable falls back to
    member-by-member probing in the host's order.  (Step *counts* differ:
    a core spread across many chunks pays the extra chunk probes, so a
    budget tuned to the wire of the sequential sweep can exhaust here —
    the usual generous budgets are orders of magnitude away from this.)

    Negative result, measured round 3: a second chunk level (64-wide
    superblocks over these 8-chunks) is a net LOSS on every workload tried
    (giant 1.7k-cons catalog: 9.0s vs 7.7s on CPU XLA; UNSAT-heavy fleet:
    1920/s vs 2009/s on TPU).  The sweep's cost is dominated by the
    kept-member probes — full SAT searches — and every hierarchy level
    whose block contains a core member adds one more of those; the cheap
    UNSAT block drops it saves were never the cost.  Don't re-try deeper
    hierarchies; cut SAT-probe cost instead (or route to the host spec
    engine for giant singles, driver.HOST_CORE_NCONS)."""
    Wv = pt.pos_bits.shape[1]
    no_min_bits = jnp.zeros((1, Wv), jnp.int32)
    active0 = (jnp.arange(NCON, dtype=jnp.int32) < pt.n_cons) & en
    G = min(CORE_CHUNK, max(NCON, 1))
    idx = jnp.arange(NCON, dtype=jnp.int32)

    def ccond(c):
        j, _, _, _, steps = c
        return en & (j < pt.n_cons) & (steps <= budget)

    def cbody(c):
        j, k, chunk_mode, active, steps = c
        in_chunk = (idx >= j) & (idx < j + G)
        trial_chunk = active & ~in_chunk
        member = jnp.where(~chunk_mode & (j + k < pt.n_cons), j + k, NCON)
        trial_member = active.at[member].set(False, mode="drop")
        trial = jnp.where(chunk_mode, trial_chunk, trial_member)
        init = _base_assignment(pt, V, NCON, act_enabled=trial)
        status, _, _, steps = dpll(
            pt, pack_mask(init == TRUE, Wv), pack_mask(init == FALSE, Wv),
            no_min_bits, jnp.int32(0), budget, steps, NV, V,
            enabled=en,
        )
        unsat = status == UNSAT
        active = jnp.where(unsat, trial, active)
        # Chunk probe UNSAT → whole chunk dropped, advance to next chunk.
        # Chunk probe SAT → re-probe this chunk member by member.  Member
        # mode advances within the chunk, then on to the next chunk.
        k2 = jnp.where(chunk_mode, jnp.int32(0), k + 1)
        chunk_done = chunk_mode & unsat
        member_done = ~chunk_mode & ((k2 >= G) | (j + k2 >= pt.n_cons))
        advance = chunk_done | member_done
        j = jnp.where(advance, j + G, j)
        k2 = jnp.where(advance, jnp.int32(0), k2)
        # Next mode is chunk-probe exactly when advancing to a fresh chunk;
        # a SAT chunk probe (or an unfinished member sweep) stays/drops
        # into member mode.
        return j, k2, advance, active, steps

    _, _, _, core, steps = lax.while_loop(
        ccond, cbody,
        (jnp.int32(0), jnp.int32(0), jnp.bool_(True), active0, steps),
    )
    return core, steps


def solve_full(pt: ProblemTensors, budget: jax.Array,
               *, V: int, NCON: int, NV: int, T: int = 0,
               with_core: bool = True) -> SolveResult:
    """One problem end to end (host: HostEngine.solve; reference
    solve.go:53-119): baseline Test, guess search if undetermined,
    extras-only minimization on SAT, deletion-based core on UNSAT.

    Every phase runs unconditionally but lane-gated: under ``vmap`` a
    ``lax.cond`` would execute both branches for every lane anyway (select
    semantics), so the phases instead take an ``enabled`` flag that makes
    their loops trip zero times on lanes that don't need them — a SAT lane
    pays nothing for core extraction, an UNSAT lane nothing for
    minimization.

    This single-program composition is kept for single-dispatch users (the
    mesh dry run, the graft entry); the driver's default path dispatches
    the three phases as separate compacted batches
    (:func:`deppy_tpu.engine.driver.solve_problems`), which removes the
    vmap max-over-lanes coupling between phases — a batch's few UNSAT
    lanes no longer serialize every SAT lane through the deletion loop."""
    red = phases_reduced()
    Vs = NV if red else V
    result, guessed, model, steps, tr_stack, tr_n = search_phase(
        pt, budget, V=Vs, NCON=NCON, NV=NV, T=T, red=red,
    )
    sat_en = result == SAT
    installed, min_found, steps = minimize_phase(
        pt, model, guessed, budget, steps, sat_en,
        V=Vs, NCON=NCON, NV=NV, red=red,
    )
    if with_core:
        unsat_en = result == UNSAT
        core, steps = core_phase(
            pt, budget, steps, unsat_en, V=V, NCON=NCON, NV=NV,
        )
    else:
        # Core extraction delegated to the caller (the driver routes giant
        # single problems to the host spec engine — driver.HOST_CORE_NCONS);
        # compiling the deletion arm out keeps the program short.
        core = jnp.zeros(NCON, bool)
    incomplete = (steps > budget) | (result == RUNNING) | (
        sat_en & ~min_found
    )
    outcome = jnp.where(incomplete, jnp.int32(RUNNING), result)
    return SolveResult(outcome=outcome, installed=installed, core=core,
                       steps=steps, trace_stack=tr_stack, trace_n=tr_n)


def phases_reduced() -> bool:
    """Whether the search/minimization phases run in the reduced
    problem-var plane space (bits/watched impls; see ProblemTensors)."""
    return _resolved_impl() in ("bits", "watched")


@functools.lru_cache(maxsize=128)
def batched_solve(V: int, NCON: int, NV: int, T: int = 0,
                  with_core: bool = True):
    """Jitted, vmapped single-program solve for one padded shape signature.
    Cached so each shape bucket compiles exactly once per process (the
    driver buckets padded dims to powers of two to bound the number of
    entries).  ``T`` is the static trace capacity (0 = tracing compiled
    out); ``with_core=False`` compiles the deletion arm out (the driver
    host-routes core extraction for giant single problems)."""
    fn = functools.partial(solve_full, V=V, NCON=NCON, NV=NV, T=T,
                           with_core=with_core)
    return jax.jit(compileguard.observe(
        "core.batched_solve", jax.vmap(fn, in_axes=(0, None)),
        static=(V, NCON, NV, T, with_core)))


@functools.lru_cache(maxsize=128)
def batched_search(V: int, NCON: int, NV: int, T: int = 0):
    """Jitted, vmapped phase-1 program (baseline + search); per-lane
    ``en`` mask gates padding lanes.  Under ``DEPPY_TPU_SEARCH=fused``
    (reduced planes, no trace buffer) the returned callable routes
    supported shapes to the fused Pallas kernel instead, falling back to
    the XLA program for shapes past the kernel's static-unroll caps."""
    red = phases_reduced()
    fn = functools.partial(search_phase, V=NV if red else V,
                           NCON=NCON, NV=NV, T=T, red=red)
    xla_fn = jax.jit(compileguard.observe(
        "core.batched_search", jax.vmap(fn, in_axes=(0, None, 0)),
        static=(V, NCON, NV, T, red)))
    if T == 0 and red and _resolved_search_impl() == "fused":
        from . import pallas_search

        def dispatch(pts, budget, en):
            if _fused_routable(pts, pts.pos_bits_r):
                return pallas_search.batched_search_fused(pts, budget, en)
            return xla_fn(pts, budget, en)

        return dispatch
    return xla_fn


@functools.lru_cache(maxsize=128)
def batched_core(V: int, NCON: int, NV: int):
    """Jitted, vmapped phase-3 program over a compacted UNSAT batch.
    Under ``DEPPY_TPU_SEARCH=fused`` supported shapes route to the fused
    deletion-sweep kernel (same dispatch rules as
    :func:`batched_search`)."""
    fn = functools.partial(core_phase, V=V, NCON=NCON, NV=NV)
    xla_fn = jax.jit(compileguard.observe(
        "core.batched_core", jax.vmap(fn, in_axes=(0, None, 0, 0)),
        static=(V, NCON, NV)))
    if _resolved_search_impl() == "fused":
        from . import pallas_search

        def dispatch(pts, budget, steps, en):
            if _has_full_planes(pts, V) and _fused_routable(pts, pts.pos_bits):
                return pallas_search.batched_core_fused(
                    pts, budget, steps, en, V=V, NCON=NCON, NV=NV)
            return xla_fn(pts, budget, steps, en)

        return dispatch
    return xla_fn


# --------------------------------------------------------------------------
# speculative deletion probes (driver._speculative_core_mask)
#
# One GIANT problem's deletion sweep turned inside out: instead of one lane
# probing its n_cons activation subsets sequentially (core_phase), ALL
# single-drop probes of one shared problem run as vmap lanes of one
# program — the problem planes broadcast (in_axes=None), only the [NCON]
# activation masks are per-lane.  Stage 1 settles most probes with a
# search-free propagation fixpoint; stage 2 finishes the stragglers with
# full DPLL lanes.


def probe_fixpoint_phase(pt: ProblemTensors, drop_j: jax.Array,
                         *, V: int, NCON: int) -> jax.Array:
    """Stage-1 probe: propagate the single-drop probe's base assignment
    (all applied constraints active except ``drop_j``, anchors NOT
    assumed — host unsat_core_mask's probe convention) to fixpoint.
    Returns the conflict flag: True proves the probe UNSAT outright; False
    means undetermined (finish with :func:`probe_phase`).  Uses the
    full-space planes (activations are live variables here, exactly like
    core_phase's probes).  Lanes carry only an int32 index — the driver
    ships [P] indices, not [P, NCON] masks."""
    Wv = pt.pos_bits.shape[1]
    idx = jnp.arange(NCON, dtype=jnp.int32)
    act_enabled = (idx < pt.n_cons) & (idx != drop_j)
    init = _base_assignment(pt, V, NCON, act_enabled=act_enabled)
    no_min = jnp.zeros((1, Wv), jnp.int32)
    conflict, _, _ = planes_fixpoint(
        pt, pack_mask(init == TRUE, Wv), pack_mask(init == FALSE, Wv),
        no_min, jnp.int32(0), jnp.bool_(True), V,
    )
    return conflict


@functools.lru_cache(maxsize=128)
def batched_probe_fixpoint(V: int, NCON: int):
    """Jitted stage-1 probe batch: problem broadcast, drop indices
    vmapped."""
    fn = functools.partial(probe_fixpoint_phase, V=V, NCON=NCON)
    return jax.jit(compileguard.observe(
        "core.batched_probe_fixpoint", jax.vmap(fn, in_axes=(None, 0)),
        static=(V, NCON)))


def probe_phase(pt: ProblemTensors, act_enabled: jax.Array,
                budget: jax.Array, *, V: int, NCON: int, NV: int
                ) -> Tuple[jax.Array, jax.Array]:
    """Stage-2 probe: complete DPLL under the activation subset — the
    exact probe core_phase runs per trial, one vmap lane per subset.
    Returns (status, steps)."""
    Wv = pt.pos_bits.shape[1]
    init = _base_assignment(pt, V, NCON, act_enabled=act_enabled)
    no_min = jnp.zeros((1, Wv), jnp.int32)
    status, _, _, steps = dpll(
        pt, pack_mask(init == TRUE, Wv), pack_mask(init == FALSE, Wv),
        no_min, jnp.int32(0), budget, jnp.int32(0), NV, V,
    )
    return status, steps


@functools.lru_cache(maxsize=128)
def batched_probe(V: int, NCON: int, NV: int):
    """Jitted stage-2 probe batch: problem broadcast, act masks vmapped."""
    fn = functools.partial(probe_phase, V=V, NCON=NCON, NV=NV)
    return jax.jit(compileguard.observe(
        "core.batched_probe", jax.vmap(fn, in_axes=(None, 0, None)),
        static=(V, NCON, NV)))


def _minimize_gated(pt, result, model, guessed, budget, steps, en_lanes,
                    *, V, NCON, NV, red):
    return minimize_phase(
        pt, model, guessed, budget, steps,
        en_lanes & (result == SAT), V=V, NCON=NCON, NV=NV, red=red,
    )


@functools.lru_cache(maxsize=128)
def batched_minimize_gated(V: int, NCON: int, NV: int):
    """Phase-2 program gated by the phase-1 ``result`` on device: runs over
    the SAME chunks (and device-resident tensors) as phase 1, so no
    host-side compaction round trip and no re-upload of problem tensors.
    Non-SAT lanes trip zero loop iterations.  Under
    ``DEPPY_TPU_SEARCH=fused`` supported shapes route to the fused
    Pallas minimize kernel (same dispatch rules as
    :func:`batched_search`)."""
    red = phases_reduced()
    fn = functools.partial(_minimize_gated, V=NV if red else V,
                           NCON=NCON, NV=NV, red=red)
    xla_fn = jax.jit(compileguard.observe(
        "core.batched_minimize_gated",
        jax.vmap(fn, in_axes=(0, 0, 0, 0, None, 0, 0)),
        static=(V, NCON, NV, red)))
    if red and _resolved_search_impl() == "fused":
        from . import pallas_search

        def dispatch(pts, result, model, guessed, budget, steps, en):
            if _fused_routable(pts, pts.pos_bits_r):
                return pallas_search.batched_minimize_fused(
                    pts, result, model, guessed, budget, steps, en)
            return xla_fn(pts, result, model, guessed, budget, steps, en)

        return dispatch
    return xla_fn


def warm_check_phase(pt: ProblemTensors, assign: jax.Array,
                     *, V: int, NCON: int, NV: int) -> jax.Array:
    """Warm-prefix screen for one lane (ISSUE 10): the assignment is
    initialized from the lane's cached model (+1 true / -1 false over
    the off-cone variables, 0 for the cone left open to the re-solve),
    activation variables constant TRUE, and every clause and cardinality
    row is evaluated in one pass.  Returns the per-lane OK flag: False
    means the warm prefix already conflicts (a dead clause or a violated
    bound with no open member) and the lane should cold-solve without
    paying a host warm attempt.  One elementwise pass, no loop — the
    lockstep DPLL equivalent of starting at a deep, model-seeded node
    instead of the root."""
    a = assign.astype(jnp.int32)
    lit = pt.clauses
    var = jnp.abs(lit) - 1
    # Activation (and any padded) variable indices read as constant
    # TRUE: the solve assumes every applied constraint active, exactly
    # like the host engine's base assignment.
    is_act = var >= pt.n_vars
    pv = jnp.clip(jnp.where(is_act, 0, var), 0, NV - 1)
    val = jnp.where(
        lit == 0,
        jnp.int32(-1),  # pad cell: falsified, like the host's _FALSE
        jnp.where(is_act, jnp.sign(lit), jnp.sign(lit) * a[pv]),
    )
    valid_row = (lit != 0).any(axis=1)
    sat_c = (val == 1).any(axis=1)
    open_c = (val == 0).any(axis=1)
    dead = valid_row & ~sat_c & ~open_c
    members = pt.card_ids
    mvals = a[jnp.clip(members, 0, NV - 1)]
    mmask = members >= 0
    trues = ((mvals == 1) & mmask).sum(axis=1)
    over = (pt.card_valid > 0) & (trues > pt.card_n)
    return ~(dead.any() | over.any())


@functools.lru_cache(maxsize=128)
def batched_warm_check(V: int, NCON: int, NV: int):
    """Jitted, vmapped warm-prefix screen: assignment planes initialized
    from the cached models, one lockstep pass per coalesced warm lane
    class (driver.warm_screen is the padding/stacking entry)."""
    fn = functools.partial(warm_check_phase, V=V, NCON=NCON, NV=NV)
    return jax.jit(compileguard.observe(
        "core.batched_warm_check", jax.vmap(fn, in_axes=(0, 0)),
        static=(V, NCON, NV)))


def _core_gated(pt, result, budget, steps, en_lanes, *, V, NCON, NV):
    return core_phase(
        pt, budget, steps, en_lanes & (result == UNSAT),
        V=V, NCON=NCON, NV=NV,
    )


@functools.lru_cache(maxsize=128)
def batched_core_gated(V: int, NCON: int, NV: int):
    """Phase-3 program gated by the phase-1 ``result`` on device — used
    when most of a batch is UNSAT, where compaction would re-upload nearly
    everything for no lane savings.  Routes to the fused kernel under
    ``DEPPY_TPU_SEARCH=fused`` like :func:`batched_core`."""
    fn = functools.partial(_core_gated, V=V, NCON=NCON, NV=NV)
    xla_fn = jax.jit(compileguard.observe(
        "core.batched_core_gated",
        jax.vmap(fn, in_axes=(0, 0, None, 0, 0)),
        static=(V, NCON, NV)))
    if _resolved_search_impl() == "fused":
        from . import pallas_search

        def dispatch(pts, result, budget, steps, en):
            if _has_full_planes(pts, V) and _fused_routable(pts, pts.pos_bits):
                return pallas_search.batched_core_fused(
                    pts, budget, steps, en & (result == UNSAT),
                    V=V, NCON=NCON, NV=NV)
            return xla_fn(pts, result, budget, steps, en)

        return dispatch
    return xla_fn
