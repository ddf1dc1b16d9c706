"""Fused phase-1 search: the whole guess search in ONE Pallas kernel.

Round-3 root cause, measured on an earlier remote chip setup: every
``lax.while_loop`` trip cost ~175µs of scheduling against ~10µs of
useful plane algebra, and the search phase is *made of* while-loop
trips — episode control steps, DPLL decisions, and propagation rounds
each pay one (on a local v5e this is not yet measured).  This module is
the escalation that measurement prescribes: the
entire phase-1 program of :func:`deppy_tpu.engine.core.search_phase` —
baseline fixpoint, episode control loop, inlined DPLL leaves
(decide + propagate + backtrack), budget accounting — runs INSIDE one
``pallas_call``, where the loops are Mosaic-native ``scf.while`` on the
scalar core with zero per-trip dispatch cost.  One kernel invocation per
problem per PHASE, not per round: hundreds of trips collapse into one.

Batch shape: the kernel runs one problem per grid step (grid=(B,)).
Grid steps serialize on a TPU core, which costs the batch-axis VPU
vectorization the jnp "bits" path enjoys — the round-3 measurement that
kept the *fixpoint* kernel opt-in (core.py:398-406).  The bet here is
different: the fused program eliminates ~17× per-trip overhead on every
trip of every loop, far more than the lost lane parallelism on the small
[C, Wr] planes of catalog problems (a full per-problem search is tens of
µs of VPU work vs tens of ms of XLA trip overhead).  Like every other
device bet in this tree it stays **opt-in until measured on the real
chip** (``DEPPY_TPU_SEARCH=fused``; ``chip_smoke.py`` runs it on the
chip) — on CPU XLA the serialized grid is a measured-class loser.

Mosaic constraints shape the implementation:

* No dynamic gathers/scatters: every ``arr[idx]`` / ``arr.at[idx].set``
  of the XLA formulation becomes one-hot select algebra over a
  broadcasted iota (an out-of-range index then matches nothing, which
  reproduces ``mode="drop"`` exactly).
* No (N,1)↔(1,N) relayouts: per-slot bookkeeping vectors live in lane
  orientation [1, N]; the only sublane-indexed arrays are the snapshot
  trails [levels, Wr], written with [levels, 1] row selectors.
* Small static tables (choice candidates Kc, per-var choice lists W)
  are walked with statically unrolled scalar loops — pure scalar-core
  code, no layout hazards.  :func:`fused_supported` gates on their size.
* Tracing (T > 0) stays on the XLA path; the kernel still counts
  backtracks (``tr_n``) so stats-only tracers keep working.

Semantics are pinned by differential tests against
:func:`core.batched_search` (bit-identical results, models, guessed
sets, step counts) — the same three-implementations strategy the BCP
kernels use (tests/test_bcp_impls.py, SURVEY.md §4).

Reference parity: this is still gini ``Solve()`` + the guess loop of
search.go:158-203 / solve.go:53-85 — only the execution substrate moved
into the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..analysis import compileguard
from . import core

WORD = core.WORD

# Static-unroll caps for the scalar-loop table walks (see module
# docstring).  Catalog lowerings sit far below both; exotic shapes fall
# back to the XLA path via fused_supported().
MAX_KC = 64
MAX_W = 32


def _smem_scalars(B: int) -> "pl.BlockSpec":
    """Whole-vector SMEM spec for per-problem ``(B,)`` scalars.

    Every grid step maps the whole vector into SMEM and the kernel
    indexes its own entry with ``pl.program_id(0)`` — SMEM scalar
    loads/stores are cheap, and because the TPU grid is sequential the
    per-step single-element writes compose into the full output.  A
    per-problem ``(1, 1)`` block over a ``(B, 1)`` column breaks Mosaic's
    block-shape rule, and a whole ``(B, 1)`` column pads its minor dim
    to 128 words in SMEM (512 B per problem): six such columns at the
    512-lane cap overflow the v5e's 1 MiB of SMEM, which a 1-D vector
    (4 B per problem) does not.
    """
    return pl.BlockSpec((B,), lambda b: (0,), memory_space=pltpu.SMEM)


# --------------------------------------------------------------------------
# one-hot indexing primitives (Mosaic-safe dynamic indexing)


def _rows_iota(n: int) -> jax.Array:
    return lax.broadcasted_iota(jnp.int32, (n, 1), 0)


def _lanes_iota(n: int) -> jax.Array:
    return lax.broadcasted_iota(jnp.int32, (1, n), 1)


def _row_read(arr: jax.Array, idx) -> jax.Array:
    """arr [N, W], scalar idx → [1, W] row (zeros when idx not in range)."""
    sel = _rows_iota(arr.shape[0]) == idx
    return core.tree_sum(jnp.where(sel, arr, 0), axis=0, keepdims=True)


def _row_write(arr: jax.Array, idx, row: jax.Array, gate=True) -> jax.Array:
    """Write [1, W] ``row`` at ``idx`` when ``gate``; out-of-range drops."""
    sel = (_rows_iota(arr.shape[0]) == idx) & gate
    return jnp.where(sel, row, arr)


def _lane_read(row: jax.Array, idx) -> jax.Array:
    """row [1, N], scalar idx → scalar (0 when idx not in range)."""
    sel = _lanes_iota(row.shape[1]) == idx
    return core.tree_sum(jnp.where(sel, row, 0))


def _lane_write(row: jax.Array, idx, val, gate=True) -> jax.Array:
    sel = (_lanes_iota(row.shape[1]) == idx) & gate
    return jnp.where(sel, val, row)


def _set_bit(plane: jax.Array, var, on) -> jax.Array:
    """Set bit ``var`` in packed [1, Wv] plane when ``on`` (the kernel
    twin of :func:`core.set_plane_bit`); out-of-range var drops."""
    word = var // WORD
    bit = jnp.int32(1) << (var % WORD)
    sel = (_lanes_iota(plane.shape[1]) == word) & on
    return jnp.where(sel, plane | bit, plane)


def _get_bit(plane: jax.Array, var) -> jax.Array:
    """Bit ``var`` of a packed [1, Wv] plane as 0/1 (0 when out of range)."""
    word = _lane_read(plane, var // WORD)
    return core._srl(word, var % WORD) & 1


def _clear_bit(plane: jax.Array, var, on) -> jax.Array:
    word = var // WORD
    bit = jnp.int32(1) << (var % WORD)
    sel = (_lanes_iota(plane.shape[1]) == word) & on
    return jnp.where(sel, plane & ~bit, plane)


# --------------------------------------------------------------------------
# in-kernel fixpoint / outcome (bits impl, no dispatch)


def _fixpoint(pos, neg, mem, card_active, card_n2, min_bits, min_w, t, f,
              run, card_act_bits=None):
    """:func:`core.planes_fixpoint`'s bits path, inlined: same
    pre-conflict overlap check, same round kernel, no impl dispatch and
    no unroll knob (there is no per-trip dispatch cost to amortize in
    here).

    ``card_act_bits`` (full plane space only — the phase-3 kernel):
    cardinality-row activity is NOT static there — a row is active iff
    its owning constraint's activation literal is TRUE in the ENTRY
    assignment, so it must be derived from ``t`` per fixpoint call
    (core.planes_fixpoint's full-space branch); the static
    ``card_active`` argument is ignored when it is given.  Reduced-space
    callers (phases 1-2) keep passing the static ``card_valid`` mask."""
    if card_act_bits is not None:
        card_active = ((card_act_bits & t) != 0).any(axis=1, keepdims=True)
    pre_conflict = run & ((t & f) != 0).any()
    go = run & ~pre_conflict

    def cond(s):
        c, _, _, ch = s
        return ~c & ch

    def body(s):
        _, t, f, _ = s
        return core.round_planes(
            pos, neg, mem, card_active, card_n2, min_bits, min_w, t, f
        )

    c, t, f, _ = lax.while_loop(cond, body, (jnp.bool_(False), t, f, go))
    return c | pre_conflict, t, f


def _first_unassigned(pvb, t, f):
    """(has_unassigned, lowest unassigned problem var) from packed planes
    — the kernel twin of dpll's packed-bit first-unassigned scan."""
    un = (pvb & ~(t | f))
    nz = un != 0
    has_un = nz.any()
    Wr = un.shape[1]
    wi = core.tree_min(jnp.where(nz, _lanes_iota(Wr), Wr)).astype(jnp.int32)
    word = _lane_read(un, wi)
    lsb = word & -word
    return has_un, wi * WORD + core.popcount32(lsb - 1)


# --------------------------------------------------------------------------
# DPLL (kernel twin of core.dpll, reduced plane space)


def _dpll(pos, neg, mem, card_active, card_n2, pvb, t_init, f_init,
          min_bits, min_w, budget, steps, NV: int, enabled,
          card_act_bits=None):
    """Complete search under a fixed partial assignment — the kernel twin
    of :func:`core.dpll` (gini Solve(), search.go:168; solve.go:107):
    false-first decisions on the lowest unassigned problem var,
    chronological backtracking, trail-style snapshots.  State layouts are
    the one-hot orientations (dec arrays [1, NV], snapshots [NV+1, Wr]);
    the decision order, phases, models, and step counts are bit-identical
    to core.dpll (pinned by tests/test_pallas_search.py)."""
    Wr = pos.shape[1]
    lvl = _lanes_iota(NV)

    conflict0, t0, f0 = _fixpoint(
        pos, neg, mem, card_active, card_n2, min_bits, min_w,
        t_init, f_init, enabled, card_act_bits,
    )
    status0 = jnp.where(conflict0, jnp.int32(core.UNSAT),
                        jnp.int32(core.RUNNING))
    snap_t0 = _row_write(jnp.zeros((NV + 1, Wr), jnp.int32), 0, t0)
    snap_f0 = _row_write(jnp.zeros((NV + 1, Wr), jnp.int32), 0, f0)

    def body(st):
        (dec_var, dec_phase, sp, flip, status, m_t, m_f,
         snap_t, snap_f, steps) = st
        t = _row_read(snap_t, jnp.clip(sp, 0, NV))
        f = _row_read(snap_f, jnp.clip(sp, 0, NV))

        has_un, first_un = _first_unassigned(pvb, t, f)
        sat_now = ~flip & ~has_un
        status = jnp.where(sat_now, jnp.int32(core.SAT), status)
        m_t = jnp.where(sat_now, t, m_t)
        m_f = jnp.where(sat_now, f, m_f)

        do_step = status == core.RUNNING
        var = jnp.where(flip, _lane_read(dec_var, jnp.clip(sp, 0, NV - 1)),
                        first_un)
        neg_phase = ~flip
        dv_idx = jnp.where(do_step & ~flip, jnp.clip(sp, 0, NV - 1), NV)
        dec_var = _lane_write(dec_var, dv_idx, var)
        dec_phase = _lane_write(dec_phase, dv_idx, jnp.int32(core.FALSE))
        fl_idx = jnp.where(do_step & flip, jnp.clip(sp, 0, NV - 1), NV)
        dec_phase = _lane_write(dec_phase, fl_idx, jnp.int32(core.TRUE))

        t2 = _set_bit(t, var, do_step & ~neg_phase)
        f2 = _set_bit(f, var, do_step & neg_phase)
        conflict, t3, f3 = _fixpoint(
            pos, neg, mem, card_active, card_n2, min_bits, min_w,
            t2, f2, do_step, card_act_bits,
        )

        ok = do_step & ~conflict
        sidx = jnp.where(ok, jnp.clip(sp + 1, 0, NV), NV + 1)
        snap_t = _row_write(snap_t, sidx, t3)
        snap_f = _row_write(snap_f, sidx, f3)

        tot = ok & (((pvb & ~(t3 | f3)) == 0).all())
        status = jnp.where(tot, jnp.int32(core.SAT), status)
        m_t = jnp.where(tot, t3, m_t)
        m_f = jnp.where(tot, f3, m_f)

        cand = (lvl <= sp) & (dec_phase == core.FALSE)
        bt_l = core.tree_max(jnp.where(cand, lvl, -1))
        no_bt = bt_l < 0
        bt = do_step & conflict & ~no_bt
        status = jnp.where(do_step & conflict & no_bt,
                           jnp.int32(core.UNSAT), status)
        sp = jnp.where(ok, sp + 1, jnp.where(bt, bt_l, sp))
        flip = jnp.where(ok, jnp.bool_(False),
                         jnp.where(bt, jnp.bool_(True), flip))
        steps = steps + do_step.astype(jnp.int32)
        return (dec_var, dec_phase, sp, flip, status, m_t, m_f,
                snap_t, snap_f, steps)

    def cond(st):
        status, steps = st[4], st[9]
        return enabled & (status == core.RUNNING) & (steps <= budget)

    st = (
        jnp.zeros((1, NV), jnp.int32),
        jnp.zeros((1, NV), jnp.int32),
        jnp.int32(0),
        jnp.bool_(False),
        status0,
        t0, f0,
        snap_t0, snap_f0,
        steps,
    )
    (_, _, _, _, status, m_t, m_f, _, _, steps) = lax.while_loop(
        cond, body, st)
    return status, m_t, m_f, steps


# --------------------------------------------------------------------------
# the fused phase-1 kernel


def _kernel(en_ref, na_ref, budget_ref,
            pos_ref, neg_ref, mem_ref, cardn_ref, cardv_ref,
            choice_ref, varch_ref, t0p_ref, f0p_ref, pvb_ref,
            out0_ref, res_ref, steps_ref, trn_ref,
            t0o_ref, f0o_ref, asm_ref, mt_ref, mf_ref):
    pos = pos_ref[0]
    neg = neg_ref[0]
    mem = mem_ref[0]
    card_n2 = cardn_ref[0]
    card_active = cardv_ref[0] != 0
    choice_cand = choice_ref[0]      # [NC, Kc]
    var_choices = varch_ref[0]       # [NV, W]
    t_seed = t0p_ref[0]              # [1, Wr] anchors-assumed plane
    f_seed = f0p_ref[0]              # [1, Wr] padding pinned false
    pvb = pvb_ref[0]                 # [1, Wr] problem-var mask
    b = pl.program_id(0)
    en = en_ref[b] != 0
    na = na_ref[b]
    budget = budget_ref[0, 0]

    NC, Kc = choice_cand.shape
    NV, W = var_choices.shape
    Wr = pos.shape[1]
    DQ = NC + 1
    GS = NC + 1
    no_min = jnp.zeros((1, Wr), jnp.int32)
    zero_w = jnp.int32(0)

    # ---- baseline Test (solve.go:74-79) --------------------------------
    conflict0, t0, f0 = _fixpoint(
        pos, neg, mem, card_active, card_n2, no_min, zero_w,
        t_seed, f_seed, en,
    )
    outcome0 = core.test_outcome(conflict0, t0, f0, pvb)
    enabled = en & (outcome0 == core.RUNNING)

    # ---- guess search (kernel twin of core.search) ---------------------
    dq_pos = _lanes_iota(DQ)
    dq_c0 = jnp.where(dq_pos < na, dq_pos, 0)
    dq_i0 = jnp.zeros((1, DQ), jnp.int32)
    snap_t0 = _row_write(jnp.zeros((GS + 1, Wr), jnp.int32), 0, t0)
    snap_f0 = _row_write(jnp.zeros((GS + 1, Wr), jnp.int32), 0, f0)
    out_st0 = _lane_write(jnp.zeros((1, GS + 1), jnp.int32), 0, outcome0)

    def body(st):
        (dq_c, dq_i, head, cnt, g_c, g_i, g_v, g_ch, gsp,
         snap_t, snap_f, out_st, result, m_t, m_f, assumed, done,
         need_leaf, steps, tr_n) = st

        is_leaf = (cnt == 0) & (result == core.RUNNING)
        is_bt = ~is_leaf & (result == core.UNSAT)
        is_done = ~is_leaf & ~is_bt & (cnt == 0)
        is_push = ~is_leaf & ~is_bt & ~is_done

        tr_n = tr_n + is_bt.astype(jnp.int32)

        cur_t = _row_read(snap_t, jnp.clip(gsp, 0, GS))
        cur_f = _row_read(snap_f, jnp.clip(gsp, 0, GS))

        # arm 0: park for the episode's leaf DPLL.
        need_leaf = need_leaf | is_leaf

        # arm 1: backtrack bookkeeping (PopGuess, search.go:79-98).
        give_up = is_bt & (gsp == 0)
        bt = is_bt & ~give_up
        gsp2 = gsp - 1
        gc = _lane_read(g_c, jnp.clip(gsp2, 0))
        gi = _lane_read(g_i, jnp.clip(gsp2, 0))
        gv = _lane_read(g_v, jnp.clip(gsp2, 0))
        gch = _lane_read(g_ch, jnp.clip(gsp2, 0))
        head_bt = jnp.mod(head - 1, DQ)

        # arm 3: push bookkeeping (PushGuess, search.go:34-77).
        cid = _lane_read(dq_c, jnp.clip(head, 0, DQ - 1))
        idx = _lane_read(dq_i, jnp.clip(head, 0, DQ - 1))
        head_push = jnp.mod(head + 1, DQ)
        cands = _row_read(choice_cand, jnp.clip(cid, 0, NC - 1))  # [1, Kc]
        ncand = core.tree_sum(cands >= 0)
        cand_var = _lane_read(cands, jnp.clip(idx, 0, Kc - 1))
        var = jnp.where(idx < ncand, cand_var, -1)
        # "some candidate already assumed" — candidate membership test on
        # the packed assumed plane, statically unrolled over Kc (static
        # column extracts, scalar-core work).
        already = jnp.bool_(False)
        for k in range(Kc):
            cv = cands[0, k]
            already = already | ((cv >= 0) & (_get_bit(assumed, cv) != 0))
        var = jnp.where(already, jnp.int32(-1), var)

        head = jnp.where(bt, head_bt, jnp.where(is_push, head_push, head))
        # Backtrack: requeue the popped choice, candidate index advanced
        # past a real guess.
        dq_c = _lane_write(dq_c, jnp.where(bt, head_bt, DQ), gc)
        dq_i = _lane_write(dq_i, jnp.where(bt, head_bt, DQ),
                           gi + (gv >= 0).astype(jnp.int32))
        # Push: enqueue the guessed variable's dependency choices —
        # statically unrolled over the W choice slots (cumulative offset
        # runs in the same loop; an invalid slot targets DQ → dropped).
        ch_row = _row_read(var_choices, jnp.clip(var, 0))  # [1, W]
        nch = jnp.int32(0)
        for w in range(W):
            ch_w = ch_row[0, w]
            valid_w = is_push & (var >= 0) & (ch_w >= 0)
            pos_w = jnp.mod(head_push + (cnt - 1) + nch, DQ)
            tgt_w = jnp.where(valid_w, pos_w, DQ)
            dq_c = _lane_write(dq_c, tgt_w, ch_w)
            dq_i = _lane_write(dq_i, tgt_w, jnp.int32(0))
            nch = nch + valid_w.astype(jnp.int32)
        cnt = jnp.where(bt, cnt - gch + 1,
                        jnp.where(is_push, cnt - 1 + nch, cnt))

        g_idx = jnp.where(is_push, jnp.clip(gsp, 0, GS - 1), GS)
        g_c = _lane_write(g_c, g_idx, cid)
        g_i = _lane_write(g_i, g_idx, idx)
        g_v = _lane_write(g_v, g_idx, var)
        g_ch = _lane_write(g_ch, g_idx, nch)

        assumed = _clear_bit(assumed, jnp.clip(gv, 0), bt & (gv >= 0))
        assumed = _set_bit(assumed, jnp.clip(var, 0), is_push & (var >= 0))

        # Push with a real variable: propagate just the new literal.
        push_test = is_push & (var >= 0)
        t2 = _set_bit(cur_t, jnp.clip(var, 0), push_test)
        conflict, t3, f3 = _fixpoint(
            pos, neg, mem, card_active, card_n2, no_min, zero_w,
            t2, cur_f, push_test,
        )
        push_out = core.test_outcome(conflict, t3, f3, pvb)
        sidx = jnp.where(is_push, jnp.clip(gsp + 1, 0, GS), GS + 1)
        snap_t = _row_write(snap_t, sidx,
                            jnp.where(push_test, t3, cur_t))
        snap_f = _row_write(snap_f, sidx,
                            jnp.where(push_test, f3, cur_f))
        out_st = _lane_write(
            out_st, sidx,
            jnp.where(push_test, push_out,
                      _lane_read(out_st, jnp.clip(gsp, 0, GS))))
        gsp = jnp.where(bt, gsp2, jnp.where(is_push, gsp + 1, gsp))

        pop_restore = bt & (gv >= 0)
        pop_out = _lane_read(out_st, jnp.clip(gsp2, 0, GS))
        result = jnp.where(pop_restore, pop_out,
                           jnp.where(push_test, push_out, result))
        pop_sat = pop_restore & (pop_out == core.SAT)
        m_t = jnp.where(pop_sat, _row_read(snap_t, jnp.clip(gsp2, 0, GS)),
                        m_t)
        m_f = jnp.where(pop_sat, _row_read(snap_f, jnp.clip(gsp2, 0, GS)),
                        m_f)
        push_sat = push_test & (push_out == core.SAT)
        m_t = jnp.where(push_sat, t3, m_t)
        m_f = jnp.where(push_sat, f3, m_f)

        done = done | give_up | is_done
        steps = steps + (bt | is_push).astype(jnp.int32)
        return (dq_c, dq_i, head, cnt, g_c, g_i, g_v, g_ch, gsp,
                snap_t, snap_f, out_st, result, m_t, m_f, assumed, done,
                need_leaf, steps, tr_n)

    def ctl_cond(st):
        done, need_leaf, steps = st[16], st[17], st[18]
        return enabled & ~done & ~need_leaf & (steps <= budget)

    def episode_body(st):
        st = lax.while_loop(ctl_cond, body, st)
        (dq_c, dq_i, head, cnt, g_c, g_i, g_v, g_ch, gsp,
         snap_t, snap_f, out_st, result, m_t, m_f, assumed, done,
         need_leaf, steps, tr_n) = st
        cur_t = _row_read(snap_t, jnp.clip(gsp, 0, GS))
        cur_f = _row_read(snap_f, jnp.clip(gsp, 0, GS))
        leaf_status, leaf_t, leaf_f, steps = _dpll(
            pos, neg, mem, card_active, card_n2, pvb, cur_t, cur_f,
            no_min, zero_w, budget, steps, NV, need_leaf,
        )
        result = jnp.where(need_leaf, leaf_status, result)
        leaf_sat = need_leaf & (leaf_status == core.SAT)
        m_t = jnp.where(leaf_sat, leaf_t, m_t)
        m_f = jnp.where(leaf_sat, leaf_f, m_f)
        need_leaf = jnp.bool_(False)
        return (dq_c, dq_i, head, cnt, g_c, g_i, g_v, g_ch, gsp,
                snap_t, snap_f, out_st, result, m_t, m_f, assumed, done,
                need_leaf, steps, tr_n)

    def episode_cond(st):
        done, steps = st[16], st[18]
        return enabled & ~done & (steps <= budget)

    st = (
        dq_c0, dq_i0, jnp.int32(0), na,
        jnp.zeros((1, GS), jnp.int32), jnp.zeros((1, GS), jnp.int32),
        jnp.zeros((1, GS), jnp.int32), jnp.zeros((1, GS), jnp.int32),
        jnp.int32(0),
        snap_t0, snap_f0, out_st0,
        jnp.int32(core.RUNNING),
        jnp.zeros((1, Wr), jnp.int32), jnp.zeros((1, Wr), jnp.int32),
        jnp.zeros((1, Wr), jnp.int32),
        jnp.bool_(False), jnp.bool_(False), jnp.int32(1),
        jnp.int32(0),
    )
    st = lax.while_loop(episode_cond, episode_body, st)
    (_, _, _, _, _, _, _, _, _, _, _, _,
     result, m_t, m_f, assumed, done, _, steps, tr_n) = st
    result = jnp.where(done, result, jnp.int32(core.RUNNING))

    out0_ref[b] = outcome0
    res_ref[b] = result
    steps_ref[b] = steps
    trn_ref[b] = tr_n
    t0o_ref[0] = t0
    f0o_ref[0] = f0
    asm_ref[0] = assumed
    mt_ref[0] = m_t
    mf_ref[0] = m_f


# --------------------------------------------------------------------------
# fused phase 2: extras-only minimization (kernel twin of
# core.minimize_phase — binary search over the extras bound, each probe a
# full in-kernel DPLL; solve.go:86-113)


def _min_kernel(en_ref, nx_ref, budget_ref, steps_ref,
                pos_ref, neg_ref, mem_ref, cardn_ref, cardv_ref,
                mit_ref, mif_ref, ext_ref, m2t0_ref, pvb_ref,
                found_ref, steps_out_ref, m2t_ref, *, NV: int):
    pos = pos_ref[0]
    neg = neg_ref[0]
    mem = mem_ref[0]
    card_n2 = cardn_ref[0]
    card_active = cardv_ref[0] != 0
    m_init_t = mit_ref[0]
    m_init_f = mif_ref[0]
    extras_bits = ext_ref[0]
    pvb = pvb_ref[0]
    b = pl.program_id(0)
    en = en_ref[b] != 0
    n_extras = nx_ref[b]
    budget = budget_ref[0, 0]
    steps = steps_ref[b]

    def mcond(c):
        lo, hi, _, _, _, steps = c
        return en & (lo < hi) & (steps <= budget)

    def mbody(c):
        lo, hi, best_w, m2_t, found, steps = c
        w = (lo + hi) // 2
        status, mt, _, steps = _dpll(
            pos, neg, mem, card_active, card_n2, pvb,
            m_init_t, m_init_f, extras_bits, w, budget, steps, NV, en,
        )
        sat_w = status == core.SAT
        best_w = jnp.where(sat_w, w, best_w)
        m2_t = jnp.where(sat_w, mt, m2_t)
        found = found | sat_w
        lo = jnp.where(sat_w, lo,
                       jnp.where(status == core.UNSAT, w + 1, hi))
        hi = jnp.where(sat_w, w, hi)
        return lo, hi, best_w, m2_t, found, steps

    _, m_hi, best_w, m2_t, m_found, steps = lax.while_loop(
        mcond, mbody,
        (jnp.int32(0), n_extras, jnp.int32(-1), m2t0_ref[0],
         jnp.bool_(False), steps),
    )
    need_final = en & (best_w != m_hi) & (n_extras > 0)
    f_status, f_t, _, steps = _dpll(
        pos, neg, mem, card_active, card_n2, pvb,
        m_init_t, m_init_f, extras_bits, m_hi, budget, steps, NV,
        need_final,
    )
    m2_t = jnp.where(need_final & (f_status == core.SAT), f_t, m2_t)
    min_found = (jnp.where(need_final, f_status == core.SAT, m_found)
                 | (en & (n_extras == 0)))
    found_ref[b] = min_found.astype(jnp.int32)
    steps_out_ref[b] = steps
    m2t_ref[0] = m2_t


def _minimize_fused_impl(pts: core.ProblemTensors, result, model,
                         guessed, budget, steps, en_lanes):
    """Phase-2 minimization via the fused kernel — the drop-in twin of
    ``core.batched_minimize_gated(...)(pts, result, model, guessed,
    budget, steps, en)`` (reduced plane space)."""
    B = pts.pos_bits_r.shape[0]
    Wr = pts.pos_bits_r.shape[2]
    NV = pts.var_choices.shape[1]

    en = en_lanes & (result == core.SAT)
    idx = jnp.arange(NV, dtype=jnp.int32)
    pv_mask = idx[None, :] < pts.n_vars[:, None]
    extras = (model == core.TRUE) & ~guessed & pv_mask
    excluded = (model != core.TRUE) & ~guessed & pv_mask
    m_init = jax.vmap(lambda p: core._base_assignment_red(p, NV))(pts)
    m_init = jax.vmap(lambda p, a: core._apply_anchors(p, a, NV))(
        pts, m_init)
    m_init = jnp.where(guessed, jnp.int32(core.TRUE), m_init)
    m_init = jnp.where(excluded, jnp.int32(core.FALSE), m_init)
    n_extras = jnp.where(en, extras.sum(axis=1), 0).astype(jnp.int32)

    pack = jax.vmap(lambda m: core.pack_mask(m, Wr))
    m_init_t = pack(m_init == core.TRUE)
    m_init_f = pack(m_init == core.FALSE)
    extras_bits = pack(extras)
    m2t0 = pack(model == core.TRUE)
    pvb = pack(pv_mask)

    smem_b = _smem_scalars(B)
    smem_c = pl.BlockSpec((1, 1), lambda b: (0, 0),
                          memory_space=pltpu.SMEM)

    def vmem(*blk):
        return pl.BlockSpec((1,) + blk, lambda b: (b,) + (0,) * len(blk),
                            memory_space=pltpu.VMEM)

    C = pts.pos_bits_r.shape[1]
    NA = pts.card_member_bits_r.shape[1]
    found, steps_out, m2_t = pl.pallas_call(
        functools.partial(_min_kernel, NV=NV),
        grid=(B,),
        in_specs=[
            smem_b, smem_b, smem_c, smem_b,
            vmem(C, Wr), vmem(C, Wr), vmem(NA, Wr),
            vmem(NA, 1), vmem(NA, 1),
            vmem(1, Wr), vmem(1, Wr), vmem(1, Wr), vmem(1, Wr),
            vmem(1, Wr),
        ],
        out_specs=(smem_b, smem_b, vmem(1, Wr)),
        out_shape=(
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, Wr), jnp.int32),
        ),
        interpret=core.pallas_interpret(),
    )(en.astype(jnp.int32), n_extras,
      jnp.full((1, 1), budget, jnp.int32), steps.astype(jnp.int32),
      pts.pos_bits_r, pts.neg_bits_r, pts.card_member_bits_r,
      pts.card_n[:, :, None], pts.card_valid[:, :, None],
      m_init_t, m_init_f, extras_bits, m2t0, pvb)

    min_found = found != 0
    installed = (jax.vmap(lambda w: core.unpack_mask(w, NV))(m2_t)
                 & pv_mask & min_found[:, None] & en[:, None])[:, :NV]
    return installed, min_found, steps_out


_batched_minimize_fused = jax.jit(compileguard.observe(
    "pallas_search.batched_minimize_fused", _minimize_fused_impl))


def batched_minimize_fused(pts, result, model, guessed, budget, steps,
                           en_lanes):
    """Public entry for the fused phase-2 program (shape-guarded like
    :func:`batched_search_fused`)."""
    if not fused_supported(pts):
        raise ValueError("fused minimize kernel caps exceeded")
    return _batched_minimize_fused(pts, result, model, guessed, budget,
                                   steps, en_lanes)


def fused_supported(pts: core.ProblemTensors) -> bool:
    """Whether the fused kernel handles this batch's static shapes (the
    static-unroll caps on the table walks)."""
    Kc = pts.choice_cand.shape[-1]
    W = pts.var_choices.shape[-1]
    return Kc <= MAX_KC and W <= MAX_W


# --------------------------------------------------------------------------
# fused phase 3: deletion-based unsat-core minimization (kernel twin of
# core.core_phase — chunk-first deletion sweep, every probe a full
# in-kernel DPLL over the FULL plane space, where activation literals are
# live variables; the analog of gini's Why minimization,
# lit_mapping.go:198-207)


def _core_kernel(en_ref, ncons_ref, nvars_ref, budget_ref, steps_ref,
                 pos_ref, neg_ref, mem_ref, cardn_ref, cardab_ref,
                 pvb_ref, baset_ref, basef_ref,
                 core_ref, steps_out_ref, *, NV: int, NCON: int, G: int):
    """One problem's whole deletion sweep in one kernel invocation.

    Constraint (de)activation is plane algebra: the all-active base
    assignment has every activation literal's TRUE bit set (base_t), and
    a probe's trial assignment clears the dropped constraints' act bits
    — leaving them UNASSIGNED, exactly core._base_assignment's
    ``act_enabled`` semantics.  The permanently-dropped set is carried as
    a packed bit plane (``dropped``) so each probe constructs its trial
    with ≤ G+1 one-hot bit ops instead of re-scattering NCON bits."""
    pos = pos_ref[0]
    neg = neg_ref[0]
    mem = mem_ref[0]
    card_n2 = cardn_ref[0]
    # Full plane space: cardinality-row activity is DERIVED per fixpoint
    # from the probe's activation bits (a dropped constraint's AtMost
    # rows must stop constraining), so the kernel carries the act-bit
    # planes, not a static card_valid mask.
    card_act_bits = cardab_ref[0]
    pvb = pvb_ref[0]
    base_t = baset_ref[0]
    base_f = basef_ref[0]
    b = pl.program_id(0)
    en = en_ref[b] != 0
    n_cons = ncons_ref[b]
    n_vars = nvars_ref[b]
    budget = budget_ref[0, 0]
    steps0 = steps_ref[b]
    Wv = pos.shape[1]
    lanes = _lanes_iota(NCON)
    active0 = ((lanes < n_cons) & en).astype(jnp.int32)
    no_min = jnp.zeros((1, Wv), jnp.int32)
    zero_w = jnp.int32(0)

    def cond(st):
        j, _, _, _, _, steps = st
        return en & (j < n_cons) & (steps <= budget)

    def body(st):
        j, k, chunk_mode, active, dropped, steps = st
        # Trial plane: the dropped set plus this probe's candidates.
        trial_plane = dropped
        for g in range(G):  # static unroll (G = CORE_CHUNK)
            idx = j + g
            on_c = (chunk_mode & (idx < n_cons)
                    & (_lane_read(active, idx) != 0))
            trial_plane = _set_bit(trial_plane, n_vars + idx, on_c)
        idx_m = j + k
        on_m = ~chunk_mode & (idx_m < n_cons)
        trial_plane = _set_bit(trial_plane, n_vars + idx_m, on_m)
        in_chunk = (lanes >= j) & (lanes < j + G)
        trial_act = jnp.where(chunk_mode & in_chunk, 0, active)
        trial_act = jnp.where(~chunk_mode & (lanes == idx_m)
                              & (idx_m < n_cons), 0, trial_act)

        status, _, _, steps = _dpll(
            pos, neg, mem, None, card_n2, pvb,
            base_t & ~trial_plane, base_f, no_min, zero_w,
            budget, steps, NV, en, card_act_bits,
        )
        unsat = status == core.UNSAT
        active = jnp.where(unsat, trial_act, active)
        dropped = jnp.where(unsat, trial_plane, dropped)
        # Control twin of core.core_phase's cbody: chunk probe UNSAT →
        # next chunk; chunk probe SAT → member-by-member; member sweep
        # exhausts the chunk → next chunk.
        k2 = jnp.where(chunk_mode, jnp.int32(0), k + 1)
        chunk_done = chunk_mode & unsat
        member_done = ~chunk_mode & ((k2 >= G) | (j + k2 >= n_cons))
        advance = chunk_done | member_done
        j = jnp.where(advance, j + G, j)
        k2 = jnp.where(advance, jnp.int32(0), k2)
        return j, k2, advance, active, dropped, steps

    st = (jnp.int32(0), jnp.int32(0), jnp.bool_(True), active0,
          jnp.zeros((1, Wv), jnp.int32), steps0)
    _, _, _, core_act, _, steps = lax.while_loop(cond, body, st)
    core_ref[0] = core_act
    steps_out_ref[b] = steps


def _core_fused_impl(pts: core.ProblemTensors, budget, steps, en,
                     *, V: int, NCON: int, NV: int):
    """Phase-3 core extraction via the fused kernel — the drop-in twin of
    ``core.batched_core(V, NCON, NV)(pts, budget, steps, en)``.  Reads
    the FULL-space planes (activation literals live)."""
    B, C, Wv = pts.pos_bits.shape
    NA = pts.card_member_bits.shape[1]
    G = min(core.CORE_CHUNK, max(NCON, 1))

    init = jax.vmap(
        lambda p: core._base_assignment(p, V, NCON))(pts)  # all active
    pack = jax.vmap(lambda m: core.pack_mask(m, Wv))
    base_t = pack(init == core.TRUE)
    base_f = pack(init == core.FALSE)
    idx = jnp.arange(V, dtype=jnp.int32)
    pvb = pack(idx[None, :] < pts.n_vars[:, None])

    smem_b = _smem_scalars(B)
    smem_c = pl.BlockSpec((1, 1), lambda b: (0, 0),
                          memory_space=pltpu.SMEM)

    def vmem(*blk):
        return pl.BlockSpec((1,) + blk, lambda b: (b,) + (0,) * len(blk),
                            memory_space=pltpu.VMEM)

    core_out, steps_out = pl.pallas_call(
        functools.partial(_core_kernel, NV=NV, NCON=NCON, G=G),
        grid=(B,),
        in_specs=[
            smem_b, smem_b, smem_b, smem_c, smem_b,
            vmem(C, Wv), vmem(C, Wv), vmem(NA, Wv),
            vmem(NA, 1), vmem(NA, Wv),
            vmem(1, Wv), vmem(1, Wv), vmem(1, Wv),
        ],
        out_specs=(vmem(1, NCON), smem_b),
        out_shape=(
            jax.ShapeDtypeStruct((B, 1, NCON), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ),
        interpret=core.pallas_interpret(),
    )(en.astype(jnp.int32),
      pts.n_cons.astype(jnp.int32),
      pts.n_vars.astype(jnp.int32),
      jnp.full((1, 1), budget, jnp.int32),
      steps.astype(jnp.int32),
      pts.pos_bits, pts.neg_bits, pts.card_member_bits,
      pts.card_n[:, :, None], pts.card_act_bits,
      pvb, base_t, base_f)

    return core_out[:, 0, :] != 0, steps_out


_batched_core_fused = jax.jit(
    compileguard.observe("pallas_search.batched_core_fused",
                         _core_fused_impl),
    static_argnames=("V", "NCON", "NV"))


def batched_core_fused(pts, budget, steps, en, *, V, NCON, NV):
    """Public entry for the fused phase-3 program (shape caps shared with
    the phase-1/2 kernels via :func:`fused_supported`; callers fall back
    to the XLA path otherwise)."""
    if not fused_supported(pts):
        raise ValueError("fused core kernel caps exceeded")
    return _batched_core_fused(pts, budget, steps, en,
                               V=V, NCON=NCON, NV=NV)


def _search_fused_impl(pts: core.ProblemTensors, budget, en):
    """Phase-1 search for a padded batch via the fused kernel — the drop-in
    twin of ``core.batched_search(...)(pts, budget, en)`` with T=0.
    Reduced plane space only (the search never disables activations;
    core.phases_reduced)."""
    B, NC, Kc = pts.choice_cand.shape
    NV, W = pts.var_choices.shape[1:]
    Wr = pts.pos_bits_r.shape[2]

    idx = jnp.arange(NV, dtype=jnp.int32)
    pv_mask = idx[None, :] < pts.n_vars[:, None]                # [B, NV]
    anchor_mask = jax.vmap(lambda p: core._anchor_mask(p, NV))(pts)
    pack = jax.vmap(lambda m: core.pack_mask(m, Wr))
    pvb = pack(pv_mask)                                         # [B, 1, Wr]
    t0p = pack(anchor_mask)
    f0p = pack(~pv_mask)
    na = (pts.anchors >= 0).sum(axis=1).astype(jnp.int32)
    en2 = en.astype(jnp.int32)
    budget2 = jnp.full((1, 1), budget, jnp.int32)
    card_n2 = pts.card_n[:, :, None]
    card_v2 = pts.card_valid[:, :, None]

    smem_b = _smem_scalars(B)
    smem_c = pl.BlockSpec((1, 1), lambda b: (0, 0),
                          memory_space=pltpu.SMEM)

    def vmem(*blk):
        return pl.BlockSpec((1,) + blk, lambda b: (b,) + (0,) * len(blk),
                            memory_space=pltpu.VMEM)

    C = pts.pos_bits_r.shape[1]
    NA = pts.card_member_bits_r.shape[1]
    outs = pl.pallas_call(
        _kernel,
        grid=(B,),
        in_specs=[
            smem_b, smem_b, smem_c,
            vmem(C, Wr), vmem(C, Wr), vmem(NA, Wr),
            vmem(NA, 1), vmem(NA, 1),
            vmem(NC, Kc), vmem(NV, W),
            vmem(1, Wr), vmem(1, Wr), vmem(1, Wr),
        ],
        out_specs=(
            smem_b, smem_b, smem_b, smem_b,
            vmem(1, Wr), vmem(1, Wr), vmem(1, Wr), vmem(1, Wr),
            vmem(1, Wr),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, Wr), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, Wr), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, Wr), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, Wr), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, Wr), jnp.int32),
        ),
        interpret=core.pallas_interpret(),
    )(en2, na, budget2,
      pts.pos_bits_r, pts.neg_bits_r, pts.card_member_bits_r,
      card_n2, card_v2, pts.choice_cand, pts.var_choices,
      t0p, f0p, pvb)

    outcome0, result_s, steps, tr_n, t0o, f0o, asm, m_t, m_f = outs

    to_assign = jax.vmap(lambda t, f: core.planes_to_assign(t, f, NV))
    a0 = to_assign(t0o, f0o)
    s_model = to_assign(m_t, m_f)
    s_guessed = jax.vmap(lambda w: core.unpack_mask(w, NV))(asm)

    need_search = en & (outcome0 == core.RUNNING)
    result = jnp.where(need_search, result_s, outcome0)
    guessed = jnp.where(need_search[:, None], s_guessed, anchor_mask)
    model = jnp.where(need_search[:, None], s_model, a0)
    result = jnp.where(en, result, jnp.int32(core.RUNNING))
    tr_stack = jnp.full((B, 0, NC + 1), -1, jnp.int32)
    return result, guessed, model, steps, tr_stack, tr_n


_batched_search_fused = jax.jit(compileguard.observe(
    "pallas_search.batched_search_fused", _search_fused_impl))


def batched_search_fused(pts: core.ProblemTensors, budget, en):
    """Public entry: shape-guarded fused phase-1 search (see
    :func:`fused_supported`; callers fall back to the XLA path when this
    raises)."""
    if not fused_supported(pts):
        raise ValueError(
            f"fused search kernel caps exceeded: Kc "
            f"{pts.choice_cand.shape[-1]} (max {MAX_KC}), W "
            f"{pts.var_choices.shape[-1]} (max {MAX_W})"
        )
    return _batched_search_fused(pts, budget, en)
