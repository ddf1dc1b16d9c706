"""Blockwise clause-partitioned BCP for problems past VMEM capacity.

The fused fixpoint kernel (:mod:`deppy_tpu.engine.pallas_bcp`) wins by
holding ALL clause planes resident in VMEM across propagation rounds —
which caps it at problems whose planes fit (~8 MiB of pos+neg at the
default caps).  Above that, the jnp "bits" path must re-stream every
clause plane from HBM **once per propagation round**, and a deep
implication chain means dozens of rounds, i.e. dozens of full-catalog
HBM sweeps.  This module is SURVEY.md §5's stated translation for the
reference's scaling axis (gini's sparse in-RAM structures,
/root/reference/pkg/sat/bench_test.go:12) on ONE device: partition the
clause rows into VMEM-sized blocks and make the expensive unit of work a
**sweep**, not a round.

Mechanics (Gauss-Seidel over blocks; BCP is monotone and confluent, so
any application order reaches the same unique fixpoint):

* one ``pallas_call`` sweep walks the blocks on a 1-D grid; the
  assignment planes (t, f — a few KiB) live in a VMEM accumulator that
  persists across grid steps, so block k+1 sees block k's forcings
  *within the same sweep*;
* while a block is resident, the kernel runs that block's LOCAL
  fixpoint to convergence (a while loop over
  :func:`core.round_planes`) — intra-block implication chains, however
  deep, cost ONE streaming of that block;
* an outer ``lax.while_loop`` repeats sweeps until a sweep changes
  nothing (or conflicts).  Sweep count tracks CROSS-block chain depth,
  which for locality-correlated encodings (the encoder emits a
  bundle's clauses together) is far below total chain depth — that gap
  is exactly the HBM traffic saved over the bits path.

Cardinality rows ride block 0 (they are few; their activity mask is
gated on ``program_id == 0``), and the dynamic minimization row is
evaluated in every block (idempotent under OR).  Conflict semantics:
the conflict FLAG is order-independent (a dead row is dead in every
completion), and post-conflict plane contents are never read by any
caller (dpll/search gate snapshot use on ¬conflict), so outcome parity
with the bits path holds bit-for-bit — pinned by
tests/test_pallas_blockwise.py's differential suite.

Like every device bet in this tree the impl is opt-in
(``DEPPY_TPU_BCP=blockwise``) until a chip measurement shows it
winning; ``benchmarks/pallas_case.py --impl blockwise`` builds the
2-4× VMEM case.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import config
from . import core

# Clause rows per block: 2 (pos+neg) x 2 (double-buffered DMA) x
# BLOCK_ROWS x Wv x 4B of streamed VMEM; at the default and Wv = 128
# that is 4 MiB, leaving headroom for the resident accumulators and
# cardinality planes inside the ~16 MiB/core budget.
BLOCK_ROWS = int(config.env_raw("DEPPY_TPU_BLOCK_ROWS", "2048"))


def _kernel(minw_ref, en_ref, pos_ref, neg_ref, mem_ref, act_ref,
            cardn_ref, min_ref, tin_ref, fin_ref,
            conf_ref, t_ref, f_ref):
    b = pl.program_id(0)
    pos = pos_ref[:]
    neg = neg_ref[:]
    mem = mem_ref[:]
    card_n2 = cardn_ref[:]
    min_bits = min_ref[:]
    min_w = minw_ref[0, 0]

    # First block of a sweep: seed the resident accumulators from the
    # sweep's input planes (they persist across the remaining steps).
    @pl.when(b == 0)
    def _():
        conf_ref[0, 0] = jnp.int32(0)
        t_ref[:] = tin_ref[:]
        f_ref[:] = fin_ref[:]

    # Cardinality rows ride block 0 only; other blocks see them all
    # inactive (their member planes are still resident inputs, just
    # masked off).
    card_active = (act_ref[:] != 0) & (b == 0)

    run = (en_ref[0, 0] != 0) & (conf_ref[0, 0] == 0)

    def cond(state):
        conflict, _, _, changed = state
        return changed & ~conflict

    def body(state):
        _, t, f, _ = state
        return core.round_planes(
            pos, neg, mem, card_active, card_n2, min_bits, min_w, t, f
        )

    state = (jnp.bool_(False), t_ref[:], f_ref[:], run)
    conflict, t, f, _ = lax.while_loop(cond, body, state)
    conf_ref[0, 0] = conf_ref[0, 0] | conflict.astype(jnp.int32)
    t_ref[:] = t
    f_ref[:] = f


def _sweep(pos, neg, mem, card_active, card_n2, min_bits, min_w, t, f,
           run, block_rows: int):
    """One full pass over the clause blocks (Gauss-Seidel within the
    sweep).  Returns (conflict, t, f)."""
    C, Wv = pos.shape
    NB = C // block_rows
    NA = mem.shape[0]
    minw2 = jnp.full((1, 1), min_w, jnp.int32)
    en2 = jnp.full((1, 1), run, jnp.int32)
    act = card_active.astype(jnp.int32)

    blk = pl.BlockSpec((block_rows, Wv), lambda b: (b, 0),
                       memory_space=pltpu.VMEM)
    res = lambda *s: pl.BlockSpec(s, lambda b: (0,) * len(s),  # noqa: E731
                                  memory_space=pltpu.VMEM)
    smem = pl.BlockSpec((1, 1), lambda b: (0, 0),
                        memory_space=pltpu.SMEM)
    conf, t, f = pl.pallas_call(
        _kernel,
        grid=(NB,),
        in_specs=[
            smem, smem,
            blk, blk,
            res(NA, Wv), res(NA, 1), res(NA, 1), res(1, Wv),
            res(1, Wv), res(1, Wv),
        ],
        out_specs=(smem, res(1, Wv), res(1, Wv)),
        out_shape=(
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, Wv), jnp.int32),
            jax.ShapeDtypeStruct((1, Wv), jnp.int32),
        ),
        interpret=core.pallas_interpret(),
    )(minw2, en2, pos, neg, mem, act, card_n2, min_bits, t, f)
    return conf[0, 0] != 0, t, f


def bcp_fixpoint(pos, neg, mem, card_active, card_n2, min_bits, min_w,
                 t0, f0, enabled=True, block_rows: int | None = None):
    """Run BCP to fixpoint with clause planes streamed blockwise.
    Signature matches :func:`pallas_bcp.bcp_fixpoint`; returns
    (conflict, t, f).  The outer loop repeats sweeps until one changes
    nothing — its trip count is the cross-block chain depth, normally a
    handful, so while-trip overhead is negligible next to each sweep's
    HBM traffic."""
    C, Wv = pos.shape
    br = block_rows or BLOCK_ROWS
    br = min(br, C)
    # Mosaic requires the block's second-to-minor dim be 8-divisible (or
    # equal to the array's row count); round up to the sublane quantum —
    # the extra rows are zero clause planes, inert under round_planes
    # (first hardware compile 2026-08-01 rejected a 2-row smoke block).
    # Interpret mode has no such constraint and keeps the exact br so
    # the tiny-block differential tests still exercise multi-block
    # sweeps (cross-block conflict/forcing propagation).
    if not core.pallas_interpret():
        br = max(8 * ((br + 7) // 8), 8)
    pad = (-C) % br
    if pad:
        zrow = jnp.zeros((pad, Wv), jnp.int32)
        pos = jnp.concatenate([pos, zrow])
        neg = jnp.concatenate([neg, zrow])

    def cond(s):
        conflict, _, _, changed = s
        return changed & ~conflict

    def body(s):
        _, t, f, _ = s
        conflict, t2, f2 = _sweep(
            pos, neg, mem, card_active, card_n2, min_bits, min_w, t, f,
            jnp.bool_(True), br,
        )
        changed = ((t2 != t) | (f2 != f)).any() & ~conflict
        return conflict, t2, f2, changed

    state = (jnp.bool_(False), t0, f0,
             jnp.asarray(enabled, bool) if not isinstance(enabled, bool)
             else jnp.bool_(enabled))
    conflict, t, f, _ = lax.while_loop(cond, body, state)
    return conflict, t, f
