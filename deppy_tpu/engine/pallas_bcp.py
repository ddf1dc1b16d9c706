"""Fused BCP-fixpoint Pallas TPU kernel.

The hot op of the whole framework is boolean-constraint propagation: every
DPLL iteration (engine/core.py:dpll) runs BCP to fixpoint, and each round is
a full pass over the clause set.  The jnp "bits" path already turns that
pass into dense bitplane algebra, but XLA still streams the clause planes
from HBM **once per round**.  This kernel instead pins the positive/negative
literal planes, the AtMost member planes, and the assignment words in VMEM
and iterates the fixpoint *inside* the kernel — clause data crosses
HBM→VMEM once per fixpoint, not once per round.  That is the TPU-native
replacement for the watched-literal scheme gini uses to avoid re-touching
clauses (the reference delegates BCP to gini's CDCL engine; see SURVEY.md
§2.6): where a CPU solver avoids memory traffic with pointers, a TPU kernel
avoids it with residency.

All planes are int32 (Mosaic has no unsigned reductions); bit extraction
uses logical shifts, so the sign bit is just bit 31.  The row dimensions
(C, NA) are padded to powers of two by the driver, which the halving-tree
OR-reduction in :func:`deppy_tpu.engine.core.round_planes` relies on.

Batch use: the caller vmaps :func:`bcp_fixpoint`; Pallas lifts the batch
axis into a grid dimension, so each grid step solves one problem's fixpoint
with its planes resident in VMEM.

Measured reality (v5-lite, 256-problem random-catalog batch, warm): the jnp
"bits" path wins — 368 solves/s vs 206/s for this kernel — because under
vmap XLA vectorizes the *batch* axis of the bitplane algebra across the
8×128 VPU lanes, while the kernel's grid serializes problems.  The kernel
is therefore opt-in (``DEPPY_TPU_BCP=pallas``), aimed at single problems
whose clause planes approach VMEM capacity, where per-round HBM streaming
is the bottleneck instead.

VMEM budget: the dominant term is (pos + neg) = 2·C·Wv·4 bytes.  At the
default caps (C ≤ 8192 clause rows, Wv ≤ 128 words = 4096 vars) that is
8 MiB, within the ~16 MiB/core budget; the driver's padding economics keep
real catalog problems far below it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import core


def _kernel(minw_ref, en_ref, pos_ref, neg_ref, mem_ref, act_ref, cardn_ref,
            min_ref, t0_ref, f0_ref, conf_ref, t_ref, f_ref):
    pos = pos_ref[:]
    neg = neg_ref[:]
    mem = mem_ref[:]
    card_active = act_ref[:] != 0    # [NA, 1] row-activity mask
    card_n2 = cardn_ref[:]
    min_bits = min_ref[:]
    min_w = minw_ref[0, 0]

    def cond(state):
        conflict, _, _, changed = state
        return changed & ~conflict

    def body(state):
        _, t, f, _ = state
        return core.round_planes(
            pos, neg, mem, card_active, card_n2, min_bits, min_w, t, f
        )

    # The lane-gating flag seeds `changed`: a disabled lane runs zero
    # rounds (see core.bcp).
    state = (jnp.bool_(False), t0_ref[:], f0_ref[:], en_ref[0, 0] != 0)
    conflict, t, f, _ = lax.while_loop(cond, body, state)
    conf_ref[0, 0] = conflict.astype(jnp.int32)
    t_ref[:] = t
    f_ref[:] = f


def bcp_fixpoint(pos, neg, mem, card_active, card_n2, min_bits, min_w, t0, f0,
                 enabled=True):
    """Run BCP to fixpoint on bitplanes.  Shapes as in
    :func:`deppy_tpu.engine.core.round_planes` (``card_active`` is the
    precomputed [NA, 1] row-activity mask); returns (conflict, t, f).
    Interprets on the CPU backend (:func:`core.pallas_interpret`) so the
    same code path is testable on the CPU mesh used by the test suite."""
    Wv = pos.shape[1]
    minw2 = jnp.full((1, 1), min_w, jnp.int32)
    en2 = jnp.full((1, 1), enabled, jnp.int32)
    act = card_active.astype(jnp.int32)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec((1, 1), memory_space=pltpu.SMEM)
    conf, t, f = pl.pallas_call(
        _kernel,
        out_shape=(
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, Wv), jnp.int32),
            jax.ShapeDtypeStruct((1, Wv), jnp.int32),
        ),
        in_specs=[
            smem, smem,
            vmem, vmem, vmem, vmem, vmem, vmem, vmem, vmem,
        ],
        out_specs=(
            pl.BlockSpec((1, 1), memory_space=pltpu.SMEM),
            vmem,
            vmem,
        ),
        interpret=core.pallas_interpret(),
    )(minw2, en2, pos, neg, mem, act, card_n2, min_bits, t0, f0)
    return conf[0, 0] != 0, t, f
