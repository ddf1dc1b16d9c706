"""block-contract: Pallas BlockSpec/grid arithmetic vs the driver's
declared size classes.

The three Pallas kernels (:mod:`..engine.pallas_bcp`,
:mod:`..engine.pallas_blockwise`, :mod:`..engine.pallas_search`) and
the driver's padding economics share a set of numeric contracts that
today only fail on real hardware (Mosaic rejections) or as silent
padding waste (ROADMAP item 3's "a 64-clause problem pays the
4096-clause pad").  This checker evaluates them statically, per
declared size class, against the kernel/driver sources:

  * ``smem-budget`` — per ``pallas_call`` in the fused search module,
    the number of whole-vector ``(B,)`` SMEM specs
    (``_smem_scalars``) x ``B=4096`` (the widest lane width
    ``scripts/lane_probe.py`` probes, the ``test_tpu_compile``
    regression anchor) x 4 bytes must stay under
    :data:`SMEM_BUDGET_BYTES`, and the column count under
    :data:`MAX_SMEM_COLS`;
  * ``smem-per-row-block`` — an SMEM ``BlockSpec`` with a ``(1, 1)``
    block indexed per grid step: the exact shape Mosaic rejected on
    first hardware compile (2026-08-01 — a block's last two dims must
    be (8, 128)-divisible or equal to the array's).  Permanent
    regression rule for the ``_smem_scalars`` fix;
  * ``block-pad-waste`` — the blockwise kernel's row padding per size
    class: ``br = min(BLOCK_ROWS, C)`` rounded to the 8-sublane
    quantum, then ``C`` padded to a multiple — the pad fraction must
    stay under :data:`BLOCK_PAD_WASTE_MAX` (driver buckets ``C`` to a
    power of two, so a contract-respecting ``BLOCK_ROWS`` divides it
    exactly);
  * ``missing-sublane-round`` — the blockwise kernel must still carry
    the 8-sublane round-up (same 2026-08-01 hardware rejection class);
  * ``padding-waste`` — the ladder's size-class economics: adjacent
    declared classes must differ by at least ``SPLIT_RATIO`` in padded
    cost (else the partitioner could never separate them and the small
    class pays the large class's pad), and the worst within-class cell
    waste under power-of-two bucketing must stay under
    :data:`CLASS_WASTE_MAX`;
  * ``bank-budget`` — the watched-impl clause bank
    (:mod:`..engine.clause_bank`): each class's adjacency tables at its
    declared ``OCC`` cap (``2·V·OCC + NV·OCC`` int32 cells) must fit
    the same VMEM residency budget as the clause planes — a class
    whose bank cannot be resident belongs on the dense rounds, not on
    a silently-thrashing bank;
  * ``contract-drift`` — a source constant this checker evaluates
    (the shared size-class table, ``_smem_scalars``, the sublane
    round) is gone or moved: the contract can no longer be checked,
    which is itself a finding, not a silent pass.

The size classes come from the SHARED ladder
(:mod:`deppy_tpu.size_classes` — import-light, stdlib only), which the
driver's partitioner consumes too (ISSUE 12): the lint contracts and
the runtime economics read one table and can never drift.  Beyond that
import, pure stdlib ``ast`` arithmetic: no JAX, evaluable in CI before
a backend exists.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, List, Optional

from .core import Checker, Finding, SourceFile
from .core import dotted as _dotted

from .. import size_classes as _shared

# Declared size classes: the SHARED ladder (deppy_tpu.size_classes) the
# driver's partitioner consumes.  C = clause rows, NV = problem vars,
# NCON = applied constraints; V = NV + NCON, Wv = ceil(V / 32) bitplane
# words; OCC = the watched bank's occurrence cap.
SIZE_CLASSES: Dict[str, Dict[str, int]] = _shared.SIZE_CLASSES
# Widest per-problem batch the SMEM scalar columns are probed at
# (scripts/lane_probe.py; tests/test_tpu_compile.py B=4096 anchor).
SMEM_ANCHOR_B = 4096
SMEM_BUDGET_BYTES = 128 * 1024
MAX_SMEM_COLS = 8
# Fused-fixpoint VMEM residency: dominant term 2*C*Wv*4 (pos+neg), with
# 2x slack for the member/assignment planes, under the ~16 MiB/core
# budget the pallas_bcp docstring declares.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024
BLOCK_PAD_WASTE_MAX = 0.25
# Power-of-two bucketing bounds each padded dim below 2x its live size;
# clause-cell waste compounds across the row and word dims.
CLASS_WASTE_MAX = 0.75

_BCP = "deppy_tpu/engine/pallas_bcp.py"
_BLOCKWISE = "deppy_tpu/engine/pallas_blockwise.py"
_SEARCH = "deppy_tpu/engine/pallas_search.py"
_DRIVER = "deppy_tpu/engine/driver.py"
_LADDER = "deppy_tpu/size_classes.py"
_BANK = "deppy_tpu/engine/clause_bank.py"


# Cost arithmetic comes from the shared ladder module — the checker
# must evaluate the SAME model the driver partitions by, or the
# economics findings go stale against a retuned proxy.
_wv = _shared.wv
_cost = _shared.class_cost


def _module_const(sf: SourceFile, name: str):
    """Top-level ``NAME = <literal>`` value, or None."""
    for stmt in sf.tree.body:
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and stmt.targets[0].id == name):
            try:
                return ast.literal_eval(stmt.value)
            except ValueError:
                return None
    return None



class BlockContractChecker(Checker):
    name = "block-contract"
    default_scope = ("deppy_tpu/engine", "deppy_tpu/parallel",
                     "deppy_tpu/size_classes.py")

    def __init__(self, size_classes: Optional[Dict[str, Dict[str, int]]]
                 = None):
        self.size_classes = size_classes or SIZE_CLASSES

    def check(self, files: List[SourceFile], root: Path) -> List[Finding]:
        out: List[Finding] = []
        by_rel = {sf.rel: sf for sf in files}
        if _SEARCH in by_rel:
            self._check_smem(out, by_rel[_SEARCH])
        if _BLOCKWISE in by_rel:
            self._check_blockwise(out, by_rel[_BLOCKWISE])
        if _BCP in by_rel:
            self._check_vmem(out, by_rel[_BCP])
        if _BANK in by_rel:
            self._check_bank(out, by_rel[_BANK])
        for rel in (_BCP, _BLOCKWISE):
            if rel in by_rel:
                self._check_per_row_smem(out, by_rel[rel])
        if _DRIVER in by_rel and not self.partial:
            # Class economics need the ladder's constants: skip on
            # --changed runs that did not touch the driver.
            self._check_classes(out, by_rel.get(_LADDER),
                                by_rel[_DRIVER])
        return out

    # ----------------------------------------------------- SMEM columns

    def _check_smem(self, out: List[Finding], sf: SourceFile) -> None:
        has_scalars_helper = any(
            isinstance(n, ast.FunctionDef) and n.name == "_smem_scalars"
            for n in ast.walk(sf.tree))
        if not has_scalars_helper:
            self.finding(
                out, sf, 1, "contract-drift", "_smem_scalars",
                "pallas_search no longer defines `_smem_scalars` — the "
                "SMEM column contract (B=4096 anchor) cannot be "
                "evaluated; update block_contract.py with the new "
                "spelling")
            return
        for fn in (n for n in ast.walk(sf.tree)
                   if isinstance(n, ast.FunctionDef)):
            # Local names bound to a whole-column scalar spec.
            scalar_cols = {
                t.id
                for stmt in ast.walk(fn)
                if isinstance(stmt, ast.Assign)
                and isinstance(stmt.value, ast.Call)
                and (_dotted(stmt.value.func) or "").endswith(
                    "_smem_scalars")
                for t in stmt.targets if isinstance(t, ast.Name)}
            if not scalar_cols:
                continue
            for call in ast.walk(fn):
                if not (isinstance(call, ast.Call)
                        and (_dotted(call.func) or "").endswith(
                            "pallas_call")):
                    continue
                n_cols = 0
                for kw in call.keywords:
                    if kw.arg in ("in_specs", "out_specs"):
                        for sub in ast.walk(kw.value):
                            if (isinstance(sub, ast.Name)
                                    and sub.id in scalar_cols):
                                n_cols += 1
                col_bytes = n_cols * SMEM_ANCHOR_B * 4
                if n_cols > MAX_SMEM_COLS or col_bytes > SMEM_BUDGET_BYTES:
                    self.finding(
                        out, sf, call.lineno, "smem-budget",
                        f"{fn.name}:{n_cols}",
                        f"pallas_call in `{fn.name}` maps {n_cols} "
                        f"whole-vector (B,) scalar specs into SMEM — "
                        f"{col_bytes} bytes at the probed B="
                        f"{SMEM_ANCHOR_B} anchor (budget "
                        f"{SMEM_BUDGET_BYTES}, max {MAX_SMEM_COLS} "
                        f"columns); see tests/test_tpu_compile.py")
        self._check_per_row_smem(out, sf)

    def _check_per_row_smem(self, out: List[Finding],
                            sf: SourceFile) -> None:
        """The 2026-08-01 Mosaic rejection, as a permanent rule: an SMEM
        BlockSpec with a (1, 1) block whose index_map moves with the
        grid — the per-problem scalar block every phase kernel failed
        on before `_smem_scalars`."""
        for call in ast.walk(sf.tree):
            if not (isinstance(call, ast.Call)
                    and (_dotted(call.func) or "").endswith("BlockSpec")):
                continue
            in_smem = any(
                kw.arg == "memory_space"
                and (_dotted(kw.value) or "").endswith("SMEM")
                for kw in call.keywords)
            if not in_smem or not call.args:
                continue
            try:
                block = ast.literal_eval(call.args[0])
            except ValueError:
                continue
            if block != (1, 1) or len(call.args) < 2:
                continue
            index_map = call.args[1]
            if not isinstance(index_map, ast.Lambda):
                continue
            grid_args = {a.arg for a in index_map.args.args}
            moves = any(isinstance(sub, ast.Name) and sub.id in grid_args
                        for sub in ast.walk(index_map.body))
            if moves:
                self.finding(
                    out, sf, call.lineno, "smem-per-row-block",
                    "BlockSpec(1,1)",
                    "SMEM BlockSpec with a (1, 1) block indexed per "
                    "grid step — Mosaic requires a block's last two "
                    "dims be (8, 128)-divisible or equal to the "
                    "array's (the 2026-08-01 hardware rejection); map "
                    "the whole (B, 1) column and index with "
                    "pl.program_id (see pallas_search._smem_scalars)")

    # ------------------------------------------------------- blockwise

    def _check_blockwise(self, out: List[Finding],
                         sf: SourceFile) -> None:
        if "(br + 7) // 8" not in sf.text:
            self.finding(
                out, sf, 1, "missing-sublane-round", "bcp_fixpoint",
                "the blockwise kernel no longer rounds its block rows "
                "to the 8-sublane quantum — Mosaic rejects blocks whose "
                "second-to-minor dim is not 8-divisible (2026-08-01 "
                "hardware compile); restore the round-up or teach "
                "block_contract.py the new spelling")
        from .. import config

        default = config.REGISTRY["DEPPY_TPU_BLOCK_ROWS"].default \
            if "DEPPY_TPU_BLOCK_ROWS" in config.REGISTRY else None
        if not isinstance(default, int):
            self.finding(
                out, sf, 1, "contract-drift", "DEPPY_TPU_BLOCK_ROWS",
                "DEPPY_TPU_BLOCK_ROWS has no integer default in "
                "config.REGISTRY — the blockwise pad-waste contract "
                "cannot be evaluated")
            return
        for cname, cls in sorted(self.size_classes.items()):
            C = cls["C"]
            br = min(default, C)
            br = max(8 * ((br + 7) // 8), 8)
            padded = C + (-C) % br
            waste = (padded - C) / padded
            if waste > BLOCK_PAD_WASTE_MAX:
                self.finding(
                    out, sf, 1, "block-pad-waste", f"{cname}:{C}",
                    f"size class `{cname}` (C={C}) pays "
                    f"{waste:.0%} row padding under BLOCK_ROWS="
                    f"{default} (block {br}, padded {padded}) — over "
                    f"the {BLOCK_PAD_WASTE_MAX:.0%} bound; a "
                    f"64-clause problem must not pay a 4096-row pad "
                    f"(ROADMAP item 3)")

    # ------------------------------------------------------------ VMEM

    def _check_vmem(self, out: List[Finding], sf: SourceFile) -> None:
        for cname, cls in sorted(self.size_classes.items()):
            # pos + neg planes dominate; 2x slack covers the member/
            # activation/assignment residents (the module docstring's
            # budget model).
            resident = 2 * cls["C"] * _wv(cls) * 4 * 2
            if resident > VMEM_BUDGET_BYTES:
                self.finding(
                    out, sf, 1, "vmem-budget", f"{cname}:{cls['C']}",
                    f"size class `{cname}` needs ~{resident} bytes of "
                    f"resident clause planes (2*C*Wv*4 with 2x slack) "
                    f"— past the {VMEM_BUDGET_BYTES} VMEM budget the "
                    f"fused fixpoint kernel declares; route this class "
                    f"to the blockwise kernel")

    # ------------------------------------------------------------ banks

    def _check_bank(self, out: List[Finding], sf: SourceFile) -> None:
        """Watched-impl bank residency (ISSUE 12): each class's
        adjacency tables at its declared OCC cap — occ_pos + occ_neg
        (2·V·OCC) plus card_occ (NV·OCC, Oc bounded by OCC) int32
        cells — must fit the same VMEM budget the clause planes
        declare, with 2x slack for the planes resident beside them."""
        for cname, cls in sorted(self.size_classes.items()):
            occ = cls.get("OCC")
            if not isinstance(occ, int):
                self.finding(
                    out, sf, 1, "contract-drift", f"{cname}:OCC",
                    f"size class `{cname}` declares no integer OCC cap "
                    f"in deppy_tpu.size_classes — the watched-bank "
                    f"residency contract cannot be evaluated")
                continue
            V = cls["NV"] + cls["NCON"]
            resident = (2 * V * occ + cls["NV"] * occ) * 4 * 2
            if resident > VMEM_BUDGET_BYTES:
                self.finding(
                    out, sf, 1, "bank-budget", f"{cname}:{occ}",
                    f"size class `{cname}`'s clause bank needs "
                    f"~{resident} bytes at its OCC={occ} cap (2x slack "
                    f"over (2V+NV)·OCC·4) — past the "
                    f"{VMEM_BUDGET_BYTES} residency budget; lower the "
                    f"class's OCC cap (dispatches past it already fall "
                    f"back to the dense rounds)")

    # ------------------------------------------------- class economics

    def _check_classes(self, out: List[Finding],
                       ladder_sf: Optional[SourceFile],
                       driver_sf: SourceFile) -> None:
        # SPLIT_RATIO lives in the shared ladder module (ISSUE 12);
        # scans without it (checker-test fixtures) fall back to a
        # driver-source literal, the pre-ladder spelling.
        sf = ladder_sf if ladder_sf is not None else driver_sf
        split_ratio = _module_const(sf, "SPLIT_RATIO")
        if not isinstance(split_ratio, (int, float)):
            self.finding(
                out, sf, 1, "contract-drift", "SPLIT_RATIO",
                "SPLIT_RATIO is no longer a module literal in "
                "deppy_tpu/size_classes.py (or the fixture driver) — "
                "the size-class separability contract cannot be "
                "evaluated")
            return
        if ladder_sf is not None and "size_classes" not in driver_sf.text:
            self.finding(
                out, driver_sf, 1, "contract-drift", "size_classes",
                "the driver no longer references the shared "
                "deppy_tpu.size_classes ladder — its partitioner and "
                "these contracts can drift apart")
            return
        ordered = sorted(self.size_classes.items(),
                         key=lambda kv: _cost(kv[1]))
        for (a_name, a), (b_name, b) in zip(ordered, ordered[1:]):
            ratio = _cost(b) / max(_cost(a), 1)
            if ratio < split_ratio:
                self.finding(
                    out, sf, 1, "padding-waste",
                    f"{a_name}->{b_name}",
                    f"size classes `{a_name}` and `{b_name}` differ by "
                    f"only {ratio:.2f}x in padded cost — below "
                    f"driver.SPLIT_RATIO={split_ratio}, so "
                    f"partition_buckets can never separate them and "
                    f"every `{a_name}` problem pays `{b_name}`'s pad")
        for cname, cls in ordered:
            # Worst live problem in the class: one past the previous
            # power-of-two bucket in every dim.
            live = {k: v // 2 + 1 for k, v in cls.items()}
            waste = 1.0 - _cost(live) / _cost(cls)
            if waste > CLASS_WASTE_MAX:
                self.finding(
                    out, sf, 1, "padding-waste", f"{cname}:cell-waste",
                    f"size class `{cname}`'s worst-case cell waste is "
                    f"{waste:.0%} — past the {CLASS_WASTE_MAX:.0%} "
                    f"bound the power-of-two bucketing is supposed to "
                    f"guarantee")
