"""Ahead-of-time compiles for a described TPU v5e — without the chip.

Every Pallas kernel and the XLA programs of the main path are lowered
AND compiled by the TPU compiler installed here, against a v5e:2x2
topology that is described, not attached.  The compiler enforces what
interpret mode cannot: the fast-memory (VMEM/SMEM) limits, the tiling
rules of block shapes, and whether a program fits the device.  Nothing
runs, so answers and times are the chip's and the interpret-mode
differential suites' business (``chip_smoke.py``).

The topology is described inside the ``topo`` fixture, never while a
module is imported: the driver's xdist workers each import every test
file, and only a worker that runs these cases should load the TPU
library.  The driver's command distributes with ``--dist loadfile``,
which keeps this file on one worker; under another distribution each
worker that draws a case builds its own ``topo``.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

pytest.importorskip("jax")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding  # noqa: E402

from deppy_tpu.engine import core, driver, pallas_search  # noqa: E402
from deppy_tpu.models import operatorhub_catalog, random_instance  # noqa: E402
from deppy_tpu.sat.encode import encode  # noqa: E402

BUDGET = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out of the cache.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def catalog_chunk():
    """The 512-lane chunk of the phase-2 catalog batch (chip_smoke.py):
    ``operatorhub_catalog(40, 5)``, ~200 bundles each."""
    problems = [encode(operatorhub_catalog(n_packages=40,
                                           versions_per_package=5, seed=s))
                for s in range(driver.MAX_LANES)]
    return problems, driver._Dims(problems, driver.MAX_LANES)


@pytest.fixture(autouse=True)
def _force_mosaic(monkeypatch):
    """The kernel wrappers interpret on the CPU backend; compiling FOR
    tpu must build the real Mosaic kernel instead."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=sharding),
        tree)


def _compile(fn, *args, sharding):
    """Lower and compile ``fn`` for the described chip; any compiler
    refusal raises here."""
    fn = fn if hasattr(fn, "lower") else jax.jit(fn)
    return fn.lower(*_shapes(args, sharding)).compile()


def _batch(problems, B=None, pack=True, full=False):
    d = driver._Dims(problems, B or len(problems))
    pts = driver.pad_stack(problems, d, d.B, pack=pack)
    if full:
        pts = jax.eval_shape(lambda p: driver._derive_full(p, d), pts)
    en = np.arange(d.B) < len(problems)
    return d, pts, en


def _resident(problems, d, full=False):
    """Shapes of a chunk as the split path holds it on the device:
    compact tensors plus planes derived for the selected impl."""
    pts = driver.pad_stack(problems, d, d.B, pack=False)
    return jax.eval_shape(
        lambda p: (driver._derive_full(p, d) if full
                   else driver._derive_planes(p, d)), pts)


def _problems(n, length):
    return [encode(random_instance(length=length, seed=s))
            for s in range(n)]


@pytest.mark.parametrize("n,length", [(2, 8), (64, 24), (512, 48)])
def test_search_fused_compiles(one_chip, n, length):
    # (512, 48) is the lane-cap chunk of the random-instance workload.
    d, pts, en = _batch(_problems(n, length))
    _compile(lambda p, e: pallas_search._batched_search_fused(
        p, jnp.int32(BUDGET), e), pts, en, sharding=one_chip)


@pytest.mark.parametrize("n,length", [(2, 8), (64, 24)])
def test_minimize_fused_compiles(one_chip, n, length):
    d, pts, en = _batch(_problems(n, length))
    NV = pts.var_choices.shape[1]
    B = pts.pos_bits_r.shape[0]
    _compile(
        lambda p, r, m, g, s, e: pallas_search._batched_minimize_fused(
            p, r, m, g, jnp.int32(BUDGET), s, e),
        pts, np.full(B, core.SAT, np.int32), np.zeros((B, NV), np.int32),
        np.zeros((B, NV), bool), np.zeros(B, np.int32), en,
        sharding=one_chip)


@pytest.mark.parametrize("n,length", [(2, 8), (48, 24)])
def test_core_fused_compiles(one_chip, n, length):
    d, pts, en = _batch(_problems(n, length), pack=False, full=True)
    _compile(
        lambda p, s, e: pallas_search._batched_core_fused(
            p, jnp.int32(BUDGET), s, e, V=d.V, NCON=d.NCON, NV=d.NV),
        pts, np.zeros(d.B, np.int32), en, sharding=one_chip)


def test_smem_scalars_compile_at_widest_probed_lane_width(one_chip):
    """B=4096: the fused kernels map whole per-problem ``(B,)`` scalar
    vectors into SMEM (``pallas_search._smem_scalars``), so their SMEM
    footprint grows linearly with B; a kernel change that adds scalar
    vectors can blow SMEM capacity only at wide B."""
    d, pts, en = _batch(_problems(2, 8), B=4096)
    assert d.B == 4096
    _compile(lambda p, e: pallas_search._batched_search_fused(
        p, jnp.int32(BUDGET), e), pts, en, sharding=one_chip)


def _planes(C=64, NA=8, Wv=4):
    return (np.zeros((C, Wv), np.int32), np.zeros((C, Wv), np.int32),
            np.zeros((NA, Wv), np.int32), np.zeros((NA, 1), bool),
            np.zeros((NA, 1), np.int32), np.zeros((1, Wv), np.int32),
            np.int32(0), np.zeros((1, Wv), np.int32),
            np.zeros((1, Wv), np.int32))


def test_blockwise_compiles(one_chip):
    from deppy_tpu.engine import pallas_blockwise

    # block_rows=16 over 64 clause rows keeps the sweep multi-block
    # after the 8-row sublane rounding.
    _compile(lambda *a: pallas_blockwise.bcp_fixpoint(
        *a, enabled=True, block_rows=16), *_planes(), sharding=one_chip)


def test_bcp_fused_compiles(one_chip):
    from deppy_tpu.engine import pallas_bcp

    _compile(lambda *a: pallas_bcp.bcp_fixpoint(*a, enabled=True),
             *_planes(), sharding=one_chip)


@pytest.mark.parametrize("impl", ["bits", "pallas", "blockwise"])
def test_main_path_search_compiles(one_chip, catalog_chunk, impl):
    """Phase 1 of the split path (``core.batched_search``) at the
    512-lane dims of the catalog batch, under the default ``bits`` BCP
    and the two Pallas BCP switches."""
    problems, d = catalog_chunk
    core.set_bcp_impl(impl)
    try:
        pts = _resident(problems, d)
        fn = core.batched_search(d.V, d.NCON, d.NV, 0)
        _compile(fn, pts, np.int32(BUDGET), np.ones(d.B, bool),
                 sharding=one_chip)
    finally:
        core.set_bcp_impl("auto")


def test_main_path_fused_search_compiles(one_chip, catalog_chunk):
    problems, d = catalog_chunk
    core.set_search_impl("fused")
    try:
        pts = _resident(problems, d)
        fn = core.batched_search(d.V, d.NCON, d.NV, 0)
        _compile(fn, pts, np.int32(BUDGET), np.ones(d.B, bool),
                 sharding=one_chip)
    finally:
        core.set_search_impl("auto")


def _search_outs(problems, d):
    """Shapes of phase 1's outputs, which phases 2 and 3 take."""
    fn = core.batched_search(d.V, d.NCON, d.NV, 0)
    return jax.eval_shape(fn, _resident(problems, d), np.int32(BUDGET),
                          np.ones(d.B, bool))


def test_main_path_minimize_compiles(one_chip, catalog_chunk):
    problems, d = catalog_chunk
    o = _search_outs(problems, d)
    fn = core.batched_minimize_gated(d.V, d.NCON, d.NV)
    _compile(fn, _resident(problems, d), o[0], o[2], o[1],
             np.int32(BUDGET), o[3], np.ones(d.B, bool), sharding=one_chip)


def test_main_path_core_compiles(one_chip, catalog_chunk):
    problems, d = catalog_chunk
    o = _search_outs(problems, d)
    fn = core.batched_core_gated(d.V, d.NCON, d.NV)
    _compile(fn, _resident(problems, d, full=True), o[0],
             np.int32(BUDGET), o[3], np.ones(d.B, bool), sharding=one_chip)


def test_batch_axis_compiles_on_four_chips(topo, one_chip, catalog_chunk):
    """The batch-axis program over a 4-device mesh of the described
    chips: lanes sharded on ``"batch"``, so each device holds a quarter
    of the chunk's arguments."""
    from deppy_tpu.parallel import BATCH_AXIS

    problems, d = catalog_chunk
    mesh = Mesh(np.array(topo.devices[:4]), (BATCH_AXIS,))
    fn = core.batched_search(d.V, d.NCON, d.NV, 0)
    args = (_resident(problems, d), np.int32(BUDGET), np.ones(d.B, bool))

    def shard(x):
        spec = PartitionSpec(BATCH_AXIS, *([None] * (len(x.shape) - 1))) \
            if len(x.shape) else PartitionSpec()
        return jax.ShapeDtypeStruct(x.shape, jnp.result_type(x),
                                    sharding=NamedSharding(mesh, spec))

    four = fn.lower(*jax.tree_util.tree_map(shard, args)).compile()
    one = _compile(fn, *args, sharding=one_chip)
    per_device = four.memory_analysis().argument_size_in_bytes
    assert 0 < per_device * 3 < one.memory_analysis().argument_size_in_bytes


def test_measured_default_routes_auto_to_fused(monkeypatch, tmp_path):
    """The measured-defaults registry flips `auto` to the fused
    dispatcher on the recorded backend — and only there."""
    import json as _json

    reg = tmp_path / "measured_defaults.json"
    reg.write_text(_json.dumps(
        {"tpu": {"search": "fused", "evidence": {}}}))
    monkeypatch.setattr(core, "_MEASURED_DEFAULTS_PATH", str(reg))
    try:
        core.reload_measured_defaults()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert core._resolved_search_impl() == "fused"
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert core._resolved_search_impl() == "xla"
    finally:
        monkeypatch.undo()
        core.reload_measured_defaults()


def test_measured_default_resolves_spec_core(monkeypatch, tmp_path):
    import json as _json

    reg = tmp_path / "measured_defaults.json"
    reg.write_text(_json.dumps(
        {"tpu": {"spec_core": "on", "evidence": {}}}))
    monkeypatch.setattr(core, "_MEASURED_DEFAULTS_PATH", str(reg))
    monkeypatch.setattr(driver, "SPEC_CORE", "auto")
    try:
        core.reload_measured_defaults()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert driver._spec_core_enabled()
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert not driver._spec_core_enabled()
        # The env knob still overrides the registry in both directions.
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(driver, "SPEC_CORE", "0")
        assert not driver._spec_core_enabled()
    finally:
        monkeypatch.undo()
        core.reload_measured_defaults()


@pytest.mark.parametrize("backend,interpret", [("tpu", False),
                                               ("cpu", True)])
def test_pallas_interpret_only_on_cpu(monkeypatch, backend, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert core.pallas_interpret() is interpret


def test_pallas_refuses_other_backends(monkeypatch):
    from deppy_tpu.sat.errors import BackendCapabilityError

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(BackendCapabilityError, match="pallas"):
        core.pallas_interpret()


def test_clause_shard_compiles_on_four_chips(topo):
    """``solve_one_sharded``'s program for the 400-package catalog of
    ``chip_smoke.py --chips 4``: clause rows over a 4-device
    ``clause_mesh`` of the described chips, with the per-round OR of
    forced literals gathered across them."""
    from deppy_tpu.parallel import clause_shard

    problem = encode(operatorhub_catalog(n_packages=400,
                                         versions_per_package=5, seed=0))
    mesh = clause_shard.clause_mesh(topo.devices[:4])
    d = clause_shard._ShardDims([problem], 4)
    pts = driver.pad_problem(problem, d, pack=True)
    host_core = problem.n_cons > driver.HOST_CORE_NCONS
    with core.clause_axis(clause_shard.CLAUSE_AXIS):
        fn = clause_shard._sharded_fn(mesh, d.V, d.NCON, d.NV,
                                      with_core=not host_core)
        compiled = fn.lower(*_shapes((pts, np.int32(BUDGET)),
                                     None)).compile()
    assert "all-gather" in compiled.as_text()
