"""chip_smoke.py's phases at tiny sizes on the CPU backend.

The script drives the main path on the chip (``python chip_smoke.py``,
``--chips 4``); here the same phase functions run on XLA:CPU, with the
Pallas kernels interpreted and the 4-chip phases on virtual devices, so
a wrong path, argument or comparison fails before it costs chip time.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

pytest.importorskip("jax")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from deppy_tpu import faults, hostpool, telemetry  # noqa: E402

TINY = {"operatorhub": 6, "chains": 3, "gvk": 3, "tenants": 8}


@pytest.fixture(scope="module")
def batch():
    return chip_smoke.fleet_batch(TINY)


@pytest.fixture(scope="module")
def library(batch):
    """Phase 2 in one process: no host-pool workers, and no child
    process started anywhere on the resolution path."""
    hostpool.configure_pool(0)
    real = subprocess.Popen

    def refuse(*a, **k):
        raise AssertionError("the resolution path started a process")

    subprocess.Popen = refuse
    try:
        return chip_smoke.phase_library(batch)
    finally:
        subprocess.Popen = real
        hostpool.configure_pool(None)


def test_fleet_batch_has_every_family(batch):
    fams = [fam for fam, _ in batch]
    assert {f: fams.count(f) for f in TINY} == TINY
    assert sum(chip_smoke.FLEET.values()) == 6656


def test_library_phase_matches_host(batch, library):
    from deppy_tpu.sat.errors import NotSatisfiable

    assert len(library) == len(batch)
    assert any(isinstance(r, NotSatisfiable) for r in library)
    assert any(isinstance(r, dict) for r in library)


def test_host_reference_samples_past_its_budget(batch, library):
    """Past the time budget the reference keeps every device-UNSAT lane
    and the seeded sample, and nothing else."""
    from deppy_tpu.sat.errors import NotSatisfiable

    variables = [vs for _, vs in batch]
    ref, _ = chip_smoke.host_reference(variables, library, budget_s=0.0,
                                       sample=2)
    unsat = {i for i, r in enumerate(library)
             if isinstance(r, NotSatisfiable)}
    assert unsat <= set(ref) and len(ref) <= len(unsat) + 2
    chip_smoke.compare("sample", library, ref, sorted(ref))


def test_served_phase_matches_library(batch, library):
    reqs = chip_smoke.served_requests(batch, library, singles=4,
                                      batch_size=3)
    assert len(reqs) == 6
    assert "problems" in reqs[-2][0]
    chip_smoke.phase_served(batch, library, singles=4, batch_size=3)


@pytest.mark.parametrize("switch", chip_smoke.KERNEL_SWITCHES,
                         ids=lambda s: "=".join(s))
def test_kernel_phase_matches_library(batch, library, switch):
    lanes = chip_smoke.kernel_lanes(batch, 6)
    chip_smoke.phase_kernels(batch, library, lanes, switches=(switch,))


def test_four_chip_phases_on_virtual_devices(batch):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual CPU devices (tests/conftest.py)")
    chip_smoke.run_four_chips(batch, n_devices=4, n_packages=12)


def test_counter_line_fails_on_host_routing():
    prev = telemetry.set_default_registry(telemetry.Registry())
    try:
        faults.fault_counter("deppy_fault_host_routed_total").inc(1)
        with pytest.raises(RuntimeError, match="host_routed_total=1"):
            chip_smoke.check_counters("routed")
    finally:
        telemetry.set_default_registry(prev)


def test_main_fails_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "platform 'cpu'" in err
    assert '"ok"' not in out


def test_script_alone_fails(tmp_path):
    """Run from a directory holding chip_smoke.py and nothing else of the
    repo: non-zero, and no result line."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    lines = out.stdout.strip().splitlines()
    if lines:
        with pytest.raises(ValueError):
            json.loads(lines[-1])
