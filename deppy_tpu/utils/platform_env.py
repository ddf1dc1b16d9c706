"""Platform and compile-cache provisioning for process entry points.

:func:`apply_platform_env` is what every entry point (CLI, service,
benchmarks, ``chip_smoke.py``) calls before its first compile:
``JAX_PLATFORMS`` from the environment, then the persistent compilation
cache.  The rest serves ``bench.py`` and the scripts, which run
benchmark stages in child processes that never share the parent's chip.
"""

from __future__ import annotations

import os
from typing import Mapping


def assert_env_platform() -> None:
    """Make ``JAX_PLATFORMS`` from the environment stick.

    JAX reads the variable once, when it is imported; a process that
    sets it later (a host-pool worker, a test) would otherwise still
    initialize every backend on the first query.  Setting ``jax.config``
    limits discovery itself to the named platforms, so a forced-CPU
    process never opens the chip.  Must run before the first backend
    query; harmlessly idempotent with tests/conftest.py's identical
    update."""
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms:
        import jax

        jax.config.update("jax_platforms", platforms)


def apply_platform_env() -> None:
    """Process-entry-point provisioning: :func:`assert_env_platform` plus
    the persistent compilation cache (see :func:`enable_compile_cache`).
    Called by every process entry point (CLI, service, benchmarks) so
    ``JAX_PLATFORMS=cpu python -m deppy_tpu ...`` behaves as
    documented."""
    assert_env_platform()
    enable_compile_cache()


def run_captured(cmd, timeout_s, env=None, cwd=None):
    """``subprocess.run(capture_output=True, timeout=...)`` that cannot
    re-hang after the timeout.

    Plain ``subprocess.run`` with captured pipes handles TimeoutExpired by
    killing only the direct child and then blocking until pipe EOF — a
    wedged runtime helper process that inherited the pipes keeps them
    open and re-hangs the parent indefinitely.  This variant starts the child in its own
    session and kills the whole process group on timeout, so EOF is
    guaranteed.  Returns ``(returncode, stdout, stderr)`` or raises
    ``subprocess.TimeoutExpired``."""
    import signal
    import subprocess

    proc = subprocess.Popen(
        cmd,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        env=env,
        cwd=cwd,
        start_new_session=True,
    )
    try:
        out, err = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()  # at least the direct child dies
        try:
            # Group normally dead -> EOF immediate; the bound covers an
            # unsignalable group member still holding the pipes.
            out, err = proc.communicate(timeout=5)
        except subprocess.TimeoutExpired:
            out, err = "", ""
        # Mirror subprocess.run: the partial output rides the exception
        # so callers can log what the child was doing when it hung.
        raise subprocess.TimeoutExpired(
            cmd, timeout_s, output=out, stderr=err
        ) from None
    return proc.returncode, out, err


# One probe source for the backend-health checks of bench.py and
# tpu_doctor: PJRT init and a tiny compile+execute+readback, each stage
# marked on stdout.
_PROBE_SRC_TEMPLATE = (
    "import signal; signal.alarm({alarm}); "
    "import os, time, jax; "
    "p = os.environ.get('JAX_PLATFORMS'); "
    "p and jax.config.update('jax_platforms', p); "
    "t0 = time.time(); d = jax.devices(); "
    "print('INIT', jax.default_backend(), len(d), round(time.time()-t0, 1),"
    " flush=True); "
    "import jax.numpy as jnp; "
    "t1 = time.time(); x = jnp.ones((8, 8), jnp.float32); "
    "v = float((x @ x).sum()); "
    "print('COMPUTE', v, round(time.time()-t1, 1), flush=True); "
    "os._exit(0)"
)


def probe_src(alarm_s: int) -> str:
    """Source for a disposable backend-health probe subprocess.

    ``alarm_s`` arms a SIGALRM self-destruct (default disposition kills
    the process even while blocked inside PJRT C code) so an ORPHANED
    probe — its caller killed mid-probe; probes run in their own session
    — cannot hang in init holding the chip.  The probe always
    ``os._exit(0)``s after the COMPUTE stage, so PJRT teardown never runs
    inside the caller's timed window.

    Stdout carries one line per completed stage (``INIT <backend>
    <n_devices> <s>``, then ``COMPUTE <checksum> <s>``), so a caller
    catching a timeout can tell which stage hung from the partial output
    that rides :func:`run_captured`'s ``TimeoutExpired``.  Parse with
    :func:`parse_probe_stages`."""
    return _PROBE_SRC_TEMPLATE.format(alarm=alarm_s)


def parse_probe_stages(stdout: str) -> dict:
    """Parse :func:`probe_src` stage lines (full or partial output).

    Returns a dict with any of ``backend``/``n_devices``/``init_s``
    (from the INIT line) and ``compute_s`` (from the COMPUTE line) that
    were present — the single parser for the single format, shared by
    tpu_doctor and bench.py so the two cannot drift."""
    out: dict = {}
    for line in (stdout or "").splitlines():
        parts = line.split()
        if parts[:1] == ["INIT"] and len(parts) >= 4:
            out["backend"] = parts[1]
            try:
                out["n_devices"] = int(parts[2])
                out["init_s"] = float(parts[3])
            except ValueError:
                pass
        elif parts[:1] == ["COMPUTE"] and len(parts) >= 3:
            try:
                out["compute_s"] = float(parts[2])
            except ValueError:
                pass
    return out


def checkout_cache_dir() -> str:
    """``<checkout>/.jax_cache``: the compile cache's directory when
    ``JAX_COMPILATION_CACHE_DIR`` is unset — a fixed path (the path is
    part of the cache key, so a moving directory never hits), and the
    one ``tests/conftest.py`` uses."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> None:
    """Turn on XLA's persistent compilation cache for this process.

    The engine compiles one executable per padded shape bucket; with the
    cache a later process loads every shape already compiled from disk.

    * ``DEPPY_TPU_COMPILE_CACHE=off`` turns the cache off.
    * ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory, on
      every backend, and this sets no other.
    * Otherwise the directory is :func:`checkout_cache_dir`, unless
      ``JAX_PLATFORMS`` names platforms and ``tpu`` is not among them:
      a forced-CPU process leaves it off (XLA:CPU's cache loader warns
      about compile-vs-host machine-feature mismatches, "could lead to
      SIGILL").

    Decided from the environment alone: it never initializes a backend,
    so host-only commands (``deppy top``, ``doctor``, ``lint``) never
    open the chip another process may need.  Must run before the first
    compile.  The thresholds are lowered so the engine's many small
    per-shape executables are cached too."""
    import jax

    from .. import config

    if (config.env_raw("DEPPY_TPU_COMPILE_CACHE") or "").strip().lower() \
            == "off":
        jax.config.update("jax_enable_compilation_cache", False)
        return
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        platforms = os.environ.get("JAX_PLATFORMS", "").strip()
        if platforms and "tpu" not in platforms.split(","):
            return
        path = checkout_cache_dir()
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def force_cpu_env(environ: Mapping[str, str], n_devices: int = 1) -> dict:
    """Copy ``environ`` with the virtual-CPU platform forced: sets
    ``JAX_PLATFORMS=cpu`` and replaces (never merely keeps) any existing
    ``--xla_force_host_platform_device_count`` flag with ``n_devices``."""
    if n_devices <= 0:
        raise ValueError("n_devices must be positive")
    env = dict(environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env
