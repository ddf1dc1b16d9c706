"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform so mesh/sharding code paths
are exercised without TPU hardware, per the multi-chip test strategy
(SURVEY.md §7.3 item 6).  Must run before the first ``import jax``.
"""

import os

# Force the 8-device CPU platform: XLA_FLAGS for the lazily created CPU
# client, then the platform through jax.config as well (the env var alone
# is read only when jax is imported).  Set DEPPY_TEST_PLATFORM to run the
# suite on real hardware instead.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Pin the env var as well as jax.config below: process entry points (the
# CLI, the service) call apply_platform_env(), which re-asserts
# JAX_PLATFORMS from the environment — an inherited TPU value would flip a
# test driving cli.main() with the tensor backend onto the chip.
os.environ["JAX_PLATFORMS"] = os.environ.get("DEPPY_TEST_PLATFORM", "cpu")

# Persistent XLA compile cache: the suite's wall is DOMINATED by per-test
# compilation (pytest --durations: 9-50s per slow test, ~750s of an
# ~1100s quick-depth run), and a warm cache halves the slow tests
# (measured: 30.4s -> 14.5s).  Env vars rather than jax.config so the
# subprocess-spawning tests (distributed fleet, graft entry, bench
# contract) inherit the same cache.  First run populates ~.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                 ".jax_cache")),
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")

# The multichip dry run's sharded-scheduler throughput row compiles one
# executable per mesh device inside its subprocess (~a minute of wall on
# 2-core CI); the tests that ride the dry run (test_parallel,
# test_driver_artifacts) pin wiring, not throughput, and the serving
# path's own pins live in tests/test_shard.py + scripts/shard_smoke.py.
# The real MULTICHIP round invokes the graft entry outside pytest and
# keeps the row (__graft_entry__._dryrun_impl).
os.environ.setdefault("DEPPY_DRYRUN_SCHED_ROW", "0")

try:
    import jax  # noqa: E402
except ImportError:  # jax-less install: importorskip guards handle the rest
    jax = None

if jax is not None:
    jax.config.update(
        "jax_platforms", os.environ.get("DEPPY_TEST_PLATFORM", "cpu")
    )
    # The env vars above are inherited by subprocess tests, but THIS
    # process may be too late for them: the cache config reads its env
    # defaults when jax is imported.  Set it through jax.config as well.
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]),
    )
