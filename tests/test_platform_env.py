"""Platform/env provisioning: forced-platform recipe + compile cache."""

import jax
import pytest

from deppy_tpu.utils import platform_env


_CACHE_KEYS = (
    "jax_enable_compilation_cache",
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


@pytest.fixture
def reset_cache_config():
    prev = {k: getattr(jax.config, k) for k in _CACHE_KEYS}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


CHECKOUT = platform_env.checkout_cache_dir()


def _no_backend(*a, **k):
    raise AssertionError("the cache rule must not initialize a backend")


@pytest.mark.parametrize("env,want", [
    # JAX_COMPILATION_CACHE_DIR wins on every platform.
    ({"JAX_COMPILATION_CACHE_DIR": "{tmp}/outside",
      "JAX_PLATFORMS": "tpu"}, "{tmp}/outside"),
    ({"JAX_COMPILATION_CACHE_DIR": "{tmp}/outside",
      "JAX_PLATFORMS": "cpu"}, "{tmp}/outside"),
    # Unset: the checkout's .jax_cache whenever the TPU may be used —
    # named, among others, or JAX_PLATFORMS unset (the chip machine)...
    ({"JAX_PLATFORMS": "tpu"}, CHECKOUT),
    ({"JAX_PLATFORMS": "cpu,tpu"}, CHECKOUT),
    ({}, CHECKOUT),
    # ...and off when the process is forced to CPU (XLA:CPU's loader
    # warns of machine-feature mismatches).
    ({"JAX_PLATFORMS": "cpu"}, None),
    # "on" is no longer a value: it changes nothing.
    ({"DEPPY_TPU_COMPILE_CACHE": "on", "JAX_PLATFORMS": "cpu"}, None),
])
def test_cache_dir_rule(monkeypatch, tmp_path, reset_cache_config, env,
                        want):
    for k in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
              "DEPPY_TPU_COMPILE_CACHE"):
        monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v.format(tmp=tmp_path))
    monkeypatch.setattr(jax, "default_backend", _no_backend)
    monkeypatch.setattr(jax, "devices", _no_backend)
    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_enable_compilation_cache", False)
    platform_env.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == (
        want and want.format(tmp=tmp_path))
    # An enabling path turns the cache back on: "off" is not sticky.
    assert jax.config.jax_enable_compilation_cache == (want is not None)


@pytest.mark.parametrize("value", ["off", "OFF", " off "])
def test_off_disables(monkeypatch, tmp_path, reset_cache_config, value):
    monkeypatch.setenv("DEPPY_TPU_COMPILE_CACHE", value)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    platform_env.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir is None
    assert not jax.config.jax_enable_compilation_cache


def test_outside_cache_dir_receives_the_cache(tmp_path):
    """A process whose environment names JAX_COMPILATION_CACHE_DIR
    writes its compiled programs there."""
    import os
    import subprocess
    import sys

    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    src = ("from deppy_tpu.utils.platform_env import apply_platform_env; "
           "apply_platform_env(); import jax, jax.numpy as jnp; "
           "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
           " 0); "
           "print(jax.config.jax_compilation_cache_dir); "
           "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()")
    rc, out, err = platform_env.run_captured(
        [sys.executable, "-c", src], timeout_s=120, env=env)
    assert rc == 0, err[-800:]
    assert out.strip().splitlines()[-1] == str(cache)
    assert any(cache.iterdir())


@pytest.mark.parametrize("argv,platforms", [
    (["top", "--router", "127.0.0.1:9", "--once"], None),
    (["top", "--router", "127.0.0.1:9", "--once"], "cpu"),
    (["doctor", "--probe-timeout", "60", "--retries", "1"], "cpu"),
])
def test_host_only_commands_never_initialize_a_backend(argv, platforms):
    """``deppy top`` and ``doctor`` run while another process may own the
    chip (doctor probes it from a child), so the CLI's entry-point
    provisioning must not open a backend in the parent — also with
    JAX_PLATFORMS unset, as on the chip machine.  The child makes any
    backend initialization fatal, so no platform is ever opened here."""
    import os
    import sys

    src = (
        "from jax._src import xla_bridge\n"
        "def _opened(*a, **k):\n"
        "    raise SystemExit('a backend was initialized')\n"
        "xla_bridge.backends = _opened\n"
        "from deppy_tpu import cli\n"
        f"rc = cli.main({argv!r})\n"
        "print('BACKENDS', xla_bridge.backends_are_initialized(), rc)\n")
    env = dict(os.environ)
    for k in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
              "DEPPY_TPU_COMPILE_CACHE"):
        env.pop(k, None)
    if platforms:
        env["JAX_PLATFORMS"] = platforms
    rc, out, err = platform_env.run_captured(
        [sys.executable, "-c", src], timeout_s=120, env=env)
    assert rc == 0, err[-800:]
    assert out.strip().splitlines()[-1].startswith("BACKENDS False"), out


def test_force_cpu_env_replaces_device_count(monkeypatch):
    env = platform_env.force_cpu_env(
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=8 --foo"},
        n_devices=2,
    )
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=2" in env["XLA_FLAGS"]
    assert "--foo" in env["XLA_FLAGS"]
    assert "=8" not in env["XLA_FLAGS"]


def test_engine_import_asserts_env_platform():
    """Importing the tensor engine in a ``JAX_PLATFORMS=cpu`` process must
    limit backend DISCOVERY to cpu via jax.config, not just selection —
    otherwise the process would open a chip that another process holds.
    Runs in a subprocess so this process's conftest config cannot mask a
    regression."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    src = (
        "import deppy_tpu.engine.driver, jax; "
        "assert jax.config.jax_platforms == 'cpu', jax.config.jax_platforms; "
        "print(jax.default_backend())"
    )
    rc, out, err = platform_env.run_captured(
        [sys.executable, "-c", src], timeout_s=120, env=env,
    )
    assert rc == 0, err[-800:]
    assert out.strip().splitlines()[-1] == "cpu"
