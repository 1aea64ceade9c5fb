"""Process-environment helpers of the entry points: where JAX's persistent
compilation cache goes, and how a benchmark parent counts devices without
importing jax (so it never holds a chip its child needs)."""

from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import CACHE_ENV, DEFAULT_CACHE_DIR, enable_compile_cache
from repro.launch.devices import device_count_without_jax, emulated_devices_env


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_wins_and_sets_nothing(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # no path set in code


def test_compile_cache_default_is_fixed_checkout_path(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    monkeypatch.chdir(tmp_path)  # the working directory must not matter
    path = enable_compile_cache()
    assert path == str(DEFAULT_CACHE_DIR) == jax.config.jax_compilation_cache_dir
    checkout = Path(__file__).resolve().parents[1]
    assert Path(path) == checkout / ".jax_cache"


def test_device_count_without_jax_reads_forced_host_devices(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    assert device_count_without_jax() == 4
    monkeypatch.setenv("XLA_FLAGS", "")
    assert device_count_without_jax() == 1


def test_emulated_child_env_pins_the_cpu(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/dev/null")
    env = emulated_devices_env(8)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"].startswith("--xla_force_host_platform_device_count=8 ")
    assert "--xla_dump_to=/dev/null" in env["XLA_FLAGS"]
