"""Process-level helpers of ``repro.runtime``: compile cache and CPU devices.

``jax.config.update`` is replaced by a recorder in every test, so nothing
here turns on a persistent cache for the rest of the session.
"""

import os

import jax
import pytest

from repro import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_env_dir_is_used_and_nothing_is_set(
        monkeypatch, tmp_path, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.compile_cache_dir() == str(tmp_path)
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert config_updates == []


def test_compile_cache_default_is_fixed_path_in_checkout(
        monkeypatch, tmp_path, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = runtime.enable_compile_cache()
    # a different temp dir and a later call change nothing: no pid, time
    # or tmp component in the path
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    second = runtime.enable_compile_cache()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert os.path.relpath(first, REPO) == ".jax_cache"
    assert config_updates == [("jax_compilation_cache_dir", first)] * 2


@pytest.mark.parametrize("platforms,flags,expect", [
    ("cpu", "", "--xla_force_host_platform_device_count=4"),
    ("cpu", "--xla_dump_to=x",
     "--xla_dump_to=x --xla_force_host_platform_device_count=4"),
    ("cpu", "--xla_force_host_platform_device_count=2",
     "--xla_force_host_platform_device_count=2"),
    ("tpu", "", ""),
])
def test_simulate_cpu_devices_only_on_cpu(monkeypatch, platforms, flags,
                                          expect):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setenv("XLA_FLAGS", flags)
    runtime.simulate_cpu_devices(4)
    assert os.environ["XLA_FLAGS"] == expect
