"""The device plane's one process-wide seam (mqtt_tpu.ops.backend,
ISSUE 21): where the persistent compilation cache goes and how a result
names its device."""

import os
import subprocess
import sys

import pytest

from mqtt_tpu.ops import backend

jax = pytest.importorskip("jax")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    prior = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    yield
    jax.config.update("jax_compilation_cache_dir", prior[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prior[1])


class TestCompileCachePlacement:
    def test_env_set_means_the_program_sets_no_path(
        self, monkeypatch, restore_cache_config, tmp_path
    ):
        monkeypatch.setenv(backend.CACHE_ENV, str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "/set/by/outside")
        assert backend.ensure_compile_cache() == str(tmp_path)
        # untouched: JAX reads the variable itself
        assert jax.config.jax_compilation_cache_dir == "/set/by/outside"

    def test_env_unset_places_the_cache_in_the_checkout(
        self, monkeypatch, restore_cache_config
    ):
        monkeypatch.delenv(backend.CACHE_ENV, raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert backend.default_cache_dir() == want
        assert backend.ensure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        # the match kernels compile in under a second: they must be
        # cached too
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

    def test_default_path_is_fixed(self):
        """Never a path built from tempfile, a pid or the time: a cache
        that moves never hits."""
        code = (
            "from mqtt_tpu.ops import backend; "
            "print(backend.default_cache_dir())"
        )
        outs = {
            subprocess.run(
                [sys.executable, "-c", code], cwd=REPO, text=True,
                capture_output=True, timeout=60, check=True,
            ).stdout.strip()
            for _ in range(2)
        }
        assert outs == {os.path.join(REPO, ".jax_cache")}

    def test_every_jit_entry_reaches_the_seam(
        self, monkeypatch, restore_cache_config
    ):
        """_LazyJit places the cache at its first build, before anything
        compiles."""
        from mqtt_tpu.ops.flat import _LazyJit

        monkeypatch.delenv(backend.CACHE_ENV, raising=False)
        jax.config.update("jax_compilation_cache_dir", None)
        lazy = _LazyJit(lambda: (lambda x: x))
        assert jax.config.jax_compilation_cache_dir is None
        lazy(1)
        assert jax.config.jax_compilation_cache_dir == backend.default_cache_dir()


def test_device_summary_names_the_default_backend():
    d = backend.device_summary()
    assert d == {
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": len(jax.devices()),
    }
