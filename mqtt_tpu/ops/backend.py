"""Where the package meets the JAX runtime's process-wide state.

Nothing under ``mqtt_tpu/`` chooses a device: arrays go to JAX's default
through ``jnp.asarray``, so the default backend IS the device plane. Two
things about that backend are process-wide and therefore live in exactly
one place:

- :func:`ensure_compile_cache` places JAX's persistent compilation cache.
  Every jit entry point reaches it (``ops/flat._LazyJit`` and the two
  ``jax.jit`` sites in ``parallel/sharded.py``), so ``benchmark/run.py``,
  chip_smoke.py and the ``exp/`` gates get the cache through the package
  and set none of their own.
- :func:`device_summary` names the device results were produced on; every
  benchmark result object carries it, so a CPU-jax number can never be
  read as a chip number.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_MIN_COMPILE_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` (gitignored). A FIXED path on purpose —
    never one built from ``tempfile``, a pid or the time: a cache that
    moves between runs never hits."""
    return os.path.join(_CHECKOUT, ".jax_cache")


def ensure_compile_cache() -> str:
    """Place the persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set the program sets no path — JAX
    reads the variable itself and whoever launched the process owns the
    location. Otherwise the cache is :func:`default_cache_dir`.

    The match kernels compile in well under JAX's default one-second
    caching threshold on a warm host, and a broker restart must not
    recompile the whole staging ladder, so the minimum-compile-time
    threshold drops to zero unless its own variable says otherwise.
    Idempotent; JAX binds the cache lazily at the first compile, so
    calling this at the first jit build is early enough."""
    import jax

    if _MIN_COMPILE_ENV not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    path = default_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_summary() -> dict:
    """The default backend as JAX reports it: ``platform``,
    ``device_kind`` and ``n_devices``. Initializes the backend, and
    raises if it cannot — a device result must name its device."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
    }
