"""Process-level JAX set-up shared by the entry points and the tests.

* :func:`enable_compile_cache` places JAX's persistent compilation cache.
  Every entry point (``main.main``, ``bench.py``, ``chip_smoke.py``) calls
  it before its first compile, so a fresh chip machine pays each compile
  once per cache directory instead of once per process.
* :func:`force_host_cpu_devices` gives the virtual multi-device CPU
  platform for distributed tests and dry runs.  The reference exercises
  multi-node behaviour with plain oversubscribed ``mpirun`` (SURVEY.md §4
  item 5); the JAX analog is N virtual CPU devices via
  ``--xla_force_host_platform_device_count``, a flag XLA reads only at
  first backend use.
"""

from __future__ import annotations

import os
import re

_FLAG = "--xla_force_host_platform_device_count"

#: the fixed in-checkout cache path used when ``JAX_COMPILATION_CACHE_DIR``
#: is unset; the path is part of the cache key, so it must not move
#: between runs (listed in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory and no other
    is set; otherwise the cache lives at :data:`DEFAULT_CACHE_DIR`.
    The minimum compile time and entry size drop to 0 so that every
    program of the join is cached, not only those over JAX's 1 s default.

    On the CPU backend it does nothing and returns None: XLA:CPU entries
    are tied to the host's CPU features, and a CPU compile is cheap.  It
    asks JAX for the backend, so call it after ``jax.distributed``
    initialization.
    """
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def force_host_cpu_devices(n: int, respect_existing: bool = False,
                           defer_check: bool = False) -> None:
    """Make ``jax.devices()`` return at least ``n`` virtual CPU devices.

    Must run before any JAX backend use in this process; raises RuntimeError
    with a clear message if a backend already exists and cannot satisfy ``n``.
    Replaces an existing device-count flag so the caller's ``n`` wins, unless
    ``respect_existing`` and the env already requests ``>= n`` devices (so
    e.g. ``XLA_FLAGS=...device_count=16 pytest`` still gets its 16).

    ``defer_check=True`` skips the ``jax.devices()`` validation, which itself
    initializes the backend — required when ``jax.distributed.initialize``
    must still run after this call (multi-process workers), since it refuses
    to run once any backend exists.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    existing = re.search(rf"{_FLAG}=(\d+)", flags)
    if existing and respect_existing and int(existing.group(1)) >= n:
        n = int(existing.group(1))
    if existing:
        flags = re.sub(rf"{_FLAG}=\d+", f"{_FLAG}={n}", flags)
    else:
        flags = f"{flags} {_FLAG}={n}".strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    try:
        from jax._src import xla_bridge

        already_initialized = bool(xla_bridge._backends)
    except (ImportError, AttributeError):
        already_initialized = False
    jax.config.update("jax_platforms", "cpu")
    if defer_check:
        return
    if len(jax.devices()) < n:
        hint = (
            "a JAX backend was already initialized in this process, so the "
            "platform/device-count override could not take effect; call "
            f"force_host_cpu_devices({n}) before any JAX computation"
            if already_initialized
            else "XLA did not honor the device-count flag"
        )
        raise RuntimeError(
            f"needed {n} virtual CPU devices, got {len(jax.devices())}: {hint}"
        )
