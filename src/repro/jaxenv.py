"""Process-wide JAX settings shared by every entry point.

``search_numerics`` is the numeric setting every search program runs
under: 64-bit types (the programs are float64/int64 end to end) and the
non-partitionable threefry key stream, which the recorded goldens and
every saved search key were drawn from (newer jax defaults to the
partitionable stream, which draws different bits from the same key).
``use_compile_cache`` turns on JAX's
persistent compilation cache at a path that does not move between runs:
``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself, so no
path is set here), otherwise ``.jax_cache/`` at the checkout root. The
path is part of the cache key, so a per-run temporary directory would
never hit.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


@contextlib.contextmanager
def search_numerics():
    """Context manager: 64-bit types and the pinned threefry stream."""
    import jax

    with jax.enable_x64(True), jax.threefry_partitionable(False):
        yield


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory.

    Every compiled program is cached (no minimum compile time), so a
    child process or a rerun finds the programs its predecessor built."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
