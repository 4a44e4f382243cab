"""Where JAX's persistent compilation cache lives.

The one place in the tree that sets ``jax_compilation_cache_dir``.
Process entry points (``python -m tidb_tpu serve``, ``chip_smoke.py``,
``bench.py``'s children) call ``place_jax_compile_cache`` before their
first program; library code and tests never do, so importing tidb_tpu
leaves JAX's cache configuration alone.

The directory is chosen from outside: ``JAX_COMPILATION_CACHE_DIR`` when
the environment sets it (JAX reads that variable itself, nothing is
touched), else ``<checkout>/.jax_cache``: a fixed path, because a
directory that moves from run to run never hits.

copforge's own executable store (``tidb_tpu_compile_cache_dir``) is a
separate, opt-in layer on top; it stays off unless that sysvar is set.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_JAX_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def place_jax_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory and keep
    every program in it: cop programs compile in well under the 1.0 s
    default threshold, and a cold statement is made of several of them.
    Returns the directory in effect."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_JAX_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


__all__ = ["place_jax_compile_cache", "DEFAULT_JAX_CACHE_DIR"]
