"""Where the program keeps state between runs: JAX's compile cache and
the autotune winner table.

Both live under one fixed directory inside the checkout,
``<repo>/.cache`` (gitignored), so a later run in the same tree finds
what an earlier one stored.  Nothing here runs at import: entry points
(``repro.launch.serve``, ``benchmarks/run.py``, ``chip_smoke.py``) call
:func:`configure_compile_cache` once at start-up.
"""

from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".cache"
AUTOTUNE_FILE = CACHE_DIR / "repro" / "autotune.json"
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def configure_compile_cache() -> pathlib.Path:
    """Turn on JAX's persistent compilation cache and return its
    directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX
    reads it (no other directory is set); otherwise ``<repo>/.cache/jax``.
    Every compiled program is cached, however quick its compile."""
    import jax

    env = os.environ.get(COMPILE_CACHE_ENV)
    path = pathlib.Path(env) if env else CACHE_DIR / "jax"
    if not env:
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
