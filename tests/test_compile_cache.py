"""Where compiled programs and autotune winners persist between runs."""

import os
import pathlib
import subprocess
import sys

import jax

from repro import cache
from repro.runtime import autotune

REPO = pathlib.Path(__file__).resolve().parent.parent
_KEYS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")


def test_default_locations_are_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv(cache.COMPILE_CACHE_ENV, raising=False)
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    try:
        path = cache.configure_compile_cache()
        assert path == REPO / ".cache" / "jax"
        assert jax.config.jax_compilation_cache_dir == str(path)
        assert jax.config.jax_enable_compilation_cache
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
    assert autotune.cache_path() == REPO / ".cache" / "repro" / \
        "autotune.json"


def test_env_dir_holds_the_cache_and_a_second_serve_reads_it(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, a serve run writes its compiled
    programs there and only there; a second run in the same tree finds
    every program it needs there (it writes no entry, new or old)."""
    jax_dir = tmp_path / "jax"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(jax_dir),
               JAX_PLATFORMS="cpu", REPRO_AUTOTUNE_CACHE="0",
               PYTHONPATH=str(REPO / "src"))
    default_dir = REPO / ".cache" / "jax"
    before = set(default_dir.rglob("*")) if default_dir.exists() else set()
    cmd = [sys.executable, "-m", "repro.launch.serve", "--workload",
           "alexnet_imagenet", "--variant", "tiny", "--batch", "1",
           "--requests", "2"]

    def run():
        r = subprocess.run(cmd, env=env, cwd=tmp_path, capture_output=True,
                           text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        return {p.name: p.stat().st_mtime_ns
                for p in jax_dir.rglob("*") if p.is_file()}

    first = run()
    assert first, "no compiled program was cached"
    second = run()
    assert second == first
    after = set(default_dir.rglob("*")) if default_dir.exists() else set()
    assert after == before, "wrote to the default cache despite the env"
