"""The entry points' persistent compilation cache: placed from outside by
``JAX_COMPILATION_CACHE_DIR``, otherwise at a fixed path in the checkout."""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax

from repro.launch import compile_cache

SRC = pathlib.Path(compile_cache.__file__).resolve().parents[2]

_PROGRAM = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import (cache_counts, compile_seconds,
                                            enable_compile_cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(enable_compile_cache())
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((32, 32))).block_until_ready()
    c = cache_counts()
    print(c["hits"], c["misses"], sum(compile_seconds().values()) > 0)
""")


def _run(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC),
               JAX_COMPILATION_CACHE_DIR=str(env_dir))
    out = subprocess.run([sys.executable, "-c", _PROGRAM], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split("\n")
    hits, misses, timed = out[1].split()
    return out[0], int(hits), int(misses), timed == "True"


def test_env_dir_wins_and_second_run_hits(tmp_path):
    """With the variable set, entries land there (and the in-repo default
    is left alone); a second process reads them back as hits."""
    default = compile_cache.CACHE_DIR
    before = sorted(default.iterdir()) if default.exists() else None
    path, hits, misses, timed = _run(tmp_path)
    assert path == str(tmp_path)
    assert hits == 0 and misses > 0 and timed
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())
    _, hits, _, _ = _run(tmp_path)
    assert hits > 0
    after = sorted(default.iterdir()) if default.exists() else None
    assert after == before


def test_default_dir_is_fixed_inside_checkout(monkeypatch):
    """Unset, the cache goes to <repo>/.jax_cache: no temp name, pid or
    time in the path, so the next run finds it."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(SRC.parent / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
