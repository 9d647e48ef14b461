"""Where the persistent compilation cache lives (repro.runtime.compile_cache).

Set from outside through ``JAX_COMPILATION_CACHE_DIR``, the cache is written
there and nowhere else; unset, it goes to one fixed, git-ignored directory
inside the checkout.
"""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import textwrap

import jax

from repro.runtime import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.runtime import compile_cache
    print(compile_cache.enable())
    jax.jit(lambda x: jnp.sort(x * 2.0) + 1.0)(jnp.arange(64.0)).block_until_ready()
""")


def test_env_dir_is_the_only_cache(tmp_path):
    where = tmp_path / "jax_cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(where),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(REPO, "src"))
    before = (set(os.listdir(compile_cache.DEFAULT_DIR))
              if compile_cache.DEFAULT_DIR.is_dir() else set())
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == str(where)
    assert any(where.iterdir()), "nothing was cached in the given directory"
    after = (set(os.listdir(compile_cache.DEFAULT_DIR))
             if compile_cache.DEFAULT_DIR.is_dir() else set())
    assert after == before, "the in-checkout cache was written as well"


def test_default_dir_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        where = compile_cache.enable()
        assert where == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == where
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert compile_cache.DEFAULT_DIR.parent == pathlib.Path(REPO)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
