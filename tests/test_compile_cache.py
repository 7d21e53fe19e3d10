"""The launchers' persistent compilation cache (``launch/cache.py``):
placed by ``JAX_COMPILATION_CACHE_DIR`` when set, else at the fixed
``<repo>/.jax_cache``; a second process finds what the first wrote."""
import os
import subprocess
import sys
import textwrap

import jax

from repro.launch import cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _restore(prev):
    for k, v in prev.items():
        jax.config.update(k, v)


def _snapshot():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    return {k: getattr(jax.config, k) for k in keys}


def test_cache_dir_from_env(monkeypatch, tmp_path):
    prev = _snapshot()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        _restore(prev)


def test_cache_default_dir_is_fixed_in_repo(monkeypatch):
    prev = _snapshot()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        _restore(prev)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_second_process_hits_cache(tmp_path):
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.cache import enable_compile_cache
        hits = []
        jax.monitoring.register_event_listener(
            lambda name, **kw: hits.append(name)
            if name == "/jax/compilation_cache/cache_hits" else None)
        enable_compile_cache()
        jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(8.0)).block_until_ready()
        print("hits", len(hits))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.path.join(REPO, "src"))
    runs = [subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=env, timeout=300)
            for _ in range(2)]
    for r in runs:
        assert r.returncode == 0, r.stderr[-4000:]
    assert "hits 0" in runs[0].stdout
    assert os.listdir(tmp_path)
    assert "hits 0" not in runs[1].stdout, runs[1].stdout
