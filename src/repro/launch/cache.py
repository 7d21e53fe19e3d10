"""Where compiled executables persist between runs.

Every cold process on a chip recompiles each executable it needs (a
VGG-16 serving bucket or train step takes tens of seconds).  JAX's
persistent compilation cache keeps them on disk, keyed in part by the
cache path, so the path must not move between runs: a temporary
directory, a pid or a timestamp in it would never hit.

``enable_compile_cache`` is called first thing by the launchers' ``main``
(``train``, ``serve_cnn``) and by ``chip_smoke.py``.
"""

from __future__ import annotations

import os

import jax

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))

#: The fixed default: ``<repo>/.jax_cache`` (listed in ``.gitignore``).
DEFAULT_CACHE_DIR = os.path.join(_REPO, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and no
    other is set; otherwise the cache lives in ``DEFAULT_CACHE_DIR``.
    Every executable is cached, however fast it compiled.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
