"""JAX configuration helpers.

The jaxbls kernels are large graphs (Miller loop + final exponentiation);
first-compile latency is minutes per padding bucket. A persistent
compilation cache turns that into a one-time cost per (shape, platform)
across processes — essential for the node's startup latency, for the test
suite, and for a chip tool that keeps nothing but one output directory.

Where the cache lives is decided OUTSIDE the program: JAX reads
`JAX_COMPILATION_CACHE_DIR` itself, and when that is set this module sets
no directory at all. Unset, the cache is one fixed directory inside the
checkout (`<repo>/.jax_cache`, gitignored) — the path is part of what a
caller must be able to predict, so it is never built from the home
directory, a pid, a time or a temporary name.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_REPO_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

_initialized = False


def cache_base_dir() -> str:
    """The persistent compilation cache directory in force. Sibling
    artifacts that share the cache's lifecycle (the autotune device
    profiles) live under this directory too."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _REPO_CACHE_DIR


def setup_compilation_cache() -> None:
    global _initialized
    if _initialized:
        return
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(_REPO_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _REPO_CACHE_DIR)
    # Cache everything, including small/fast compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _initialized = True
