"""Build the package's small C++ libraries from their sources, on first use.

The binaries are build outputs, not sources: they are never tracked and
never written into the package directory. Each is compiled into
`<repo>/.native_cache/` (gitignored) when absent or older than its `.cc`,
and loaded from there. A caller that cannot get its library logs why and
serves from its pure-Python engine.
"""

from __future__ import annotations

import os
import subprocess
import threading
from pathlib import Path

_CACHE_DIR = Path(__file__).resolve().parents[2] / ".native_cache"
_lock = threading.Lock()


def build_native(src: Path, lib_name: str) -> Path:
    """Path of the shared library built from `src` (compiling it first
    when the cached build is absent or stale). Raises what g++ raises."""
    lib = _CACHE_DIR / lib_name
    with _lock:
        if lib.exists() and lib.stat().st_mtime >= src.stat().st_mtime:
            return lib
        _CACHE_DIR.mkdir(parents=True, exist_ok=True)
        # per-pid temp path + atomic rename: a concurrent process must
        # never CDLL a half-written library
        tmp = lib.with_suffix(f".tmp.{os.getpid()}")
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
             str(src), "-o", str(tmp)],
            check=True, capture_output=True,
        )
        os.replace(tmp, lib)
    return lib
