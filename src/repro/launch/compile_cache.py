"""JAX's persistent compilation cache for the program's entry points.

Set by `repro.launch.serve`, `chip_smoke.py` and the benchmark scripts
before they compile anything, so a second run of the same checkout
loads its executables instead of compiling them again.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: fixed in-checkout cache directory (git-ignored). A path built from a
#: temp dir, pid or time would never be found again: the directory is
#: where later runs look.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Where
    JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and no other
    path is set here; otherwise the cache is DEFAULT_DIR."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
