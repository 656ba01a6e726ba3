"""Where JAX's persistent compilation cache lives.

The cache directory is part of every entry's key, so a directory that
moves never hits.  The rule, in one place: `JAX_COMPILATION_CACHE_DIR`
places the cache from outside (JAX reads the variable itself — nothing is
set in code); otherwise it is ONE fixed, git-ignored directory inside the
checkout.  Called first thing by `chip_smoke.py` and `benchmarks/run.py`; the test
suite leaves it off (tests/conftest.py).
"""
from __future__ import annotations

import os

__all__ = ["CACHE_DIRNAME", "enable_compile_cache"]

CACHE_DIRNAME = ".jax_compile_cache"
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_ROOT, CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
