"""Where JAX's persistent compilation cache lives — the one place in the
repository that sets `jax_compilation_cache_dir`.

`chip_smoke.py`, `benchmark/run.py`, `tools/diag_fused_mem.py` and
`tests/conftest.py` call `use_compile_cache()` before their first
compile. Runs that are meant to share compiled programs must agree on
the directory, so it is never a temporary one: `JAX_COMPILATION_CACHE_DIR`
when the machine sets it (JAX reads that itself — nothing is set here),
else `<checkout>/.jax_cache` (git-ignored, and kept out of the copy the
chip tool makes by `.chiprunignore`).
"""
from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Point the persistent compile cache at its directory (see module
    docstring) and return that directory. Touches no backend."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
