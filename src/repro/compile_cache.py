"""JAX's persistent compilation cache for the programs that serve.

``enable_compile_cache()`` is called by the entry points that run on a
chip — ``chip_smoke.py``, ``benchmarks/run.py`` and the serving examples —
and never at ``import repro``: the test suite cross-compiles kernels for
a described TPU, and such executables must not land in a cache that
cannot read them back without the chip.
"""

from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX reads it and
    nothing is set here; otherwise the cache lives in ``.jax_cache`` at
    the checkout's root (git-ignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
