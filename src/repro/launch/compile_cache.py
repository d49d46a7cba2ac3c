"""JAX's persistent compilation cache for the command-line entry points.

Compiling the Pallas kernels and the serving cells is a large part of a
cold run, so the entry points (``chip_smoke.py``, ``serve_gen``,
``benchmarks/run.py``, ``examples/train_dcgan.py``) keep compiled
programs across processes.  Library code and tests never enable it.
"""

from __future__ import annotations

import os

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set here.  Otherwise the cache lives at the fixed
    path ``<checkout>/.jax_cache`` (listed in ``.gitignore``), so the
    next process finds what this one compiled; a per-run directory
    would never be read again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
