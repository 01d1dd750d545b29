"""Placement of JAX's persistent compilation cache for the repo's scripts.

Compiling is most of a cold run of the tiled pipeline (the executor unrolls
every wave into the program), so the scripts that start the pipeline —
``chip_smoke.py``, ``examples/*.py`` and ``benchmarks/run.py`` — call
:func:`use_persistent_cache` first.  Importing this module sets nothing.
"""

from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache: src/repro/compile_cache.py -> parents[2] is the
# checkout.  A fixed path, never derived from a temp name, pid or time, so
# a later run in the same checkout finds what an earlier one compiled.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing else is set.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
