"""Persistent compilation cache for the entry points.

A cold process spends most of its start-up compiling: AlexNet's Pallas
convs, a 32-layer LM's step programs.  JAX's persistent cache keeps the
compiled programs on disk.  Its directory is part of each entry's key, so
it must not move between runs: it is ``JAX_COMPILATION_CACHE_DIR`` when
that is set, else one fixed directory inside the checkout,
``<checkout>/.jax_cache`` (listed in ``.gitignore``).

Entry points (``chip_smoke.py``, ``launch/serve.py``,
``launch/plan_smoke.py``, the ``benchmarks/*.py`` mains) call
:func:`enable_compile_cache` first thing in ``main``; importing the
library never touches the cache.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and
    nothing is changed."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
