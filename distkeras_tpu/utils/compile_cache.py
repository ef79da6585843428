"""JAX's persistent compilation cache, at one fixed place.

Every entry point that compiles for the chip (``chip_smoke.py``,
``bench.py``, ``benchmarks/*.py``, ``examples/lm_training.py``,
``examples/lm_serving.py``) calls :func:`enable` first. The flagship
train step alone is half a minute of compilation, and a machine
borrowed for one run keeps nothing else from the previous one.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable() -> str:
    """Turn the persistent cache on and return the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is touched here. Otherwise the cache lives at
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part
    of what a later process must find again; never a temp name, a pid
    or a timestamp."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
