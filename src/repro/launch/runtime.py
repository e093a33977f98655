"""Process set-up shared by the launchers and ``chip_smoke.py``.

Nothing here runs at import: a launcher calls :func:`setup_compile_cache`
from its ``main`` before its first compilation.
"""
from __future__ import annotations

import os

import jax

#: Root of the checkout (``src/repro/launch/`` is three levels below it).
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
#: Everything a run writes lives under here; ``.gitignore`` lists it.
RUNS_DIR = os.path.join(REPO_ROOT, "runs")
COMPILE_CACHE_DIR = os.path.join(RUNS_DIR, "jax_cache")


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here. Otherwise the cache is a fixed directory
    inside the checkout, so that every run from this checkout finds the
    programs the runs before it compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
