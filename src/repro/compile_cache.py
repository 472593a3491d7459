"""JAX persistent compilation cache location for the repo's entry points.

A cold ViTDet-L grid takes minutes to compile, so ``chip_smoke.py`` and
the ``benchmarks/`` mains keep compiled executables on disk.  JAX keys a
cache entry on its directory too, so the directory must not move between
runs: it is either the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads
that variable itself, so nothing is set) or the fixed, gitignored
``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
