"""Where JAX keeps its persistent compilation cache.

Every entry point (``repro.serve.cli``, ``repro.launch.run_mcmc``,
``benchmarks/bench_serve.py``, ``chip_smoke.py``) calls
:func:`enable_compile_cache` before its first compile, so processes of
one checkout share compiled programs.  ``JAX_COMPILATION_CACHE_DIR``,
when set, wins: JAX reads it itself and this module sets nothing.
Otherwise the cache lives at ``.jax_cache/`` in the root of the
checkout (gitignored).  The path is fixed, never built from a temp
name, a pid or the time, so a later process of the same checkout finds
what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str | None:
    """Turn on the persistent compilation cache; returns its directory.

    Returns None (and leaves the cache off) when the package does not
    run from a checkout and the variable is unset.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    if not (_CHECKOUT / "pyproject.toml").is_file():
        return None
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
