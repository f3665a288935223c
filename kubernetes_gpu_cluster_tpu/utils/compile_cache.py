"""Persistent XLA compilation cache, placeable from outside.

The server compiles one program per (step kind, bucketed shape) lazily, and
a cold start of a 36-layer model pays every one of them. JAX's persistent
cache makes the second process on the same machine skip that — but only if
the cache lives at the SAME path both times: the directory is part of the
cache key, so a temporary, pid- or time-named directory never hits.

Contract (one place, called first thing by every entry point that compiles:
``api_server.main``, ``bench.main``, the ``benchmarks/`` scripts):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set in
  code and the in-checkout directory is not created.
- unset: the cache goes to one fixed directory inside the checkout
  (``.jax_compile_cache/``, ignored by git).
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_compile_cache — three levels up from this file
# (utils/ -> package -> checkout).
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory. Idempotent; call before the first compilation."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
