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

Beside it, the count of what was compiled: ``COMPILE_COUNTERS`` listens to
JAX's own monitoring events, so it sees every program of the process, the
eager one-op ones the host issues between steps included
(``kgct_xla_compile_*`` on ``/metrics``).
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_compile_cache — three levels up from this file
# (utils/ -> package -> checkout).
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


# JAX 0.9: the duration event is recorded around compile_or_get_cached(),
# once for every program that reaches the backend, whether it is compiled
# there or loaded from the persistent cache (the seconds are then the
# load's); the plain event once for every load from the persistent cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounters:
    """Process-wide: programs that reached the backend (``requests``), the
    seconds that took (``seconds``), and how many of them the persistent
    cache answered (``cache_hits``). requests - cache_hits is what XLA
    really compiled."""

    def __init__(self):
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()   # compiles come from any thread
        self._installed = False

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.requests += 1
                self.seconds += duration

    def _on_event(self, event: str, **kwargs) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def install(self) -> None:
        """Register the two listeners, once. A step whose program is
        already compiled never reaches them: JAX records events only where
        it traces, lowers or compiles."""
        with self._lock:
            if self._installed:
                return
            self._installed = True
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)


COMPILE_COUNTERS = CompileCounters()


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory; start counting compilations. Idempotent; call before
    the first compilation."""
    COMPILE_COUNTERS.install()
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
