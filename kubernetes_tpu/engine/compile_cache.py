"""Persistent XLA compilation cache configuration.

Every scheduler start used to pay the full XLA compile tax because jit
executables lived only in process memory.  This module turns on JAX's
persistent compilation cache so the cost is paid once per (machine,
jaxlib, program) and every later start deserializes the executables
instead of re-running XLA.

Where the cache lives is decided from OUTSIDE the program:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself at import and
  this module sets no directory at all;
* unset: ``<checkout>/.jax_cache`` — a fixed path next to the package
  (ignored by git), never derived from ``$HOME``, a temp name, a pid or
  the time: the path is part of the cache key, so a directory that moves
  never hits.

The cache thresholds are dropped to zero so *every* executable persists —
the drain path's small shapes (the stream bucket ladder, the explain-pass
batch) individually compile in under JAX's default 1 s floor but add up
to the multi-second warm-start stall the ladder pre-warm then re-pays.

``configure()`` is idempotent and must run before the first jit trace to
cover it; ``GenericScheduler.__init__`` calls it, which puts it ahead of
every Solver executable in every rig (daemon, bench, tests).
"""

from __future__ import annotations

import os
import threading
from typing import Optional

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_lock = threading.Lock()
_configured = False
_dir: Optional[str] = None


def configure() -> str:
    """Turn on JAX's persistent compilation cache and return the
    directory it uses.  Safe to call from any thread, any number of
    times; the environment is read ONCE — like the stream bucket floor,
    a mid-run change must not silently split state between two
    directories."""
    global _configured, _dir
    with _lock:
        if _configured:
            return _dir
        import jax
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              DEFAULT_CACHE_DIR)
        # Persist everything: the bucket-ladder scans and explain-pass
        # shapes each compile below the default 1 s floor but together
        # are the warm-start stall this cache exists to kill.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        _register_hit_miss_listener()
        _dir = jax.config.jax_compilation_cache_dir
        _configured = True
        return _dir


_listener_registered = False


def _register_hit_miss_listener() -> None:
    """Feed ``compile_cache_{hits,misses}_total`` from JAX's monitoring
    events: a hit is a jit executable deserialized from the persistent
    cache, a miss one that re-paid the full XLA compile.  Without them
    the multi-second \"warm\" start is undiagnosable — the counters say
    exactly which restarts still compile."""
    global _listener_registered
    if _listener_registered:
        return
    from jax import monitoring

    from kubernetes_tpu.utils.metrics import (COMPILE_CACHE_HITS,
                                              COMPILE_CACHE_MISSES)

    def _on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            COMPILE_CACHE_HITS.inc()
        elif event == "/jax/compilation_cache/cache_misses":
            COMPILE_CACHE_MISSES.inc()

    monitoring.register_event_listener(_on_event)
    _listener_registered = True


def cache_dir() -> Optional[str]:
    """The active cache directory (None = not configured yet)."""
    with _lock:
        return _dir


def _reset_for_tests() -> None:
    """Drop the idempotence latch (tests exercising the env contract)."""
    global _configured, _dir
    with _lock:
        _configured = False
        _dir = None
