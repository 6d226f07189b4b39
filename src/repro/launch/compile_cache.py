"""JAX's persistent compilation cache, configured from outside the program.

``JAX_COMPILATION_CACHE_DIR``, when set, is where the cache lives (JAX
reads the variable itself, so no other directory is set here).  Otherwise
the cache goes to ``.jax_cache`` at the root of the checkout: a fixed
path, because the path is part of every entry's key.

:func:`compile_counts` counts, for the whole process, what JAX's own
monitoring events report: executables built (compiled or loaded from the
persistent cache), persistent-cache loads, and jaxpr traces.  A server or
a benchmark reads it before and after a window to see whether anything
compiled inside it.
"""

from __future__ import annotations

import collections
import os
import threading
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  Call from a
    program's entry point, never at import.  Pallas kernels compile in
    one or two seconds, so every compile is persisted, however short."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


# JAX's monitoring events (jax/_src/dispatch.py, jax/_src/compiler.py).
# The backend-compile event wraps ``compile_or_get_cached``, so it fires for
# a persistent-cache load too; ``cache_hits`` counts those loads alone.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"

_lock = threading.Lock()
_counts = {"executables": 0, "cache_hits": 0, "traces": 0}
_names: collections.Counter = collections.Counter()
_listening = False


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    if event == BACKEND_COMPILE_EVENT:
        with _lock:
            _counts["executables"] += 1
            _names[str(kw.get("fun_name", "?"))] += 1
    elif event == TRACE_EVENT:
        with _lock:
            _counts["traces"] += 1


def _on_event(event: str, **kw) -> None:
    if event == CACHE_HIT_EVENT:
        with _lock:
            _counts["cache_hits"] += 1


def compile_counts() -> dict:
    """Process-wide counts since the first call: ``executables`` (backend
    compilations, persistent-cache loads included), ``cache_hits`` (of
    those, loads from the persistent cache), ``traces`` (jaxprs traced)
    and ``names`` (executables built, by function name).  The first call
    registers the listeners, so it counts nothing before itself; call it
    once before the stretch to be watched."""
    global _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
        return dict(_counts, names=dict(_names))
