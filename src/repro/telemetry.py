"""Named host spans and compile counters for the AIDW program.

Spans: ``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation`` named
``aidw.<name>``.  While a profiler session is active the profiler records
it into the same trace as the device operations, on the same clock; with
no session it costs one annotation's construction and records nothing.
``spanned(name)`` wraps a whole function in such a span.  Open spans at
layer boundaries, once per call: never per query or per block.

Device scopes are ``jax.named_scope("aidw.<stage>")`` inside the jitted
program (``engine/execute.py``): they name the compiled operations in the
HLO metadata (``op_name``) and change no operation.

Counters: ``snapshot()`` returns the process's compile counters as a plain
dict.  They come from ``jax.monitoring`` listeners, installed by the first
``snapshot()`` call (never on import); events before it are not counted.

* ``traces`` / ``trace_s``: jaxprs traced and their seconds;
* ``lowerings`` / ``lower_s``: jaxprs lowered to MLIR and their seconds;
* ``compiles`` / ``compile_s``: backend compiles, each a real compile or a
  retrieval from the persistent compilation cache, and their seconds;
* ``cache_hits`` / ``cache_misses`` / ``cache_retrieval_s``: persistent
  cache reads that found an entry, entries written after a compile, and
  the seconds spent reading (inside ``compile_s``).
"""

from __future__ import annotations

import threading

import jax

PREFIX = "aidw."

# jax.monitoring events -> (count key, seconds key)
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": ("traces", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lowerings", "lower_s"),
    "/jax/core/compile/backend_compile_duration": ("compiles", "compile_s"),
    "/jax/compilation_cache/cache_retrieval_time_sec": (None, "cache_retrieval_s"),
}
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}

_lock = threading.Lock()
_counters: dict | None = None  # None until the listeners are installed


def span(name: str, **ids):
    """A host span ``aidw.<name>``; ``ids`` (ints or strings) are recorded
    on it, such as the call's number."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)


def spanned(name: str):
    """Decorator: run the function inside the span ``aidw.<name>``."""
    return lambda fn: jax.profiler.annotate_function(fn, name=PREFIX + name)


def _on_duration(event: str, duration: float, **_kwargs):
    keys = _DURATIONS.get(event)
    if keys is None:
        return
    count, seconds = keys
    with _lock:
        if count is not None:
            _counters[count] += 1
        _counters[seconds] += float(duration)


def _on_event(event: str, **_kwargs):
    key = _EVENTS.get(event)
    if key is not None:
        with _lock:
            _counters[key] += 1


def snapshot() -> dict:
    """The compile counters since the first call, as a plain dict."""
    global _counters
    with _lock:
        if _counters is None:
            _counters = {k: 0 for k, _ in _DURATIONS.values() if k}
            _counters.update({k: 0 for k in _EVENTS.values()})
            _counters.update({s: 0.0 for _, s in _DURATIONS.values()})
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
        return dict(_counters)
