"""The program's tracing: host spans, device scopes and counters.

``span(name, **ids)``
    A host span ``repro.<name>`` written into the JAX profiler's trace
    (``jax.profiler.TraceAnnotation``), on the profiler's clock, so it
    shares its clock with the device trace.  It records only while a
    profiler trace is active; otherwise it costs one small object.
``scope(name)``
    ``jax.named_scope(name)`` for device work: traced operations carry
    the name in their HLO ``op_name`` metadata.  It acts at trace time
    and costs nothing at run time.
``count(name, n=1)``
    Adds ``n`` to a process-wide counter and returns the new total.  While
    a profiler trace is active it also writes a marker
    ``repro.count.<name>`` (an empty span carrying ``n``) into the trace.
    ``counters()`` returns a snapshot of every counter.

Importing this module registers JAX monitoring listeners that feed four
counters:

- ``jax.traces``: jaxpr traces (``/jax/core/compile/jaxpr_trace_duration``);
- ``jax.program_loads``: programs handed to the backend
  (``/jax/core/compile/backend_compile_duration``), either compiled or
  read from the persistent compilation cache;
- ``jax.cache_reads``: of those, the persistent-cache reads
  (``/jax/compilation_cache/cache_hits``);
- ``jax.compile_s``: the seconds of the ``jax.program_loads`` events.
"""

from __future__ import annotations

import threading

import jax

PREFIX = "repro."
COUNT_PREFIX = PREFIX + "count."

_JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_counts: dict[str, float] = {}


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """Host span ``repro.<name>``; ``ids`` become the event's stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)


def scope(name: str):
    """Name the device work traced inside it (HLO ``op_name``)."""
    return jax.named_scope(name)


def count(name: str, n: float = 1):
    """Add ``n`` to counter ``name``; returns the new total."""
    with _lock:
        total = _counts.get(name, 0) + n
        _counts[name] = total
    with jax.profiler.TraceAnnotation(COUNT_PREFIX + name, n=n):
        pass
    return total


def counters() -> dict:
    """A snapshot of every counter."""
    with _lock:
        return dict(_counts)


def _on_duration(event: str, secs: float, **_) -> None:
    if event == _JAXPR_TRACE:
        count("jax.traces")
    elif event == _BACKEND_COMPILE:
        count("jax.program_loads")
        count("jax.compile_s", secs)


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        count("jax.cache_reads")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
