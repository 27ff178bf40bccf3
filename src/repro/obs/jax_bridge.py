"""The JAX side of the telemetry plane: the profiler bridge and the compile
tally.

:func:`install` (called once when ``repro.core`` is imported) does two
things:

* it installs ``jax.profiler.TraceAnnotation`` as :mod:`repro.obs`'s span
  factory, so every ``obs.span`` is also a profiler span, with the
  attributes known at its start as the event's stats, on the device
  trace's clock;
* it registers one ``jax.monitoring`` listener that keeps a tally of the
  process's compile events, ``(event, time.perf_counter() at the event,
  seconds)`` each, read by :func:`compile_events`.  While the ring is on it
  also keeps the gauges ``compile.<kind>`` (events so far) and
  ``compile.<kind>_s`` (their seconds so far): gauges, not counters, since
  whether a run compiles depends on what ran before it in the process.

Jax-free processes (the subprocess fleet workers) never import this
module, so their spans stay ring-only.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

from . import core

#: the compile events the tally keeps, each with its short kind
COMPILE_EVENTS: Dict[str, str] = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # a persistent-cache hit is a backend event too (its time is the load)
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_misses": "cache_miss",
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}

_EVENTS: List[Tuple[str, float, float]] = []
_TOTALS: Dict[str, List[float]] = {}
_LOCK = threading.Lock()
_INSTALLED = False


def _record(event: str, seconds: float) -> None:
    kind = COMPILE_EVENTS[event]
    with _LOCK:
        _EVENTS.append((event, time.perf_counter(), seconds))
        total = _TOTALS.setdefault(kind, [0, 0.0])
        total[0] += 1
        total[1] += seconds
        n, s = total
    if core.enabled():
        core.gauge("compile." + kind, n)
        core.gauge("compile." + kind + "_s", round(s, 9))


def _on_duration(event: str, duration: float, **_) -> None:
    if event in COMPILE_EVENTS:
        _record(event, float(duration))


def _on_event(event: str, **_) -> None:
    if event in COMPILE_EVENTS:
        _record(event, 0.0)


def compile_events(before: Optional[float] = None
                   ) -> List[Tuple[str, float, float]]:
    """The tally: ``(event, perf_counter time, seconds)`` per compile event
    since :func:`install`, those at or after ``before`` left out."""
    with _LOCK:
        return [e for e in _EVENTS if before is None or e[1] < before]


def install() -> None:
    """Install the profiler bridge and the compile tally, once."""
    global _INSTALLED
    if _INSTALLED:
        return
    import jax
    core.set_annotator(jax.profiler.TraceAnnotation)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    _INSTALLED = True
