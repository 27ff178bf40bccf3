"""Structured telemetry plane: spans, counters, trace export.

Stdlib-only (importable from jax-free subprocess workers, like
:mod:`repro.faults`).  Off by default; ``obs.configure()`` flips the
process-global switch, mirroring ``lsm/read_path.py``'s kernel-mode
pattern.  See ``docs/observability.md`` for the taxonomy and schema.

The calibration pass (:mod:`repro.obs.calibrate`) and the JAX side of the
plane (:mod:`repro.obs.jax_bridge`: the profiler bridge and the compile
tally) are deliberately NOT re-exported here: they need numpy or jax, and
keeping them leaf submodules keeps ``import repro.obs`` dependency-free.
"""

from .core import (NULL_SPAN, Span, Telemetry, VALID_CLOCKS, clear,
                   configure, count, disable, enabled, event,
                   events_snapshot, gauge, get, metrics_snapshot, scoped,
                   set_annotator, span, track)
from .trace import chrome_trace, write_trace

__all__ = [
    "NULL_SPAN", "Span", "Telemetry", "VALID_CLOCKS",
    "chrome_trace", "clear", "configure", "count", "disable", "enabled",
    "event", "events_snapshot", "gauge", "get", "metrics_snapshot",
    "scoped", "set_annotator", "span", "track", "write_trace",
]
