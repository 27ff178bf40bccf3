"""The program's own spans and compile tally, as a traced run leaves them.

The program puts each of its ``obs`` spans on the profiler's clock
(``repro.obs.jax_bridge``): in the window's ``.xplane.pb`` they are host
events beside the benchmark's own ``bench.*`` spans, with the attributes
known at their start as event stats.  This module reads that file once per
run and hands the metric readers:

* the program spans that lie inside a ``bench.request`` span (per-request
  metrics), or inside ``bench.setup`` (set-up metrics);
* the program's compile tally before the first request.

A program without the bridge (a commit before it) leaves no such spans and
no tally: every reading is then None, and nothing raises.
"""

from __future__ import annotations

import bisect
import dataclasses
import os
from typing import Dict, List, Optional, Tuple

from . import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
#: where ``run.py --trace 1`` writes its profile
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")

REQUEST, SETUP = "bench.request", "bench.setup"

#: the compile tally's events that make up compile time; a persistent-cache
#: hit is a backend event whose time is the load
COMPILE_TIME = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration",
                "/jax/core/compile/backend_compile_duration")
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int
    stats: Dict[str, object]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class Spans:
    """The host spans of one trace, and which benchmark span holds each."""

    def __init__(self, spans: List[Span]):
        self.spans = spans
        self._outer = {name: sorted((s.start_ns, s.end_ns) for s in spans
                                    if s.name == name)
                       for name in (REQUEST, SETUP)}

    def inside(self, name: str, outer: str = REQUEST) -> List[Span]:
        """The spans called ``name`` that lie wholly inside an ``outer``
        span."""
        iv = self._outer[outer]
        starts = [a for a, _ in iv]
        out = []
        for s in self.spans:
            if s.name != name:
                continue
            k = bisect.bisect_right(starts, s.start_ns) - 1
            if k >= 0 and s.end_ns <= iv[k][1]:
                out.append(s)
        return out

    def seconds(self, name: str, outer: str = REQUEST) -> Optional[float]:
        """Total seconds of the ``name`` spans inside ``outer`` spans
        (None where there are none)."""
        found = self.inside(name, outer)
        return sum(s.seconds for s in found) if found else None


def read_trace(path: str) -> Spans:
    """Every event of the ``/host:CPU`` plane of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans = [Span(e.name, int(e.start_ns), int(e.end_ns), dict(e.stats))
             for p in data.planes if p.name == "/host:CPU"
             for line in p.lines for e in line.events]
    return Spans(spans)


def spans(ctx) -> Optional[Spans]:
    """The spans of the run's trace, read once per run (None for a run
    without a trace)."""
    if ctx.trace is None:
        return None
    cached = getattr(ctx, "_progspans", None)
    if cached is None:
        cached = ctx._progspans = read_trace(xplane.find(TRACE_DIR))
    return cached


def per_request_ms(ctx, name: str) -> Optional[float]:
    """Milliseconds per request of the ``name`` spans inside requests."""
    s = spans(ctx)
    total = s.seconds(name) if s is not None else None
    return None if total is None else total / ctx.requests * 1e3


def setup_compiles(ctx) -> Optional[Tuple[int, float]]:
    """(compiles the persistent cache did not serve, seconds of tracing,
    lowering and backend compile or cache load, overlaps counted once) of
    the program's compile tally before the first request; None for a
    program without the tally."""
    try:
        from repro.obs.jax_bridge import compile_events
    except ImportError:
        return None
    events = compile_events(before=ctx.records[0][0])
    backend = sum(1 for e, _, _ in events if e == BACKEND)
    hits = sum(1 for e, _, _ in events if e == CACHE_HIT)
    busy = xplane.union((t - s, t) for e, t, s in events
                        if e in COMPILE_TIME)
    return backend - hits, sum(b - a for a, b in busy)
