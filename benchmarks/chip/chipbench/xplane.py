"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

What is read, and from where:

* device planes: ``/device:TPU:<n>``; their ``XLA Ops`` line holds every
  operation that ran, their ``XLA Modules`` line every program run (named
  ``jit_<function>(<fingerprint>)``);
* the host plane ``/host:CPU``: the line of the thread that made them
  holds the ``jax.profiler.TraceAnnotation`` spans the benchmark put
  around its window, each request and each set-up phase.

Host and device events are on one clock in the file.  The window is the
benchmark's own ``window`` span.  Busy time is the union of the operation
intervals inside the window, averaged over the device planes; an idle gap
is a stretch of the window in which no operation ran, and it takes the name
of the benchmark span that covers most of it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[int, int]
WINDOW = "window"


def find(logdir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping ``[start, end)`` intervals."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The stretches of ``[lo, hi)`` that ``busy`` (merged) leaves free."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                          # mean over the device planes
    devices: int
    modules: Dict[str, Tuple[int, float]]  # program -> (runs, seconds)
    idle_gaps: List[Tuple[str, float]]     # longest gaps, by host span
    spans: Dict[str, int]                  # benchmark spans in the window

    def module_seconds(self, function: str) -> Tuple[int, float]:
        """(runs, device seconds) of the programs jitted from
        ``function`` (mean over the device planes)."""
        runs = secs = 0.0
        for name, (n, s) in self.modules.items():
            if name == f"jit_{function}":
                runs, secs = runs + n, secs + s
        return int(runs), secs


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            yield from line.events


def reduce(path: str, span_names: Iterable[str], top: int = 10,
           window: str = WINDOW) -> Reduction:
    """Read one trace: the window (the one span named ``window``), device
    busy time, programs and the ``top`` longest idle gaps.  ``span_names`` are the
    benchmark's own span names."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = list(data.planes)
    host = [p for p in planes if p.name == "/host:CPU"]
    ours = set(span_names) | {window}
    spans = [(e.name, int(e.start_ns), int(e.end_ns))
             for p in host for line in p.lines for e in line.events
             if e.name in ours]
    win = [(a, b) for n, a, b in spans if n == window]
    if len(win) != 1:
        raise ValueError(f"{len(win)} '{window}' spans in {path}")
    lo, hi = win[0]
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    if not devices:
        raise ValueError(f"no TPU device plane in {path}")
    busy_total = 0.0
    modules: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    first_busy: List[Interval] = []
    for k, plane in enumerate(devices):
        iv = []
        for e in _events(plane, "XLA Ops"):
            a, b = int(e.start_ns), int(e.end_ns)
            if b > lo and a < hi:
                iv.append((a, b))
        merged = union(clip(iv, lo, hi))
        busy_total += sum(b - a for a, b in merged) / 1e9
        if k == 0:
            first_busy = merged
        for e in _events(plane, "XLA Modules"):
            a, b = int(e.start_ns), int(e.end_ns)
            if b > lo and a < hi:
                m = modules[e.name.split("(")[0]]
                m[0] += 1
                m[1] += (min(b, hi) - max(a, lo)) / 1e9
    n = len(devices)
    inner = [(name, a, b) for name, a, b in spans if name != window]
    longest = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])
    labelled = []
    for a, b in longest[:top]:
        cover: Dict[str, int] = defaultdict(int)
        for name, sa, sb in inner:
            over = min(b, sb) - max(a, sa)
            if over > 0:
                cover[name] += over
        label = max(cover, key=cover.get) if cover else "outside spans"
        labelled.append((label, (b - a) / 1e9))
    counted: Dict[str, int] = defaultdict(int)
    for name, a, b in inner:
        if lo <= a < hi:
            counted[name] += 1
    return Reduction(
        window_s=(hi - lo) / 1e9, busy_s=busy_total / n, devices=n,
        modules={k: (int(v[0] / n), v[1] / n) for k, v in modules.items()},
        idle_gaps=labelled, spans=dict(counted))
