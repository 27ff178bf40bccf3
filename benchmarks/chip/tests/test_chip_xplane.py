"""The reduction from a profiler trace to busy time, programs and idle
gaps: interval arithmetic, and a small trace recorded on a TPU v5e (one
jitted program run three times inside ``request`` spans of a ``window``)."""

import os

import pytest

from chip_small import CHIP

from chipbench import xplane

TRACE = os.path.join(CHIP, "tests", "data", "tiny.xplane.pb")


def test_union_merges_overlaps():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert xplane.union([]) == []


def test_gaps_and_clip():
    busy = xplane.union(xplane.clip([(0, 4), (6, 8), (12, 20)], 2, 15))
    assert busy == [(2, 4), (6, 8), (12, 15)]
    assert xplane.gaps(busy, 2, 15) == [(4, 6), (8, 12)]
    assert xplane.gaps([], 0, 5) == [(0, 5)]


def test_recorded_trace():
    r = xplane.reduce(TRACE, ("setup", "request"))
    assert r.devices == 1
    assert 0 < r.busy_s < r.window_s < 1.0
    runs, secs = r.module_seconds("step")
    assert runs == 3 and 0 < r.busy_s <= secs < r.window_s
    assert r.spans == {"setup": 1, "request": 3}
    total_gaps = sum(s for _, s in r.idle_gaps)
    assert total_gaps <= r.window_s - r.busy_s + 1e-9
    assert r.idle_gaps[0][0] in ("setup", "request", "outside spans")
    assert r.idle_gaps == sorted(r.idle_gaps, key=lambda g: -g[1])


def test_a_trace_without_a_window_is_refused(tmp_path):
    with pytest.raises(ValueError):
        xplane.reduce(TRACE, ("setup",), window="request")
