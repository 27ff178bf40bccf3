"""The telemetry plane's JAX side: every ``obs`` span is also a profiler
span (on the device trace's clock, with its start attributes as event
stats), and a tally of the process's compile events.

The bridge is installed when ``repro.core`` is imported; each test that
needs it absent removes it and puts it back."""

from __future__ import annotations

import glob
import os
import time

import pytest

import repro.core  # noqa: F401  (installs the bridge and the tally)
from repro import obs
from repro.obs import jax_bridge
from repro.obs.core import NULL_SPAN


@pytest.fixture(autouse=True)
def _isolated_obs():
    prev = obs.get()
    obs.disable()
    yield
    obs.core._T = prev


def _host_events(logdir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(path)
    return [(e.name, int(e.start_ns), int(e.end_ns), dict(e.stats))
            for p in data.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events]


@pytest.mark.parametrize("ring", [False, True])
def test_span_lands_in_a_profiler_trace(tmp_path, ring):
    import jax
    if ring:
        obs.configure(enabled=True, clock="ticks")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with obs.span("engine.flush", entries=12) as outer:
            outer.set(pages=3)                     # the ring's, not a stat
            with obs.span("engine.compact", kind="spill"):
                time.sleep(0.001)
    events = {name: (a, b, stats) for name, a, b, stats
              in _host_events(str(tmp_path))}
    fa, fb, fstats = events["engine.flush"]
    ca, cb, cstats = events["engine.compact"]
    assert fa <= ca < cb <= fb                     # nesting
    assert fstats == {"entries": 12} and cstats == {"kind": "spill"}
    assert cb - ca >= 1_000_000
    ring_events = obs.events_snapshot()
    if ring:
        assert [e["name"] for e in ring_events] == ["engine.compact",
                                                     "engine.flush"]
        assert ring_events[1]["attrs"] == {"entries": 12, "pages": 3}
    else:
        assert ring_events == []


def test_span_with_the_ring_off_is_falsy_and_absorbs_attributes():
    with obs.span("engine.read", keys=5) as sp:
        assert not sp and sp is not NULL_SPAN
        sp.set(random_reads=2)
    assert obs.events_snapshot() == []


def test_without_the_annotator_a_span_is_the_null_span():
    prev = obs.set_annotator(None)
    try:
        assert obs.span("engine.read", keys=5) is NULL_SPAN
    finally:
        obs.set_annotator(prev)
    assert obs.span("engine.read", keys=5) is not NULL_SPAN


def test_compile_tally_counts_a_fresh_jit_and_cuts_at_a_time():
    import jax
    import jax.numpy as jnp
    backend = "/jax/core/compile/backend_compile_duration"
    x = jnp.arange(4.0).block_until_ready()
    t0 = time.perf_counter()
    n0 = len(jax_bridge.compile_events())
    f = jax.jit(lambda x: jnp.cos(x) * 7.654321)
    f(x).block_until_ready()
    events = jax_bridge.compile_events()[n0:]
    kinds = [e for e, _, _ in events]
    assert kinds.count(backend) == 1
    assert "/jax/core/compile/jaxpr_trace_duration" in kinds
    assert all(t >= t0 and s >= 0.0 for _, t, s in events)
    assert len(jax_bridge.compile_events(before=t0)) == n0
    f(x).block_until_ready()                       # cached: no event
    assert len(jax_bridge.compile_events()) == n0 + len(events)


def test_compile_tally_feeds_gauges_while_the_ring_is_on():
    import jax
    import jax.numpy as jnp
    obs.configure(enabled=True, clock="ticks")
    jax.jit(lambda x: jnp.tan(x) * 3.21)(jnp.arange(2.0)).block_until_ready()
    gauges = obs.metrics_snapshot()["gauges"]
    assert gauges["compile.backend"] >= 1
    assert gauges["compile.backend_s"] > 0.0
    assert obs.metrics_snapshot()["counters"] == {}
