"""The readers of the program's own spans and compile tally, on a small
recorded trace (``data/progspans.xplane.pb``, made by
``data/record_progspans.py``): three requests, with program spans in
set-up and between requests that a per-request metric must leave out."""

import os
import shutil
import time

import pytest

from chip_small import CHIP

import run as runner
from chipbench import progspans
from chipbench.xplane import Reduction

DATA = os.path.join(CHIP, "tests", "data")
SPAN_METRICS = ("select_score_ms", "grid_problems_per_cell", "read_batch_ms",
                "flush_compact_ms", "populate_s")


def _ctx(records=None, trace=True):
    spec = runner.load_cell("tune.fig6")
    reduction = Reduction(window_s=1.0, busy_s=0.2, devices=1, modules={},
                          idle_gaps=[], spans={}) if trace else None
    if records is None:
        records = [(0.0, 0.1, 90, True)] * 3
    return runner.Context(spec, 12.5, records, {}, reduction)


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """The recorded trace, where a traced run leaves its own."""
    shutil.copy(os.path.join(DATA, "progspans.xplane.pb"),
                tmp_path / "run.xplane.pb")
    monkeypatch.setattr(progspans, "TRACE_DIR", str(tmp_path))
    return tmp_path


def test_spans_inside_requests_only(recorded):
    s = progspans.spans(_ctx())
    assert len(s.inside("tune.score")) == 6
    assert len(s.inside("tune.grid")) == 6
    assert [g.stats["problems"] for g in s.inside("tune.grid")][:2] == \
        [15, 75]
    assert len(s.inside("engine.flush")) == 2
    assert len(s.inside("engine.compact")) == 2
    assert len(s.inside("lsm.populate")) == 0
    assert len(s.inside("lsm.populate", progspans.SETUP)) == 1
    assert s.inside("engine.read")[0].stats["keys"] == 500


def test_trace_is_read_once_per_run(recorded):
    ctx = _ctx()
    first = progspans.spans(ctx)
    os.remove(recorded / "run.xplane.pb")
    assert progspans.spans(ctx) is first


@pytest.mark.parametrize("name,lo,hi", [
    # 2 x 2 ms of scoring per request; set-up's 20 ms and the 10 ms
    # between requests would add at least 10 ms per request
    ("select_score_ms", 4.0, 7.0),
    # (15 + 75) problems for 90 cells; set-up's 999 would add 3.7
    ("grid_problems_per_cell", 1.0, 1.0),
    # one 3-ms batch per request; set-up's 20 ms would add 6.7 ms
    ("read_batch_ms", 3.0, 6.0),
    # two 4-ms flushes in three requests; set-up's 20 ms would add 6.7 ms
    ("flush_compact_ms", 8.0 / 3, 5.0),
    # 30 ms of loading in set-up
    ("populate_s", 0.030, 0.045),
])
def test_span_metric_on_the_recorded_trace(recorded, name, lo, hi):
    value = runner.metric_reader(name)(_ctx())
    assert lo <= value <= hi, value


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_metric_without_program_spans_is_absent(tmp_path, monkeypatch,
                                                     name):
    """A program without the bridge leaves only the benchmark's spans: the
    reader finds nothing and says so, without raising."""
    shutil.copy(os.path.join(DATA, "tiny.xplane.pb"),
                tmp_path / "run.xplane.pb")
    monkeypatch.setattr(progspans, "TRACE_DIR", str(tmp_path))
    assert runner.metric_reader(name)(_ctx()) is None


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_metric_without_a_trace_is_absent(name):
    assert runner.metric_reader(name)(_ctx(trace=False)) is None


def test_setup_compiles_counts_compiles_before_the_first_request():
    import jax
    import jax.numpy as jnp
    import repro.core  # noqa: F401  (installs the compile tally)
    compiles = runner.metric_reader("setup_compiles")
    seconds = runner.metric_reader("setup_compile_s")
    t0 = time.perf_counter()
    f = jax.jit(lambda x: jnp.sin(x) * 1.2345678)
    f(jnp.arange(3.0)).block_until_ready()
    t1 = time.perf_counter()
    before, after = _ctx([(t0, t1, 1, True)]), _ctx([(t1, t1, 1, True)])
    # read after the compile: the events at or after the first request's
    # start are left out
    assert compiles(after) >= compiles(before) + 1
    assert seconds(after) > seconds(before) >= 0.0
    f(jnp.arange(3.0)).block_until_ready()          # no new compile
    assert compiles(_ctx([(time.perf_counter(), 0, 1, True)])) == \
        compiles(after)


def test_setup_compiles_without_the_tally_is_absent(monkeypatch):
    import sys
    monkeypatch.setitem(sys.modules, "repro.obs.jax_bridge", None)
    ctx = _ctx([(time.perf_counter(), 0, 1, True)])
    for name in ("setup_compiles", "setup_compile_s"):
        assert runner.metric_reader(name)(ctx) is None
