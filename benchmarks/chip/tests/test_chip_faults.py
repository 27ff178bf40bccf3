"""A run drives the timed path with a fault planted underneath and sees
``correct`` come out false, once for each fault the cell can have; the
same run without a fault is correct.  The cells are cut to a CPU test's
size (``chip_small``); the checks and limits are the cells' own."""

import jax
import pytest

from chip_small import run_small

from chipbench import faults

TUNE = ("tune.fig6", "tune.storm-64")
SERVE = ("serve.ycsb-a", "serve.ycsb-c")


@pytest.fixture
def fresh_jit():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell", TUNE)
def test_tune_sound(cell, fresh_jit):
    out = run_small(cell)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", list(faults.TUNE.values()))
@pytest.mark.parametrize("cell", TUNE)
def test_tune_fault(cell, fault, monkeypatch, fresh_jit):
    fault(monkeypatch)
    out = run_small(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", SERVE)
def test_serve_sound(cell):
    out = run_small(cell)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("cell,fault", [
    ("serve.ycsb-a", faults.writes_dropped),  # YCSB C writes nothing
    ("serve.ycsb-a", faults.half_session),
    ("serve.ycsb-c", faults.half_session),
    ("serve.ycsb-a", faults.read_altered),
    ("serve.ycsb-c", faults.read_altered)])
def test_serve_fault(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = run_small(cell)
    assert not out["correct"], out["checks"]
