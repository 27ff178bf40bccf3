"""A run drives the timed path with a fault planted underneath and sees
``correct`` come out false, once for each fault the cell can have; the
same run without a fault is correct.  The cases come from BENCHMARK.json:
every cell, and each fault its kind names for it (``faults(traffic)``).
The cells are cut to a CPU test's size (``chip_small``); the checks and
limits are the cells' own."""

import jax
import pytest

from chip_small import cells, fault_cases, kind_of, run_small

import run as runner


@pytest.fixture
def fresh_jit(cell):
    """JAX's caches cleared around the run where the cell's kind plants
    its faults in traced code."""
    fresh = getattr(kind_of(runner.load_cell(cell)), "fresh_jit", False)
    if fresh:
        jax.clear_caches()
    yield
    if fresh:
        jax.clear_caches()


@pytest.mark.parametrize("cell", cells())
def test_sound(cell, fresh_jit):
    out = run_small(cell)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("cell,fault", fault_cases())
def test_fault(cell, fault, monkeypatch, fresh_jit):
    spec = runner.load_cell(cell)
    kind_of(spec).faults(spec["traffic"])[fault](monkeypatch)
    out = run_small(cell)
    assert not out["correct"], out["checks"]
