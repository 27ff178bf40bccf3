"""Cells of BENCHMARK.json cut to a size a CPU test run holds, with the
cells' own traffic kinds, checks and limits.  Each kind cuts its own cells
(its ``small``); the per-cell tests take their cases from BENCHMARK.json
through :func:`cells`, :func:`fault_cases` and :func:`control_cells`."""

import os
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
for _p in (CHIP, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as runner  # noqa: E402
from chipbench import generator  # noqa: E402

SEED = 2 ** 33 + 7


def kind_of(spec: dict):
    """The traffic kind of a cell (as ``runner.load_cell`` gives it)."""
    return generator.kind(spec["traffic"]["generator"])


def cells(root: str = ROOT) -> list:
    """The names of ``<root>/BENCHMARK.json``'s cells."""
    return [w["name"] for w in
            runner.load_json(os.path.join(root, "BENCHMARK.json"))
            ["workloads"]]


def fault_cases(root: str = ROOT) -> list:
    """``(cell, fault)`` for every fault each cell's kind names for it."""
    out = []
    for name in cells(root):
        spec = runner.load_cell(name, root)
        out += [(name, f) for f in kind_of(spec).faults(spec["traffic"])]
    return out


def control_cells(root: str = ROOT) -> list:
    """The cells whose kind has its control run by the CPU test."""
    return [name for name in cells(root)
            if kind_of(runner.load_cell(name, root)).cpu_control]


def small(name: str, root: str = ROOT) -> dict:
    spec = runner.load_cell(name, root)
    kind_of(spec).small(spec["config"], spec["traffic"])
    return spec


def run_small(name: str, seconds: float = 1.0) -> dict:
    return runner.run(small(name), SEED, seconds, trace=False,
                      need_chip=False)
