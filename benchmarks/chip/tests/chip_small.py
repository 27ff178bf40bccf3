"""Cells of BENCHMARK.json cut to a size a CPU test run holds, with the
cells' own traffic kinds, checks and limits."""

import os
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
for _p in (CHIP, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as runner  # noqa: E402

SEED = 2 ** 33 + 7


def small(name: str) -> dict:
    spec = runner.load_cell(name)
    t, c = spec["traffic"], spec["config"]
    if t["generator"] == "experiment":
        t.update(workloads=t["workloads"][:5], rhos=t["rhos"][:2],
                 bench_n=100)
        c["tuner"].update(n_starts=16, steps=100)
    elif t["generator"] == "storm":
        t.update(tenants=5, pool=2)
        c["retune"].update(n_starts=16, steps=100)
    else:
        n = 20_000
        c["engine"]["n_records"] = n
        c["system"].update(N=float(n), min_buf_bits=64.0 * 8192)
        c["deployment"].update(n_starts=8, steps=40)
        t.update(pool_requests=64, readback=500, request_ops=200)
    return spec


def run_small(name: str, seconds: float = 1.0) -> dict:
    return runner.run(small(name), SEED, seconds, trace=False,
                      need_chip=False)
