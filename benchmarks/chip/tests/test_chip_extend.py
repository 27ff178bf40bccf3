"""A cell of a new traffic kind comes as new files only: a kind module, a
configuration, a traffic file and its entries in BENCHMARK.json.  The test
writes them into a copy of the benchmark's data under ``tmp_path``, points
the kind search path there, runs the cell on the CPU and sees the per-cell
tests' parametrisation and CPU cut take it up, while no file of the
benchmark changes."""

import hashlib
import json
import os
import shutil
import sys
import textwrap

import pytest

from chip_small import (CHIP, ROOT, SEED, cells, control_cells, fault_cases,
                        small)

import run as runner
from chipbench import kinds

NAME, KIND = "tune.costs", "costs"

#: a kind of its own: each request scores a batch of tunings drawn from the
#: seed with the program's cost model, checked against the plain reference
KIND_MODULE = '''
    """costs: the program's cost model over tunings drawn from the seed."""

    import numpy as np

    from chipbench.reference import cost_model as ref


    def cost_altered(mp):
        """The cost model's write cost comes back doubled."""
        import jax.numpy as jnp
        import repro.core as core
        orig = core.cost_vector

        def altered(phi, sys, **kw):
            return orig(phi, sys, **kw) * jnp.array([1.0, 1.0, 1.0, 2.0])
        mp.setattr(core, "cost_vector", altered)


    class Costs:
        trace_setup = False
        cpu_control = False

        def __init__(self, config, traffic, seed):
            self.config, self.traffic = config, traffic
            self.rng = np.random.default_rng(seed)
            self.done = {}

        @staticmethod
        def small(config, traffic):
            traffic.update(tunings=64)

        @staticmethod
        def faults(traffic):
            return {"cost_altered": cost_altered}

        def setup(self):
            import jax
            import repro.core as core
            s = self.config["system"]
            self.refsys = ref.System.from_config(s)
            n, p = int(self.traffic["tunings"]), int(self.traffic["pool"])
            self.T = self.rng.uniform(2.0, 40.0, (p, n))
            self.M = self.rng.uniform(0.0, 0.9, (p, n)) \\
                * s["bits_per_entry"] * s["N"]
            self.K = np.ones((p, n, int(s["max_levels"])))
            sys = core.LSMSystem(**s)
            self.cost = jax.jit(jax.vmap(lambda T, M, K: core.cost_vector(
                core.Phi(T=T, mfilt_bits=M, K=K), sys)))
            self.request(0)
            self.done.clear()

        def request(self, i):
            k = i % len(self.T)
            self.done[i] = np.asarray(self.cost(
                self.T[k].astype(np.float32), self.M[k].astype(np.float32),
                self.K[k].astype(np.float32)), np.float64)
            return len(self.done[i])

        def counters(self):
            return {}

        def checks(self):
            gap = 0.0
            for i, got in self.done.items():
                k = i % len(self.T)
                want = ref.cost_vector(self.T[k], self.M[k], self.K[k],
                                       self.refsys)
                gap = max(gap, float(np.max(np.abs(got - want) / want)))
            return [("cost_gap", gap, self.traffic["limits"]["cost_gap"])]


    KIND = Costs
'''


def _hashes() -> dict:
    """Every file of the benchmark (bytecode caches aside) by its hash."""
    out = {}
    files = [os.path.join(ROOT, "BENCHMARK.json")] + [
        os.path.join(d, f) for d, sub, fs in os.walk(CHIP)
        if "__pycache__" not in d for f in fs]
    for path in files:
        with open(path, "rb") as f:
            out[os.path.relpath(path, ROOT)] = hashlib.sha256(
                f.read()).hexdigest()
    return out


def _write(path, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f, indent=1)


@pytest.fixture
def root(tmp_path, monkeypatch):
    """A checkout's data with one more cell, of a kind found only in
    ``tmp_path/kinds``."""
    chip = tmp_path / os.path.relpath(CHIP, ROOT)
    for d in ("configs", "traffic"):
        shutil.copytree(os.path.join(CHIP, d), chip / d)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(CHIP, "configs", "endure-model-study.json")) as f:
        system = json.load(f)["system"]
    config = "benchmarks/chip/configs/costs-study.json"
    _write(str(tmp_path / config),
           {"name": "costs-study", "system": system, "reduced": []})
    _write(str(chip / "traffic" / "costs.json"),
           {"generator": KIND, "tunings": 1024, "pool": 4,
            "limits": {"cost_gap": 1e-3}})
    _write(str(tmp_path / "kinds" / (KIND + ".py")),
           textwrap.dedent(KIND_MODULE))
    bench["configs"].append({
        "name": "costs-study", "source": "https://arxiv.org/abs/2311.10005",
        "file": config, "reduced": [], "why": "the cost model alone"})
    bench["workloads"].append({
        "name": NAME, "config": "costs-study", "traffic": "costs",
        "chips": 1, "why": "batches of tunings scored by the cost model"})
    for m in bench["end_to_end"]:
        if m["name"] == "tune_cells_per_s":
            m["workloads"].append(NAME)
    _write(str(tmp_path / "BENCHMARK.json"), bench)
    monkeypatch.setattr(kinds, "__path__", [str(tmp_path / "kinds")])
    yield str(tmp_path)
    sys.modules.pop(f"{kinds.__name__}.{KIND}", None)


def test_new_kind_runs_correct_and_its_fault_is_caught(root, monkeypatch):
    before = _hashes()
    out = runner.run(small(NAME, root), SEED, 0.5, trace=False,
                     need_chip=False)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "tune_cells_per_s"}
    assert out["checks"]["cost_gap"]["limit"] == 1e-3
    spec = runner.load_cell(NAME, root)
    from chipbench import generator
    generator.kind(KIND).faults(spec["traffic"])["cost_altered"](monkeypatch)
    out = runner.run(small(NAME, root), SEED, 0.5, trace=False,
                     need_chip=False)
    assert not out["correct"], out["checks"]
    assert _hashes() == before


def test_new_kind_is_taken_up_by_the_per_cell_tests(root):
    before = _hashes()
    assert cells(root)[-1] == NAME
    assert (NAME, "cost_altered") in fault_cases(root)
    assert fault_cases(root)[:-1] == fault_cases()
    assert NAME not in control_cells(root)
    spec = small(NAME, root)
    assert spec["traffic"]["tunings"] == 64
    assert _hashes() == before


def test_unknown_kind_names_both_places(root):
    from chipbench import generator
    for name in ("no_such_kind", "No-Kind"):
        with pytest.raises(KeyError) as e:
            generator.kind(name)
        assert "KINDS" in str(e.value) and "chipbench/kinds" in str(e.value)
