"""Tests for the unified experiment API (repro.api).

The facade's contract is *zero semantic surface*: a spec lowered through
``compile.py`` + any backend must produce bit-identical tunings and
``IOStats`` to hand-wiring the same experiment on the low-level layer
(``tune_nominal_many`` / ``tune_robust_many`` + ``run_policy_fleet``).
These tests pin that contract on a small grid for the inline and
sharded-fallback backends (single device -> the sharded backend must take
the inline path), plus the subprocess fleet backend, the spec <-> JSON
round-trip, and the joint policy-arm selection.

Deliberately hypothesis-free; solver sizes are small so the file runs in
about a minute on CPU.
"""

import dataclasses

import numpy as np
import pytest

from repro.api import (DesignSpec, ExperimentSpec, TrialSpec, WorkloadSpec,
                       run_experiment)
from repro.core import EXPECTED_WORKLOADS, LSMSystem, tune_nominal_many, \
    tune_robust_many
from repro.lsm import run_policy_fleet

SMALL = dict(n_starts=8, steps=60, seed=3)
RHOS = (0.25, 1.0)
WIDX = (7, 11)
SYS_PAIRS = (("N", 8000.0), ("entry_bits", 512.0), ("bits_per_entry", 6.0),
             ("min_buf_bits", 512.0 * 64), ("max_T", 20.0))
SESSIONS = ((0.05, 0.85, 0.05, 0.05), (0.05, 0.05, 0.05, 0.85))


def _spec(**kw) -> ExperimentSpec:
    base = dict(
        name="t",
        workload=WorkloadSpec(indices=WIDX, rhos=RHOS, nominal=True),
        design=DesignSpec(**SMALL),
        system=SYS_PAIRS,
    )
    base.update(kw)
    return ExperimentSpec(**base)


def _assert_same_tuning(a, b):
    assert float(a.phi.T) == float(b.phi.T)
    assert np.array_equal(np.asarray(a.phi.K), np.asarray(b.phi.K))
    assert float(a.phi.mfilt_bits) == float(b.phi.mfilt_bits)
    assert a.cost == b.cost
    assert a.design is b.design


# ---------------------------------------------------------------------------
# Spec <-> JSON round-trip
# ---------------------------------------------------------------------------

def test_spec_json_round_trip():
    spec = _spec(
        trial=TrialSpec(n_keys=5000, n_queries=300, sessions=SESSIONS,
                        key_space=2 ** 22, session_seeds=(4, 5)),
        design=DesignSpec(policies=("klsm", "lazy_leveling"),
                          policy_params=(
                              ("lazy_leveling", (("read_trigger", 64),)),),
                          **SMALL),
        backend="subprocess", backend_params=(("workers", 2),))
    back = ExperimentSpec.from_json(spec.to_json())
    assert back == spec
    # frozen dataclasses: equal means field-for-field equal, incl. nesting
    assert back.trial.sessions == spec.trial.sessions
    assert back.design.params_for("lazy_leveling") == (("read_trigger", 64),)


def test_spec_validation():
    with pytest.raises(ValueError):
        WorkloadSpec(indices=(1,), workloads=((0.25,) * 4,))
    with pytest.raises(ValueError):
        WorkloadSpec(indices=(1,), rhos=(), nominal=False)
    with pytest.raises(ValueError):
        DesignSpec(policies=())
    with pytest.raises(ValueError):
        TrialSpec(sessions=())


# ---------------------------------------------------------------------------
# Bit-identity vs the direct low-level calls
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def direct():
    sys_small = LSMSystem().replace(**dict(SYS_PAIRS))
    W = EXPECTED_WORKLOADS[list(WIDX)]
    nominal = tune_nominal_many(W, sys_small, **SMALL)
    robust = tune_robust_many(W, list(RHOS), sys_small, **SMALL)
    return sys_small, nominal, robust


@pytest.mark.parametrize("backend", ["inline", "sharded"])
def test_tunings_bit_identical_to_direct(direct, backend):
    """Facade tunings == direct tune_*_many, inline AND sharded fallback
    (this host has one device, so `sharded` must take the inline path)."""
    _, nominal, robust = direct
    report = run_experiment(_spec(backend=backend))
    for i in range(len(WIDX)):
        _assert_same_tuning(report.tuning((i, None)), nominal[i])
        for j, rho in enumerate(RHOS):
            _assert_same_tuning(report.tuning((i, rho)), robust[i][j])


def test_trial_bit_identical_to_run_policy_fleet(direct):
    """Facade fleet IOStats == a direct run_policy_fleet on the same phis
    (same key draw, same session seeds, same tree order)."""
    sys_small, _, robust = direct
    spec = _spec(
        workload=WorkloadSpec(indices=WIDX, rhos=(1.0,), nominal=False),
        trial=TrialSpec(n_keys=5000, n_queries=300, sessions=SESSIONS,
                        key_space=2 ** 22, range_fraction=1e-3, key_seed=7))
    report = run_experiment(spec)
    phis = [robust[i][1].phi for i in range(len(WIDX))]  # rho=1.0 column
    _, results = run_policy_fleet(
        phis, sys_small, ["klsm"], np.asarray(SESSIONS), n_keys=5000,
        n_queries=300, seed=7, key_space=2 ** 22, range_fraction=1e-3)
    for i in range(len(WIDX)):
        facade = report.fleet[((i, 1.0), "klsm")]
        for s, direct_res in enumerate(results[i][0]):
            assert facade[s].io == direct_res.io
            assert facade[s].avg_io_per_query == direct_res.avg_io_per_query


def test_subprocess_backend_matches_inline():
    spec = _spec(
        workload=WorkloadSpec(indices=WIDX, rhos=(1.0,), nominal=False),
        trial=TrialSpec(n_keys=5000, n_queries=300, sessions=SESSIONS,
                        key_space=2 ** 22, per_workload_keys=True))
    inline = run_experiment(spec)
    sub = run_experiment(dataclasses.replace(
        spec, backend="subprocess", backend_params=(("workers", 2),)))
    assert set(sub.fleet) == set(inline.fleet)
    for key in inline.fleet:
        for a, b in zip(inline.fleet[key], sub.fleet[key]):
            assert a.io == b.io
    assert sub.walls["trial_workers"] == 2


_SHARDED_SCRIPT = """
import dataclasses, json, jax
from repro import obs
from repro.api import run_experiment
import test_api_spec as t
spec = t._spec()
with obs.scoped():
    sharded = run_experiment(dataclasses.replace(spec, backend="sharded"))
    events = [e["attrs"] for e in obs.events_snapshot()
              if e["name"] == "tune.sharded"]
inline = run_experiment(spec)
cells = [[repr(c), float(sharded.tuning(c).phi.T),
          float(inline.tuning(c).phi.T), sharded.tuning(c).cost,
          inline.tuning(c).cost] for c in inline.cells]
print(json.dumps({"devices": len(jax.devices()), "events": events,
                  "cells": cells}))
"""


def test_sharded_backend_spans_four_devices():
    """On a host with four devices the grid's problem axis really is split
    (inputs and outputs on all four, padding dropped), and every cell keeps
    the inline design with its cost equal to f32 rounding: each device's
    program is compiled for a quarter of the lanes."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(p for p in sys.path if p))
    out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(__file__))
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4
    assert [(e["problems"], e["padded"], e["returned"])
            for e in got["events"]] == [(2, 4, 2), (4, 4, 4)]
    for e in got["events"]:
        assert e["inputs"] == [[0, 1, 2, 3]] * 2
        assert e["outputs"] == [[0, 1, 2, 3]] * len(e["outputs"])
    for cell, t_sh, t_in, c_sh, c_in in got["cells"]:
        assert t_sh == t_in, cell
        assert c_sh == pytest.approx(c_in, rel=1e-5), cell


_WORKER_SCRIPT = """
import sys
from repro.api.backends import _worker_main
_worker_main()
assert "jax" not in sys.modules, "the worker imported jax"
"""


def test_subprocess_worker_never_imports_jax():
    """A fleet worker runs its shard without importing jax, so it cannot
    take or wait for the accelerator its parent holds."""
    import os
    import pickle
    import subprocess
    import sys
    from repro.api import compile_spec
    spec = _spec(
        workload=WorkloadSpec(indices=WIDX[:1], rhos=(1.0,), nominal=False),
        trial=TrialSpec(n_keys=2000, n_queries=200, sessions=SESSIONS,
                        key_space=2 ** 22))
    report = run_experiment(spec)
    plan = compile_spec(spec).build_trial(report)
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(p for p in sys.path if p))
    out = subprocess.run([sys.executable, "-c", _WORKER_SCRIPT],
                         input=pickle.dumps((plan, plan.trees, None)),
                         env=env, capture_output=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    results, _, _, _ = pickle.loads(out.stdout)
    assert [r.io for r in results[0]] == \
        [r.io for r in report.fleet[((0, 1.0), "klsm")]]


# ---------------------------------------------------------------------------
# Joint policy-arm selection + report surface
# ---------------------------------------------------------------------------

def test_policy_arm_selection_is_joint():
    """Write-heavy cells pick the lazy arm, read-heavy cells the leveled
    K-LSM arm, under the same spec — the discrete axis is optimized per
    cell, not globally."""
    spec = ExperimentSpec(
        name="arms",
        workload=WorkloadSpec(indices=(4, 11), rhos=(1.0,), nominal=False),
        design=DesignSpec(policies=("klsm", "lazy_leveling"), **SMALL))
    report = run_experiment(spec)
    assert report.chosen[(0, 1.0)] == "lazy_leveling"   # w4: write-heavy
    assert report.chosen[(1, 1.0)] == "klsm"            # w11: read-mixed
    for cell in report.cells:
        costs = report.arm_costs[cell]
        assert costs[report.chosen[cell]] == min(costs.values())


def test_single_arm_spec_chooses_primary():
    report = run_experiment(_spec())
    assert all(report.chosen[c] == "klsm" for c in report.cells)


def test_report_bench_payload_schema():
    """The report serializes in exactly the BENCH_<suite>.json shape the
    perf gate consumes."""
    spec = _spec(workload=WorkloadSpec(indices=(7,), rhos=(1.0,),
                                       nominal=True, bench_n=200))
    report = run_experiment(spec)
    from repro import obs
    with obs.scoped(enabled=False):
        payload = report.to_bench_payload()
    # the baseline shape — REPRO_OBS must not change untraced payloads
    assert set(payload) == {"suite", "wall_time_s", "error", "rows",
                            "checksum"}
    with obs.scoped(enabled=True, clock="ticks"):
        traced = report.to_bench_payload()
    # a live telemetry plane merges its metrics block (and re-checksums)
    assert set(traced) == {"suite", "wall_time_s", "error", "rows",
                           "metrics", "checksum"}
    assert payload["suite"] == "t"
    assert payload["error"] is None
    for row in payload["rows"]:
        assert set(row) == {"name", "us_per_call", "derived"}
    import json
    json.dumps(payload, allow_nan=False)     # strict-JSON clean
    from repro.faults import checksum_ok
    assert checksum_ok(payload)              # self-validating baseline
    # delta-throughput metric surface
    d = report.delta_tp_vs_nominal(0, 1.0)
    assert d.shape == (200,)
    assert np.isfinite(d).all()


def test_fixed_design_skips_tuning():
    spec = ExperimentSpec(
        name="fixed",
        workload=WorkloadSpec(workloads=((0.25, 0.25, 0.25, 0.25),),
                              rhos=(), nominal=True),
        design=DesignSpec(fixed=(6.0, 4.0, 1.0),
                          policies=("klsm", "lazy_leveling")),
        system=SYS_PAIRS)
    report = run_experiment(spec)
    assert report.walls["tuning_s"] == pytest.approx(0.0, abs=0.05)
    r = report.tuning((0, None), "klsm")
    assert float(r.phi.T) == 6.0
    assert r.solver == "fixed"
    # the lazy arm's effective profile differs -> different model cost
    mc = report.model_costs[(0, None)]
    assert not np.allclose(mc["klsm"], mc["lazy_leveling"])


# ---------------------------------------------------------------------------
# Batched arm scoring against a per-cell reference
# ---------------------------------------------------------------------------

ARM_POLICIES = ("klsm", "lazy_leveling")
ARM_PARAMS = (("lazy_leveling", (("fill", 0.3),)),)


@pytest.fixture(scope="module", params=["tuned", "fixed"])
def arm_case(request):
    """A two-policy spec (nominal + two radii, a benchmark set, and for the
    tuned design one design-space arm), lowered and solved inline."""
    from repro.api import compile_spec, get_backend
    if request.param == "fixed":
        design = DesignSpec(policies=ARM_POLICIES, policy_params=ARM_PARAMS,
                            fixed=(6.0, 4.0, 1.0))
    else:
        design = DesignSpec(policies=ARM_POLICIES, policy_params=ARM_PARAMS,
                            spaces=(("lazy_leveling", 4),), **SMALL)
    spec = _spec(workload=WorkloadSpec(indices=WIDX, rhos=RHOS, nominal=True,
                                       bench_n=50),
                 design=design)
    cx = compile_spec(spec)
    backend = get_backend("inline")
    solved = {key: backend.solve(plan)
              for key, plan in cx.tuning_plans().items()}
    return cx, solved


def _per_cell_scorer(sys, policy, params):
    import jax
    from repro.core import cost_vector, policy_effective_phi
    from repro.core.robust import robust_cost

    def score(phi, w, rho):
        c = cost_vector(policy_effective_phi(phi, sys, policy, params), sys)
        return c, robust_cost(c, w, rho)

    return jax.jit(score)


def test_batched_arm_selection_matches_per_cell(arm_case):
    """One scorer call per policy gives what one call per cell gives."""
    cx, solved = arm_case
    d = cx.spec.design
    report = cx.select_arms(solved)
    scorers = {p: _per_cell_scorer(cx.sys, p, d.params_for(p))
               for p in d.policies}
    fixed = cx._fixed_phi() if d.fixed is not None else None
    B = np.asarray(cx.bench, np.float64)

    def ref(pol, r, cell):
        i, rho = cell
        c, cost = scorers[pol](r.phi, np.asarray(cx.W[i], np.float32),
                               np.float32(rho or 0.0))
        return np.asarray(c, np.float64), float(cost)

    for cell in cx.cells:
        want = {}
        for pol in d.policies:
            r = report.tunings[cell][pol]
            if fixed is None:
                assert r is solved[(cx.arm_design[pol], d.n_starts)][cell]
            else:
                assert r.solver == "fixed" and float(r.phi.T) == 6.0
            want[pol] = ref(pol, r, cell)
            got = report.arm_costs[cell][pol]
            assert type(got) is float
            np.testing.assert_allclose(got, want[pol][1], rtol=1e-5)
            model = report.model_costs[cell][pol]
            assert model.dtype == np.float64 and model.shape == (4,)
            np.testing.assert_allclose(model, want[pol][0], rtol=1e-5)
        a, b = (want[p][1] for p in d.policies)
        if abs(a - b) > 1e-5 * max(abs(a), abs(b)):
            assert report.chosen[cell] == d.policies[int(b < a)]
        np.testing.assert_allclose(report.bench_costs[cell],
                                   B @ want[report.chosen[cell]][0],
                                   rtol=1e-5)
    assert set(report.design_bench_costs) == {n for n, _ in cx.space_arms}
    for name, key in cx.space_arms:
        for cell in cx.cells:
            r = report.design_tunings[name][cell]
            assert r is solved[key][cell]
            np.testing.assert_allclose(report.design_bench_costs[name][cell],
                                       B @ ref(d.policies[0], r, cell)[0],
                                       rtol=1e-5)


def test_arm_selection_scores_each_arm_in_one_call(arm_case):
    """One ``tune.score`` span per policy and per space arm, each over
    every cell."""
    from repro import obs
    cx, solved = arm_case
    d = cx.spec.design
    with obs.scoped(enabled=True, clock="ticks"):
        cx.select_arms(solved)
        spans = [e for e in obs.events_snapshot()
                 if e["kind"] == "span" and e["name"] == "tune.score"]
    assert [s["attrs"]["policy"] for s in spans] == \
        list(d.policies) + [d.policies[0]] * len(cx.space_arms)
    assert all(s["attrs"]["cells"] == len(cx.cells) for s in spans)
