"""The unified experiment API: declarative specs over the whole stack.

One call::

    from repro.api import ExperimentSpec, WorkloadSpec, run_experiment

    spec = ExperimentSpec(
        name="demo",
        workload=WorkloadSpec(indices=(7, 11), rhos=(1.0,), bench_n=2000),
    )
    report = run_experiment(spec)

lowers the spec (:mod:`repro.api.compile`) onto the batched tuners and the
fleet executor, runs it on the spec's execution backend
(:mod:`repro.api.backends`), and returns one :class:`repro.api.Report`
(:mod:`repro.api.report`) — serializable in the ``BENCH_<suite>.json``
schema the perf gate consumes.  Specs round-trip through JSON, so
``benchmarks/run.py --spec FILE.json`` runs any experiment with no new
bench script.
"""

from __future__ import annotations

import time

from .backends import (BACKENDS, ExecutionBackend, InlineBackend,
                       RemoteBackend, ShardedBackend, SubprocessBackend,
                       deploy_tree, execute_trial, get_backend)
from .compile import (CompiledExperiment, DriftPlan, MemoryPlan, TrialPlan,
                      TuningPlan, compile_spec, drift_schedule)
from .report import (Report, Row, TreeProbe, costs_over_benchmark, delta_tp,
                     fmt, jsonable, timed)
from .spec import (DesignSpec, DriftSpec, ExperimentSpec, MemorySpec,
                   TrialSpec, WorkloadSpec)
from repro import obs
from repro.faults import FaultPlan, FaultSpec

__all__ = [
    "ExperimentSpec", "WorkloadSpec", "DesignSpec", "TrialSpec", "DriftSpec",
    "MemorySpec",
    "FaultSpec", "FaultPlan",
    "Report", "Row", "TreeProbe", "run_experiment",
    "compile_spec", "CompiledExperiment", "TuningPlan", "TrialPlan",
    "DriftPlan", "MemoryPlan", "drift_schedule",
    "BACKENDS", "ExecutionBackend", "InlineBackend", "ShardedBackend",
    "SubprocessBackend", "RemoteBackend", "get_backend", "deploy_tree",
    "execute_trial",
    "costs_over_benchmark", "delta_tp", "timed", "fmt", "jsonable",
]


def run_experiment(spec: ExperimentSpec, backend=None) -> Report:
    """Compile and execute an :class:`ExperimentSpec`; returns its
    :class:`Report`.

    ``backend`` overrides the spec's backend instance (e.g. a
    pre-configured :class:`SubprocessBackend`); by default the spec's
    ``backend`` / ``backend_params`` fields select it.  ``spec.faults``
    compiles into a :class:`repro.faults.FaultPlan` handed to the trial
    executor — the deterministic chaos schedule the backend must recover
    from (bit-identically to :class:`InlineBackend`; see
    ``docs/faults.md``)."""
    from repro.faults import FaultPlan
    cx = compile_spec(spec)
    if backend is None:
        backend = get_backend(spec.backend, spec.backend_params)
    faults = FaultPlan.from_specs(spec.faults) if spec.faults else None

    t0 = time.perf_counter()
    solved = {design: backend.solve(plan)
              for design, plan in cx.tuning_plans().items()}
    tuning_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    with obs.span("tune.select", cells=len(cx.cells)):
        report = cx.select_arms(solved)
    report.walls["tuning_s"] = tuning_s
    report.walls["select_s"] = time.perf_counter() - t0

    trial = cx.build_trial(report)
    if trial is not None:
        backend.run_trial(trial, report, faults=faults)
    memory = cx.build_memory(report)
    if memory is not None:
        # the memory axis REPLACES drift-arm execution: the drift spec is
        # consumed as the schedule/loop configuration of the paired
        # static/arbitrated fleet comparison (docs/memory.md)
        backend.run_memory(memory, report)
    else:
        drift = cx.build_drift(report)
        if drift is not None:
            backend.run_drift(drift, report)
    return report
