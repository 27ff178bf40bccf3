# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure (+ roofline).

Usage:
    PYTHONPATH=src python -m benchmarks.run                  # all
    PYTHONPATH=src python -m benchmarks.run fig6 tab5        # substring filter
    PYTHONPATH=src python -m benchmarks.run --json out/      # + BENCH_*.json
    PYTHONPATH=src python -m benchmarks.run --check tuner tab5   # perf gate
    PYTHONPATH=src python -m benchmarks.run --spec exp.json  # run any spec

``--json OUT`` writes one ``BENCH_<suite>.json`` per executed suite into the
OUT directory: per-suite wall time plus every row's derived metrics, so later
PRs have a machine-readable perf trajectory to compare against.

``--spec FILE.json`` runs an arbitrary :class:`repro.api.ExperimentSpec`
(the declarative experiment facade) end-to-end and emits its report in the
same CSV/BENCH-json formats — new scenarios need a JSON file, not a new
bench script.  The spec's ``name`` becomes the suite name.

``--spec`` composes with the crash-safe sweep substrate: ``--run-dir DIR``
hands the subprocess backend a directory to persist per-shard results into
(atomic, checksummed), and ``--resume`` re-runs a killed sweep executing
only the shards that never completed (``docs/faults.md``).

``--check`` re-runs the selected suites and diffs the measured perf
trajectory against the committed ``BENCH_<suite>.json`` baselines
(``--baseline DIR``, default the repo root): per-suite wall time plus the
curated directional metrics in ``CHECK_METRICS`` must stay within
``--tolerance`` (default 1.5x slack for machine noise) of the baseline.
Exit codes are distinct so CI can tell the failure modes apart: 1 for a
perf regression (or a crashed suite), 2 for a *misconfigured* gate — a
checked suite with no committed baseline (a new suite must commit its
``BENCH_<suite>.json`` before the gate can watch it), a committed baseline
that fails checksum validation (torn, tampered, or hand-edited — a corrupt
reference must read as "fix the baseline", never as a phantom regression),
one that parses as JSON but lacks the suite's ``CHECK_METRICS`` rows/keys
(e.g. stale, or committed before a metric was added), or a filter that
selects no suite at all (a typo would otherwise pass vacuously).

``--list`` prints the suite names one per line, each with the one-line
description from its bench module's docstring (parsed via ``ast`` — no
jax import), and exits; ``--list --gated`` prints only the suites the
perf gate watches (the ``CHECK_METRICS`` keys) as *bare* names, so CI
derives its gate list from here instead of hardcoding it.
"""

import argparse
import json
import os
import time
import traceback

# suite -> {"row_name.metric": "lower"|"higher"} perf metrics the --check
# gate enforces in addition to every suite's wall_time_s ("lower").
CHECK_METRICS = {
    "tuner": {
        "perf_tuner_fig6_grid.batched_s": "lower",
        "perf_tuner_throughput.tunings_per_sec": "higher",
    },
    "tab5": {
        "tab5_fleet.engine_s": "lower",
    },
    "compaction": {
        "compaction_fleet.engine_s": "lower",
    },
    "api": {
        "api_fleet.engine_s": "lower",
    },
    "online": {
        "online_fleet.engine_s": "lower",
        "online_summary.online_recovery_min": "higher",
        # bool (int subclass): flipping to False reads as 0 < 1/tol
        "online_summary.claim_online_ge_robust_ge_stale": "higher",
    },
    "faults": {
        # bool: recovered-under-chaos results bit-identical to inline
        "faults_recovery.identical_to_inline": "higher",
        # supervised no-fault path vs raw path: must stay near 1.0
        "faults_overhead.overhead_ratio": "lower",
    },
    "kernels": {
        # fused data plane must stay faster than its jnp references
        "kernels_point_read.speedup_fused_vs_ref": "higher",
        "kernels_dual_solve.speedup_fused_vs_ref": "higher",
    },
    "roofline": {
        # the roofline table must keep measuring real kernel cells —
        # an all-empty run raises, and a shrinking cell count gates
        "roofline_kernels.measured_cells": "higher",
    },
    "memory": {
        "memory_fleet.engine_s": "lower",
        # arbitrated fleet throughput over the static equal split
        "memory_summary.fleet_speedup_min": "higher",
        # bools: arbitration never loses; disabled stays bit-identical
        "memory_summary.claim_arbitrated_ge_static": "higher",
        "memory_summary.claim_disabled_identical": "higher",
    },
    "scenarios": {
        "scenarios_fleet.engine_s": "lower",
        # bools: the robust hedge survives every named stress pattern,
        # and every adversary window's realized model cost stays under
        # the independently-solved KL dual bound (Eq. 13, measured live)
        "scenarios_summary.claim_robust_ge_stale": "higher",
        "scenarios_summary.claim_regret_le_dual_bound": "higher",
    },
    "obs": {
        "obs_fleet.engine_s": "lower",
        # enabled-vs-disabled telemetry tax on the same fleet (<= 1.05
        # gated in the suite itself; the baseline watches for creep)
        "obs_overhead.overhead_ratio": "lower",
        # bools: tracing never perturbs engine results; the measured-IO
        # calibration fit is at least as close as the hand constants
        "obs_identity.claim_bit_identical": "higher",
        "obs_calibration.claim_fit_ge_hand": "higher",
    },
}

#: --check exit codes: regression vs misconfiguration (missing baseline /
#: filters matching nothing) — CI treats both as failures but reports them
#: differently.
EXIT_REGRESSION = 1
EXIT_MISCONFIGURED = 2

#: suite key -> module name, kept static so ``--list`` (and filter
#: validation) need no jax import; modules are imported only when run.
SUITE_MODULES = [
    ("fig4", "bench_nominal_designs"),
    ("fig6", "bench_robust_vs_nominal"),
    ("fig7_8", "bench_rho_impact"),
    ("fig9", "bench_rho_choice"),
    ("fig10", "bench_entry_size"),
    ("tab5", "bench_system_eval"),
    ("fig19", "bench_flexible_robustness"),
    ("tuner", "bench_tuner_perf"),
    ("kernels", "bench_kernels"),
    ("roofline", "bench_roofline"),
    ("robust_sharding", "bench_robust_sharding"),
    ("compaction", "bench_compaction_space"),
    ("api", "bench_api"),
    ("online", "bench_online_drift"),
    ("faults", "bench_faults"),
    ("memory", "bench_memory_fleet"),
    ("scenarios", "bench_scenarios"),
    ("obs", "bench_obs"),
]


def _suite_description(module_name: str) -> str:
    """First docstring line of a bench module, parsed via ``ast`` so
    ``--list`` stays jax-import-free (module import pulls in the stack)."""
    import ast
    path = os.path.join(os.path.dirname(__file__), module_name + ".py")
    try:
        with open(path, encoding="utf-8") as f:
            doc = ast.get_docstring(ast.parse(f.read()))
    except (OSError, SyntaxError):
        doc = None
    return doc.strip().splitlines()[0] if doc else ""


def _load_baselines(suites, baseline_dir):
    """Snapshot every baseline BEFORE any suite runs (or --json rewrites
    them): with OUT == baseline dir the gate would otherwise compare each
    fresh BENCH_<suite>.json against itself and pass vacuously.

    Returns ``(baselines, invalid)``: baselines that exist but are torn
    (unparseable JSON), unchecksummed, or checksum-invalid land in
    ``invalid`` — the caller exits EXIT_MISCONFIGURED for those, because
    diffing against a corrupt reference would report phantom regressions
    (or worse, vacuously pass)."""
    from repro.faults import CHECKSUM_KEY, checksum_ok
    out, invalid = {}, []
    for key, _ in suites:
        path = os.path.join(baseline_dir, f"BENCH_{key}.json")
        if not os.path.exists(path):
            continue
        try:
            with open(path) as f:
                base = json.load(f)
        except (json.JSONDecodeError, UnicodeDecodeError) as exc:
            invalid.append(f"BENCH_{key}.json: unparseable "
                           f"(torn write? {exc})")
            continue
        if not isinstance(base, dict) or CHECKSUM_KEY not in base:
            invalid.append(f"BENCH_{key}.json: no '{CHECKSUM_KEY}' field "
                           "(regenerate with --json and commit)")
            continue
        if not checksum_ok(base):
            invalid.append(f"BENCH_{key}.json: checksum mismatch "
                           "(corrupt, truncated, or hand-edited baseline)")
            continue
        out[key] = base
    return out, invalid


def _check_suite(key, rows, wall, base, tol):
    """Compare one executed suite against its committed baseline.

    Returns ``(regressions, misconfigured)`` — two lists of human-readable
    strings (both empty = pass).  A *misconfigured* gate (a committed
    baseline that parses as JSON but is not the BENCH schema, or is missing
    the CHECK_METRICS rows/keys for its suite — e.g. a stale baseline
    committed before a metric was added) is reported separately so the
    caller exits EXIT_MISCONFIGURED instead of crashing or reporting a
    phantom regression; a metric missing from the *run* is a real
    regression (the suite stopped producing it)."""
    regressions = []
    misconfigured = []
    if not isinstance(base, dict):
        return [], [f"BENCH_{key}.json: baseline is "
                    f"{type(base).__name__}, not a BENCH schema object"]

    def compare(label, measured, reference, direction, slack=1.0):
        if not isinstance(measured, (int, float)) or \
                not isinstance(reference, (int, float)) or reference <= 0:
            return
        ratio = measured / reference
        t = tol * slack
        bad = ratio > t if direction == "lower" else ratio < 1.0 / t
        status = "REGRESSION" if bad else "ok"
        print(f"# check {label}: {measured:.4g} vs baseline "
              f"{reference:.4g} ({direction} is better) [{status}]")
        if bad:
            regressions.append(f"{label}: {measured:.4g} vs {reference:.4g}")

    # wall time gates at double slack: absolute seconds vary with the host
    # (laptop vs CI runner, cold jit caches); the curated relative metrics
    # below are the primary signal
    compare(f"{key}.wall_time_s", wall, base.get("wall_time_s"), "lower",
            slack=2.0)
    derived_by_row = {r.name: r.derived for r in rows}
    base_rows = base.get("rows")
    if not isinstance(base_rows, list):
        base_rows = []
        misconfigured.append(f"BENCH_{key}.json: no 'rows' list")
    base_by_row = {r["name"]: r.get("derived") or {}
                   for r in base_rows
                   if isinstance(r, dict) and "name" in r}
    for spec, direction in CHECK_METRICS.get(key, {}).items():
        row_name, metric = spec.rsplit(".", 1)
        measured = derived_by_row.get(row_name, {}).get(metric)
        reference = base_by_row.get(row_name, {}).get(metric)
        if reference is None:
            misconfigured.append(
                f"{spec}: missing from BENCH_{key}.json (regenerate the "
                "baseline with --json and commit it)")
            continue
        if measured is None:
            regressions.append(f"{spec}: missing (run)")
            continue
        compare(spec, float(measured), float(reference), direction)
    return regressions, misconfigured


def _jsonable(x):
    """Strict-JSON coercion; one implementation, in the report module."""
    from repro.api.report import jsonable
    return jsonable(x)


def _run_spec(args) -> None:
    """``--spec FILE.json``: run one declarative experiment end-to-end.

    ``--run-dir`` / ``--resume`` override the subprocess backend's
    persistence knobs (CLI wins over ``backend_params`` so one committed
    spec file serves both fresh runs and resumes)."""
    from repro.api import ExperimentSpec, get_backend, run_experiment
    with open(args.spec) as f:
        spec = ExperimentSpec.from_json(f.read())
    backend = None
    if args.run_dir or args.resume:
        params = dict(spec.backend_params)
        params["run_dir"] = args.run_dir
        params["resume"] = args.resume
        backend = get_backend(spec.backend, tuple(params.items()))
    print(f"# spec {args.spec!r} -> experiment {spec.name!r} "
          f"(backend={spec.backend}"
          + (f", run_dir={args.run_dir!r}" if args.run_dir else "")
          + (", resume" if args.resume else "") + ")", flush=True)
    print("name,us_per_call,derived")
    report = run_experiment(spec, backend=backend)
    rows = report.rows()
    for row in rows:
        print(row.csv(), flush=True)
    recovery = {k: int(v) for k, v in report.walls.items()
                if k in ("resumed_trees", "shards_run", "shard_retries",
                         "reshard_trees", "failed_trees")}
    if recovery:
        print("# recovery: " + " ".join(f"{k}={v}"
                                        for k, v in sorted(recovery.items())),
              flush=True)
    for (cell, pol), err in sorted(report.failed_cells.items(),
                                   key=lambda kv: str(kv[0])):
        print(f"# WARNING unrecovered cell {cell} arm {pol!r}: "
              + (err.splitlines()[-1][:200] if err else "?"), flush=True)
    print(f"# {spec.name} done in {report.wall_time_s:.1f}s", flush=True)
    if args.trace:
        from repro import obs
        from repro.faults import atomic_write_json
        from repro.obs.trace import write_trace
        n = write_trace(os.path.join(args.trace,
                                     f"trace_{spec.name}.json"))
        atomic_write_json(os.path.join(args.trace,
                                       f"metrics_{spec.name}.json"),
                          _jsonable(obs.metrics_snapshot()))
        print(f"# trace {spec.name}: {n} events -> "
              f"{args.trace}/trace_{spec.name}.json", flush=True)
    if args.json:
        os.makedirs(args.json, exist_ok=True)
        path = os.path.join(args.json, f"BENCH_{spec.name}.json")
        report.write_bench_json(path, rows)
        print(f"# wrote {path}", flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("filters", nargs="*",
                        help="substring filters on suite names")
    parser.add_argument("--json", metavar="OUT", default=None,
                        help="directory to write per-suite BENCH_<suite>.json")
    parser.add_argument("--check", action="store_true",
                        help="diff measured perf against committed baselines; "
                             "exit 1 on regression, 2 on a missing baseline "
                             "or a filter matching no suite")
    parser.add_argument("--list", action="store_true",
                        help="print the available suite names and exit")
    parser.add_argument("--gated", action="store_true",
                        help="with --list: print only the perf-gated suites "
                             "(CHECK_METRICS keys)")
    parser.add_argument("--spec", metavar="FILE.json", default=None,
                        help="run one declarative repro.api.ExperimentSpec "
                             "and emit its report (honors --json)")
    parser.add_argument("--trace", metavar="DIR", default=None,
                        help="enable structured telemetry (repro.obs) and "
                             "write per-suite trace_<suite>.json (Chrome/"
                             "Perfetto) + metrics_<suite>.json into DIR; "
                             "off by default and guaranteed not to change "
                             "any measured result")
    parser.add_argument("--run-dir", metavar="DIR", default=None,
                        help="with --spec: persist per-shard results into "
                             "DIR (atomic, checksummed) as they complete")
    parser.add_argument("--resume", action="store_true",
                        help="with --spec --run-dir: reuse valid persisted "
                             "shard results, execute only the remainder")
    parser.add_argument("--baseline", metavar="DIR",
                        default=os.path.join(os.path.dirname(__file__), ".."),
                        help="baseline directory for --check "
                             "(default: repo root)")
    parser.add_argument("--tolerance", type=float, default=1.5,
                        help="--check slack factor on every metric "
                             "(default 1.5x)")
    args = parser.parse_args()

    if args.list:
        if args.gated:
            # bare names, one per line: CI job matrices parse this output,
            # so it must stay byte-stable as suites gain descriptions
            for key, _ in SUITE_MODULES:
                if key in CHECK_METRICS:
                    print(key)
            return
        width = max(len(key) for key, _ in SUITE_MODULES)
        for key, name in SUITE_MODULES:
            print(f"{key:<{width}}  {_suite_description(name)}".rstrip())
        print()
        print("# --trace DIR: any suite above also emits trace_<suite>.json"
              " (open in Perfetto / chrome://tracing) and"
              " metrics_<suite>.json; see docs/observability.md")
        return
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.trace:
        # One switch flips the whole stack: the instrumented seams all go
        # through the repro.obs process-global, and bench modules that
        # emit artifacts (bench_obs's calibration) look for REPRO_OBS_OUT.
        os.makedirs(args.trace, exist_ok=True)
        os.environ["REPRO_OBS_OUT"] = args.trace
        from repro import obs
        obs.configure(enabled=True, clock="wall")
    if args.resume and not args.run_dir:
        parser.error("--resume requires --run-dir (the directory holding "
                     "the persisted shard results)")
    if (args.run_dir or args.resume) and not args.spec:
        parser.error("--run-dir/--resume only apply to --spec runs")
    if args.spec:
        if args.check:
            parser.error("--spec and --check are mutually exclusive: the "
                         "gate runs registered suites against committed "
                         "baselines; to gate a spec-driven experiment, add "
                         "it as a suite with a CHECK_METRICS entry")
        _run_spec(args)
        return
    selected_names = [(key, name) for key, name in SUITE_MODULES
                      if not args.filters or any(f in key for f in
                                                 args.filters)]
    if not selected_names:
        print(f"error: filters {args.filters} match no suite; "
              "run --list to see suite names")
        raise SystemExit(EXIT_MISCONFIGURED)
    import importlib
    # `python -m benchmarks.run` imports siblings relatively; a direct
    # `python benchmarks/run.py` has no package, but the script's own
    # directory leads sys.path, so the absolute name resolves there.
    selected = [(key, importlib.import_module(f".{name}", __package__)
                 if __package__ else importlib.import_module(name))
                for key, name in selected_names]
    if args.json:
        os.makedirs(args.json, exist_ok=True)
    baselines, invalid_baselines = \
        _load_baselines(selected, args.baseline) if args.check else ({}, [])
    if invalid_baselines:
        # fail fast: running the suites first would waste minutes before
        # telling the user their reference files need regenerating
        print("error: invalid perf-gate baselines:\n  "
              + "\n  ".join(invalid_baselines))
        raise SystemExit(EXIT_MISCONFIGURED)
    print("name,us_per_call,derived")
    failures = 0
    all_regressions = []
    all_misconfigured = []
    missing_baselines = []
    for key, mod in selected:
        if args.trace:
            from repro import obs
            obs.clear()  # per-suite trace files, not one giant ring
        t0 = time.time()
        rows, error = [], None
        try:
            for row in mod.run():
                rows.append(row)
                print(row.csv(), flush=True)
        except Exception as exc:
            failures += 1
            error = f"{type(exc).__name__}: {exc}"
            print(f"{key},nan,ERROR", flush=True)
            traceback.print_exc()
        wall = time.time() - t0
        print(f"# {key} done in {wall:.1f}s", flush=True)
        if args.trace:
            from repro import obs
            from repro.faults import atomic_write_json
            from repro.obs.trace import write_trace
            n = write_trace(os.path.join(args.trace, f"trace_{key}.json"))
            atomic_write_json(os.path.join(args.trace,
                                           f"metrics_{key}.json"),
                              _jsonable(obs.metrics_snapshot()))
            print(f"# trace {key}: {n} events -> "
                  f"{args.trace}/trace_{key}.json", flush=True)
        if args.json:
            from repro.faults import atomic_write_json
            payload = {
                "suite": key,
                "wall_time_s": round(wall, 3),
                "error": error,
                "rows": [{"name": r.name,
                          "us_per_call": _jsonable(round(float(r.us), 1)),
                          "derived": _jsonable(r.derived)} for r in rows],
            }
            path = os.path.join(args.json, f"BENCH_{key}.json")
            atomic_write_json(path, payload)  # stamps the checksum field
            print(f"# wrote {path}", flush=True)
        if args.check and error is None:
            base = baselines.get(key)
            if base is None:
                missing_baselines.append(key)
            else:
                regs, miscfg = _check_suite(key, rows, wall, base,
                                            args.tolerance)
                all_regressions += regs
                all_misconfigured += miscfg
    if failures:
        raise SystemExit(f"{failures} benchmark suites failed")
    if args.check:
        if missing_baselines:
            print("error: no committed baseline for: "
                  + ", ".join(f"BENCH_{k}.json" for k in missing_baselines)
                  + " (generate with --json and commit before gating)")
            raise SystemExit(EXIT_MISCONFIGURED)
        if all_misconfigured:
            print("error: misconfigured perf gate:\n  "
                  + "\n  ".join(all_misconfigured))
            raise SystemExit(EXIT_MISCONFIGURED)
        if all_regressions:
            raise SystemExit("perf regressions vs committed baselines:\n  "
                             + "\n  ".join(all_regressions))
        print("# --check passed: no perf regressions", flush=True)


if __name__ == "__main__":
    main()
