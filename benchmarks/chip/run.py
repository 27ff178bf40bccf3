#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, on the machine it starts on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are data:
``BENCHMARK.json`` at the root of the checkout names them, and this
directory holds one file for each (``configs/<config>.json``,
``traffic/<traffic>.json``, ``metrics/<metric>.py``).

A run sets up the cell (the system under test is built and every shape the
window uses is compiled and run once: that is ``setup_s``), then drives
requests for ``--seconds`` seconds, counts the compilations inside the
window (there must be none), checks what the window produced against the
plain reference, and prints one JSON line last on standard output: the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics (from
a profiler trace of the window) with ``--trace 1``.  The numbers compared
for ``correct`` end standard error and the result line.

It runs only on a TPU: with no TPU, or fewer chips than the cell asks for,
it exits 2 and prints no result.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: where a traced run writes its profile (inside the checkout, emptied first)
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")

#: the benchmark's own profiler spans
SPANS = {"window": "bench.window", "setup": "bench.setup",
         "request": "bench.request"}

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell ``name`` of ``BENCHMARK.json``, with its configuration,
    traffic and metric entries resolved."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m["workloads"] or ("workloads" not in m
                                           and m["moves"] in names)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer}


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(ctx)``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Context:
    """What a metric reader sees of one run."""

    def __init__(self, spec: dict, setup_s: float, records: list,
                 counters: dict, trace=None):
        self.cell = spec["cell"]
        self.config = spec["config"]
        self.traffic = spec["traffic"]
        self.setup_s = setup_s
        #: (start, end, units, ok) per request, host clock seconds
        self.records = records
        self.counters = counters
        #: ``chipbench.xplane.Reduction`` of the traced window, or None
        self.trace = trace

    @property
    def requests(self) -> int:
        return len(self.records)


def require_chips(chips: int):
    """The TPU devices, or :class:`NoChip`."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's default device is {devices[0].platform}:"
                     f"{devices[0].device_kind}, not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices


def run_window(gen, seconds: float, annotate) -> list:
    """Drive requests for ``seconds``: one client in a closed loop issues
    whole requests, each after the last completed, until ``seconds`` have
    passed.  Returns one record per request: ``(start, end, units, ok)``."""
    records = []
    t0 = time.perf_counter()
    i = 0
    while not i or time.perf_counter() - t0 < seconds:
        start = time.perf_counter()
        try:
            with annotate("request"):
                units = gen.request(i)
            ok = True
        except Exception:                      # counted, the run goes on
            say(f"request {i} failed:\n{traceback.format_exc()}")
            units, ok = 0, False
        records.append((start, time.perf_counter(), units, ok))
        i += 1
    return records


def run(spec: dict, seed: int, seconds: float, trace: bool,
        need_chip: bool = True) -> dict:
    """One run of a cell (as :func:`load_cell` gives it); returns the
    result line as a dict.  ``need_chip=False`` runs on whatever device
    JAX has (the tests' way to drive a run on the CPU)."""
    name = spec["cell"]["name"]
    for path in (HERE, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import jax
    if need_chip:
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        devices = require_chips(int(spec["cell"]["chips"]))
    else:
        devices = jax.devices()
    from chipbench import generator, xplane

    compiles = [0]

    def on_event(event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    gen = generator.KINDS[spec["traffic"]["generator"]](
        spec["config"], spec["traffic"], seed)

    def annotate(label: str):
        return jax.profiler.TraceAnnotation(SPANS[label]) if trace \
            else contextlib.nullcontext()

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
    with contextlib.ExitStack() as window:
        def open_window():
            if trace:
                window.enter_context(jax.profiler.trace(
                    TRACE_DIR, profiler_options=opts))
            window.enter_context(annotate("window"))

        # a window that runs nothing on the device is traced from set-up
        if gen.trace_setup:
            open_window()
        with annotate("setup"):
            gen.setup()
        setup_s = time.perf_counter() - PROCESS_START
        if not gen.trace_setup:
            open_window()
        before = compiles[0]
        records = run_window(gen, seconds, annotate)
        in_window = compiles[0] - before
    say(f"{name}: set-up {setup_s:.3f} s, {len(records)} requests in "
        f"{records[-1][1] - records[0][0]:.3f} s; compilations inside the "
        f"window: {in_window}")
    if in_window:
        raise RuntimeError(f"{in_window} compilations inside the window")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    reduction = None
    if trace:
        reduction = xplane.reduce(
            xplane.find(TRACE_DIR), (SPANS["setup"], SPANS["request"]),
            window=SPANS["window"])
        say(f"trace: window {reduction.window_s:.3f} s, busy "
            f"{reduction.busy_s:.3f} s; programs {reduction.modules}")
    ctx = Context(spec, setup_s, records, gen.counters(), reduction)
    checks = gen.checks()
    metrics = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": all(v <= lim for _, v, lim in checks),
           "attempted": len(records),
           "failed": sum(1 for r in records if not r[3]),
           "metrics": metrics, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        out["breakdown"] = {
            "device_ops": [[k, s] for k, (_, s) in sorted(
                reduction.modules.items(), key=lambda kv: -kv[1][1])[:10]],
            "idle_gaps": [[k, s] for k, s in reduction.idle_gaps]}
    for c, v, lim in checks:
        say(f"check {c}: {v!r} (limit {lim!r})")
    out["checks"] = {c: {"value": v, "limit": lim} for c, v, lim in checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(load_cell(args.workload), args.seed, args.seconds,
                  bool(args.trace))
    except NoChip as e:
        say(f"run.py: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
