#!/usr/bin/env python3
"""Run ENDURE's tune -> deploy -> serve path once on a TPU and check it.

    python chip_smoke.py              # one chip: phases 1-5
    python chip_smoke.py --chips 4    # four chips: the sharded grid only

Phases, in one process; the first failure ends the run with a non-zero
exit and no result line:

1. device  -- the default JAX device must be a TPU.  Nothing falls back
   to the CPU.
2. tune    -- the Figure 6 grid (``bench_robust_vs_nominal.SPEC``: 15
   expected workloads x 5 rhos plus nominal, paper-default
   ``LSMSystem``) through ``run_experiment`` on the TPU, then the same
   grid on the host CPU of this process.  Both devices must score the
   same tunings alike (``EVAL_RTOL``), and each device's tuned costs
   must agree within the search's own seed-to-seed spread
   (``SEARCH_RTOL`` per cell, ``SEARCH_MEAN_RTOL`` on average).
3. serve   -- the Table 5 spec (``bench_system_eval``: 5 workloads,
   nominal + robust at rho = 1, four drifted sessions) deployed at
   ``N_KEYS`` keys with ``QUERIES`` operations per session.  Every served
   tree is replayed: each session's ``IOStats`` must equal the served
   one, and a sample of keys checked against a dict model must read back
   its newest value (and read absent once deleted).  At ``LEGACY_KEYS``
   keys, every tuning's ``IOStats`` must equal the frozen engine's
   (``tests/_legacy_engine.py``).
4. reads   -- one session's point reads in ``jnp`` read mode (XLA
   programs on the TPU) must equal ``numpy`` mode, results and
   ``IOStats``.
5. workers -- with this process holding the TPU, a small trial on the
   ``subprocess`` backend must give the ``inline`` backend's ``IOStats``
   (the workers are numpy-only and never touch the chip).

With ``--chips 4`` only the Figure 6 grid runs: on the ``sharded``
backend over the four chips, and ``inline`` on one of them; the results
must be equal and the sharded inputs and outputs must span four devices.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Data comes from ``--seed``; the compile cache goes where
``repro.compile_cache.enable_compile_cache`` puts it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
for sub in ("src", "benchmarks", "tests"):
    sys.path.insert(0, os.path.join(ROOT, sub))

import numpy as np  # noqa: E402

# Engine scale of the serve phase (Table 5 runs 250k keys x 10k queries on
# the CPU suite); the tuner's N is raised with it.
N_KEYS = 10_000_000
QUERIES = 100_000
READBACK_SAMPLE = 20_000      # keys per tree (and per session) in the model
LEGACY_KEYS = 20_000          # what the per-query frozen engine takes
LEGACY_QUERIES = 2_000
WORKER_KEYS = 100_000         # the subprocess-vs-inline trial
WORKER_QUERIES = 10_000

# The tune phase checks the device's arithmetic and its search apart.
#
# Arithmetic: the exact objective (cost model + cold-grid KL dual) of one
# integral tuning is a pure f32 function of about a hundred operations.
# TPU and CPU differ only in the last bits of exp/log and in reduction
# order (a few ulp each, 2^-23 = 1.2e-7), and the golden-section value
# error is second order in the bracket, so both devices score the same
# tuning alike to EVAL_RTOL.  A dot rounded to bf16 (2^-9) would not pass.
EVAL_RTOL = 1e-4
# Search: the tuner rounds a continuous optimum to integers (ceil T,
# round K), so ulp-level noise in 250 Adam steps can land a cell on a
# neighbouring integral design, as another seed does.  On the CPU alone,
# seeds 1-23 against seed 0 move single Figure 6 cells by up to 8.0% and
# the mean over the 90 cells by at most 1.7e-3 (|rel|).  The TPU's run is
# one more such draw: its cells must agree within SEARCH_RTOL and their
# mean within SEARCH_MEAN_RTOL.
SEARCH_RTOL = 0.1
SEARCH_MEAN_RTOL = 5e-3

# The sharded grid is the same computation, but compiled for a quarter of
# the lanes per device, so its reductions may round differently: designs
# must match exactly and costs to a few f32 ulps (2^-23 = 1.2e-7 each).
SHARD_RTOL = 1e-5

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[0] += duration


class Timer:
    """Wall seconds of a phase, with the compile seconds inside it apart."""

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = _compile_s[0]
        return self

    def __exit__(self, *exc):
        self.compile_s = _compile_s[0] - self.c0
        self.run_s = time.perf_counter() - self.t0 - self.compile_s

    def __str__(self) -> str:
        return f"compile {self.compile_s:.2f} s, run {self.run_s:.2f} s"


def say(*parts) -> None:
    print(*parts, flush=True)


# ---------------------------------------------------------------------------
# phase 2: tune on the chip, check against the host CPU
# ---------------------------------------------------------------------------

def _discrete(t):
    return (t.design, float(t.phi.T), tuple(np.asarray(t.phi.K).tolist()))


def score_tunings(spec, report):
    """Exact objective of every cell's tuning in ``report`` (cost model +
    cold-grid KL dual, as the tuner scores its winner), as one jitted
    program on JAX's default device.  Returns a float64 array by cell."""
    import jax
    from repro.api import compile_spec
    from repro.core import Phi, cost_vector, robust_cost
    sys_ = report.sys
    W = compile_spec(spec).W
    w = np.stack([np.asarray(W[i], np.float32) for i, _ in report.cells])
    rho = np.array([r or 0.0 for _, r in report.cells], np.float32)
    phis = [report.tuning(c).phi for c in report.cells]
    phi = Phi(T=np.array([p.T for p in phis], np.float32),
              mfilt_bits=np.array([p.mfilt_bits for p in phis], np.float32),
              K=np.stack([np.asarray(p.K, np.float32) for p in phis]))
    score = jax.jit(jax.vmap(
        lambda p, w, r: robust_cost(cost_vector(p, sys_), w, r)))
    return np.asarray(score(phi, w, rho), np.float64)


def phase_tune(spec) -> None:
    import jax
    from repro.api import run_experiment
    cpu_dev = jax.devices("cpu")[0]
    with Timer() as tt:
        tpu = run_experiment(spec)
    with Timer() as tc, jax.default_device(cpu_dev):
        cpu = run_experiment(spec)
    say(f"tune: {len(tpu.cells)} cells; tpu {tt}; cpu {tc}")
    # arithmetic: both devices score both devices' tunings
    evals = []
    for report in (tpu, cpu):
        on_tpu = score_tunings(spec, report)
        with jax.default_device(cpu_dev):
            on_cpu = score_tunings(spec, report)
        if not (np.isfinite(on_tpu).all() and np.isfinite(on_cpu).all()):
            raise AssertionError("tune: an exact objective is not finite")
        evals.append(np.abs(on_tpu - on_cpu) / np.abs(on_cpu))
    ev = np.concatenate(evals)
    say(f"tune: exact objective of the same {len(ev)} tunings, tpu vs cpu: "
        f"max relative difference {ev.max():.3e}, bit-equal in "
        f"{int((ev == 0).sum())} (tolerance {EVAL_RTOL:g})")
    # search: each device's own winner per cell
    a = np.array([tpu.tuning(c).cost for c in tpu.cells], np.float64)
    b = np.array([cpu.tuning(c).cost for c in tpu.cells], np.float64)
    rel = np.abs(a - b) / np.abs(b)
    differ = sum(_discrete(tpu.tuning(c)) != _discrete(cpu.tuning(c))
                 for c in tpu.cells)
    say(f"tune: tuned cost tpu vs cpu: max relative difference "
        f"{rel.max():.3e} (tolerance {SEARCH_RTOL:g}), mean {rel.mean():.3e} "
        f"(tolerance {SEARCH_MEAN_RTOL:g}); cells with a different design "
        f"{differ}/{len(rel)}, tpu lower in {int((a < b).sum())}, higher in "
        f"{int((a > b).sum())}")
    if ev.max() > EVAL_RTOL:
        raise AssertionError(f"tune: tpu and cpu score the same tuning "
                             f"{ev.max():.3e} apart > {EVAL_RTOL:g}")
    if rel.max() > SEARCH_RTOL or rel.mean() > SEARCH_MEAN_RTOL:
        raise AssertionError(f"tune: tuned costs differ by {rel.max():.3e} "
                             f"(mean {rel.mean():.3e})")


# ---------------------------------------------------------------------------
# phase 3: deploy and serve the Table 5 spec, replay against a dict model
# ---------------------------------------------------------------------------

def tab5_spec(n_keys: int, n_queries: int):
    import bench_system_eval as tab5
    spec = tab5.make_spec()
    system = dict(spec.system)
    system["N"] = float(n_keys)
    return dataclasses.replace(
        spec, system=tuple(system.items()),
        trial=dataclasses.replace(spec.trial, n_keys=n_keys,
                                  n_queries=n_queries))


def _read_back(tree, model: dict, where: str) -> None:
    keys = np.fromiter(model.keys(), np.uint64, len(model))
    got = tree.point_query_batch(keys)
    bad = [(int(k), model[int(k)], g) for k, g in zip(keys.tolist(), got)
           if g != model[int(k)]]
    if bad:
        raise AssertionError(f"{where}: {len(bad)}/{len(keys)} keys read "
                             f"back wrong, e.g. (key, want, got) {bad[:3]}")


def replay_tree(plan, build, served, rng, sample: int):
    """Rebuild one served tree from its :class:`TreeBuild`, rerun its
    sessions, and check them against the served ``IOStats`` and a dict
    model of a key sample.  Returns ``(tree, session plans)``."""
    from repro.api import deploy_tree
    from repro.lsm import (draw_keys, execute_session, materialize_session,
                           populate)
    where = f"serve[{build.cell}/{build.policy}]"
    tree = deploy_tree(plan, build)
    keys = draw_keys(plan.n_keys, seed=build.key_seed,
                     key_space=plan.key_space)
    populate(tree, plan.n_keys, key_space=plan.key_space, keys=keys)
    pick = rng.choice(len(keys), min(sample, len(keys)), replace=False)
    model = {int(k): int(k) % 997 for k in keys[pick]}   # populate's values
    plans = []
    for s, mix in enumerate(plan.sessions):
        sp = materialize_session(
            keys, np.asarray(mix), n_queries=plan.n_queries,
            seed=build.session_seeds[s], key_space=plan.key_space,
            range_fraction=plan.range_fraction, zipf_a=plan.zipf_a)
        res = execute_session(tree, sp, f_a=plan.f_a, f_seq=plan.f_seq)
        if res.io != served[s].io:
            raise AssertionError(f"{where} session {s}: replayed IOStats "
                                 f"{res.io} != served {served[s].io}")
        wk = sp.write_keys
        if len(wk):
            new = rng.choice(len(wk), min(sample, len(wk)), replace=False)
            model.update({int(k): None for k in wk[new]})
        tombs = sp.write_tombs
        for j, k in enumerate(wk.tolist()):           # stream order
            if k in model:
                model[k] = None if tombs is not None and tombs[j] else 1
        _read_back(tree, model, f"{where} session {s}")
        plans.append(sp)
    # deletes: half the sample, read back from the buffer, then from runs
    dead = list(model)[::2]
    for k in dead:
        tree.delete(k)
        model[k] = None
    _read_back(tree, model, f"{where} deletes in buffer")
    tree.flush()
    _read_back(tree, model, f"{where} deletes flushed")
    return tree, plans


def check_legacy(report, plan, n_keys: int, n_queries: int) -> int:
    """Every tuning of the report at ``n_keys``: IOStats of the engine ==
    the frozen pre-refactor engine, session for session."""
    import _legacy_engine as legacy
    from repro.lsm import LSMTree, populate, run_session
    checked = 0
    for cell in report.cells:
        phi = report.tuning(cell).phi
        new = LSMTree.from_phi(phi, report.sys, expected_entries=n_keys,
                               entry_bytes=plan.entry_bytes)
        old = legacy.LSMTree.from_phi(phi, report.sys,
                                      expected_entries=n_keys,
                                      entry_bytes=plan.entry_bytes)
        kn = populate(new, n_keys, seed=cell[0], key_space=plan.key_space)
        ko = legacy.populate(old, n_keys, seed=cell[0],
                             key_space=plan.key_space)
        assert np.array_equal(kn, ko)
        for s, mix in enumerate(plan.sessions):
            kw = dict(n_queries=n_queries, seed=100 + s,
                      key_space=plan.key_space,
                      range_fraction=plan.range_fraction)
            a = run_session(new, kn, np.asarray(mix), **kw).io
            b = legacy.run_session(old, ko, np.asarray(mix), **kw).io
            if dataclasses.asdict(a) != dataclasses.asdict(b):
                raise AssertionError(f"legacy[{cell}] session {s}: "
                                     f"{a} != frozen {b}")
            checked += 1
    return checked


def phase_serve(n_keys: int, n_queries: int, seed: int, sample: int,
                legacy_keys: int, legacy_queries: int):
    from repro.api import compile_spec, run_experiment
    spec = tab5_spec(n_keys, n_queries)
    with Timer() as ts:
        report = run_experiment(spec)
    plan = compile_spec(spec).build_trial(report)
    w = report.walls
    say(f"serve: {len(plan.trees)} trees x {len(plan.sessions)} sessions at "
        f"{n_keys} keys, {n_queries} operations per session; tuning "
        f"{w['tuning_s']:.2f} s, populate {w['populate_s']:.2f} s, sessions "
        f"{w['fleet_s']:.2f} s ({ts})")
    rng = np.random.default_rng(seed)
    keep = None
    with Timer() as tr:
        for b in plan.trees:
            tree, plans = replay_tree(plan, b, report.fleet[(b.cell,
                                                             b.policy)],
                                      rng, sample)
            if keep is None:
                keep = (tree, plans)
    say(f"serve: replayed {len(plan.trees)} trees; every session's IOStats "
        f"equals the served one, every sampled acknowledged write read back "
        f"its newest value and every deleted key read absent ({tr})")
    n = check_legacy(report, plan, legacy_keys, legacy_queries)
    say(f"serve: {n} sessions at {legacy_keys} keys have the frozen "
        f"engine's IOStats")
    return keep


# ---------------------------------------------------------------------------
# phase 4: engine point reads as XLA programs on the device
# ---------------------------------------------------------------------------

def phase_reads(tree, plans) -> None:
    from repro.lsm.read_path import read_kernel
    sp = max(plans, key=lambda p: len(p.point_keys))
    keys = sp.point_keys
    out = {}
    for mode in ("numpy", "jnp"):
        before = tree.stats.snapshot()
        with Timer() as t, read_kernel(mode):
            got = tree.point_query_batch(keys)
        out[mode] = (got, tree.stats.minus(before), t)
    (a, io_a, t_a), (b, io_b, t_b) = out["numpy"], out["jnp"]
    if a != b:
        diff = sum(x != y for x, y in zip(a, b))
        raise AssertionError(f"reads: jnp mode answered {diff} of "
                             f"{len(keys)} keys differently from numpy")
    if io_a != io_b:
        raise AssertionError(f"reads: jnp IOStats {io_b} != numpy {io_a}")
    runs = sum(lv.num_runs for lv in tree.store.levels)
    say(f"reads: {len(keys)} point reads over {runs} runs, jnp mode on "
        f"{_device_name()} equals numpy (results and IOStats); jnp {t_b}; "
        f"numpy {t_a}")


# ---------------------------------------------------------------------------
# phase 5: subprocess workers next to the chip
# ---------------------------------------------------------------------------

def phase_workers(n_keys: int, n_queries: int, tuned_n: int) -> None:
    from repro.api import run_experiment
    # tuned at the serve phase's N (no new compile), deployed small
    spec = tab5_spec(tuned_n, n_queries)
    spec = dataclasses.replace(spec, trial=dataclasses.replace(
        spec.trial, n_keys=n_keys))
    inline = run_experiment(spec)
    with Timer() as t:
        sub = run_experiment(dataclasses.replace(
            spec, backend="subprocess", backend_params=(("workers", 2),)))
    workers = sub.walls.get("trial_workers", 0)
    if workers < 2:
        raise AssertionError(f"workers: the trial ran on {workers} workers")
    for key, rows in inline.fleet.items():
        got = [r.io for r in sub.fleet[key]]
        if got != [r.io for r in rows]:
            raise AssertionError(f"workers: {key} IOStats differ from "
                                 "inline")
    say(f"workers: {len(inline.fleet)} trees on {workers} subprocess workers "
        f"next to the TPU-holding parent; IOStats equal inline ({t})")


# ---------------------------------------------------------------------------
# --chips 4: the sharded tuning grid
# ---------------------------------------------------------------------------

def phase_sharded(spec, n_devices: int) -> None:
    from repro import obs
    from repro.api import run_experiment
    with Timer() as ts, obs.scoped():
        sharded = run_experiment(dataclasses.replace(spec, backend="sharded"))
        events = [e["attrs"] for e in obs.events_snapshot()
                  if e["name"] == "tune.sharded"]
    with Timer() as ti:
        inline = run_experiment(spec)
    if len(events) != 2:       # one nominal and one robust grid
        raise AssertionError(f"sharded: {len(events)} sharded solves")
    everyone = list(range(n_devices))
    for ev in events:
        if ev["returned"] != ev["problems"]:
            raise AssertionError(f"sharded: {ev['returned']} results for "
                                 f"{ev['problems']} problems")
        for ids in ev["inputs"] + ev["outputs"]:
            if ids != everyone:
                raise AssertionError(f"sharded: shards on devices {ids}, "
                                     f"not {everyone}")
        say(f"sharded: {ev['problems']} problems padded to {ev['padded']}, "
            f"{ev['returned']} results kept; inputs on devices "
            f"{ev['inputs'][0]}, outputs on {ev['outputs'][0]}")
    if sharded.cells != inline.cells:
        raise AssertionError("sharded: cell lists differ")
    differ = [c for c in inline.cells if _discrete(sharded.tuning(c))
              != _discrete(inline.tuning(c))]
    if differ:
        raise AssertionError(f"sharded: {len(differ)} cells chose another "
                             f"design than inline, e.g. {differ[:3]}")
    rel = [abs(sharded.tuning(c).cost - inline.tuning(c).cost)
           / abs(inline.tuning(c).cost) for c in inline.cells]
    bits = sum(r > 0 for r in rel)
    say(f"sharded: {len(inline.cells)} cells, every design equal to the "
        f"one-chip inline grid; costs bit-equal in "
        f"{len(rel) - bits}, max relative difference {max(rel):.3e} "
        f"(tolerance {SHARD_RTOL:g}); sharded {ts}; inline {ti}")
    if max(rel) > SHARD_RTOL:
        raise AssertionError(f"sharded: costs differ by {max(rel):.3e}")


# ---------------------------------------------------------------------------

def _device_name() -> str:
    import jax
    d = jax.devices()[0]
    return f"{d.platform}:{d.device_kind}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the key samples the replay checks")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded tuning grid on four chips")
    args = ap.parse_args(argv)

    # The tune phase needs the host CPU backend beside the TPU.
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    from repro.compile_cache import enable_compile_cache
    import jax
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's default device is "
              f"{dev.platform}:{dev.device_kind})", file=sys.stderr)
        return 1
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    say(f"device: {dev.platform} {dev.device_kind}, {len(devices)} "
        f"device(s); jax {jax.__version__}; compile cache {cache} "
        f"({warm} entries at start)")
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "devices are visible", file=sys.stderr)
        return 1

    from bench_robust_vs_nominal import SPEC as FIG6
    with Timer() as total:
        if args.chips == 4:
            phase_sharded(FIG6, len(devices))
        else:
            phase_tune(FIG6)
            tree, plans = phase_serve(N_KEYS, QUERIES, args.seed,
                                      READBACK_SAMPLE, LEGACY_KEYS,
                                      LEGACY_QUERIES)
            phase_reads(tree, plans)
            del tree
            phase_workers(WORKER_KEYS, WORKER_QUERIES, N_KEYS)
    say(f"total: {total}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
