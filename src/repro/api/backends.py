"""Pluggable execution backends: the *where/how* axis of an experiment.

A backend executes a compiled experiment's two heavy phases — the batched
tuning grid and the engine fleet trial — without changing their semantics:

* :class:`InlineBackend` (``"inline"``, default) — today's single-process
  path: one ``tune_nominal_many`` / ``tune_robust_many`` vmap grid per
  plan, one :func:`repro.lsm.run_fleet` call for the whole (tree x session)
  grid.  Every other backend must produce results identical to this one.
* :class:`ShardedBackend` (``"sharded"``) — splits the flattened
  (workload x rho) problem axis across JAX devices with a 1-D
  ``launch.mesh`` mesh + ``NamedSharding`` (each device solves a contiguous
  slab of the grid's vmap lanes).  On a single-device host it is the
  inline path, so the same spec runs anywhere.  The per-lane solves are
  independent, so sharding picks the same designs; each device's program
  is compiled for its slab, so costs may differ from inline by f32
  rounding.
* :class:`SubprocessBackend` (``"subprocess"``) — shards the fleet grid's
  *trees* across worker processes (spawned, jax-free: the engine is pure
  numpy).  Trees sharing a key draw stay on one worker so materialized
  session plans stay shared; tuning falls back inline.

Backends are registered in :data:`BACKENDS`; the spec's ``backend`` field
selects one, so the same experiment scales from laptop to cluster by
flipping a string.

**The fault-recovery invariant.**  Backends must also be semantics-free
under *failure*: the engine shard is deterministic (keys and session plans
are pure functions of their seeds), so retrying a dead worker, re-sharding
its trees onto survivors, or resuming a killed sweep from persisted shard
results moves work but never changes it — under ANY injected fault
schedule (:class:`repro.faults.FaultPlan`), every recovered result is
bit-identical to :class:`InlineBackend`.  When recovery itself is
exhausted (bounded retries, then one elastic re-shard round), the sweep
degrades gracefully: it completes with the unrecoverable trees recorded in
``Report.failed_cells`` instead of crashing.  The chaos suite
(``tests/test_faults.py``) and the gated ``BENCH_faults.json`` enforce
both halves; ``docs/faults.md`` has the full contract.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro import obs

from .compile import TreeBuild, TrialPlan, TuningPlan
from .report import Cell, Report, TreeProbe


# ---------------------------------------------------------------------------
# The shared (jax-free) trial executor
# ---------------------------------------------------------------------------

class _SysLite:
    """The two LSMSystem fields ``LSMTree.from_phi`` reads, as plain floats
    (worker processes never import jax)."""

    __slots__ = ("bits_per_entry", "N")

    def __init__(self, bits_per_entry: float, N: float):
        self.bits_per_entry = bits_per_entry
        self.N = N


class _PhiLite:
    __slots__ = ("T", "mfilt_bits", "K")

    def __init__(self, T: float, mfilt_bits: float, K: Tuple[float, ...]):
        self.T = T
        self.mfilt_bits = mfilt_bits
        self.K = np.asarray(K, np.float64)


def deploy_tree(plan: TrialPlan, b: TreeBuild):
    """The empty engine tree one :class:`TreeBuild` deploys (jax-free)."""
    from repro.lsm import LSMTree
    tree = LSMTree.from_phi(_PhiLite(b.T, b.mfilt_bits, b.K),
                            _SysLite(plan.bits_per_entry, plan.sys_N),
                            expected_entries=plan.n_keys,
                            entry_bytes=plan.entry_bytes,
                            policy=b.policy,
                            policy_params=b.policy_params)
    tree.obs_label = f"w{b.cell[0]}.rho{b.cell[1]}/{b.policy}"
    return tree


def execute_trial(plan: TrialPlan, trees: Optional[List[TreeBuild]] = None):
    """Build, populate, and run one shard of the fleet grid.

    Returns ``(results, probes, populate_s, fleet_s)`` with one entry per
    :class:`TreeBuild` (in input order): the per-session
    :class:`~repro.lsm.SessionResult` list and the post-trial
    :class:`TreeProbe`.  Pure numpy end-to-end — both the inline backend
    and subprocess workers run exactly this function, so sharding cannot
    change measured I/O."""
    from repro.lsm import IOStats, draw_keys, populate, run_fleet

    builds = plan.trees if trees is None else trees
    t0 = time.time()
    keys_by_group: Dict[int, np.ndarray] = {}
    dead_by_group: Dict[int, np.ndarray] = {}
    engine_trees, keys_list, seed_rows = [], [], []
    with obs.span("trial.populate", trees=len(builds)):
        for b in builds:
            keys = keys_by_group.get(b.key_group)
            if keys is None:
                keys = draw_keys(plan.n_keys, seed=b.key_seed,
                                 key_space=plan.key_space)
                keys_by_group[b.key_group] = keys
                if plan.delete_fraction > 0:
                    dead_by_group[b.key_group] = \
                        keys[::int(1 / plan.delete_fraction)]
            tree = deploy_tree(plan, b)
            populate(tree, plan.n_keys, key_space=plan.key_space, keys=keys)
            if plan.delete_fraction > 0:
                for k in dead_by_group[b.key_group]:  # seed tombstones
                    tree.delete(int(k))
                tree.flush()
                tree.stats = IOStats()    # deletes are setup, not workload
            engine_trees.append(tree)
            keys_list.append(keys)
            seed_rows.append(list(b.session_seeds))
    populate_s = time.time() - t0

    t0 = time.time()
    with obs.span("trial.fleet", trees=len(builds),
                  sessions=len(plan.sessions)):
        results = run_fleet(engine_trees,
                            np.asarray(plan.sessions, np.float64),
                            keys_list, n_queries=plan.n_queries,
                            seeds=np.asarray(seed_rows),
                            key_space=plan.key_space,
                            range_fraction=plan.range_fraction,
                            f_a=plan.f_a, f_seq=plan.f_seq,
                            zipf_a=plan.zipf_a)
    fleet_s = time.time() - t0
    probes = [TreeProbe.from_tree(
        t, dead_by_group.get(b.key_group, np.empty(0))[:plan.probe_dead_keys]
        if plan.delete_fraction > 0 else None)
        for t, b in zip(engine_trees, builds)]
    return results, probes, populate_s, fleet_s


def _attach_trial(report: Report, builds: List[TreeBuild], results,
                  probes) -> None:
    for b, res, probe in zip(builds, results, probes):
        report.fleet[(b.cell, b.policy)] = res
        report.probes[(b.cell, b.policy)] = probe


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------

class ExecutionBackend:
    """The backend protocol: solve one tuning plan, run one fleet trial.

    ``solve`` returns ``{cell: TuningResult}`` for every cell of the plan's
    (workload x rho [x nominal]) grid; ``run_trial`` fills the report's
    ``fleet`` / ``probes`` / wall-time fields in place (and, when recovery
    is exhausted, ``failed_cells``).  Implementations must be
    *semantics-free*: any backend, on any topology, under any injected
    fault schedule (``faults``, a :class:`repro.faults.FaultPlan`),
    produces the same tunings (costs to f32 rounding when the grid is
    split over devices) and the same measured ``IOStats`` as
    :class:`InlineBackend` for every tree it recovers (sharding and
    retrying move work, never change it)."""

    name = "abstract"

    def solve(self, plan: TuningPlan) -> Dict[Cell, object]:
        raise NotImplementedError

    def run_trial(self, plan: TrialPlan, report: Report,
                  faults=None) -> None:
        raise NotImplementedError

    def run_drift(self, plan, report: Report) -> None:
        """Run a compiled drift experiment (``repro.api.compile.DriftPlan``).

        One shared implementation: the online loop is a feedback system —
        segment s+1's tunings depend on what segment s observed — so it is
        inherently sequential per deployment and every backend runs the
        same inline driver (re-tune storms inside it are still one batched
        dispatch across the whole fleet)."""
        from repro.online import execute_drift
        t0 = time.time()
        results, regret = execute_drift(plan)
        report.drift.update(results)
        for widx, recs in regret.items():
            report.regret.setdefault(widx, []).extend(recs)
        report.walls["drift_s"] = time.time() - t0

    def run_memory(self, plan, report: Report) -> None:
        """Run a compiled memory-arbitration experiment
        (``repro.api.compile.MemoryPlan``).

        Shared for the same reason as :meth:`run_drift`: the arbitration
        loop feeds observed segments back into memory divisions, so it is
        sequential per fleet and every backend runs the same inline driver
        (its re-tune storms are still one batched dispatch per granted
        share)."""
        from repro.online import execute_memory_fleet
        t0 = time.time()
        results, events = execute_memory_fleet(plan)
        report.memory.update(results)
        report.memory_events.extend(events)
        report.walls["memory_s"] = time.time() - t0


class InlineBackend(ExecutionBackend):
    """Single-process reference execution (today's vmap path).

    Worker-scoped faults are a no-op here by definition — there is no
    worker process to kill — which is exactly what makes this backend the
    reference side of the fault-recovery invariant."""

    name = "inline"

    def __init__(self, **_):
        pass

    def solve(self, plan: TuningPlan) -> Dict[Cell, object]:
        from repro.core import tune_nominal_many, tune_robust_many
        kw = dict(design=plan.design, n_starts=plan.n_starts,
                  steps=plan.steps, lr=plan.lr, seed=plan.seed)
        out: Dict[Cell, object] = {}
        if plan.nominal:
            for i, r in enumerate(tune_nominal_many(plan.W, plan.sys, **kw)):
                out[(i, None)] = r
        if plan.rhos:
            grid = tune_robust_many(plan.W, list(plan.rhos), plan.sys, **kw)
            for i, row in enumerate(grid):
                for j, rho in enumerate(plan.rhos):
                    out[(i, rho)] = row[j]
        return out

    def run_trial(self, plan: TrialPlan, report: Report,
                  faults=None) -> None:
        results, probes, populate_s, fleet_s = execute_trial(plan)
        _attach_trial(report, plan.trees, results, probes)
        report.walls["populate_s"] = populate_s
        report.walls["fleet_s"] = fleet_s


def _shard_devices(x) -> List[int]:
    """Ids of the devices holding a shard of array ``x``."""
    return sorted(s.device.id for s in x.addressable_shards)


class ShardedBackend(InlineBackend):
    """Device-sharded tuning: the flattened problem axis is placed across
    all JAX devices via ``NamedSharding`` before the single-jit solve, so
    XLA partitions the vmap lanes device-parallel.  With one visible device
    this is the inline path.  With several, the designs equal inline's and
    the costs agree to f32 rounding (the partitioned program is compiled
    for a slab of the lanes); the ``tune.sharded`` event records the
    devices holding the inputs and outputs."""

    name = "sharded"

    def solve(self, plan: TuningPlan) -> Dict[Cell, object]:
        import jax
        devices = jax.devices()
        if len(devices) <= 1:
            return super().solve(plan)
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.core import batch
        from repro.launch.mesh import make_problem_mesh

        shard = NamedSharding(make_problem_mesh(), PartitionSpec("problem"))

        def solve_flat(W_flat, rho_flat, robust) -> list:
            P0 = len(W_flat)
            pad = (-P0) % len(devices)
            if pad:        # pad with repeats of the last cell, dropped below
                W_flat = np.concatenate([W_flat, np.repeat(
                    W_flat[-1:], pad, axis=0)])
                rho_flat = np.concatenate([rho_flat, np.repeat(
                    rho_flat[-1:], pad)])
            W_d = jax.device_put(jnp.asarray(W_flat, jnp.float32), shard)
            r_d = jax.device_put(jnp.asarray(rho_flat, jnp.float32), shard)
            out = batch.solve_grid(jax.random.PRNGKey(plan.seed), W_d, r_d,
                                   plan.design, plan.sys, plan.n_starts,
                                   plan.steps, plan.lr, robust)
            placed = ([_shard_devices(x) for x in (W_d, r_d)],
                      [_shard_devices(x) for x in out])
            out = [np.asarray(x)[:P0] for x in jax.device_get(out)]
            if obs.enabled():
                # where the grid really ran (the devices holding each
                # input's and output's shards), and the padding dropped
                obs.event("tune.sharded", problems=P0, padded=P0 + pad,
                          returned=len(out[0]), inputs=placed[0],
                          outputs=placed[1])
            return batch.build_results(out, plan.design, plan.sys)

        out: Dict[Cell, object] = {}
        n_w = len(plan.W)
        if plan.nominal:
            flat = solve_flat(np.asarray(plan.W, np.float32),
                              np.zeros(n_w, np.float32), robust=False)
            out.update({(i, None): r for i, r in enumerate(flat)})
        if plan.rhos:
            R = np.asarray(plan.rhos, np.float32)
            W_flat = np.repeat(np.asarray(plan.W, np.float32),
                               len(R), axis=0)
            rho_flat = np.tile(R, n_w)
            flat = solve_flat(W_flat, rho_flat, robust=True)
            for i in range(n_w):
                for j, rho in enumerate(plan.rhos):
                    out[(i, rho)] = flat[i * len(R) + j]
        return out


# ---------------------------------------------------------------------------
# Subprocess fleet backend: workers, retries, re-sharding, resume
# ---------------------------------------------------------------------------

class ShardFailure(RuntimeError):
    """One shard attempt failed; the message carries the phase (launch /
    timeout / exit code / result decode) and the worker's stderr tail."""


def _stderr_tail(data, limit: int = 2000) -> str:
    if not data:
        return "<no stderr>"
    if isinstance(data, bytes):
        data = data.decode("utf-8", "replace")
    return data[-limit:].strip()


def _inject_worker_fault(fault) -> None:
    """Execute a pre-launch worker fault (crash / hang / slow) inside the
    worker process.  Crash announces itself on stderr first — the parent's
    stderr capture is part of what the chaos suite verifies."""
    import os
    import sys
    from repro.faults import HANG_SLEEP_S
    if fault.kind == "crash":
        print("InjectedWorkerCrash: deterministic chaos fault (kind=crash)",
              file=sys.stderr)
        sys.stderr.flush()
        os._exit(17)
    elif fault.kind == "hang":
        time.sleep(HANG_SLEEP_S)     # parent's per-shard timeout kills us
    elif fault.kind == "slow":
        time.sleep(fault.delay_s)


def _worker_main() -> None:
    """Entry point of one fleet-shard worker process.

    Reads a pickled ``(plan, builds, fault)`` job from stdin (the legacy
    2-tuple without a fault is still accepted), runs
    :func:`execute_trial`, and writes the pickled result to stdout.
    ``fault`` is the parent's resolved :class:`repro.faults.FaultAction`
    for this (shard, attempt) coordinate — crash/hang/slow execute before
    the work, ``corrupt`` truncates the result pickle after it.  Importing
    this module pulls no jax — the engine shard is pure numpy — so worker
    startup is cheap and safe regardless of the parent's device runtime
    state (no fork-with-threads, no ``__main__`` re-import)."""
    import pickle
    import sys
    job = pickle.load(sys.stdin.buffer)
    plan, builds, fault = job if len(job) == 3 else (job[0], job[1], None)
    if fault is not None and fault.kind in ("crash", "hang", "slow"):
        _inject_worker_fault(fault)
    out = execute_trial(plan, builds)
    payload = pickle.dumps(out, protocol=pickle.HIGHEST_PROTOCOL)
    if fault is not None and fault.kind == "corrupt":
        payload = payload[: max(1, len(payload) // 2)]
    sys.stdout.buffer.write(payload)
    sys.stdout.buffer.flush()


def _plan_digest(plan: TrialPlan) -> str:
    """A stable fingerprint of the trial plan, stamped into every persisted
    shard result so a resume never consumes results from a different
    experiment (pickle of the plan's plain-data fields is deterministic
    for equal content)."""
    import hashlib
    import pickle
    return hashlib.sha256(
        pickle.dumps(plan, protocol=4)).hexdigest()[:16]


class SubprocessBackend(InlineBackend):
    """Fleet-trial sharding across worker processes, hardened against the
    faults :mod:`repro.faults` can inject.

    The (tree x session) grid is partitioned by *key group* (trees sharing
    a key draw — and therefore materialized session plans — stay together),
    groups are assigned to workers largest-first, and each worker process
    runs the same :func:`execute_trial` the inline backend runs, on its
    shard.  Workers are plain ``python -c`` subprocesses fed pickles over
    stdin/stdout (jax-free: the engine is numpy-only).

    Recovery layers, in order (all deterministic — see
    :class:`repro.faults.RetryPolicy` and ``docs/faults.md``):

    * **per-attempt timeout** (``timeout_s``) — a hung worker is killed and
      the attempt failed, with whatever stderr it produced attached;
    * **bounded retries with seeded exponential backoff**
      (``max_retries`` / ``backoff_s`` / ``retry_seed``) — crashes,
      timeouts, and corrupt result pickles re-launch the same shard;
    * **elastic re-shard** (``reshard``) — a shard dead after every retry
      has its trees regrouped onto fresh worker slots
      (:class:`repro.faults.ShardSupervisor`, the ``launch/elastic.py``
      membership pattern) and re-run once with a fresh retry budget;
    * **graceful degradation** — trees still unrecovered land in
      ``Report.failed_cells`` with their final error; the sweep completes.

    With ``run_dir`` set, every completed shard's per-tree results persist
    atomically (checksummed pickles, :func:`repro.faults.dump_job`) as soon
    as that shard finishes, so a driver killed mid-sweep loses only
    in-flight shards; ``resume=True`` loads any valid persisted results for
    this exact plan (by digest) and executes only the remainder —
    ``benchmarks/run.py --spec ... --run-dir D --resume`` is the CLI."""

    name = "subprocess"

    def __init__(self, workers: int = 0, max_retries: int = 2,
                 backoff_s: float = 0.05, timeout_s: float = 900.0,
                 retry_seed: int = 0, reshard: bool = True,
                 run_dir: str = "", resume: bool = False, **_):
        import os
        from repro.faults import RetryPolicy
        self.workers = int(workers) or min(4, os.cpu_count() or 1)
        self.retry = RetryPolicy(max_retries=int(max_retries),
                                 backoff_s=float(backoff_s),
                                 timeout_s=float(timeout_s),
                                 seed=int(retry_seed))
        self.reshard = bool(reshard)
        self.run_dir = str(run_dir or "")
        self.resume = bool(resume)

    # -- sharding ----------------------------------------------------------

    def _partition(self, plan: TrialPlan) -> List[List[int]]:
        """Tree indices per shard.  Prefer keeping key groups together
        (trees sharing a draw also share materialized session plans):
        largest-group-first onto the emptiest shard.  With fewer groups
        than workers, split within groups instead — each worker re-draws
        the (seed-deterministic) keys, trading one redundant draw for
        tree-level parallelism."""
        by_group: Dict[int, List[int]] = {}
        for t, b in enumerate(plan.trees):
            by_group.setdefault(b.key_group, []).append(t)
        if len(by_group) >= self.workers:
            shards: List[List[int]] = [[] for _ in range(self.workers)]
            for members in sorted(by_group.values(), key=len, reverse=True):
                min(shards, key=len).extend(members)
        else:
            order = list(range(len(plan.trees)))
            shards = [order[i::self.workers] for i in range(self.workers)]
        return [s for s in shards if s]

    # -- one shard attempt -------------------------------------------------

    def _launch(self, cmd, env, plan: TrialPlan, shard: List[int],
                sid: int, attempt: int, faults):
        """One worker launch; raises :class:`ShardFailure` on timeout,
        nonzero exit, or an undecodable/short result — always with the
        worker's stderr attached."""
        import pickle
        import subprocess
        fault = faults.worker_fault(sid, attempt) if faults else None
        if fault is not None and obs.enabled():
            # cross-reference: this attempt's outcome event carries the
            # same (shard, attempt) key as the injection that shaped it
            obs.event("shard.fault_injected", shard=sid, attempt=attempt,
                      fault=getattr(fault, "kind", None) or str(fault))
        job = pickle.dumps((plan, [plan.trees[t] for t in shard], fault),
                           protocol=pickle.HIGHEST_PROTOCOL)
        try:
            proc = subprocess.run(cmd, input=job, stdout=subprocess.PIPE,
                                  stderr=subprocess.PIPE, env=env,
                                  timeout=self.retry.timeout_s)
        except subprocess.TimeoutExpired as exc:
            raise ShardFailure(
                f"shard {sid} attempt {attempt}: no result within "
                f"timeout_s={self.retry.timeout_s:g} (hung worker killed); "
                f"stderr: {_stderr_tail(exc.stderr)}") from None
        if proc.returncode != 0:
            raise ShardFailure(
                f"shard {sid} attempt {attempt}: worker exited "
                f"{proc.returncode}; stderr: {_stderr_tail(proc.stderr)}")
        try:
            results, probes, p_s, f_s = pickle.loads(proc.stdout)
            if len(results) != len(shard) or len(probes) != len(shard):
                raise ValueError(f"{len(results)} results for "
                                 f"{len(shard)} trees")
        except ShardFailure:
            raise
        except Exception as exc:
            raise ShardFailure(
                f"shard {sid} attempt {attempt}: corrupt result pickle "
                f"({type(exc).__name__}: {exc}); "
                f"stderr: {_stderr_tail(proc.stderr)}") from None
        return results, probes, p_s, f_s

    def _job_path(self, digest: str, shard: List[int]) -> str:
        import hashlib
        import os
        tag = hashlib.sha256(",".join(map(str, shard)).encode()) \
            .hexdigest()[:12]
        return os.path.join(self.run_dir, f"job_{digest}_{tag}.pkl")

    def _load_resumed(self, digest: str, n_trees: int) -> Dict[int, tuple]:
        """Per-tree results recovered from a previous (killed) sweep:
        every valid ``job_<digest>_*.pkl`` in the run dir whose plan digest
        matches.  Torn or corrupt files load as ``None`` and are simply
        re-executed — a checksum never trusts, it only skips work."""
        import glob
        import os
        from repro.faults import load_job
        out: Dict[int, tuple] = {}
        if not (self.run_dir and os.path.isdir(self.run_dir)):
            return out
        for path in sorted(glob.glob(
                os.path.join(self.run_dir, f"job_{digest}_*.pkl"))):
            payload = load_job(path)
            if not isinstance(payload, dict) \
                    or payload.get("plan") != digest:
                continue
            for t, entry in payload.get("trees", {}).items():
                if isinstance(t, int) and 0 <= t < n_trees:
                    out[t] = entry
        return out

    def _persist(self, digest: str, shard: List[int], out, faults) -> int:
        """Atomically persist one completed shard's per-tree results;
        returns 1 if the write failed (injected torn write / disk error) —
        the sweep itself continues, a later resume just re-runs the
        shard."""
        if not self.run_dir:
            return 0
        import os
        from repro.faults import dump_job
        results, probes, p_s, f_s = out
        os.makedirs(self.run_dir, exist_ok=True)
        try:
            dump_job(self._job_path(digest, shard),
                     {"plan": digest,
                      "trees": {t: (results[i], probes[i])
                                for i, t in enumerate(shard)},
                      "populate_s": p_s, "fleet_s": f_s},
                     fault=faults)
            return 0
        except OSError:
            return 1

    # -- the sweep ---------------------------------------------------------

    def run_trial(self, plan: TrialPlan, report: Report,
                  faults=None) -> None:
        if self.workers <= 1 or len(plan.trees) <= 1:
            return super().run_trial(plan, report, faults)
        import concurrent.futures
        import os
        import sys
        from repro.faults import FaultPlan, ShardSupervisor

        faults = faults if faults is not None else FaultPlan(())
        sup = ShardSupervisor()
        digest = _plan_digest(plan)

        shards = self._partition(plan)
        report.walls["trial_workers"] = len(shards)

        # -- resume: trust only checksum-valid results for this exact plan
        done: Dict[int, tuple] = \
            self._load_resumed(digest, len(plan.trees)) if self.resume else {}
        report.walls["resumed_trees"] = len(done)
        pending = [(sid, [t for t in s if t not in done])
                   for sid, s in enumerate(shards)]
        jobs = [(sid, s) for sid, s in pending if s]

        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(p for p in sys.path if p)
        cmd = [sys.executable, "-c",
               "from repro.api.backends import _worker_main; _worker_main()"]

        stats = {"attempts": 0, "persist_failures": 0, "shards_run": 0}
        walls = {"populate_s": 0.0, "fleet_s": 0.0}
        # Every attempt — including the ones a later success used to mask —
        # is recorded here and surfaced in the Report: a silently-flapping
        # shard (fails, backs off, then succeeds) used to be invisible
        # because only failure stderr was kept.  list.append is atomic, so
        # the pool threads share this without a lock.
        attempt_log: List[dict] = []

        def run_with_retries(job):
            """(sid, shard) -> (sid, shard, out-or-None, [errors]).
            Bounded retries with seeded backoff; persists on success so a
            killed driver keeps every completed shard.  Per-attempt
            latencies and outcomes land in ``attempt_log`` either way."""
            sid, shard = job
            errors: List[str] = []
            for attempt in range(self.retry.attempts()):
                if attempt:
                    time.sleep(self.retry.delay(sid, attempt))
                a_t0 = time.perf_counter()
                try:
                    out = self._launch(cmd, env, plan, shard, sid, attempt,
                                       faults)
                except ShardFailure as exc:
                    latency = time.perf_counter() - a_t0
                    attempt_log.append({"shard": sid, "attempt": attempt,
                                        "ok": False,
                                        "latency_s": round(latency, 6)})
                    obs.count("shard.attempts")
                    obs.count("shard.failed_attempts")
                    if obs.enabled():
                        obs.event("shard.attempt", shard=sid,
                                  attempt=attempt, ok=False,
                                  latency_s=round(latency, 6),
                                  error=str(exc)[:200])
                    errors.append(str(exc))
                    continue
                latency = time.perf_counter() - a_t0
                attempt_log.append({"shard": sid, "attempt": attempt,
                                    "ok": True,
                                    "latency_s": round(latency, 6)})
                obs.count("shard.attempts")
                if obs.enabled():
                    obs.event("shard.attempt", shard=sid, attempt=attempt,
                              ok=True, latency_s=round(latency, 6))
                stats["persist_failures"] += \
                    self._persist(digest, shard, out, faults)
                return sid, shard, out, errors
            return sid, shard, None, errors

        def run_round(round_jobs):
            """Execute one round of shard jobs; returns the tree indices
            (with errors) that exhausted this round's retry budget."""
            if not round_jobs:
                return []
            stats["shards_run"] += len(round_jobs)
            with concurrent.futures.ThreadPoolExecutor(
                    len(round_jobs)) as pool:
                outs = list(pool.map(run_with_retries, round_jobs))
            lost: List[Tuple[int, str]] = []
            for sid, shard, out, errors in outs:
                for err in errors:
                    sup.record_failure(sid, err)
                stats["attempts"] += 1 + len(errors)
                if out is None:
                    sup.mark_dead(sid)
                    lost.extend((t, errors[-1]) for t in shard)
                    continue
                sup.mark_completed(sid)
                results, probes, p_s, f_s = out
                for i, t in enumerate(shard):
                    done[t] = (results[i], probes[i])
                # workers run in parallel: phase wall = slowest worker
                walls["populate_s"] = max(walls["populate_s"], p_s)
                walls["fleet_s"] = max(walls["fleet_s"], f_s)
            return lost

        lost = run_round(jobs)

        # -- elastic re-shard: dead workers' trees onto fresh slots, once.
        # Membership logic mirrors launch/elastic.py's remesh: with zero
        # surviving shards the failure is systemic (the machine, not the
        # shard), so degrade instead of re-running everything doomed.
        report.walls["reshard_trees"] = 0
        if lost and self.reshard and sup.completed:
            last_err = dict(lost)
            regrouped = sup.reassign([t for t, _ in lost], self.workers)
            report.walls["reshard_trees"] = len(last_err)
            obs.count("shard.reshards")
            if obs.enabled():
                obs.event("shard.reshard", trees=len(last_err),
                          new_shards=len(regrouped))
            next_sid = len(shards)
            lost = run_round([(next_sid + j, s)
                              for j, s in enumerate(regrouped)])

        # -- graceful degradation: explicit holes, not a crash
        for t, err in lost:
            b = plan.trees[t]
            report.failed_cells[(b.cell, b.policy)] = err

        for t, (res, probe) in done.items():
            b = plan.trees[t]
            report.fleet[(b.cell, b.policy)] = res
            report.probes[(b.cell, b.policy)] = probe

        report.walls["populate_s"] = walls["populate_s"]
        report.walls["fleet_s"] = walls["fleet_s"]
        report.walls["shards_run"] = stats["shards_run"]
        report.walls["shard_retries"] = sup.retries
        report.walls["failed_trees"] = len(report.failed_cells)
        if stats["persist_failures"]:
            report.walls["persist_failures"] = stats["persist_failures"]
        # per-attempt accounting (sorted: pool threads interleave appends):
        # total attempts, flapping shards (>= 1 failed attempt before a
        # success), and the latency spread — Report.rows renders these, so
        # a flapping fleet is visible without digging through stderr
        report.shard_attempts = sorted(
            attempt_log, key=lambda a: (a["shard"], a["attempt"]))
        report.walls["shard_attempt_count"] = len(attempt_log)
        obs.count("shard.resumed", report.walls["resumed_trees"])


class RemoteBackend(ExecutionBackend):
    """Cluster-scheduler stub (the ROADMAP "remote backend" item).

    Registered so ``ExperimentSpec.backend = "remote"`` round-trips through
    JSON and ``get_backend`` like any real backend, and so the submission
    payload contract is pinned today: :meth:`serialize_job` emits the
    versioned job envelope a scheduler shim would ship to a worker that
    runs ``benchmarks/run.py --spec job-spec.json``.  Since the
    fault-tolerance work the envelope carries the full job shape a flaky
    cluster needs — the spec, a content checksum the worker validates
    before executing (a torn submission must be rejected, not run), and
    the retry/timeout policy the remote executor should apply.  Execution
    itself is NOT implemented — every execution entry point raises with
    instructions rather than silently running locally, so a misconfigured
    deployment cannot masquerade as a cluster run."""

    name = "remote"
    #: bumped when the envelope shape changes; v2 added spec_checksum and
    #: the retry/timeout policy block.
    ENVELOPE_VERSION = 2
    _MSG = ("the 'remote' backend is a scheduling stub: it serializes the "
            "experiment (RemoteBackend.serialize_job(spec) -> JSON job "
            "envelope for `benchmarks/run.py --spec`) but cannot execute "
            "it in this process.  Submit the payload to your cluster "
            "scheduler, or pick backend='inline'/'sharded'/'subprocess' "
            "to run here.")

    def __init__(self, scheduler: str = "", queue: str = "",
                 max_retries: int = 2, backoff_s: float = 0.05,
                 timeout_s: float = 900.0, retry_seed: int = 0, **_):
        from repro.faults import RetryPolicy
        self.scheduler = scheduler
        self.queue = queue
        self.retry = RetryPolicy(max_retries=int(max_retries),
                                 backoff_s=float(backoff_s),
                                 timeout_s=float(timeout_s),
                                 seed=int(retry_seed))

    def serialize_job(self, spec) -> str:
        """The submission payload: a versioned envelope of the spec's JSON
        round-trip, its content checksum, and the retry/timeout policy the
        remote executor must honor."""
        import json
        from repro.faults import stamp_checksum
        return json.dumps(stamp_checksum({
            "version": self.ENVELOPE_VERSION,
            "scheduler": self.scheduler,
            "queue": self.queue,
            "retry": {"max_retries": self.retry.max_retries,
                      "backoff_s": self.retry.backoff_s,
                      "timeout_s": self.retry.timeout_s,
                      "seed": self.retry.seed},
            "spec": spec.to_dict(),
        }), indent=1, sort_keys=True)

    @classmethod
    def deserialize_job(cls, text: str):
        """Validate + unpack an envelope: ``(ExperimentSpec, retry dict)``.
        Raises ``ValueError`` on a version mismatch or a checksum failure —
        a torn/tampered submission must never execute."""
        import json
        from repro.faults import checksum_ok
        from .spec import ExperimentSpec
        env = json.loads(text)
        if not isinstance(env, dict) \
                or env.get("version") != cls.ENVELOPE_VERSION:
            raise ValueError(f"unknown job envelope version "
                             f"{env.get('version')!r}; expected "
                             f"{cls.ENVELOPE_VERSION}")
        if not checksum_ok(env):
            raise ValueError("job envelope checksum mismatch "
                             "(torn or tampered submission)")
        return ExperimentSpec.from_dict(env["spec"]), dict(env["retry"])

    def solve(self, plan: TuningPlan) -> Dict[Cell, object]:
        raise NotImplementedError(self._MSG)

    def run_trial(self, plan: TrialPlan, report: Report,
                  faults=None) -> None:
        raise NotImplementedError(self._MSG)

    def run_drift(self, plan, report: Report) -> None:
        raise NotImplementedError(self._MSG)

    def run_memory(self, plan, report: Report) -> None:
        raise NotImplementedError(self._MSG)


BACKENDS = {
    "inline": InlineBackend,
    "sharded": ShardedBackend,
    "subprocess": SubprocessBackend,
    "remote": RemoteBackend,
}


def get_backend(name: str, params=()):
    try:
        cls = BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; "
                         f"known: {sorted(BACKENDS)}") from None
    return cls(**dict(params))
