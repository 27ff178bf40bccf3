"""The traffic generator: one class per kind of traffic file.

A traffic file (``traffic/<name>.json``) names its kind under
``"generator"`` and holds the parameters; the configuration file
(``configs/<name>.json``) holds the deployment.  Each kind:

* ``setup()`` builds the system under test from the configuration and
  draws every request of the run from the seed, then warms up the shapes
  the window will use;
* ``request(i)`` drives request ``i`` through the program and returns the
  units it completed (tunings returned, operations served);
* ``counters()`` returns what the program counted over the window;
* ``checks()`` compares what the window produced with the plain reference
  and returns ``(name, value, limit)`` triples; ``value <= limit`` passes.

Kinds:

* ``experiment``: a tuning experiment through ``api.run_experiment`` per
  request (a workload x rho grid), with the design's seed drawn per
  request.
* ``storm``: a fleet retune storm through ``online.retune_fleet`` per
  request, each tenant's radius from Algorithm 1 over its own windows.
* ``ycsb``: a YCSB core workload against one deployed engine tree, one
  ``lsm.execute_session`` per request.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

from .reference import cost_model as ref
from .reference.kv_model import KVModel
from . import ycsb

Check = Tuple[str, float, float]

#: a tuning whose level argument (``reference.cost_model.level_argument``)
#: lies within this relative distance of an integer has the level count of
#: either side at float32 precision: its cost is taken at both
LEVEL_EDGE = 1e-5


def _seeds(rng: np.random.Generator, n: int) -> np.ndarray:
    return rng.integers(0, 2 ** 31 - 1, size=n)


def _sample(rng: np.random.Generator, n: int, k: int) -> np.ndarray:
    """``k`` of ``range(n)`` (all, where ``n <= k``), drawn from ``rng``."""
    return np.sort(rng.choice(n, size=min(k, n), replace=False))


def _arrays(tunings) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(T, mfilt_bits, K) of a list of ``TuningResult``s, as float64."""
    return (np.array([float(r.phi.T) for r in tunings]),
            np.array([float(r.phi.mfilt_bits) for r in tunings]),
            np.stack([np.asarray(r.phi.K, np.float64) for r in tunings]))


class TuneBase:
    """What the two tuning kinds share: scoring returned tunings."""

    #: the traced run starts at the window (the window drives the device)
    trace_setup = False

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.rng = np.random.default_rng(seed)
        self.refsys = ref.System.from_config(config["system"])
        self.limits = traffic["limits"]
        self.done: Dict[int, object] = {}
        self._best: Dict[int, np.ndarray] = {}
        self._control: Dict[int, np.ndarray] = {}
        #: requests checked by :meth:`checks`, for :meth:`control_checks`
        self.picked: List[Tuple[int, tuple]] = []

    def best(self, key: int, W, R) -> np.ndarray:
        """The reference's best cost of each problem of one request."""
        if key not in self._best:
            self._best[key] = ref.best_costs(W, R, self.refsys)
        return self._best[key]

    def score_gap(self, W, R, T, M, K, reported: List[np.ndarray]
                  ) -> Tuple[float, np.ndarray]:
        """(score gap, reference cost) of one request's tunings.

        The score gap is the largest relative distance between a cost
        reported for a tuning and the reference's cost of the same tuning,
        where a tuning on a level-count edge (``LEVEL_EDGE``) is scored at
        both counts and the nearer is taken."""
        s = self.refsys
        exact = ref.robust_cost(ref.cost_vector(T, M, K, s), W, R)
        x = ref.level_argument(T, M, s)
        frac = x - np.floor(x)
        edge = np.minimum(frac, 1.0 - frac) < LEVEL_EDGE * x
        shift = np.where(frac < 0.5, -1, 1) * edge
        other = ref.robust_cost(ref.cost_vector(T, M, K, s, level_shift=shift),
                                W, R)
        score = 0.0
        for c in reported:
            c = np.asarray(c, np.float64)
            gap = np.minimum(np.abs(c - exact) / exact,
                             np.abs(c - other) / other)
            score = max(score, float(np.max(gap)))
        return score, exact

    def opt_gaps(self, key: int, W, R, exact: np.ndarray) -> np.ndarray:
        """How far above the reference's best design the reference puts
        each returned tuning (relative), one per problem."""
        return exact / self.best(key, W, R) - 1.0

    def checks(self) -> List[Check]:
        pick = _sample(self.rng, len(self.done),
                       int(self.traffic["check_requests"]))
        score = 0.0
        gaps = []
        missing = 0
        for i in pick:
            try:
                key, W, R, T, M, K, reported = self.answers(int(i))
            except ValueError:
                missing += 1
                continue
            self.picked.append((key, (W, R, T, M, K)))
            s, exact = self.score_gap(W, R, T, M, K, reported)
            score = max(score, s)
            gaps.append(self.opt_gaps(key, W, R, exact))
        return self._named(float(missing), score, _mean(gaps))

    def _named(self, missing: float, score: float, opt: float
               ) -> List[Check]:
        lim = self.limits
        return [("missing_requests", missing, 0.0),
                ("score_gap", score, lim["score_gap"]),
                ("opt_gap_mean", opt, lim["opt_gap_mean"])]

    def control_checks(self, dtype) -> List[Check]:
        """The control's readings on the requests :meth:`checks` read: the
        reference computed in ``dtype`` in the program's place, read by the
        same comparisons.  Its score gap is that of its own costs for the
        program's tunings; its optimality gap that of its own search's
        tunings."""
        score = 0.0
        gaps = []
        for key, (W, R, T, M, K) in self.picked:
            low = np.asarray(ref.robust_cost(
                ref.cost_vector(T, M, K, self.refsys, dtype), W, R, dtype),
                np.float64)
            score = max(score, self.score_gap(W, R, T, M, K, [low])[0])
            if key not in self._control:
                _, Tc, Mc, Kc = ref.best_designs(W, R, self.refsys,
                                                 dtype=dtype)
                mine = ref.robust_cost(ref.cost_vector(Tc, Mc, Kc,
                                                       self.refsys), W, R)
                self._control[key] = self.opt_gaps(key, W, R, mine)
            gaps.append(self._control[key])
        return self._named(0.0, score, _mean(gaps))


def _mean(gaps: List[np.ndarray]) -> float:
    """The mean of the optimality gaps of every checked cell."""
    return float(np.mean(np.concatenate(gaps))) if gaps else 0.0


class TuneExperiment(TuneBase):
    """``api.run_experiment`` over a (workload x rho) grid per request."""

    def setup(self) -> None:
        from repro.api import DesignSpec, ExperimentSpec, WorkloadSpec
        t, tuner = self.traffic, self.config["tuner"]
        self.spec = ExperimentSpec(
            name="bench",
            workload=WorkloadSpec(workloads=tuple(map(tuple, t["workloads"])),
                                  rhos=tuple(t["rhos"]),
                                  nominal=bool(t["nominal"]),
                                  bench_n=int(t["bench_n"]),
                                  bench_seed=int(t["bench_seed"])),
            design=DesignSpec(space=tuner["design"],
                              policies=tuple(tuner["policies"]),
                              n_starts=int(tuner["n_starts"]),
                              steps=int(tuner["steps"]), lr=float(tuner["lr"])),
            system=tuple(self.config["system"].items()))
        W = np.asarray(t["workloads"], np.float64)
        W = W / W.sum(axis=1, keepdims=True)
        cells = ([(i, 0.0) for i in range(len(W))] if t["nominal"] else []) \
            + [(i, float(r)) for i in range(len(W)) for r in t["rhos"]]
        self.W = np.stack([W[i] for i, _ in cells])
        self.R = np.array([r for _, r in cells])
        self.units = len(cells)
        self.seeds = _seeds(self.rng, 1 + 100_000)
        self.select_s: List[float] = []
        self._run(int(self.seeds[-1]))              # warm-up: compiles

    def _run(self, seed: int):
        from repro.api import run_experiment
        spec = dataclasses.replace(
            self.spec, design=dataclasses.replace(self.spec.design,
                                                  seed=seed))
        return run_experiment(spec)

    def request(self, i: int) -> int:
        report = self._run(int(self.seeds[i]))
        self.select_s.append(float(report.walls["select_s"]))
        self.done[i] = report
        return sum(1 for c in report.cells if c in report.tunings)

    def counters(self) -> dict:
        return {"select_s": list(self.select_s)}

    def answers(self, i: int):
        """Request ``i``'s problems and tunings, in the reference's cell
        order, with the costs the program reported for them: the tuner's
        and the arm scorer's."""
        report = self.done[i]
        if len(report.cells) != self.units:
            raise ValueError(f"request {i}: {len(report.cells)} cells "
                             f"returned, {self.units} asked for")
        tuned = [report.tuning(c) for c in report.cells]
        arm = np.array([report.arm_costs[c][report.chosen[c]]
                        for c in report.cells])
        return (0, self.W, self.R) + _arrays(tuned) \
            + ([np.array([r.cost for r in tuned]), arm],)


def algorithm1_rho(counts: np.ndarray) -> float:
    """The paper's Algorithm 1 on window counts: the largest KL divergence
    of a window's mix from the mean mix."""
    m = counts / counts.sum(axis=1, keepdims=True)
    c = m.mean(axis=0)
    with np.errstate(divide="ignore", invalid="ignore"):
        t = np.where(m > 0, m * np.log(m / c), 0.0)
    return float(t.sum(axis=1).max())


class RetuneStorm(TuneBase):
    """``online.retune_fleet`` over a fleet's fired triggers per request."""

    def setup(self) -> None:
        t = self.traffic
        F, nw, ops = int(t["tenants"]), int(t["windows"]), int(t["window_ops"])
        from repro.core import LSMSystem
        self.sys = LSMSystem(**self.config["system"])
        self.pool = []
        for _ in range(int(t["pool"])):
            # the paper's benchmark set B: per-class counts ~ U(1, max)
            W = self.rng.uniform(1.0, float(t["bench_max_count"]), (F, 4))
            W /= W.sum(axis=1, keepdims=True)
            rhos = np.array([algorithm1_rho(self.rng.multinomial(
                ops, W[f], size=nw).astype(np.float64)) for f in range(F)])
            if len(np.unique(rhos)) != F:
                raise ValueError("two tenants drew the same radius")
            self.pool.append((W, rhos))
        self.seeds = _seeds(self.rng, 1 + 100_000)
        self.retune = self.config["retune"]
        self._run(0, int(self.seeds[-1]))           # warm-up: compiles

    def _run(self, k: int, seed: int):
        from repro.online import RetuneRequest, retune_fleet
        W, rhos = self.pool[k % len(self.pool)]
        reqs = [RetuneRequest(w=W[f], rho=float(rhos[f]))
                for f in range(len(W))]
        r = self.retune
        return retune_fleet(reqs, self.sys, n_starts=int(r["n_starts"]),
                            steps=int(r["steps"]), lr=float(r["lr"]),
                            seed=seed)

    def request(self, i: int) -> int:
        out = self._run(i, int(self.seeds[i]))
        self.done[i] = out
        return sum(1 for r in out if r is not None)

    def counters(self) -> dict:
        return {}

    def answers(self, i: int):
        """Request ``i``'s problems and tunings, with the reported costs."""
        out = self.done[i]
        key = i % len(self.pool)
        W, R = self.pool[key]
        if len(out) != len(W) or any(r is None for r in out):
            raise ValueError(f"request {i}: {len(out)} tunings returned, "
                             f"{len(W)} asked for")
        return (key, W, R) + _arrays(out) \
            + ([np.array([r.cost for r in out])],)


class YCSB:
    """A YCSB core workload against one deployed tree."""

    #: nothing in the window runs on the device, so the traced run starts
    #: before the deployment's tuning, which does
    trace_setup = True

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.rng = np.random.default_rng(seed)
        #: the control: the reference model in the engine's place, losing
        #: every hundredth loaded record and the acknowledged updates of
        #: every second request (set by ``control.py``; None in runs)
        self.lossy = None

    def deploy(self):
        """Tune the deployment on the device and build its empty tree."""
        from repro.api import (DesignSpec, ExperimentSpec, TrialSpec,
                               WorkloadSpec, compile_spec, deploy_tree,
                               run_experiment)
        d, e = self.config["deployment"], self.config["engine"]
        t = self.traffic
        spec = ExperimentSpec(
            name="bench",
            workload=WorkloadSpec(workloads=(tuple(d["workload"]),),
                                  rhos=(float(d["rho"]),), nominal=False),
            design=DesignSpec(space=d["design"], policies=(d["policy"],),
                              n_starts=int(d["n_starts"]),
                              steps=int(d["steps"]), lr=float(d["lr"]),
                              seed=int(d["seed"])),
            trial=TrialSpec(n_keys=int(e["n_records"]),
                            n_queries=int(t["request_ops"]),
                            sessions=((0.0, float(t["read"]), 0.0,
                                       float(t["update"])),),
                            entry_bytes=int(e["entry_bytes"])),
            system=tuple(self.config["system"].items()))
        report = run_experiment(dataclasses.replace(spec, trial=None))
        plan = compile_spec(spec).build_trial(report)
        return deploy_tree(plan, plan.trees[0])

    def setup(self) -> None:
        from repro.lsm import populate
        t, e = self.traffic, self.config["engine"]
        n = int(e["n_records"])
        self.tree = self.deploy()
        self.keys = ycsb.record_keys(n)
        populate(self.tree, n, keys=self.keys)
        self.model = KVModel(self.keys)
        upd, rec = self.draw_pool(n)
        self.n_pool = len(upd)
        self.pool = [self._plan(upd[k], rec[k]) for k in range(self.n_pool)]
        self.pool_writes = [rec[k][upd[k]] for k in range(self.n_pool)]
        self.reads = self.writes = 0
        # a level packs its Bloom filters on its first read: one read of a
        # key that no level holds packs them all here, not in the window
        self.tree.classify_point_batch(np.array([2 ** 63 + 1], np.uint64))
        self.before = self.tree.stats.snapshot()

    def draw_pool(self, n_records: int) -> Tuple[np.ndarray, np.ndarray]:
        """Every request's operations: (is-update mask, record index), each
        of shape (pool_requests, request_ops)."""
        t = self.traffic
        n, ops = int(t["pool_requests"]), int(t["request_ops"])
        upd = self.rng.random((n, ops)) < float(t["update"])
        rec = ycsb.scrambled_zipf(self.rng, n * ops, n_records,
                                  float(t["zipfian_constant"]))
        return upd, rec.reshape(n, ops)

    def _plan(self, upd: np.ndarray, rec: np.ndarray):
        from repro.lsm import SessionPlan
        t = self.traffic
        kinds = np.where(upd, 3, 1).astype(np.int64)
        empty = np.zeros(0, np.uint64)
        return SessionPlan(
            workload=np.array([0.0, float(t["read"]), 0.0,
                               float(t["update"])]),
            kinds=kinds, point_keys=self.keys[rec[~upd]],
            range_los=empty, range_his=empty, write_keys=self.keys[rec[upd]])

    def request(self, i: int) -> int:
        from repro.lsm import execute_session
        k = i % self.n_pool
        plan = self.pool[k]
        execute_session(self.tree, plan,
                        f_a=float(self.config["system"]["f_a"]),
                        f_seq=float(self.config["system"]["f_seq"]))
        # acknowledged: the reference applies the request's writes
        self.model.update(self.pool_writes[k], 1)
        if self.lossy is not None and i % 2 == 0:
            self.lossy.update(self.pool_writes[k], 1)
        self.reads += len(plan.point_keys)
        self.writes += len(plan.write_keys)
        return plan.n_queries

    def counters(self) -> dict:
        io = self.tree.stats.minus(self.before)
        s = self.config["system"]
        return {"io": io, "reads": self.reads, "writes": self.writes,
                "f_a": float(s["f_a"]), "f_seq": float(s["f_seq"])}

    def checks(self) -> List[Check]:
        io = self.tree.stats.minus(self.before)
        q = io.queries
        count_gap = abs(q["z0"] + q["z1"] - self.reads) \
            + abs(q["w"] - self.writes) + q["q"]
        rec = self.model.sample(self.rng, int(self.traffic["readback"]))
        self.picked = rec
        got = self.tree.point_query_batch(self.keys[rec])
        wrong = self.model.wrong(rec, got)
        return [("lost_reads", float(q["z0"]), 0.0),
                ("op_count_gap", float(count_gap), 0.0),
                ("readback_wrong", float(wrong), 0.0)]

    def control_model(self) -> None:
        """Put the control in place (before the window)."""
        self.lossy = KVModel(self.keys)
        self.lossy.values[::100] = -1                   # lost records

    def control_checks(self, dtype=None) -> List[Check]:
        """The control's readings on the records :meth:`checks` read."""
        got = [None if v < 0 else v
               for v in self.lossy.values[self.picked].tolist()]
        return [("lost_reads", 0.0, 0.0), ("op_count_gap", 0.0, 0.0),
                ("readback_wrong",
                 float(self.model.wrong(self.picked, got)), 0.0)]


KINDS = {"experiment": TuneExperiment, "storm": RetuneStorm, "ycsb": YCSB}
