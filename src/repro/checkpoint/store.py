"""Checkpointing with an ENDURE-tuned LSM manifest — the paper's technique
as a first-class framework feature.

Tensor shards are written as flat ``.npy`` files; all *metadata* (manifest
entries, step registry, data-pipeline cursors, health heartbeats) lives in a
:class:`repro.lsm.LSMTree` whose tuning comes from the robust tuner: the
framework derives its expected storage workload mix from the run config
(checkpoint writes vs. restore reads vs. manifest scans) and an uncertainty
radius rho from the preemption-rate assumption, then deploys
``tune_robust(...)`` output via ``LSMTree.from_phi``.

Restore is *elastic*: tensors are saved with their global shape and layout
metadata and can be restored onto a different mesh/device count — each host
reads only the byte ranges its new shards need (here: full arrays on one
host, sliced per-shard on load).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import pathlib
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import LSMSystem, tune_robust_many
from repro.lsm import EngineConfig, LSMTree


def _key_of(name: str) -> int:
    """Manifest keys are uint64 hashes of the logical name."""
    return int.from_bytes(hashlib.blake2b(name.encode(),
                                          digest_size=8).digest(), "big")


def framework_storage_workload(ckpt_interval: int, restore_prob: float,
                               scan_frac: float = 0.05) -> np.ndarray:
    """Map run behaviour to the paper's (z0, z1, q, w) workload vector.

    writes  ~ manifest puts per checkpoint; z1 ~ restores + lookups;
    z0 ~ existence probes of absent steps; q ~ manifest scans (listing)."""
    w_write = 1.0 / max(ckpt_interval, 1) * 20
    z1 = 0.2 + restore_prob
    z0 = 0.1
    q = scan_frac
    v = np.array([z0, z1, q, w_write], np.float64)
    return v / v.sum()


def retune_storm(workloads, rhos, sys, seed: int = 0, design=None,
                 n_starts: int = 64, steps: int = 250, lr: float = 0.25,
                 pad_pow2: bool = False) -> list:
    """One batched tuner dispatch for a fleet-wide re-tuning storm.

    The storm path every online re-tune in the framework goes through: a
    batch of (workload, rho) re-tune requests — manifest stores after a
    config shift, :mod:`repro.online` drift triggers firing across a fleet —
    becomes ONE ``tune_robust_many`` grid (workloads on one axis, the
    distinct positive rhos on the other, each request picking its cell) plus
    one ``tune_nominal_many`` batch for the ``rho <= 0`` requests, instead
    of a per-request ``tune_robust`` loop.

    ``pad_pow2`` pads the workload axis to the next power of two with
    repeats of the last row (dropped from the result): storm sizes vary
    call-to-call, and the batched tuners recompile per distinct grid shape —
    bucketing shapes keeps a long-running adaptive loop to O(log fleet)
    compilations.  The vmap lanes are independent, so padding never changes
    the surviving results.

    Returns one :class:`repro.core.TuningResult` per request, in order."""
    W = np.atleast_2d(np.asarray(workloads, np.float64))
    R = np.asarray(rhos, np.float64).reshape(-1)
    if len(W) != len(R):
        raise ValueError(f"{len(W)} workloads for {len(R)} rhos")
    obs.count("tuner.storms")
    obs.count("tuner.storm_requests", len(W))
    n_nom = int((R <= 0).sum())
    n_rob_rhos = len(set(R[R > 0].tolist()))
    problems = _rows(n_nom, pad_pow2) \
        + _rows(len(W) - n_nom, pad_pow2) * n_rob_rhos
    with obs.span("tuner.storm", requests=len(W), pad_pow2=bool(pad_pow2),
                  problems=problems, tunings=len(W)):
        return _retune_storm(W, R, sys, seed, design, n_starts, steps, lr,
                             pad_pow2)


def _rows(n: int, pad_pow2: bool) -> int:
    """The rows a batch of ``n`` workloads takes (the next power of two
    with ``pad_pow2``)."""
    return 1 << (n - 1).bit_length() if pad_pow2 and n >= 2 else n


def _retune_storm(W, R, sys, seed, design, n_starts, steps, lr,
                  pad_pow2) -> list:
    from repro.core import tune_nominal_many
    kw = dict(n_starts=n_starts, steps=steps, lr=lr, seed=seed)
    if design is not None:
        kw["design"] = design

    def padded(M: np.ndarray) -> np.ndarray:
        P = _rows(len(M), pad_pow2)
        if P == len(M):
            return M
        return np.concatenate([M, np.repeat(M[-1:], P - len(M), axis=0)])

    out: list = [None] * len(W)
    nom = np.flatnonzero(R <= 0)
    if nom.size:
        res = tune_nominal_many(padded(W[nom]), sys, **kw)
        for i, r in zip(nom, res):
            out[i] = r
    rob = np.flatnonzero(R > 0)
    if rob.size:
        uniq = sorted(set(float(r) for r in R[rob]))
        grid = tune_robust_many(padded(W[rob]), uniq, sys, **kw)
        for row, i in zip(grid, rob):
            out[i] = row[uniq.index(float(R[i]))]
    return out


def tuned_manifest_trees(specs: Sequence[Dict[str, Any]],
                         seed: int = 0) -> list:
    """Deploy ENDURE-tuned manifests for a whole fleet in ONE tuner dispatch.

    ``specs`` is a sequence of dicts with the :func:`tuned_manifest_tree`
    keywords (``expected_entries``, ``ckpt_interval``, ``restore_prob``,
    ``rho``).  A re-tuning storm — every store in a fleet re-deriving its
    manifest tuning after a config/workload shift — goes through
    :func:`retune_storm` (one batched grid per distinct store size) instead
    of a per-(workload, rho) ``tune_robust`` loop.  Specs sharing
    ``expected_entries`` share a compiled sweep."""
    trees: list = [None] * len(specs)
    by_n: Dict[int, list] = {}
    for i, spec in enumerate(specs):
        by_n.setdefault(int(spec.get("expected_entries", 50_000)),
                        []).append(i)
    for n_entries, idxs in by_n.items():
        sys_small = LSMSystem(N=float(n_entries), entry_bits=256 * 8,
                              page_bits=4096 * 8, bits_per_entry=16.0,
                              min_buf_bits=256 * 8 * 64, s_rq=2e-5)
        W = [framework_storage_workload(
            specs[i].get("ckpt_interval", 100),
            specs[i].get("restore_prob", 0.3)) for i in idxs]
        rhos = [float(specs[i].get("rho", 1.0)) for i in idxs]
        tunings = retune_storm(np.stack(W), rhos, sys_small, seed=seed)
        for i, tuning in zip(idxs, tunings):
            trees[i] = LSMTree.from_phi(tuning.phi, sys_small,
                                        expected_entries=n_entries,
                                        entry_bytes=256)
    return trees


def tuned_manifest_tree(expected_entries: int = 50_000,
                        ckpt_interval: int = 100,
                        restore_prob: float = 0.3,
                        rho: float = 1.0,
                        seed: int = 0) -> LSMTree:
    """An LSM manifest whose (T, K, memory split) comes from ENDURE."""
    return tuned_manifest_trees([dict(expected_entries=expected_entries,
                                      ckpt_interval=ckpt_interval,
                                      restore_prob=restore_prob, rho=rho)],
                                seed=seed)[0]


@dataclasses.dataclass
class CheckpointStore:
    root: pathlib.Path
    manifest: LSMTree

    @classmethod
    def create(cls, root: str, **tuning_kw) -> "CheckpointStore":
        p = pathlib.Path(root)
        p.mkdir(parents=True, exist_ok=True)
        return cls(root=p, manifest=tuned_manifest_tree(**tuning_kw))

    # -- manifest KV helpers --------------------------------------------

    def _mput(self, name: str, value: Dict[str, Any]) -> None:
        self.manifest.put(_key_of(name), json.dumps(value))

    def _mget(self, name: str) -> Optional[Dict[str, Any]]:
        v = self.manifest.get(_key_of(name))
        return None if v is None else json.loads(v)

    # -- save / restore ----------------------------------------------------

    @staticmethod
    def _write_array(path: pathlib.Path, arr: np.ndarray) -> None:
        """One tensor file, atomically: serialize to memory, then temp +
        ``os.replace`` — a crash mid-save can leave an *unreferenced* file,
        never a torn ``.npy`` at a path the manifest points to."""
        import io
        from repro.faults import atomic_write_bytes
        buf = io.BytesIO()
        np.save(buf, arr)
        atomic_write_bytes(str(path), buf.getvalue())

    @staticmethod
    def _write_npz(path: pathlib.Path, arrays: Dict[str, np.ndarray]) -> None:
        import io
        from repro.faults import atomic_write_bytes
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        atomic_write_bytes(str(path), buf.getvalue())

    def save(self, step: int, params: Any, opt_state: Any = None,
             data_state: Optional[Dict[str, int]] = None) -> None:
        """Write one checkpoint crash-safely.

        Ordering is the durability contract (``docs/faults.md``): every
        tensor file and per-step manifest entry lands *before* the
        ``latest`` pointer flips, and each file write is atomic — so a save
        interrupted anywhere leaves ``latest_step()`` on the previous fully
        written checkpoint, which remains restorable, and never leaves a
        torn tensor file at a manifest-referenced path."""
        ckdir = self.root / f"step_{step:08d}"
        ckdir.mkdir(parents=True, exist_ok=True)
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        names = []
        for path, leaf in flat:
            name = jax.tree_util.keystr(path)
            arr = np.asarray(jax.device_get(leaf))
            if arr.dtype.name not in ("float32", "float64", "int32",
                                      "int64", "uint32", "uint64", "bool"):
                arr = arr.astype(np.float32)  # bf16 etc: store widened
            fname = hashlib.md5(name.encode()).hexdigest() + ".npy"
            self._write_array(ckdir / fname, arr)
            self._mput(f"tensor/{step}/{name}", {
                "file": fname, "shape": list(arr.shape),
                "dtype": str(arr.dtype)})
            names.append(name)
        extras: Dict[str, Any] = {"names": names, "step": step}
        if data_state is not None:
            extras["data_state"] = data_state
        self._mput(f"ckpt/{step}", extras)
        if opt_state is not None:
            def widen(l):
                a = np.asarray(jax.device_get(l))
                return a.astype(np.float32) if a.dtype.name == "bfloat16" \
                    else a
            self._write_npz(ckdir / "opt_state.npz", {
                f"s{i}": widen(l)
                for i, l in enumerate(jax.tree.leaves(opt_state))})
        # the commit point: everything above must already be durable
        self._mput("latest", {"step": step})
        self.manifest.flush()

    def latest_step(self) -> Optional[int]:
        v = self._mget("latest")
        return None if v is None else int(v["step"])

    def restore(self, params_like: Any, step: Optional[int] = None,
                shardings: Any = None) -> Tuple[Any, Dict[str, Any]]:
        """Restore onto (possibly different) shardings — elastic restart."""
        step = self.latest_step() if step is None else step
        assert step is not None, "no checkpoint found"
        meta = self._mget(f"ckpt/{step}")
        assert meta is not None, f"manifest missing ckpt/{step}"
        ckdir = self.root / f"step_{step:08d}"
        flat, treedef = jax.tree_util.tree_flatten_with_path(params_like)
        leaves = []
        for path, like in flat:
            name = jax.tree_util.keystr(path)
            info = self._mget(f"tensor/{step}/{name}")
            assert info is not None, f"manifest missing {name}"
            arr = np.load(ckdir / info["file"])
            assert list(arr.shape) == list(like.shape), (name, arr.shape,
                                                         like.shape)
            leaves.append(jnp.asarray(arr).astype(like.dtype))
        restored = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(params_like), leaves)
        if shardings is not None:
            restored = jax.tree.map(
                lambda a, s: jax.device_put(a, s), restored, shardings)
        return restored, meta

    def restore_opt_state(self, opt_like: Any, step: Optional[int] = None
                          ) -> Any:
        step = self.latest_step() if step is None else step
        z = np.load(self.root / f"step_{step:08d}" / "opt_state.npz")
        leaves = [jnp.asarray(z[f"s{i}"]).astype(l.dtype)
                  if hasattr(l, "dtype") else z[f"s{i}"]
                  for i, l in enumerate(jax.tree.leaves(opt_like))]
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(opt_like), leaves)

    # -- health / straggler bookkeeping (elastic.py reads these) -----------

    def heartbeat(self, worker: int, step: int, t: float) -> None:
        self._mput(f"hb/{worker}", {"step": step, "t": t})

    def heartbeats(self, workers: int) -> Dict[int, Dict[str, Any]]:
        out = {}
        for w in range(workers):
            v = self._mget(f"hb/{w}")
            if v is not None:
                out[w] = v
        return out
