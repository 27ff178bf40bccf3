"""An executable LSM-tree key-value engine with K-LSM compaction semantics.

This is the framework's "RocksDB": a real storage engine (memtable, immutable
sorted runs, Monkey Bloom filters, fence pointers, K_i-parameterized
compaction) with *exact logical-I/O accounting*, so that measured I/Os per
query can be compared against the paper's cost model — the Section 9
system-based evaluation, reproduced on CPU.

Architecture: three explicit layers
-----------------------------------
* **Storage** (:mod:`repro.lsm.store`) — a structure-of-arrays run store:
  each level keeps ALL of its runs in contiguous ``uint64`` key /
  ``int64`` encoded-value arenas with run-boundary offsets, per-run fence
  metadata, and per-run Bloom words packable into a level-wide bit matrix
  (:class:`repro.lsm.bloom.BloomPack`).  Values are int64-encoded (inline
  ints / interned objects / an integer tombstone sentinel), so merges and
  tombstone drops are pure vector ops.
* **Policy** (:mod:`repro.lsm.planner`) — pluggable compaction planners
  that read level-occupancy arrays and fence/tombstone metadata and emit
  :class:`repro.lsm.planner.MergePlan` values (which runs -> which level,
  optional key-range slice, drop-tombstones flag) as plain data.
  ``EngineConfig.policy`` selects from the design-space registry: the
  paper's K-LSM triggers (default), lazy leveling (read-pressure last-level
  squeeze), partial/partitioned compaction (key-range slices per trigger),
  or tombstone-TTL sweeps (bounded delete persistence) — see
  ``docs/compaction.md`` for the taxonomy mapping.
* **Execution** — this module's :class:`LSMTree` drives the
  plan-execute-replan loop on the write path and owns the batched read
  paths: ``point_query_batch`` probes a key batch against every run of a
  level at once (one shared hash round per level, sequential-equivalent
  I/O accounting) and ``range_query_batch`` runs one two-sided
  ``searchsorted`` per run for a whole batch of ranges.  Sessions execute
  on these primitives via :mod:`repro.lsm.workload_runner`.

Per-level semantics (paper Section 4.2):
  * Level i holds at most ``K_i`` sorted runs and at most
    ``(T-1) * T^(i-1) * buf_entries`` entries.
  * Incoming runs are eagerly merged into the level's *active* run until that
    run reaches its flush capacity (level_capacity / K_i); then a new run
    starts ("we only merge runs or logically move them").
  * When the level exceeds its entry capacity, a full-level compaction merges
    every run and pushes the result to level i+1.
  * ``K_i = 1`` reduces to leveling; ``K_i = T-1`` to tiering.

I/O accounting (paper Section 2 "Optimizing Lookups" assumptions):
  * A point lookup on a run costs exactly 1 random page I/O after a positive
    Bloom probe (fence pointers identify the page).
  * A range lookup costs 1 seek (random I/O) per overlapping run plus
    sequential I/Os for the subsequent pages.
  * Compactions read every input page and write every output page
    (sequential); buffer flushes write sequentially.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs

from .bloom import monkey_bits_per_key
from .planner import make_planner
from .read_path import point_read_level
from .store import TOMB, RunData, RunStore, pages_of

TOMBSTONE = object()


@dataclasses.dataclass
class IOStats:
    random_reads: int = 0        # random page reads (point lookups, seeks)
    seq_reads: int = 0           # sequential page reads (range scans)
    comp_pages_read: int = 0     # compaction input pages (sequential)
    comp_pages_written: int = 0  # compaction/flush output pages (sequential)
    bloom_probes: int = 0
    bloom_false_positives: int = 0
    queries: dict = dataclasses.field(
        default_factory=lambda: {"z0": 0, "z1": 0, "q": 0, "w": 0})

    def snapshot(self) -> "IOStats":
        return dataclasses.replace(self, queries=dict(self.queries))

    def minus(self, other: "IOStats") -> "IOStats":
        return IOStats(
            random_reads=self.random_reads - other.random_reads,
            seq_reads=self.seq_reads - other.seq_reads,
            comp_pages_read=self.comp_pages_read - other.comp_pages_read,
            comp_pages_written=self.comp_pages_written - other.comp_pages_written,
            bloom_probes=self.bloom_probes - other.bloom_probes,
            bloom_false_positives=self.bloom_false_positives
            - other.bloom_false_positives,
            queries={k: self.queries[k] - other.queries[k]
                     for k in self.queries},
        )

    def as_dict(self) -> dict:
        """Plain-dict view (telemetry span attributes, JSON sinks)."""
        return {
            "random_reads": self.random_reads,
            "seq_reads": self.seq_reads,
            "comp_pages_read": self.comp_pages_read,
            "comp_pages_written": self.comp_pages_written,
            "bloom_probes": self.bloom_probes,
            "bloom_false_positives": self.bloom_false_positives,
            "queries": dict(self.queries),
        }

    def io_per_query(self, f_a: float = 1.0, f_seq: float = 1.0) -> dict:
        """Measured average logical I/O per query class, write-amortized the
        way the paper does (compaction I/O redistributed over writes)."""
        n = self.queries
        reads = max(n["z0"] + n["z1"] + n["q"], 1)
        out = {}
        out["read_io"] = (self.random_reads + f_seq * self.seq_reads) / reads
        writes = max(n["w"], 1)
        out["write_io"] = (f_seq * (self.comp_pages_read
                                    + f_a * self.comp_pages_written)) / writes
        return out


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    T: int = 4
    K: Tuple[int, ...] = ()            # per-level caps; empty -> leveling
    buf_entries: int = 1024            # memtable capacity (entries)
    entry_bytes: int = 64
    page_bytes: int = 4096
    mfilt_bits_per_entry: float = 10.0  # Monkey budget, bits per *total* entry
    expected_entries: int = 200_000     # N used for Monkey allocation + L
    #: compaction policy name (see repro.lsm.planner.POLICIES) + its
    #: constructor params as (name, value) pairs (kept a tuple so the
    #: config stays hashable)
    policy: str = "klsm"
    policy_params: Tuple[Tuple[str, Any], ...] = ()

    @property
    def entries_per_page(self) -> int:
        return max(1, self.page_bytes // self.entry_bytes)

    def k_at(self, level: int) -> int:
        """1-indexed level -> K_i, clamped to [1, T-1]."""
        if level - 1 < len(self.K):
            k = self.K[level - 1]
        elif len(self.K) > 0:
            k = self.K[-1]
        else:
            k = 1
        return int(max(1, min(k, self.T - 1)))

    @property
    def est_levels(self) -> int:
        ratio = self.expected_entries / self.buf_entries
        return max(1, int(math.ceil(math.log(ratio + 1, self.T))))


class LSMTree:
    """The engine. Keys: ints (uint64 range); values: arbitrary objects."""

    def __init__(self, config: EngineConfig):
        self.cfg = config
        self.buffer: dict = {}           # int key -> int64-encoded value
        self.store = RunStore(config.entries_per_page)
        self.planner = make_planner(config)
        self.stats = IOStats()
        self.flush_seq = 0               # logical clock: flushes so far
        #: telemetry track label (``"<tenant-or-cell>/<policy>"`` by fleet
        #: convention); "" keeps this tree on the main trace track
        self.obs_label = ""
        #: intern-table sweep threshold (doubling schedule): the codec table
        #: is reclaimed when it crosses this, keeping it within 2x the live
        #: object count.  Int-only workloads never intern and never sweep.
        self._intern_sweep_at = 64

    # -- construction from a tuning -------------------------------------

    @staticmethod
    def config_from_phi(phi, sys, expected_entries: int,
                        buf_entries: Optional[int] = None,
                        entry_bytes: int = 64, page_bytes: int = 4096,
                        policy: str = "klsm",
                        policy_params: Tuple[Tuple[str, Any], ...] = ()
                        ) -> EngineConfig:
        """Lower a tuner-recommended Phi to an :class:`EngineConfig` at
        reduced scale.

        The *shape* of the tuning (T, K profile, filter bits/entry) carries
        over; N/buffer are scaled to CPU-testable sizes with the memory split
        preserved as bits-per-entry."""
        import numpy as _np
        T = int(float(phi.T))
        K = tuple(int(k) for k in _np.asarray(phi.K))
        m_total_bpe = sys.bits_per_entry
        filt_bpe = float(phi.mfilt_bits) / sys.N
        assert filt_bpe <= 1024, (
            f"filter bits/entry = {filt_bpe:.3g}: `sys` must be the SAME "
            "LSMSystem the tuning was produced under (mfilt_bits is "
            "normalized by sys.N)")
        buf_bpe = m_total_bpe - filt_bpe
        if buf_entries is None:
            # preserve buffer share: buf_bits = buf_bpe * N_small
            buf_bits = buf_bpe * expected_entries
            buf_entries = max(64, int(buf_bits / (entry_bytes * 8)))
        return EngineConfig(T=T, K=K, buf_entries=buf_entries,
                            entry_bytes=entry_bytes, page_bytes=page_bytes,
                            mfilt_bits_per_entry=filt_bpe,
                            expected_entries=expected_entries,
                            policy=policy, policy_params=tuple(policy_params))

    @classmethod
    def from_phi(cls, phi, sys, expected_entries: int,
                 buf_entries: Optional[int] = None,
                 entry_bytes: int = 64, page_bytes: int = 4096,
                 policy: str = "klsm",
                 policy_params: Tuple[Tuple[str, Any], ...] = ()) -> "LSMTree":
        """Deploy a tuner-recommended Phi at reduced scale
        (see :meth:`config_from_phi`)."""
        return cls(cls.config_from_phi(
            phi, sys, expected_entries, buf_entries=buf_entries,
            entry_bytes=entry_bytes, page_bytes=page_bytes, policy=policy,
            policy_params=policy_params))

    def retune(self, phi, sys) -> None:
        """Swap the deployed tuning in place, at a flush boundary.

        The online re-tuning primitive (:mod:`repro.online`): the write
        buffer is flushed under the OLD tuning (so the swap lands exactly on
        a flush boundary), then the config and planner are replaced.  The
        adaptation is *gradual*, as in a live LSM deployment: existing runs
        keep their Bloom allocations and layout; new flushes, merges, and
        capacity triggers follow the new (T, K, memory split), so the tree
        converges to the new shape through normal compaction — whose I/O is
        charged to ``stats`` like any other compaction (the transition cost
        is real and measured, not waved away).  Engine-scale knobs
        (``expected_entries``, entry/page bytes) and the compaction policy
        carry over from the current config.  A re-tune that resolves to the
        CURRENT config is a no-op (no forced flush): an adaptive loop may
        re-derive the same integral tuning every window without perturbing
        the tree."""
        cfg = self.config_from_phi(
            phi, sys, self.cfg.expected_entries,
            entry_bytes=self.cfg.entry_bytes,
            page_bytes=self.cfg.page_bytes, policy=self.cfg.policy,
            policy_params=self.cfg.policy_params)
        if cfg == self.cfg:
            obs.count("engine.retune.noop")
            return
        obs.count("engine.retune")
        with obs.track(self.obs_label), \
                obs.span("engine.retune", policy=cfg.policy,
                         T=cfg.T, buf_entries=cfg.buf_entries):
            self.flush()
            self.cfg = cfg
            self.planner = make_planner(cfg)
            self._maintain()

    # -- bits allocation --------------------------------------------------

    def _bits_per_key(self, level: int) -> float:
        return monkey_bits_per_key(
            level, self.cfg.est_levels, float(self.cfg.T),
            self.cfg.mfilt_bits_per_entry * self.cfg.expected_entries,
            float(self.cfg.expected_entries))

    # -- write path --------------------------------------------------------

    def _encode(self, value: Any) -> int:
        if value is TOMBSTONE:
            return TOMB
        return self.store.codec.encode(value)

    def put(self, key: int, value: Any) -> None:
        self.stats.queries["w"] += 1
        self.buffer[int(key)] = self._encode(value)
        if len(self.buffer) >= self.cfg.buf_entries:
            self.flush()

    def delete(self, key: int) -> None:
        self.put(key, TOMBSTONE)

    def put_batch(self, keys, values: Sequence[Any]) -> None:
        """Bulk insert in buffer-sized chunks; equivalent to sequential
        :meth:`put` calls without the per-key Python overhead: same flush
        boundaries (chunks are cut to the buffer's remaining room) and same
        newest-wins semantics (insertion order is preserved, so later
        duplicates overwrite earlier ones; :meth:`flush` sorts each run)."""
        keys = np.asarray(keys, np.uint64)
        n = len(keys)
        if len(values) != n:
            raise ValueError(f"put_batch: {n} keys but {len(values)} values")
        int_vals = isinstance(values, np.ndarray) and values.dtype.kind in "iu"
        i = 0
        while i < n:
            room = max(1, self.cfg.buf_entries - len(self.buffer))
            chunk = keys[i:i + room]
            vals = values[i:i + room]
            # Encode per chunk, never ahead of insertion: a flush at a chunk
            # boundary may run the intern-table sweep, which only sees slots
            # already in the buffer/arenas — pre-encoded pending values
            # would be swept as dead and their slot ids dangle.
            if int_vals:
                enc = self.store.codec.encode_many(vals)
            else:
                # object dtypes route per-element so TOMBSTONE maps to TOMB
                enc = np.fromiter((self._encode(v) for v in vals), np.int64,
                                  len(chunk))
            self.buffer.update(zip(chunk.tolist(), enc.tolist()))
            self.stats.queries["w"] += len(chunk)
            i += len(chunk)
            if len(self.buffer) >= self.cfg.buf_entries:
                self.flush()

    def flush(self) -> None:
        if not self.buffer:
            return
        obs.count("engine.flush")
        with obs.span("engine.flush", entries=len(self.buffer)):
            n = len(self.buffer)
            keys = np.fromiter(self.buffer.keys(), np.uint64, n)
            vals = np.fromiter(self.buffer.values(), np.int64, n)
            order = np.argsort(keys)
            self.flush_seq += 1
            tomb_seq = self.flush_seq if bool((vals == TOMB).any()) else -1
            run = RunData.build(keys[order], vals[order],
                                self._bits_per_key(1), flushes=1,
                                tomb_seq=tomb_seq)
            self.stats.comp_pages_written += pages_of(
                len(run), self.cfg.entries_per_page)   # sequential flush
            self.buffer.clear()
            self._push_run(1, run)
            self._maintain()
            # Compaction-time intern reclamation: the buffer is empty here,
            # so every live interned slot is visible in the level arenas.
            if len(self.store.codec.objects) >= self._intern_sweep_at:
                self.store.reclaim_interned()
                self._intern_sweep_at = max(
                    64, 2 * len(self.store.codec.objects))

    def _execute_plan(self, plan, run, bpk):
        """``store.execute`` under the ``engine.compact`` span, with
        telemetry attached: plan kinds and compactions per policy as
        counters, compaction page deltas on the span."""
        with obs.span("engine.compact", kind=plan.kind) as sp:
            s = self.stats
            read0, written0 = s.comp_pages_read, s.comp_pages_written
            out = self.store.execute(plan, run, s, bpk)
            obs.count("engine.plan." + plan.kind)
            obs.count("engine.compaction." + self.cfg.policy)
            sp.set(comp_pages_read=s.comp_pages_read - read0,
                   comp_pages_written=s.comp_pages_written - written0)
            return out

    def _push_run(self, level: int, run: RunData) -> None:
        """Plan-execute-replan until the incoming run finds a home."""
        while True:
            occ = self.store.occupancy(min_levels=level)
            plan = self.planner.plan_push(occ, level, len(run), run.flushes)
            if plan.kind == "spill":
                run = self._execute_plan(plan, run,
                                         self._bits_per_key(level + 1))
                level += 1
                continue
            bpk = self._bits_per_key(level)
            self._execute_plan(plan, run, bpk)
            for clamp in self.planner.plan_clamps(
                    self.store.occupancy(min_levels=level), level):
                self._execute_plan(clamp, None, bpk)
            return

    def _maintain(self) -> None:
        """Poll the planner's maintenance hook until it is satisfied.

        Read-pressure squeezes (lazy leveling), over-capacity partial
        spills, and tombstone-TTL sweeps all arrive through here as the
        same :class:`~repro.lsm.planner.MergePlan` vocabulary the write
        path executes.  Spill-kind plans re-enter :meth:`_push_run` at
        their target level, so a maintenance merge cascades through the
        same plan-execute-replan loop an overflowing flush would.  The
        K-LSM planner has no maintenance; this is a no-op for it."""
        if not self.planner.has_maintenance:
            return
        for _ in range(100_000):
            plans = self.planner.plan_maintenance(self.store, self.stats,
                                                  self.flush_seq)
            if not plans:
                return
            for plan in plans:
                # merge outputs live at target_level, so they take ITS
                # Monkey bits budget (only in-level plans stay at level)
                bpk = self._bits_per_key(plan.target_level)
                if plan.kind == "spill":
                    out = self._execute_plan(plan, None, bpk)
                    if len(out):
                        self._push_run(plan.target_level, out)
                else:
                    self._execute_plan(plan, None, bpk)
        raise RuntimeError(
            f"{type(self.planner).__name__}.plan_maintenance did not "
            "converge within 100000 rounds")

    # -- read path ----------------------------------------------------------

    def _buffer_sorted(self) -> Tuple[np.ndarray, np.ndarray]:
        bkeys = np.fromiter(self.buffer.keys(), np.uint64, len(self.buffer))
        benc = np.fromiter(self.buffer.values(), np.int64, len(self.buffer))
        order = np.argsort(bkeys)
        return bkeys[order], benc[order]

    @staticmethod
    def resolve_in_sorted(bkeys: np.ndarray, benc: np.ndarray,
                          keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(hit, encoded) membership of ``keys`` in a sorted (keys, enc)
        buffer view — the one buffer-resolution primitive shared by the
        engine's read path and the session executor's window simulation."""
        loc = np.searchsorted(bkeys, keys)
        inb = loc < len(bkeys)
        hit = np.zeros(len(keys), bool)
        hit[inb] = bkeys[loc[inb]] == keys[inb]
        henc = benc[loc[hit]] if hit.any() else np.empty(0, np.int64)
        return hit, henc

    def _lookup_batch(self, keys_arr: np.ndarray,
                      resolved: Optional[np.ndarray] = None,
                      found: Optional[np.ndarray] = None,
                      enc: Optional[np.ndarray] = None,
                      use_buffer: bool = True
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """(found, encoded_values) for a key batch.

        Visits the buffer, then every level newest -> oldest.  Bloom probes
        run as one whole-level :class:`BloomPack` probe, but probe /
        random-read / false-positive counts follow the sequential visit
        order (a key resolved by a newer run is not probed in older ones),
        so ``IOStats`` is identical to per-key execution.

        Callers that already resolved some keys upstream (the session
        executor accounts an evolving write buffer itself) pass the partial
        ``resolved``/``found``/``enc`` state and ``use_buffer=False``."""
        n = len(keys_arr)
        resolved = np.zeros(n, bool) if resolved is None else resolved
        found = np.zeros(n, bool) if found is None else found
        enc = np.zeros(n, np.int64) if enc is None else enc
        if use_buffer and self.buffer:
            if n == 1:        # scalar get/point_query: O(1) dict probe
                v = self.buffer.get(int(keys_arr[0]))
                if v is not None:
                    resolved[0] = True
                    found[0] = v != TOMB
                    enc[0] = v
            else:
                bkeys, benc = self._buffer_sorted()
                hit, henc = self.resolve_in_sorted(bkeys, benc, keys_arr)
                if hit.any():
                    resolved |= hit
                    found[hit] = henc != TOMB
                    enc[hit] = henc
        stats = self.stats
        for lv in self.store.levels:
            if lv.num_runs == 0:
                continue
            sub = np.flatnonzero(~resolved)     # still-unresolved query ids
            if sub.size == 0:
                break
            # Fused per-level read (Bloom probe + fence + binary search);
            # every implementation behind the dispatch keeps the exact
            # sequential-equivalent counters — see lsm/read_path.py.
            hit, henc, probes, reads, fps = point_read_level(
                lv, keys_arr[sub])
            stats.bloom_probes += probes
            stats.random_reads += reads
            stats.bloom_false_positives += fps
            if hit.any():
                gidx = sub[hit]
                venc = henc[hit]
                resolved[gidx] = True
                found[gidx] = venc != TOMB
                enc[gidx] = venc
        return found, enc

    def get(self, key: int) -> Optional[Any]:
        found, enc = self._lookup_batch(np.asarray([key], np.uint64))
        return self.store.codec.decode(enc[0]) if found[0] else None

    def point_query(self, key: int) -> Optional[Any]:
        """A classified point query (updates z0/z1 accounting)."""
        found, enc = self._lookup_batch(np.asarray([key], np.uint64))
        self.stats.queries["z1" if found[0] else "z0"] += 1
        out = self.store.codec.decode(enc[0]) if found[0] else None
        self._maintain()     # read-triggered policies (lazy leveling)
        return out

    def point_query_batch(self, keys) -> List[Optional[Any]]:
        """Classified point queries for a key batch; equivalent to
        ``[point_query(k) for k in keys]`` (same run visit order, same I/O
        and bloom accounting, same z0/z1 classification)."""
        keys_arr = np.asarray(keys, np.uint64)
        found, enc = self.classify_point_batch(keys_arr)
        results: List[Optional[Any]] = [None] * len(keys_arr)
        idx = np.flatnonzero(found)
        for i, v in zip(idx.tolist(),
                        self.store.codec.decode_many(enc[idx])):
            results[i] = v
        return results

    def classify_point_batch(self, keys_arr: np.ndarray,
                             resolved: Optional[np.ndarray] = None,
                             found: Optional[np.ndarray] = None,
                             enc: Optional[np.ndarray] = None,
                             use_buffer: bool = True
                             ) -> Tuple[np.ndarray, np.ndarray]:
        """The accounting core of :meth:`point_query_batch`, without
        materializing a Python result list (the fleet executor's path),
        under the ``engine.read`` span (with its ``IOStats`` deltas)."""
        with obs.span("engine.read", keys=len(keys_arr)) as sp:
            s = self.stats
            before = (s.bloom_probes, s.bloom_false_positives,
                      s.random_reads)
            found, enc = self._lookup_batch(keys_arr, resolved=resolved,
                                            found=found, enc=enc,
                                            use_buffer=use_buffer)
            nz1 = int(found.sum())
            s.queries["z1"] += nz1
            s.queries["z0"] += len(keys_arr) - nz1
            sp.set(bloom_probes=s.bloom_probes - before[0],
                   bloom_false_positives=s.bloom_false_positives - before[1],
                   random_reads=s.random_reads - before[2])
            self._maintain()     # read-triggered policies fire at batch ends
            return found, enc

    def range_query(self, lo: int, hi: int) -> List[Tuple[int, Any]]:
        return self.range_query_batch([lo], [hi], return_results=True)[0]

    def range_query_batch(self, los, his, return_results: bool = False
                          ) -> Optional[List[List[Tuple[int, Any]]]]:
        """A batch of inclusive-lo, exclusive-hi range queries.

        Per run: one two-sided ``searchsorted`` for the whole batch; each
        overlapping (query, run) pair counts 1 seek + sequential page reads,
        exactly like the per-query path.  With ``return_results`` the
        newest-wins merge across runs + buffer happens in one global
        (query, key, recency) lexsort; without it (workload sessions discard
        range results) only the accounting runs."""
        los = np.asarray(los, np.uint64)
        his = np.asarray(his, np.uint64)
        Q = len(los)
        self.stats.queries["q"] += Q
        if obs.enabled():
            obs.count("engine.range.batches")
            obs.count("engine.range.queries", Q)
        epp = self.cfg.entries_per_page
        pieces = []                         # (qid, keys, vals, recency)
        recency = 0
        for lv in self.store.levels:
            for r in range(lv.num_runs):    # newest -> oldest
                if lv.run_len(r) == 0:
                    recency += 1
                    continue
                # fence fast-path: runs no query overlaps cost nothing
                if not ((los <= lv.max_keys[r]) & (his > lv.min_keys[r])
                        ).any():
                    recency += 1
                    continue
                rkeys, rvals = lv.run_slice(r)
                i = np.searchsorted(rkeys, los, side="left")
                j = np.searchsorted(rkeys, his, side="left")
                ov = i < j
                n_ov = int(ov.sum())
                if n_ov:
                    self.stats.random_reads += n_ov           # the seeks
                    self.stats.seq_reads += int(
                        ((j[ov] - 1) // epp - i[ov] // epp).sum())
                    if return_results:
                        idx, qid = _multi_ranges(i[ov], j[ov],
                                                 np.flatnonzero(ov))
                        pieces.append((qid, rkeys[idx], rvals[idx],
                                       np.full(len(idx), recency, np.int64)))
                recency += 1
        self._maintain()     # range seeks count as read pressure too
        if not return_results:
            return None
        if self.buffer:                     # newest of all: recency -1
            bkeys, benc = self._buffer_sorted()
            i = np.searchsorted(bkeys, los, side="left")
            j = np.searchsorted(bkeys, his, side="left")
            ov = i < j
            if ov.any():
                idx, qid = _multi_ranges(i[ov], j[ov], np.flatnonzero(ov))
                pieces.append((qid, bkeys[idx], benc[idx],
                               np.full(len(idx), -1, np.int64)))
        results: List[List[Tuple[int, Any]]] = [[] for _ in range(Q)]
        if not pieces:
            return results
        qid = np.concatenate([p[0] for p in pieces])
        keys = np.concatenate([p[1] for p in pieces])
        vals = np.concatenate([p[2] for p in pieces])
        rec = np.concatenate([p[3] for p in pieces])
        order = np.lexsort((rec, keys, qid))
        qid, keys, vals = qid[order], keys[order], vals[order]
        keep = np.ones(len(qid), bool)      # first (newest) version per
        keep[1:] = (qid[1:] != qid[:-1]) | (keys[1:] != keys[:-1])  # (q, key)
        sel = keep & (vals != TOMB)
        qs = qid[sel].tolist()
        ks = keys[sel].tolist()
        vs = self.store.codec.decode_many(vals[sel])
        for q, k, v in zip(qs, ks, vs):
            results[q].append((k, v))
        return results

    # -- introspection --------------------------------------------------------

    @property
    def num_entries(self) -> int:
        return len(self.buffer) + self.store.total_entries

    def shape(self) -> List[Tuple[int, List[int]]]:
        """[(level, [run sizes])] for non-empty levels."""
        return self.store.shape()

    def filter_bits_in_use(self) -> int:
        return self.store.filter_bits_in_use()


def _multi_ranges(starts: np.ndarray, ends: np.ndarray, qids: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Flatten [starts, ends) index ranges into one gather-index array plus
    the query id of every gathered element."""
    lens = (ends - starts).astype(np.int64)
    total = int(lens.sum())
    offs = np.concatenate([np.zeros(1, np.int64), np.cumsum(lens)[:-1]])
    idx = (np.arange(total, dtype=np.int64) - np.repeat(offs, lens)
           + np.repeat(starts.astype(np.int64), lens))
    return idx, np.repeat(qids, lens)
