"""Tests for the scenario subsystem (repro.scenarios) and its satellites.

Covers the registry and spec validation, schedule lowering, the
statistical shape of each generator (Zipf tail, burst amplitude,
tombstone fraction), rng-sequence preservation for default parameters
(the bit-identity contract with classic sessions), the adversary's
inner-max against a hand-computed symmetric golden, the Page-Hinkley
change-point trigger, overlap-based partial-compaction slice selection,
the uint32-limb splitmix64 bit-identity, and the five scenario kinds end
to end on all three execution backends with inline/sharded/subprocess
bit-identity.

Solver sizes match test_online_drift's SMALL so the jit cache is shared.
"""

import numpy as np
import pytest

from repro.core import LSMSystem, tune_nominal
from repro.lsm import EngineConfig, LSMTree, execute_session, \
    materialize_session, populate
from repro.lsm.planner import PartialCompactionPlanner
from repro.online import DriftPolicy, OnlineSession, PageHinkleyDetector
from repro.scenarios import SCENARIO_KINDS, SCENARIOS, get_scenario, \
    validate_scenario_params

SMALL = dict(n_starts=8, steps=60, seed=3)
SYS_PAIRS = (("N", 8000.0), ("entry_bits", 512.0), ("bits_per_entry", 6.0),
             ("min_buf_bits", 512.0 * 64), ("max_T", 20.0))
SYS = LSMSystem().replace(**dict(SYS_PAIRS))


def _api():
    from repro import api
    return api


def _drift(kind, **kw):
    api = _api()
    kw.setdefault("segments", 4)
    return api.DriftSpec(kind=kind, **kw)


# ---------------------------------------------------------------------------
# Registry + spec validation
# ---------------------------------------------------------------------------

def test_registry_kinds_and_knob_validation():
    assert SCENARIO_KINDS == {"zipf_migrate", "burst_storm",
                              "tombstone_churn", "scan_heavy", "adversary"}
    for kind, cls in SCENARIOS.items():
        sc = get_scenario(_drift(kind))
        assert isinstance(sc, cls) and sc.kind == kind
        assert sc.is_adversary == (kind == "adversary")
    # classic kinds have no scenario
    assert get_scenario(_drift("flip", target=(0.3, 0.3, 0.3, 0.1))) is None
    with pytest.raises(ValueError):
        _drift("mystery_kind", target=(0.3, 0.3, 0.3, 0.1))
    # unknown knob names are rejected at spec construction
    with pytest.raises(ValueError, match="zipf_migrate"):
        _drift("zipf_migrate", scenario_params=(("zip_a", 1.5),))
    with pytest.raises(ValueError):
        validate_scenario_params("burst_storm", (("volume", 2.0),))
    validate_scenario_params("burst_storm", (("amplitude", 2.0),))
    # value-range checks live in the constructors and fire at spec time
    with pytest.raises(ValueError, match=r"\[1, 1000\]"):
        _drift("burst_storm", scenario_params=(("amplitude", 2000.0),))
    with pytest.raises(ValueError, match="period"):
        _drift("burst_storm", scenario_params=(("period", 1),))
    with pytest.raises(ValueError, match=r"\[0, 1\]"):
        _drift("tombstone_churn", scenario_params=(("delete_fraction", 1.5),))
    with pytest.raises(ValueError, match="rho"):
        _drift("adversary", scenario_params=(("rho", -0.1),))
    # scenario_params on a classic kind is a spec error
    with pytest.raises(ValueError, match="scenario_params"):
        _drift("flip", target=(0.3, 0.3, 0.3, 0.1),
               scenario_params=(("zipf_a", 1.5),))
    with pytest.raises(ValueError, match="detector"):
        _drift("zipf_migrate", detector="cusum_but_wrong")


def test_scenario_spec_json_round_trip_and_memory_guard():
    api = _api()
    spec = api.ExperimentSpec(
        name="rt",
        workload=api.WorkloadSpec(indices=(4,), nominal=True,
                                  rho_source="from_history",
                                  history=((0.01, 0.01, 0.01, 0.97),
                                           (0.3, 0.3, 0.3, 0.1))),
        drift=api.DriftSpec(kind="burst_storm", segments=4,
                            scenario_params=(("amplitude", 4.0),
                                             ("period", 2)),
                            detector="page_hinkley", ph_lambda=0.1))
    assert api.ExperimentSpec.from_json(spec.to_json()) == spec
    # the adversary needs a drift defender arm; memory fleets have none
    with pytest.raises(ValueError, match="adversary"):
        api.ExperimentSpec(
            name="bad",
            workload=api.WorkloadSpec(indices=(4,), rhos=(1.0,)),
            drift=api.DriftSpec(kind="adversary", segments=2),
            memory=api.MemorySpec())


def test_schedules_lower_onto_drift_plan():
    """Every scenario kind produces a normalized (S, 4) schedule tilted
    the way its docstring promises."""
    from repro.api.compile import drift_schedule
    w0 = np.array([0.01, 0.01, 0.01, 0.97])
    for kind in SCENARIO_KINDS:
        sched = drift_schedule(w0, _drift(kind, segments=6))
        assert sched.shape == (6, 4)
        np.testing.assert_allclose(sched.sum(axis=1), 1.0, atol=1e-12)
        np.testing.assert_allclose(sched[0], w0 / w0.sum(), atol=1e-12)
    zipf = drift_schedule(w0, _drift("zipf_migrate", segments=6))
    assert zipf[-1][1] > 0.5                       # non-empty-read dominant
    tomb = drift_schedule(w0, _drift("tombstone_churn", segments=6))
    assert all(row[3] > 0.5 for row in tomb[1:])   # write dominant from s=1
    scan = drift_schedule(w0, _drift("scan_heavy", segments=6))
    assert scan[-1][2] > 0.5                       # range dominant
    burst = drift_schedule(
        w0, _drift("burst_storm", segments=6,
                   scenario_params=(("period", 3),)))
    quiet, stormy = burst[0], burst[2]             # period 3: s=2, 5 burst
    assert stormy[0] + stormy[1] > quiet[0] + quiet[1]


# ---------------------------------------------------------------------------
# Statistical shape of the generators
# ---------------------------------------------------------------------------

def _tree_and_keys(n=1500, buf=64):
    tree = LSMTree(EngineConfig(T=4, buf_entries=buf,
                                mfilt_bits_per_entry=6.0,
                                expected_entries=n))
    keys = populate(tree, n, seed=11, key_space=2 ** 20)
    return tree, keys


def test_zipf_tail_concentration():
    _, keys = _tree_and_keys()
    sc = get_scenario(_drift("zipf_migrate", n_queries=2000))
    kw = sc.session_kwargs(0, len(keys))
    assert kw["hot_offset"] == 0                   # no migration at s=0
    plan = materialize_session(keys, (0.02, 0.93, 0.02, 0.03),
                               n_queries=2000, seed=5, key_space=2 ** 20,
                               **kw)
    pts = plan.point_keys[plan.kinds[plan.kinds <= 1] == 1]
    _, counts = np.unique(pts, return_counts=True)
    top_share = counts.max() / len(pts)
    # Zipf(1.35): the rank-1 key draws ~30% of hits; uniform would be 1/n
    assert top_share > 0.15
    assert top_share > 100.0 / len(keys)


def test_hot_offset_is_pure_rotation():
    """hot_offset=0 is bit-identical to the classic draw; a nonzero offset
    maps every non-empty read through the same rotated rank->key table
    without touching any other draw (the rng-sequence contract)."""
    _, keys = _tree_and_keys()
    mix = (0.1, 0.6, 0.1, 0.2)
    base = materialize_session(keys, mix, n_queries=800, seed=7,
                               key_space=2 ** 20)
    same = materialize_session(keys, mix, n_queries=800, seed=7,
                               key_space=2 ** 20, hot_offset=0)
    for f in ("kinds", "point_keys", "range_los", "range_his", "write_keys"):
        assert np.array_equal(getattr(base, f), getattr(same, f)), f
    off = 123
    shifted = materialize_session(keys, mix, n_queries=800, seed=7,
                                  key_space=2 ** 20, hot_offset=off)
    # every non-kind-1 draw is untouched
    assert np.array_equal(base.kinds, shifted.kinds)
    assert np.array_equal(base.range_los, shifted.range_los)
    assert np.array_equal(base.write_keys, shifted.write_keys)
    pos = {int(k): i for i, k in enumerate(keys)}
    is_z1 = base.kinds[base.kinds <= 1] == 1
    for b, s in zip(base.point_keys[is_z1], shifted.point_keys[is_z1]):
        assert pos[int(s)] == (pos[int(b)] + off) % len(keys)
    # empty reads (high-bit perturbed) are identical
    assert np.array_equal(base.point_keys[~is_z1],
                          shifted.point_keys[~is_z1])


def test_burst_amplitude_and_volume():
    sc = get_scenario(_drift("burst_storm", segments=6, n_queries=200,
                             scenario_params=(("amplitude", 7.0),
                                              ("period", 3))))
    vols = [sc.segment_queries(s) for s in range(6)]
    assert vols == [200, 200, 1400, 200, 200, 1400]
    sc_max = get_scenario(_drift("burst_storm", n_queries=10,
                                 scenario_params=(("amplitude", 1000.0),
                                                  ("period", 2))))
    assert sc_max.segment_queries(1) == 10_000     # the 1000x ceiling works


def test_tombstone_fraction_and_delete_execution():
    tree, keys = _tree_and_keys()
    mix = (0.05, 0.1, 0.05, 0.8)
    base = materialize_session(keys, mix, n_queries=1000, seed=9,
                               key_space=2 ** 20)
    plan = materialize_session(keys, mix, n_queries=1000, seed=9,
                               key_space=2 ** 20, delete_fraction=0.5)
    # the classic draws are untouched: deletes are drawn after the loop
    assert np.array_equal(base.kinds, plan.kinds)
    assert np.array_equal(base.point_keys, plan.point_keys)
    n_w = len(plan.write_keys)
    assert plan.write_tombs is not None and len(plan.write_tombs) == n_w
    frac = plan.write_tombs.mean()
    assert abs(frac - 0.5) < 2.0 / n_w             # rounding only
    # non-delete slots keep the fresh draw; delete slots target OLD keys
    keep = ~plan.write_tombs
    assert np.array_equal(plan.write_keys[keep], base.write_keys[keep])
    targets = plan.write_keys[plan.write_tombs]
    old_half = set(int(k) for k in keys[:len(keys) // 2])
    assert all(int(t) in old_half for t in targets)
    assert np.array_equal(plan.insert_keys, plan.write_keys[keep])
    assert np.array_equal(base.insert_keys, base.write_keys)
    # execution: deleted keys must read as absent afterwards
    res = execute_session(tree, plan)
    assert res.avg_io_per_query > 0
    tree.flush()
    for t in targets[:32]:
        assert tree.get(int(t)) is None, int(t)
    # a surviving fresh insert is present
    assert tree.get(int(plan.insert_keys[0])) is not None


def test_scan_heavy_widens_ranges():
    sc = get_scenario(_drift("scan_heavy", segments=5, range_fraction=1e-4,
                             scenario_params=(("scan_scale", 6.0),)))
    rf0 = sc.session_kwargs(0, 1000)["range_fraction"]
    rf_last = sc.session_kwargs(4, 1000)["range_fraction"]
    assert abs(rf0 - 1e-4) < 1e-12
    assert abs(rf_last - 6e-4) < 1e-12


# ---------------------------------------------------------------------------
# Adversary: hand-computed symmetric golden + live attack
# ---------------------------------------------------------------------------

def test_adversary_inner_max_symmetric_golden():
    """For cost e4 and the uniform center, the tilted worst case is
    ((1-p)/3, ..., p) with p pinned by the hand-derived KL equation
    p*ln(4p) + (1-p)*ln(4(1-p)/3) = rho — solved here by independent
    bisection, not by the library under test."""
    from repro.core import worst_case_workload, robust_cost
    c = np.array([0.0, 0.0, 0.0, 1.0])
    w = np.full(4, 0.25)
    rho = 0.1

    def kl_of(p):
        return p * np.log(4 * p) + (1 - p) * np.log(4 * (1 - p) / 3)

    lo, hi = 0.25, 1.0 - 1e-12
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if kl_of(mid) < rho else (lo, mid)
    p_star = 0.5 * (lo + hi)
    w_adv = np.asarray(worst_case_workload(c, w, rho, iters=80))
    assert abs(w_adv[3] - p_star) < 1e-4
    np.testing.assert_allclose(w_adv[:3], (1 - p_star) / 3, atol=1e-4)
    # zero duality gap: the primal attack meets the independent dual bound
    assert abs(float(c @ w_adv) - float(robust_cost(c, w, rho))) < 1e-3
    # degenerate ball: rho >= ln 4 covers the whole simplex -> point mass
    w_big = np.asarray(worst_case_workload(c, w, 2.0, iters=80))
    assert w_big[3] > 0.99


def test_adversary_attack_stays_on_ball_boundary():
    tr = tune_nominal(np.full(4, 0.25), SYS, **SMALL)
    sc = get_scenario(_drift("adversary", scenario_params=(("rho", 0.2),)))
    w_adv, rec = sc.attack(tr.phi, np.full(4, 0.25), 0.0, SYS)
    assert abs(rec["kl_adv"] - 0.2) < 1e-3         # fallback rho, exact KL
    assert rec["le_dual_bound"] and rec["regret"] >= 0.0
    # a live defender rho overrides the fallback
    _, rec2 = sc.attack(tr.phi, np.full(4, 0.25), 0.05, SYS)
    assert abs(rec2["kl_adv"] - 0.05) < 1e-3
    assert rec2["cost_adv"] <= rec["cost_adv"] + 1e-9   # smaller ball


# ---------------------------------------------------------------------------
# Page-Hinkley change-point trigger
# ---------------------------------------------------------------------------

def test_page_hinkley_detector_units():
    det = PageHinkleyDetector(delta=0.0, lam=0.1)
    assert not any(det.update(0.0) for _ in range(8))   # flat: no alarm
    assert det.update(0.5)                              # upward shift fires
    det.reset()
    assert not any(det.update(0.01) for _ in range(8))  # re-armed
    # delta absorbs drifts below the noise floor
    det2 = PageHinkleyDetector(delta=0.05, lam=0.1)
    assert not any(det2.update(x) for x in [0.0, 0.02, 0.03, 0.02, 0.03])


def test_change_point_reason_fires_in_session():
    """With the KL triggers parked out of reach, a sustained shift in the
    per-segment KL stream fires the policy through reason='change_point'."""
    tree, keys = _tree_and_keys()
    policy = DriftPolicy(kl_threshold=99.0, budget_slack=1e9,
                         min_windows=1, cooldown=1,
                         detector="page_hinkley", ph_delta=0.0,
                         ph_lambda=0.05)
    assert isinstance(policy.make_detector(), PageHinkleyDetector)
    assert DriftPolicy().make_detector() is None
    expected = (0.01, 0.01, 0.01, 0.97)
    sess = OnlineSession(tree, expected=expected, rho=0.0, sys=SYS,
                         mode="online", policy=policy)
    matched = materialize_session(keys, expected, n_queries=300, seed=1,
                                  key_space=2 ** 20)
    drifted = materialize_session(keys, (0.4, 0.4, 0.1, 0.1),
                                  n_queries=300, seed=2, key_space=2 ** 20)
    for s in range(2):
        sess.execute_segment(matched, expected, s)
    assert sess.take_request() is None
    reasons = []
    for s in range(2, 5):
        sess.execute_segment(drifted, (0.4, 0.4, 0.1, 0.1), s)
        req = sess.take_request()
        if req is not None:
            reasons.append(req.reason)
    assert "change_point" in reasons


# ---------------------------------------------------------------------------
# Overlap-based partial-compaction slice selection
# ---------------------------------------------------------------------------

def test_overlap_select_validates_and_defaults_unchanged():
    cfg = EngineConfig(T=4, buf_entries=64, mfilt_bits_per_entry=6.0,
                       expected_entries=2000, policy="partial")
    assert PartialCompactionPlanner(cfg).select == "round_robin"
    with pytest.raises(ValueError, match="slice selection"):
        PartialCompactionPlanner(cfg, select="best_effort")


def test_overlap_picks_min_overlap_slice_and_progresses():
    tree = LSMTree(EngineConfig(T=4, buf_entries=64,
                                mfilt_bits_per_entry=6.0,
                                expected_entries=4000, policy="partial",
                                policy_params=(("select", "overlap"),)))
    keys = populate(tree, 4000, seed=11, key_space=2 ** 20)
    # drive an overfull level through a write-heavy session; the skip-set
    # guarantees _maintain terminates even when a slice extracts nothing
    from repro.lsm import run_session
    res = run_session(tree, keys, (0.05, 0.15, 0.05, 0.75),
                      n_queries=2500, seed=3, key_space=2 ** 20)
    assert res.avg_io_per_query > 0
    # logical equivalence with round-robin selection: same live content
    tree2 = LSMTree(EngineConfig(T=4, buf_entries=64,
                                 mfilt_bits_per_entry=6.0,
                                 expected_entries=4000, policy="partial"))
    populate(tree2, 4000, seed=11, key_space=2 ** 20)
    run_session(tree2, keys, (0.05, 0.15, 0.05, 0.75),
                n_queries=2500, seed=3, key_space=2 ** 20)
    for k in keys[::97]:
        assert tree.get(int(k)) == tree2.get(int(k))


def test_overlap_scoring_prefers_empty_target_span():
    """The score is the uniform-density estimate of target-level entries
    under the slice; a slice over a hole in the target level must win."""
    tree = LSMTree(EngineConfig(T=4, buf_entries=64,
                                mfilt_bits_per_entry=6.0,
                                expected_entries=4000, policy="partial",
                                policy_params=(("select", "overlap"),
                                               ("parts", 4))))
    populate(tree, 4000, seed=11, key_space=2 ** 20)
    planner = tree.planner
    planner._tried.clear()      # re-arm: populate already cycled the state
    planner._state.clear()
    store = tree.store
    # find a populated level with a populated next level
    level = next(i + 1 for i, lv in enumerate(store.levels)
                 if lv.num_runs and i + 1 < len(store.levels)
                 and store.levels[i + 1].num_runs)
    lv = store.levels[level - 1]
    lo_key, hi_key = int(lv.min_keys.min()), int(lv.max_keys.max())
    width = max(1, (hi_key - lo_key + 1) // planner.parts)
    cands = planner._candidates(lo_key, hi_key, width)
    scores = [planner._overlap_score(store, level, clo, chi)
              for clo, chi in cands]
    picked = planner._pick_overlap(store, level, lo_key, hi_key, width)
    assert picked in cands
    assert planner._overlap_score(store, level, *picked) == min(scores)
    # progress: with frozen state, repeated picks cycle without repeats
    seen = {picked}
    for _ in range(len(cands) - 1):
        nxt = planner._pick_overlap(store, level, lo_key, hi_key, width)
        assert nxt not in seen
        seen.add(nxt)


# ---------------------------------------------------------------------------
# uint32-limb splitmix64
# ---------------------------------------------------------------------------

def test_limb_splitmix64_bit_identity():
    import jax
    from repro.lsm.bloom import splitmix64
    with jax.enable_x64(True):
        import jax.numpy as jnp
        from repro.kernels.point_read.limb import (from_limbs, mod_limbs,
                                                   split64_jnp,
                                                   splitmix64_limbs,
                                                   to_limbs)
        rng = np.random.default_rng(0)
        x = rng.integers(0, 2 ** 64, size=4096, dtype=np.uint64)
        x = np.concatenate([x, np.array(
            [0, 1, 2 ** 32 - 1, 2 ** 32, 2 ** 64 - 1, 0x9E3779B97F4A7C15],
            np.uint64)])
        lo, hi = to_limbs(x)
        assert np.array_equal(from_limbs(lo, hi), x)     # round trip
        jlo, jhi = split64_jnp(jnp.asarray(x))
        for seed in (1, 2, 7, 255):
            ref = splitmix64(x, np.uint64(seed))
            zlo, zhi = splitmix64_limbs(jlo, jhi, seed)
            got = from_limbs(np.asarray(zlo), np.asarray(zhi))
            assert np.array_equal(ref, got), f"seed={seed}"
            for m in (63, 64, 1021, 2 ** 20 + 7, 2 ** 31 - 1):
                want = (ref % np.uint64(m)).astype(np.uint64)
                have = np.asarray(mod_limbs(zlo, zhi, m)).astype(np.uint64)
                assert np.array_equal(want, have), f"m={m}"
        with pytest.raises(ValueError, match="2\\^31"):
            mod_limbs(jlo, jhi, 2 ** 31)


def test_limb_read_kernel_matches_native():
    from repro.lsm import read_path
    tree, keys = _tree_and_keys(n=2000)
    sub = np.concatenate([keys[:400], keys[:100] | np.uint64(1 << 60)])
    outs = {}
    for mode in ("jnp", "jnp_limb"):
        with read_path.read_kernel(mode):
            lv = next(lv for lv in tree.store.levels if lv.num_runs)
            outs[mode] = read_path.point_read_level_numpy(lv, sub)
    a, b = outs["jnp"], outs["jnp_limb"]
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))
    assert a[2:] == b[2:]
    with pytest.raises(ValueError):
        read_path.set_read_kernel("uint128")


# ---------------------------------------------------------------------------
# End to end: five kinds x three backends, bit-identical across backends
# ---------------------------------------------------------------------------

SCENARIO_MATRIX = [
    ("zipf_migrate", ()),
    ("burst_storm", (("amplitude", 3.0), ("period", 2))),
    ("tombstone_churn", (("delete_fraction", 0.4),)),
    ("scan_heavy", (("scan_scale", 4.0),)),
    ("adversary", (("rho", 0.2),)),
]


def _scenario_spec(kind, params, backend):
    api = _api()
    return api.ExperimentSpec(
        name=f"sc_{kind}",
        workload=api.WorkloadSpec(indices=(4,), nominal=True,
                                  rho_source="from_history",
                                  history=((0.01, 0.01, 0.01, 0.97),
                                           (0.3, 0.3, 0.3, 0.1))),
        design=api.DesignSpec(**SMALL), system=SYS_PAIRS,
        backend=backend,
        backend_params=(("workers", 2),) if backend != "inline" else (),
        drift=api.DriftSpec(kind=kind, segments=3, n_queries=150,
                            scenario_params=params, n_keys=2500,
                            key_space=2 ** 20, window=2, min_windows=1,
                            cooldown=1, retune_starts=4, retune_steps=40))


def _segment_ios(report):
    return {key: [r.avg_io_per_query for r in res.records]
            for key, res in sorted(report.drift.items())}


@pytest.mark.parametrize("kind,params", SCENARIO_MATRIX,
                         ids=[k for k, _ in SCENARIO_MATRIX])
def test_scenarios_end_to_end_all_backends(kind, params):
    """Each scenario kind runs unchanged on inline, sharded and subprocess
    backends, measuring bit-identical I/O (the backend moves work, never
    changes it); the adversary's regret claim holds on every backend."""
    api = _api()
    reports = {}
    for backend in ("inline", "sharded", "subprocess"):
        rep = api.run_experiment(_scenario_spec(kind, params, backend))
        arms = {arm for _, arm in rep.drift}
        assert arms == {"stale_nominal", "static_robust", "online", "oracle"}
        for res in rep.drift.values():
            assert all(r.avg_io_per_query > 0 for r in res.records)
        qs = {tuple(r.queries for r in res.records)
              for res in rep.drift.values()}
        assert len(qs) == 1                    # paired arms, same volume
        if kind == "burst_storm":
            assert list(qs)[0] == (150, 450, 150)
        if kind == "adversary":
            recs = rep.regret[0]
            assert len(recs) == 3
            assert all(r["le_dual_bound"] for r in recs)
            assert all(r["kl_adv"] > 0 for r in recs)
        else:
            assert rep.regret == {}
        reports[backend] = rep
    base = _segment_ios(reports["inline"])
    for other in ("sharded", "subprocess"):
        assert _segment_ios(reports[other]) == base, other
    # the report serializes in the BENCH schema with the regret row
    import json
    payload = reports["inline"].to_bench_payload()
    json.dumps(payload, allow_nan=False)
    names = [r["name"] for r in payload["rows"]]
    if kind == "adversary":
        assert f"sc_{kind}_regret_w0" in names
