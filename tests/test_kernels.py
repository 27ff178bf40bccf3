"""Per-kernel validation: shape/dtype sweeps in interpret mode against the
pure-jnp oracles (+ hypothesis property tests).

The interpret leg (``interpret=True``, exercised below) runs on any
backend; each ``ops.py`` wrapper selects the compiled leg when the
default backend is a TPU (``repro.kernels._compat.interpret_default``).
Which kernels Mosaic accepts for v5e is checked by
``tests/test_tpu_compile.py``.
"""

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.bloom_probe.kernel import bloom_probe_kernel
from repro.kernels.bloom_probe.ref import build_plane, probe_ref
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.dual_solve.ops import (dual_solve_warm,
                                          dual_solve_warm_batch)
from repro.kernels.merge.ops import merge_runs_arrays
from repro.kernels.point_read.ops import point_read_level_arrays
from repro.kernels.rwkv6.kernel import rwkv6_kernel
from repro.kernels.rwkv6.ops import rwkv6_chunked
from repro.kernels.rwkv6.ref import wkv_ref
from repro.lsm.merge_path import merge_runs_numpy
from repro.lsm.read_path import point_read_level_numpy
from repro.lsm.store import TOMB, LevelStore, RunData


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,d,causal,window,dtype", [
    (128, 64, True, None, jnp.float32),
    (256, 64, False, None, jnp.float32),
    (256, 128, True, None, jnp.float32),
    (256, 96, True, None, jnp.float32),        # phi3 head_dim
    (512, 64, True, 128, jnp.float32),         # SWA
    (256, 64, True, None, jnp.bfloat16),
])
def test_flash_attention_shapes(S, d, causal, window, dtype):
    rng = np.random.default_rng(hash((S, d, causal)) % 2 ** 31)
    q = jnp.asarray(rng.normal(size=(3, S, d)), dtype)
    k = jnp.asarray(rng.normal(size=(3, S, d)), dtype)
    v = jnp.asarray(rng.normal(size=(3, S, d)), dtype)
    out = flash_attention_kernel(q, k, v, causal=causal, window=window,
                                 block_q=64, block_kv=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@settings(max_examples=8, deadline=None)
@given(bq=st.sampled_from([32, 64, 128]), bkv=st.sampled_from([32, 64, 128]),
       seed=st.integers(0, 100))
def test_flash_attention_block_shape_invariance(bq, bkv, seed):
    """Output must not depend on the tiling."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.float32)
    a = flash_attention_kernel(q, k, v, block_q=bq, block_kv=bkv,
                               interpret=True)
    b = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_gqa_wrapper_matches_model_sdpa():
    """ops.flash_attention (GQA expansion) vs the model's XLA attention."""
    from repro.configs import get_config
    from repro.models.layers import _repeat_kv, _sdpa, causal_mask
    cfg = get_config("qwen3-14b").reduced()
    rng = np.random.default_rng(0)
    B, S, H, KV, hd = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    a = flash_attention(q, k, v, causal=True, block_q=32, block_kv=32)
    b = _sdpa(q, k, v, causal_mask(S, S, None), cfg)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5,
                               rtol=3e-5)


# ---------------------------------------------------------------------------
# rwkv6 wkv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,n,chunk,dtype", [
    (64, 64, 16, jnp.float32),
    (128, 64, 32, jnp.float32),
    (96, 32, 32, jnp.float32),    # chunk == S/3
    (128, 64, 32, jnp.bfloat16),
])
def test_rwkv6_kernel_shapes(S, n, chunk, dtype):
    rng = np.random.default_rng(S + n)
    BH = 4
    r = jnp.asarray(rng.normal(size=(BH, S, n)), dtype)
    k = jnp.asarray(rng.normal(size=(BH, S, n)), dtype)
    v = jnp.asarray(rng.normal(size=(BH, S, n)), dtype)
    logw = -jnp.exp(jnp.asarray(rng.normal(size=(BH, S, n)) * 0.5 - 0.6,
                                jnp.float32)).astype(dtype)
    u = jnp.asarray(rng.normal(size=(BH, n)) * 0.1, jnp.float32)
    y, s = rwkv6_kernel(r, k, v, logw, u, chunk=chunk, interpret=True)
    y_ref, s_ref = wkv_ref(r, k, v, logw, u)
    tol = 5e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=tol,
                               rtol=tol)


@settings(max_examples=6, deadline=None)
@given(chunk=st.sampled_from([8, 16, 32, 64]), seed=st.integers(0, 50))
def test_rwkv6_chunk_size_invariance(chunk, seed):
    """The chunked algorithm must be exact for any chunk size."""
    rng = np.random.default_rng(seed)
    BH, S, n = 2, 64, 32
    r = jnp.asarray(rng.normal(size=(BH, S, n)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(BH, S, n)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(BH, S, n)), jnp.float32)
    logw = -jnp.exp(jnp.asarray(rng.normal(size=(BH, S, n)) * 0.3 - 1.0,
                                jnp.float32))
    u = jnp.asarray(rng.normal(size=(BH, n)) * 0.1, jnp.float32)
    y, _ = rwkv6_kernel(r, k, v, logw, u, chunk=chunk, interpret=True)
    y_ref, _ = wkv_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=5e-4,
                               rtol=5e-4)


def test_rwkv6_ops_matches_model_path():
    """kernels.rwkv6.ops vs models.rwkv.wkv_chunked (the XLA path)."""
    from repro.models.rwkv import wkv_chunked
    rng = np.random.default_rng(3)
    B, S, H, n = 2, 64, 3, 32
    r = jnp.asarray(rng.normal(size=(B, S, H, n)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, n)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, n)), jnp.float32)
    logw = -jnp.exp(jnp.asarray(rng.normal(size=(B, S, H, n)) * 0.3 - 1.0,
                                jnp.float32))
    u = jnp.asarray(rng.normal(size=(H, n)) * 0.1, jnp.float32)
    y1, s1 = rwkv6_chunked(r, k, v, logw, u, chunk=16)
    y2, s2 = wkv_chunked(r, k, v, logw, u, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-4,
                               rtol=2e-4)


# ---------------------------------------------------------------------------
# bloom probe
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_blocks,block_bits,num_hashes", [
    (128, 256, 3), (256, 512, 4), (64, 1024, 6),
])
def test_bloom_probe_shapes(num_blocks, block_bits, num_hashes):
    rng = np.random.default_rng(num_blocks)
    keys = rng.choice(2 ** 32, 2048, replace=False).astype(np.uint32)
    plane = build_plane(keys[:1024], num_blocks, block_bits, num_hashes)
    out = bloom_probe_kernel(jnp.asarray(keys), jnp.asarray(plane),
                             num_hashes=num_hashes, interpret=True)
    ref = probe_ref(keys, plane, num_hashes)
    assert (np.asarray(out) == ref).all()
    # no false negatives, ever
    assert (np.asarray(out[:1024]) > 0.5).all()


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000))
def test_bloom_probe_no_false_negatives(seed):
    rng = np.random.default_rng(seed)
    keys = rng.choice(2 ** 32, 512, replace=False).astype(np.uint32)
    plane = build_plane(keys, 128, 512, 4)
    out = bloom_probe_kernel(jnp.asarray(keys), jnp.asarray(plane),
                             num_hashes=4, interpret=True)
    assert (np.asarray(out) > 0.5).all()


# ---------------------------------------------------------------------------
# point read (fused per-level batched read; PR 7)
# ---------------------------------------------------------------------------

def _mk_level(run_specs, bpk=8.0):
    """LevelStore from newest-first ``[(keys, vals), ...]`` run specs."""
    runs = [RunData.build(np.asarray(k, np.uint64), np.asarray(v, np.int64),
                          bpk, flushes=1) for k, v in run_specs]
    lv = LevelStore()
    lv._set_runs(runs)
    return lv


def _level_arrays(lv):
    pack = lv.pack
    return (lv.keys, lv.vals, np.asarray(lv.starts, np.int64), pack.words,
            np.asarray(pack.n_bits, np.uint64), np.asarray(pack.ks, np.int64),
            lv.min_keys, lv.max_keys)


def _assert_read_modes_bit_equal(lv, q):
    """numpy (engine-verbatim) / jnp ref / pallas must agree exactly."""
    q = np.asarray(q, np.uint64)
    ref = point_read_level_numpy(lv, q)
    for impl in ("jnp", "pallas"):
        hit, enc, probes, reads, fps = point_read_level_arrays(
            q, *_level_arrays(lv), impl=impl)
        np.testing.assert_array_equal(hit, ref[0], err_msg=impl)
        np.testing.assert_array_equal(enc[hit], ref[1][ref[0]],
                                      err_msg=impl)
        assert (probes, reads, fps) == ref[2:], impl


def test_point_read_multi_run_level_bit_equal():
    rng = np.random.default_rng(0)
    pool = rng.choice(1 << 48, 3000, replace=False).astype(np.uint64)
    specs = [(np.sort(pool[:900]), np.arange(900)),
             (np.sort(pool[900:1100]), np.arange(200) + 10_000),
             (np.sort(pool[1100:2400]), np.arange(1300) + 50_000)]
    lv = _mk_level(specs)
    # present in various runs, absent, duplicated queries; B = 200 is
    # not a multiple of the 128-key pallas tile (exercises padding)
    q = np.concatenate([pool[rng.integers(0, 2400, 120)],
                        pool[2400:2470], pool[:10]])
    _assert_read_modes_bit_equal(lv, q)


def test_point_read_overlapping_runs_newest_wins():
    """Same key in several runs: only the newest run's value counts and
    older runs are not probed for the resolved key (counter semantics)."""
    keys = np.arange(100, 200, dtype=np.uint64)
    specs = [(keys[:60], np.full(60, 1)),       # newest
             (keys[20:80], np.full(60, 2)),
             (keys, np.full(100, 3))]           # oldest
    lv = _mk_level(specs)
    _assert_read_modes_bit_equal(lv, keys)
    hit, enc, *_ = point_read_level_arrays(keys, *_level_arrays(lv),
                                           impl="pallas")
    assert hit.all()
    np.testing.assert_array_equal(enc[:60], 1)
    np.testing.assert_array_equal(enc[60:80], 2)
    np.testing.assert_array_equal(enc[80:], 3)


@pytest.mark.parametrize("case", ["empty_run", "single_entry",
                                  "all_tombstone", "odd_batch"])
def test_point_read_edge_cases(case):
    rng = np.random.default_rng(hash(case) % 2 ** 31)
    if case == "empty_run":
        specs = [(np.arange(10, 20), np.arange(10)),
                 ([], []),                       # merged-away run
                 (np.arange(15, 40), np.arange(25))]
        q = np.arange(5, 45)
    elif case == "single_entry":
        specs = [([7], [70]), ([7], [71]), ([9], [90])]
        q = np.array([7, 8, 9, 7])
    elif case == "all_tombstone":
        keys = np.arange(50, 80, dtype=np.uint64)
        specs = [(keys, np.full(30, TOMB)),      # deletes shadow ...
                 (keys, np.arange(30))]          # ... the older values
        q = np.arange(40, 90)
    else:                                        # batch % 128 != 0
        keys = np.sort(rng.choice(1 << 32, 500, replace=False)
                       .astype(np.uint64))
        specs = [(keys[::2], np.arange(250))]
        q = rng.choice(keys, 37)
    lv = _mk_level(specs)
    _assert_read_modes_bit_equal(lv, q)
    if case == "all_tombstone":
        hit, enc, *_ = point_read_level_arrays(
            np.arange(50, 80, dtype=np.uint64), *_level_arrays(lv),
            impl="pallas")
        assert hit.all() and (enc == TOMB).all()


def test_point_read_empty_level_and_empty_batch():
    lv = _mk_level([(np.arange(5), np.arange(5))])
    hit, enc, probes, reads, fps = point_read_level_arrays(
        np.empty(0, np.uint64), *_level_arrays(lv), impl="pallas")
    assert len(hit) == 0 and (probes, reads, fps) == (0, 0, 0)
    lv0 = _mk_level([([], []), ([], [])])
    q = np.arange(3, dtype=np.uint64)
    _assert_read_modes_bit_equal(lv0, q)


# ---------------------------------------------------------------------------
# dual solve (robust tuner inner loop; PR 7)
# ---------------------------------------------------------------------------

def _dual_solve_batch(L, n=33, seed=0):
    rng = np.random.default_rng(seed)
    C = rng.gamma(2.0, 2.0, (L, n)).astype(np.float32)
    W = rng.dirichlet(np.ones(n), L).astype(np.float32)
    rho = rng.uniform(0.0, 2.0, L).astype(np.float32)
    rho[::3] = 0.0                      # exercise the nominal branch
    llam = np.log(C.max(1) - C.min(1)).astype(np.float32)
    return C, W, rho, llam


@pytest.mark.parametrize("L", [1, 7, 128, 300])
def test_dual_solve_pallas_bit_equals_fused(L):
    """Lane-tiled kernel vs vmapped fused: exact f32 equality, including
    lane counts that are not a multiple of the 128-lane tile."""
    C, W, rho, llam = _dual_solve_batch(L, seed=L)
    vf, lf = dual_solve_warm_batch(C, W, rho, llam, impl="fused")
    vp, lp = dual_solve_warm_batch(C, W, rho, llam, impl="pallas")
    np.testing.assert_array_equal(np.asarray(vf), np.asarray(vp))
    np.testing.assert_array_equal(np.asarray(lf), np.asarray(lp))


def test_dual_solve_fused_matches_ref_values():
    """Cached-point golden (12 evals) vs two-point reference (16 evals):
    same bracket-shrink rate, so values agree to optimizer-noise level."""
    C, W, rho, llam = _dual_solve_batch(64, seed=3)
    vr, lr = dual_solve_warm_batch(C, W, rho, llam, impl="ref")
    vf, lf = dual_solve_warm_batch(C, W, rho, llam, impl="fused")
    np.testing.assert_allclose(np.asarray(vf), np.asarray(vr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lr), atol=2e-3)


def test_dual_solve_single_lane_dispatch():
    C, W, rho, llam = _dual_solve_batch(1, seed=9)
    vf, _ = dual_solve_warm(C[0], W[0], rho[0], llam[0], impl="fused")
    vr, _ = dual_solve_warm(C[0], W[0], rho[0], llam[0], impl="ref")
    assert float(vf) == pytest.approx(float(vr), rel=1e-4, abs=1e-4)
    with pytest.raises(ValueError):
        dual_solve_warm(C[0], W[0], rho[0], llam[0], impl="pallas")


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 200), L=st.integers(1, 40))
def test_dual_solve_pallas_fused_property(seed, L):
    C, W, rho, llam = _dual_solve_batch(L, n=17, seed=seed)
    vf, lf = dual_solve_warm_batch(C, W, rho, llam, impl="fused")
    vp, lp = dual_solve_warm_batch(C, W, rho, llam, impl="pallas")
    np.testing.assert_array_equal(np.asarray(vf), np.asarray(vp))
    np.testing.assert_array_equal(np.asarray(lf), np.asarray(lp))


# ---------------------------------------------------------------------------
# compaction merge (k-way stable merge; PR 7)
# ---------------------------------------------------------------------------

def _mk_runs(sizes, seed=0, overlap=True):
    """Newest-first sorted-unique runs with heavy key overlap."""
    rng = np.random.default_rng(seed)
    pool = rng.choice(1 << 20 if overlap else 1 << 48, max(sizes) * 2 + 4,
                      replace=False).astype(np.uint64)
    keys, vals = [], []
    for i, n in enumerate(sizes):
        k = np.sort(rng.choice(pool, n, replace=False)) if n else \
            np.empty(0, np.uint64)
        keys.append(k)
        vals.append((rng.integers(0, 1 << 30, n) * 10 + i).astype(np.int64))
    return keys, vals


@pytest.mark.parametrize("sizes", [
    (100, 80), (1, 1), (1, 0, 5), (0, 0), (257, 100, 3),   # != 128 tiles
    (64, 64, 64, 64),
])
def test_merge_modes_bit_equal(sizes):
    keys, vals = _mk_runs(list(sizes), seed=sum(sizes))
    ref_k, ref_v = merge_runs_numpy(keys, vals)
    for impl in ("jnp", "pallas"):
        mk, mv = merge_runs_arrays(keys, vals, impl=impl)
        np.testing.assert_array_equal(mk, ref_k, err_msg=impl)
        np.testing.assert_array_equal(mv, ref_v, err_msg=impl)


def test_merge_newest_wins_on_duplicates():
    """Every key duplicated across all runs: output must keep run 0's
    value (newest-first input order, like the legacy argsort merge)."""
    keys = np.arange(1000, 1300, dtype=np.uint64)
    klist = [keys, keys, keys]
    vlist = [np.full(300, i, np.int64) for i in range(3)]
    ref_k, ref_v = merge_runs_numpy(klist, vlist)
    assert (ref_v == 0).all()
    for impl in ("jnp", "pallas"):
        mk, mv = merge_runs_arrays(klist, vlist, impl=impl)
        np.testing.assert_array_equal(mk, ref_k)
        np.testing.assert_array_equal(mv, ref_v)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 500), na=st.integers(0, 60),
       nb=st.integers(0, 60), nc=st.integers(0, 60))
def test_merge_modes_property(seed, na, nb, nc):
    keys, vals = _mk_runs([na, nb, nc], seed=seed)
    ref_k, ref_v = merge_runs_numpy(keys, vals)
    mk, mv = merge_runs_arrays(keys, vals, impl="jnp")
    np.testing.assert_array_equal(mk, ref_k)
    np.testing.assert_array_equal(mv, ref_v)


# ---------------------------------------------------------------------------
# model integration: attention_impl="pallas" end to end
# ---------------------------------------------------------------------------

def test_model_with_pallas_attention_matches_xla():
    from repro.configs import get_config
    from repro.models import build_model
    cfg_x = get_config("mixtral-8x7b").reduced()
    cfg_p = cfg_x.replace(attention_impl="pallas")
    api_x, api_p = build_model(cfg_x), build_model(cfg_p)
    params = api_x.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg_x.vocab_size, (2, 32)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg_x.vocab_size, (2, 32)),
                              jnp.int32),
    }
    lx, _ = api_x.loss_fn(params, batch)
    lp, _ = api_p.loss_fn(params, batch)
    assert float(lx) == pytest.approx(float(lp), rel=1e-3)


def test_model_with_pallas_rwkv_matches_xla():
    from repro.configs import get_config
    from repro.models import build_model
    cfg_x = get_config("rwkv6-3b").reduced()
    cfg_p = cfg_x.replace(attention_impl="pallas")
    api_x, api_p = build_model(cfg_x), build_model(cfg_p)
    params = api_x.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg_x.vocab_size, (2, 32)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg_x.vocab_size, (2, 32)),
                              jnp.int32),
    }
    lx, _ = api_x.loss_fn(params, batch)
    lp, _ = api_p.loss_fn(params, batch)
    assert float(lx) == pytest.approx(float(lp), rel=1e-3)
