"""The benchmark's YCSB generator: mix shares, the zipfian constant, and
the same stream from the same seed."""

import json
import os
import sys

import numpy as np
import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)

from chipbench import ycsb  # noqa: E402


def test_fnv64_matches_ycsb():
    # YCSB's Utils.fnvhash64(0) and (1), computed by hand from FNV-1 over
    # the eight bytes of the value, then Math.abs
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = ((h ^ 0) * 1099511628211) % 2 ** 64
    want0 = abs(h - 2 ** 64 if h >= 2 ** 63 else h)
    assert int(ycsb.fnv64([0])[0]) == want0
    assert (ycsb.fnv64(np.arange(1000)) >= 0).all()


def test_zipf_constant_sets_the_head():
    rng = np.random.default_rng(7)
    ranks = ycsb.zipf_ranks(rng.random(2_000_000), ycsb.ITEM_COUNT,
                            ycsb.ZIPFIAN_CONSTANT, ycsb.ZETAN)
    p0, p1 = np.mean(ranks == 0), np.mean(ranks == 1)
    # P(rank i) = i^-theta / zeta(n, theta): rank 0 has 1/ZETAN of the mass
    assert abs(p0 - 1 / ycsb.ZETAN) < 0.002
    assert abs(p0 / p1 - 2 ** 0.99) < 0.08
    assert (ranks >= 0).all() and (ranks < ycsb.ITEM_COUNT).all()


def test_scrambled_zipf_is_skewed_and_spread():
    rng = np.random.default_rng(1)
    rec = ycsb.scrambled_zipf(rng, 500_000, 100_000)
    assert rec.min() >= 0 and rec.max() < 100_000
    counts = np.bincount(rec, minlength=100_000)
    top = np.sort(counts)[::-1]
    assert top[0] / len(rec) > 0.02            # the hottest record
    assert top[:100].sum() / len(rec) > 0.2    # a hot set
    hot = np.argsort(counts)[::-1][:10]
    assert np.ptp(hot) > 10_000                # not clustered by index


def test_same_seed_same_stream():
    a = ycsb.scrambled_zipf(np.random.default_rng(2 ** 40 + 9), 10_000, 5_000)
    b = ycsb.scrambled_zipf(np.random.default_rng(2 ** 40 + 9), 10_000, 5_000)
    c = ycsb.scrambled_zipf(np.random.default_rng(2 ** 40 + 10), 10_000, 5_000)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)


def test_record_keys_distinct():
    keys = ycsb.record_keys(200_000)
    assert keys.dtype == np.uint64
    assert len(np.unique(keys)) == 200_000
    assert int(keys.max()) < 2 ** 63


def _traffic(name):
    with open(os.path.join(CHIP, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,update", [("ycsb-a", 0.5), ("ycsb-c", 0.0)])
def test_pool_mix_shares(name, update):
    from chipbench import generator
    t = dict(_traffic(name), pool_requests=200)
    upd, rec = generator.YCSB({}, t, 2 ** 35 + 1).draw_pool(100_000)
    assert upd.shape == rec.shape == (200, t["request_ops"])
    assert abs(upd.mean() - update) < 0.01
    again, rec2 = generator.YCSB({}, t, 2 ** 35 + 1).draw_pool(100_000)
    assert np.array_equal(upd, again) and np.array_equal(rec, rec2)
