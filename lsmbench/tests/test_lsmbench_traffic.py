"""Keys, YCSB's key choosers and the traffic generator."""
import numpy as np
import pytest

from lsmbench import keys
from lsmbench.traffic import INSERT, READ, SCAN, UPDATE, Traffic


def test_record_keys_are_distinct_and_avoid_the_sentinel():
    k = keys.record_keys(np.arange(1 << 20))
    assert len(np.unique(k)) == 1 << 20
    assert keys.record_keys(np.array([keys.SENTINEL_RECORD - 1]))[0] != \
        0xFFFFFFFF
    h = keys.SENTINEL_RECORD
    x = np.uint32(h)                 # the finalizer, in plain Python ints
    x = int(x)
    for shift, mul in ((16, 0x85EBCA6B), (13, 0xC2B2AE35), (16, None)):
        x ^= x >> shift
        if mul:
            x = (x * mul) & 0xFFFFFFFF
    assert x == 0xFFFFFFFF
    with pytest.raises(ValueError):
        keys.record_keys(np.array([keys.SENTINEL_RECORD]))


def test_fnv64_matches_ycsb():
    # YCSB Utils.fnvhash64(0): FNV-1a over eight zero bytes, then abs
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = (h * 1099511628211) & (2 ** 64 - 1)
    if h >= 2 ** 63:
        h = 2 ** 64 - h
    assert int(keys.fnv64(np.array([0]))[0]) == h


def test_zipfian_is_skewed_and_in_range():
    rng = np.random.default_rng(0)
    z = keys.zipfian(rng, 200_000, items=1000, zetan=sum(
        1 / i ** 0.99 for i in range(1, 1001)))
    assert z.min() >= 0 and z.max() < 1000
    counts = np.bincount(z, minlength=1000)
    assert counts[0] > counts[1] > counts[10] > counts[500]
    s = keys.scrambled_zipfian(rng, 100_000, 5000)
    assert s.min() >= 0 and s.max() < 5000
    top = np.sort(np.bincount(s, minlength=5000))[::-1]
    assert top[0] > 20 * np.median(top)


MIX = {"rate_ops_per_s": 100,
       "ops": {"read": 0.5, "update": 0.3, "scan": 0.15, "insert": 0.05},
       "key_dist": "scrambled_zipfian", "zipf_theta": 0.99,
       "scan_length": {"min": 1, "max": 100}, "max_batch": 64,
       "warmup_s": 1}


def test_every_seed_gets_the_same_work():
    ta, tb = Traffic(MIX, 1, 10_000), Traffic(MIX, 2 ** 40 + 3, 10_000)
    a, b = ta.take(1000), tb.take(1000)
    assert ta.next_record == tb.next_record
    assert not np.array_equal(a.key, b.key)
    for kind in (READ, UPDATE, SCAN, INSERT):
        assert (a.kind == kind).sum() == (b.kind == kind).sum()
    # every scan spans a whole number of records' mean key gaps, unless
    # it was clamped at the top of the key space
    gap = 2 ** 32 / ta.next_record
    spans = {int(np.round(n * gap)) for n in range(1, 101)}
    for o in (a, b):
        s = o.kind == SCAN
        span = o.hi[s] - o.key[s].astype(np.int64)
        free = o.hi[s] < 2 ** 32 - 1
        assert set(span[free].tolist()) <= spans


def test_same_seed_same_ops_and_inserts_are_new_records():
    t1, t2 = Traffic(MIX, 9, 10_000), Traffic(MIX, 9, 10_000)
    a, b = t1.take(500), t2.take(500)
    assert np.array_equal(a.key, b.key) and np.array_equal(a.val, b.val)
    old = set(keys.record_keys(np.arange(10_000)).tolist())
    assert not old & set(a.key[a.kind == INSERT].tolist())
    assert old >= set(a.key[a.kind != INSERT].tolist())
    assert (a.val[(a.kind == UPDATE) | (a.kind == INSERT)] != -2 ** 31).all()
    assert np.allclose(Traffic(MIX, 9, 10).arrivals(1.0),
                       np.arange(100) / 100)


def test_record_numbers_invert_record_keys():
    r = np.random.default_rng(4).integers(0, keys.SENTINEL_RECORD, 10_000)
    assert np.array_equal(keys.record_numbers(keys.record_keys(r)), r)
    k = np.arange(0, 2 ** 32, 2 ** 20 + 7, dtype=np.int64).astype(np.uint32)
    r = keys.record_numbers(k)
    ok = r < keys.SENTINEL_RECORD
    assert np.array_equal(keys.record_keys(r[ok]), k[ok])
