"""The reference's as-of answers against a plain dict replay."""
import numpy as np

from lsmbench import check
from lsmbench.reference import TOMBSTONE


def test_as_of_and_scan_as_of_match_a_dict_replay():
    rng = np.random.default_rng(5)
    base_k = np.unique(rng.integers(0, 5000, 1000).astype(np.uint32))
    base_v = rng.integers(0, 100, len(base_k)).astype(np.int32)
    wk = rng.integers(0, 6000, 3000).astype(np.uint32)
    wv = rng.integers(0, 100, 3000).astype(np.int32)
    wv[::17] = TOMBSTONE
    hist = check.history(base_k, base_v, wk, wv)
    for pos in (0, 1, 700, 2999, 3000):
        state = dict(zip(base_k.tolist(), base_v.tolist()))
        for k, v in zip(wk[:pos].tolist(), wv[:pos].tolist()):
            state[k] = v
        q = rng.integers(0, 6000, 500).astype(np.uint32)
        f, v = hist.as_of(q, pos)
        for key, fi, vi in zip(q.tolist(), f, v):
            want = state.get(key)
            live = want is not None and want != TOMBSTONE
            assert fi == live and (not live or vi == want)
        ks, vs = hist.scan_as_of(1000, 3000, pos)
        want = sorted((k, v) for k, v in state.items()
                      if 1000 <= k < 3000 and v != TOMBSTONE)
        assert ks.tolist() == [k for k, _ in want]
        assert vs.tolist() == [v for _, v in want]


def test_mismatch_counts():
    hist = check.history(np.array([1, 2], np.uint32),
                         np.array([10, 20], np.int32),
                         np.array([2], np.uint32), np.array([21], np.int32))
    keys = np.array([1, 2, 3], np.uint32)
    good = (1, keys, np.array([1, 1, 0], bool), np.array([10, 21, 0]))
    stale = (1, keys, np.array([1, 1, 0], bool), np.array([10, 20, 0]))
    assert check.read_mismatches(hist, [good]) == 0
    assert check.read_mismatches(hist, [stale]) == 1
    assert check.wal_mismatches(np.array([2], np.uint32),
                                np.array([21], np.int32),
                                np.array([2], np.uint32),
                                np.array([21], np.int32)) == 0
    assert check.wal_mismatches(np.empty(0, np.uint32),
                                np.empty(0, np.int32),
                                np.array([2], np.uint32),
                                np.array([21], np.int32)) == 1


def test_loaded_base_answers_like_a_settled_reference():
    from lsmbench.keys import record_keys
    rng = np.random.default_rng(8)
    n = 3000
    vals = rng.integers(-2 ** 31 + 1, 2 ** 31, n, dtype=np.int32)
    keys = record_keys(np.arange(n))
    wk = record_keys(rng.integers(0, n + 200, 500))
    wv = rng.integers(0, 100, 500).astype(np.int32)
    plain = check.history(keys, vals, wk, wv)
    fast = check.loaded_history(vals, wk, wv)
    q = np.concatenate([keys[::3], record_keys(np.arange(n, n + 300)),
                        rng.integers(0, 2 ** 32 - 1, 300, dtype=np.uint32)])
    for pos in (0, 250, 500):
        for a, b in zip(plain.as_of(q, pos), fast.as_of(q, pos)):
            assert np.array_equal(a, b)
        for a, b in zip(plain.scan_as_of(2 ** 30, 2 ** 31, pos),
                        fast.scan_as_of(2 ** 30, 2 ** 31, pos)):
            assert np.array_equal(a, b)
