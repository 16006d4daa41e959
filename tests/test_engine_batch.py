"""Differential tests for the vectorized batch read/write plane: the
batch paths (``get_batch``, bulk ``put_batch``, fused multi-table Bloom
probe) must be semantically identical to the scalar paths they replace —
newest-wins resolution, stall/accept counts, and bloom no-false-negatives.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.backend import ExecBackend
from repro.core.constraints import GlobalConstraint, NoConstraint
from repro.core.engine import LSMEngine
from repro.core.memtable import MemTable
from repro.core.policies import (LevelingPolicy, PartitionedLevelingPolicy,
                                 SizeTieredPolicy, TieringPolicy)
from repro.core.scheduler import FairScheduler, GreedyScheduler


def _mk(policy: str, memtable=128, unique=2048, constraint=200):
    pol = {
        "tiering": lambda: TieringPolicy(3, memtable, unique),
        "leveling": lambda: LevelingPolicy(3, memtable, unique),
        "size_tiered": lambda: SizeTieredPolicy(1.2, memtable, unique),
        "partitioned": lambda: PartitionedLevelingPolicy(
            4, memtable, unique, file_entries=64, l1_capacity=256),
    }[policy]()
    return LSMEngine(pol, GreedyScheduler(), GlobalConstraint(constraint),
                     memtable_entries=memtable, unique_keys=unique,
                     use_kernels=True, merge_block=64)


def _seed_scalar_put_batch(eng: LSMEngine, keys, values) -> int:
    """The seed's per-entry admission loop — the semantic oracle for the
    vectorized ``put_batch``."""
    keys = np.asarray(keys)
    values = np.asarray(values)
    n_ok = 0
    for i in range(len(keys)):
        if not eng.put(int(keys[i]), int(values[i])):
            break
        n_ok += 1
    return n_ok


# --------------------------------------------------------------- reads
@pytest.mark.parametrize("policy", ["tiering", "leveling", "partitioned"])
def test_get_batch_equals_scalar_get(policy):
    """Random workload with duplicate keys across memtables and
    merged/unmerged tables: get_batch == per-key get == dict oracle, both
    mid-stream (memtables populated) and after drain."""
    rng = np.random.default_rng(11)
    eng = _mk(policy)
    ref = {}
    for i in range(2500):
        k = int(rng.integers(0, 1024))       # heavy key reuse
        v = int(rng.integers(0, 1 << 30))
        while not eng.put(k, v):
            eng.pump(256)
        ref[k] = v
        if i % 40 == 0:
            eng.pump(96)
    for phase in ("mid", "drained"):
        qs = rng.integers(0, 2048, 400, dtype=np.uint32)  # hits + misses
        found, vals = eng.get_batch(qs)
        for i, k in enumerate(qs):
            want = ref.get(int(k))
            got = int(vals[i]) if found[i] else None
            assert got == want, (phase, int(k), got, want)
            assert eng.get(int(k)) == want, (phase, int(k))
        eng.drain()


def test_get_batch_sees_fresh_tables_after_flush_and_merge():
    """Read-view invalidation: lookups reflect every flush/merge
    completion, never a stale snapshot."""
    eng = _mk("tiering", memtable=32, unique=256)
    for v, pump in ((1, 0), (2, 64), (3, 512)):
        n = eng.put_batch(np.arange(32, dtype=np.uint32),
                          np.full(32, v, np.int32))
        assert n == 32
        eng._seal_active()
        if pump:
            eng.pump(pump)
        found, vals = eng.get_batch(np.arange(32, dtype=np.uint32))
        assert found.all() and (vals == v).all(), v
    eng.drain()
    found, vals = eng.get_batch(np.arange(32, dtype=np.uint32))
    assert found.all() and (vals == 3).all()


def test_scan_and_get_agree_on_ordering():
    """The unified read-view ordering: a full-range scan must equal the
    per-key point lookups for every live key, including under merges."""
    rng = np.random.default_rng(5)
    eng = _mk("size_tiered", memtable=64, unique=512)
    ref = {}
    for i in range(1500):
        k, v = int(rng.integers(0, 512)), int(rng.integers(0, 1 << 30))
        while not eng.put(k, v):
            eng.pump(128)
        ref[k] = v
        if i % 30 == 0:
            eng.pump(64)
    scan = eng.scan_range_dict(0, 512)
    assert scan == ref
    keys = np.fromiter(ref, dtype=np.uint32)
    found, vals = eng.get_batch(keys)
    assert found.all()
    assert {int(k): int(v) for k, v in zip(keys, vals)} == ref


class _DenseProbe(ExecBackend):
    """Probes every table's row for every key, as before the probe was
    pruned by key range: each table's range widened to all keys (free
    rows keep theirs, which holds none)."""

    def probe_multi(self, filts, meta, keys, filts_host=None):
        meta = np.array(meta)
        meta[meta[:, 2] <= meta[:, 3], 2:] = (0, 2 ** 32 - 1)
        return super().probe_multi(filts, meta, keys, filts_host=filts_host)


@pytest.mark.parametrize("mode", ["host", "interpret"])
def test_get_batch_pruned_equals_dense_probing(mode):
    """A partitioned-leveling store answers every batch identically with
    the probe pruned by key range and with every row probed for every
    key, as flushes and merges rewrite stack rows and their ranges;
    both agree with a dict oracle, deletes included."""
    rng = np.random.default_rng(23)
    eng = LSMEngine(PartitionedLevelingPolicy(4, 64, 4096, file_entries=64,
                                              l1_capacity=256),
                    GreedyScheduler(), GlobalConstraint(200),
                    memtable_entries=64, unique_keys=4096, merge_block=64,
                    backend=ExecBackend(mode=mode))
    pruned, dense = eng.backend, _DenseProbe(mode="host")
    ref, ranges, seen = {}, set(), {"flushes": 0, "merges": 0}
    for step in range(12):
        keys = rng.integers(0, 4096, 150, dtype=np.uint32)
        vals = rng.integers(0, 1 << 30, 150).astype(np.int32)
        taken = eng.put_batch(keys, vals)
        ref.update(zip(keys[:taken].tolist(), vals[:taken].tolist()))
        gone = keys[:taken][::7]
        for k in gone[:eng.delete_batch(gone)].tolist():
            ref.pop(k, None)
        eng.pump(200)
        q = np.concatenate([keys, rng.integers(0, 4096, 200,
                                               dtype=np.uint32)])
        got = eng.get_batch(q)
        eng.backend = dense
        want = eng.get_batch(q)
        eng.backend = pruned
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)
        assert [int(v) if f else None for f, v in zip(*got)] == \
            [ref.get(int(k)) for k in q]
        fs = eng._fstack
        ranges.add(fs.meta[list(fs.slots.values()), 2:].tobytes())
        for t in eng.tables.values():
            assert tuple(fs.meta[t.stack_slot, 2:]) == \
                (t.keys_np[0], t.keys_np[-1])
        seen = {k: eng.stats[k] for k in seen}
    assert seen["flushes"] > 2 and seen["merges"] > 2
    assert len(ranges) > 8          # rows and ranges kept changing


# --------------------------------------------------------------- writes
@pytest.mark.parametrize("constraint", [2, 6, 200])
def test_put_batch_accept_count_equals_scalar(constraint):
    """Bulk admission accepts exactly as many entries as the seed scalar
    loop under identical stall constraints, across pump interleavings."""
    rng = np.random.default_rng(7)
    batches = [rng.integers(0, 512, int(n)) for n in
               rng.integers(1, 300, 12)]
    vals = [np.arange(len(b), dtype=np.int32) for b in batches]
    pumps = rng.integers(0, 128, len(batches))

    def run(bulk: bool) -> tuple[list[int], int, int]:
        eng = _mk("tiering", memtable=32, unique=512,
                  constraint=constraint)
        accepted = []
        for b, v, p in zip(batches, vals, pumps):
            if bulk:
                accepted.append(eng.put_batch(b, v))
            else:
                accepted.append(_seed_scalar_put_batch(eng, b, v))
            if p:
                eng.pump(int(p))
        return accepted, eng.stats["puts"], eng.total_entries()

    acc_bulk, puts_bulk, tot_bulk = run(bulk=True)
    acc_scalar, puts_scalar, tot_scalar = run(bulk=False)
    assert acc_bulk == acc_scalar
    assert puts_bulk == puts_scalar
    assert tot_bulk == tot_scalar


def test_put_batch_resumes_after_pump():
    """A stalled bulk admission accepts 0, then proceeds once background
    I/O frees a memtable — same contract as the scalar path."""
    eng = _mk("tiering", memtable=32, unique=512)
    keys = np.arange(100, dtype=np.uint32)
    vals = np.arange(100, dtype=np.int32)
    n1 = eng.put_batch(keys, vals)
    assert n1 == 64                       # 2 memtables x 32
    assert eng.put_batch(keys[n1:], vals[n1:]) == 0
    eng.pump(64)                          # flush a sealed memtable
    n2 = eng.put_batch(keys[n1:], vals[n1:])
    assert n2 > 0
    eng.drain()
    found, got = eng.get_batch(keys[:n1 + n2])
    assert found.all() and (got == vals[:n1 + n2]).all()


def test_memtable_put_batch_reports_fit():
    """MemTable.put_batch admits the prefix that fits and reports the
    count instead of raising on overflow."""
    mt = MemTable(10)
    assert mt.put_batch(np.arange(6), np.arange(6)) == 6
    assert mt.put_batch(np.arange(100, 108), np.arange(8)) == 4
    assert len(mt) == 10 and mt.full
    assert mt.put_batch(np.arange(3), np.arange(3)) == 0
    with pytest.raises(ValueError):
        mt.put_batch(np.array([0xFFFFFFFF], np.uint32), np.array([0]))
    f, v = mt.get_batch(np.array([0, 100, 103, 99], np.uint32))
    assert f.tolist() == [True, True, True, False]
    assert v[0] == 0 and v[1] == 0 and v[2] == 3


def test_memtable_get_batch_newest_wins():
    mt = MemTable(8)
    mt.put(5, 1)
    mt.put(5, 2)
    mt.put_batch(np.array([5, 7]), np.array([3, 9]))
    f, v = mt.get_batch(np.array([5, 7, 6], np.uint32))
    assert f.tolist() == [True, True, False]
    assert v[0] == 3 and v[1] == 9


def test_leveling_concurrent_merges_stay_age_adjacent():
    """Regression: the bLSM swap semantics could pair a frozen run with an
    age-NON-adjacent resident (skipping a fresher sibling elsewhere in the
    tree), making stamp-ordered reads return stale values.  This workload
    produced ~100 stale keys before the age-adjacency guard in
    ``LevelingPolicy.collect_merges``."""
    rng = np.random.default_rng(0)
    eng = LSMEngine(LevelingPolicy(3, 64, 1024), GreedyScheduler(),
                    GlobalConstraint(200), memtable_entries=64,
                    unique_keys=1024, use_kernels=False)
    ref = {}
    for i in range(2000):
        k, v = int(rng.integers(0, 1024)), int(rng.integers(0, 1 << 30))
        while not eng.put(k, v):
            eng.pump(128)
        ref[k] = v
        if i % 40 == 0:
            eng.pump(96)
    eng.drain()
    keys = np.fromiter(ref, dtype=np.uint32)
    found, vals = eng.get_batch(keys)
    assert found.all()
    assert dict(zip(keys.tolist(), vals.tolist())) == ref


# --------------------------------------------------- interpret plumbing
def test_interpret_flag_plumbed_to_tables():
    """An explicit ``interpret=True`` reaches every kernel launch the
    engine makes for its tables (they are screened by the fused probe, so
    no per-table mode exists), and reads of flushed tables still resolve."""
    eng = LSMEngine(TieringPolicy(3, 32, 256), GreedyScheduler(),
                    GlobalConstraint(200), memtable_entries=32,
                    unique_keys=256, use_kernels=True, interpret=True)
    for op in ("probe_multi", "merge_kway", "merge_kway_window"):
        assert eng.backend.decide(op, 1 << 20) == "interpret", op
    keys = np.arange(32, dtype=np.uint32)
    eng.put_batch(keys, keys.astype(np.int32) + 1)
    eng._seal_active()
    eng.pump(64)
    assert eng.tables and not len(eng.active)
    found, vals = eng.get_batch(keys)
    assert found.all() and np.array_equal(vals, keys.astype(np.int32) + 1)
