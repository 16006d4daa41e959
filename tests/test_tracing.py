"""The store's spans and counters: get, put, pump and scan open the
``lsm.*`` spans at their layer boundaries, nested as documented, in a
real profiler trace on the CPU; each probe launch adds to the probe
counters in ``StorageGroup.stats``."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.core.constraints import GlobalConstraint
from repro.core.engine import LSMEngine
from repro.core.fleet import LSMFleet
from repro.core.policies import TieringPolicy
from repro.core.scheduler import GreedyScheduler
from repro.core.wal import WriteAheadLog
from repro.kernels.bloom.ops import probe_batch

KEYS = 1 << 16
MEMTABLE = 128
COUNTERS = ("probe_cells", "probe_live_cells")

# (parent, span) for every span the four calls open; None is the call
EDGES = {
    (None, "lsm.get"), ("lsm.get", "lsm.lock"),
    ("lsm.get", "lsm.get.memtables"), ("lsm.get", "lsm.get.filters"),
    ("lsm.get", "lsm.get.probe"), ("lsm.get", "lsm.get.search"),
    (None, "lsm.put"), ("lsm.put", "lsm.lock"),
    ("lsm.put", "lsm.put.wal"), ("lsm.put", "lsm.wal.sync"),
    ("lsm.put", "lsm.put.memtable"),
    (None, "lsm.pump"), ("lsm.pump", "lsm.lock"),
    ("lsm.pump", "lsm.wal.sync"), ("lsm.pump", "lsm.pump.flush"),
    ("lsm.pump", "lsm.pump.merge"),
    (None, "lsm.scan"), ("lsm.scan", "lsm.lock"),
    ("lsm.scan", "lsm.scan.runs"), ("lsm.scan", "lsm.scan.merge"),
}


def _engine(wal=None, backend="host"):
    return LSMEngine(TieringPolicy(3, MEMTABLE, KEYS), GreedyScheduler(),
                     GlobalConstraint(200), memtable_entries=MEMTABLE,
                     unique_keys=KEYS, merge_block=64, wal=wal,
                     group_commit_entries=64, backend=backend)


def _fill(eng, rng, n):
    keys = rng.choice(KEYS, n, replace=False).astype(np.uint32)
    vals = rng.integers(0, 1 << 30, n).astype(np.int32)
    for s in range(0, n, MEMTABLE):
        assert eng.put_batch(keys[s:s + MEMTABLE], vals[s:s + MEMTABLE])
        eng.pump(1 << 20)
    return keys


def _spans(path) -> list[tuple[str, str]]:
    """(parent, name) of every ``lsm.`` span in a trace, the parent
    being the innermost ``lsm.`` span holding it on its thread."""
    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            evs = sorted(((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events
                          if ev.name.startswith("lsm.")),
                         key=lambda e: (e[1], -e[2]))
            stack = []
            for name, s, e in evs:
                while stack and stack[-1][2] <= s:
                    stack.pop()
                out.append((stack[-1][0] if stack else None, name))
                stack.append((name, s, e))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One trace of a put, a get, pumps through a flush and a merge, and
    a scan, on a small store with a WAL."""
    tmp = tmp_path_factory.mktemp("tracing")
    eng = _engine(WriteAheadLog(tmp / "wal"))
    rng = np.random.default_rng(7)
    loaded = _fill(eng, rng, 3 * MEMTABLE)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=opts)
    # a chunk past the group commit, then one the pump syncs
    _fill(eng, rng, 100)
    eng.put_batch(np.array([1, 2], np.uint32), np.array([1, 2], np.int32))
    eng.get_batch(loaded)
    for _ in range(64):                  # flush, then merge quanta
        eng.pump(16)
    eng.scan_range(0, KEYS)
    jax.profiler.stop_trace()
    eng.close()
    return _spans(next((tmp / "trace").rglob("*.xplane.pb"))), eng


def test_calls_open_their_spans_nested(traced):
    spans, eng = traced
    assert set(spans) == EDGES
    assert eng.stats["flushes"] and eng.stats["merges"]


def test_no_span_opens_per_table_or_key(traced):
    spans, _ = traced
    calls = {n: sum(1 for p, m in spans if m == n and p is None)
             for n in ("lsm.get", "lsm.put", "lsm.pump", "lsm.scan")}
    # one get of 256 keys over several tables: each step opens once
    for step in ("memtables", "filters", "probe", "search"):
        assert sum(1 for _, m in spans if m == f"lsm.get.{step}") == \
            calls["lsm.get"] == 1
    assert sum(1 for p, m in spans if m == "lsm.lock") == sum(calls.values())


def test_no_trace_no_spans_recorded(tmp_path):
    eng = _engine()
    _fill(eng, np.random.default_rng(1), 2 * MEMTABLE)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    jax.profiler.stop_trace()
    assert _spans(next(tmp_path.rglob("*.xplane.pb"))) == []


def _live_cells(tables, keys) -> int:
    """(table, key) pairs whose table's key range holds the key, one
    table at a time."""
    return sum(int(((keys >= t.keys_np[0]) & (keys <= t.keys_np[-1])).sum())
               for t in tables if len(t))


@pytest.mark.parametrize("backend", ["host", "interpret"])
def test_probe_counters(backend):
    """Two flushed tables over disjoint key ranges (the third batch
    stays in the memtable): a key is live in at most one, and the
    launch probed only those live cells, on either path."""
    eng = _engine(backend=backend)
    for t in range(3):
        keys = np.arange(t * 1000, t * 1000 + MEMTABLE, dtype=np.uint32)
        assert eng.put_batch(keys, keys.astype(np.int32))
        eng.pump(1 << 20)
    before = eng.stats
    q = np.array([0, 5, 1001, 1002, 900, 5000], np.uint32)
    eng.get_batch(q)
    d = {k: eng.stats[k] - before[k] for k in COUNTERS}
    tables = list(eng.tables.values())
    assert len(tables) == 2 and eng._fstack.cap >= len(tables)
    assert _live_cells(tables, q) == 4
    assert d == {"probe_cells": 4, "probe_live_cells": 4}


def test_live_cells_follow_the_filter_stack():
    """After flushes, merges and their row reuse, the stack's key
    ranges count what the tables' own ranges count."""
    eng = _engine()
    rng = np.random.default_rng(5)
    for _ in range(12):
        lo = int(rng.integers(0, KEYS - 4 * MEMTABLE))
        keys = rng.choice(np.arange(lo, lo + 4 * MEMTABLE), MEMTABLE,
                          replace=False).astype(np.uint32)
        assert eng.put_batch(keys, keys.astype(np.int32))
        eng.pump(300)
        q = rng.integers(0, KEYS, 64).astype(np.uint32)
        before = eng.stats["probe_live_cells"]
        eng.get_batch(q)
        assert eng.stats["probe_live_cells"] - before == \
            _live_cells(eng.tables.values(), q)


@pytest.mark.parametrize("backend", ["host", "interpret"])
def test_probe_cells_are_the_cells_probed(backend):
    """Over tiering runs whose key ranges overlap, with keys resolved in
    the memtable left out of the probe: ``probe_cells`` is the (row,
    key) cells the launch probed, each row's keys inside its table's
    range, never fewer than ``probe_live_cells``, and the share the
    benchmark reads from the two is 100% when every probed cell is
    live."""
    from lsmbench.metrics import probe_live_share
    eng = _engine(backend=backend)
    rng = np.random.default_rng(9)
    _fill(eng, rng, 5 * MEMTABLE)
    eng.put_batch(np.arange(8, dtype=np.uint32), np.ones(8, np.int32))
    q = np.concatenate([np.arange(8, dtype=np.uint32),
                        rng.integers(0, KEYS, 300, dtype=np.uint32)])
    before = eng.stats
    eng.get_batch(q)
    d = {k: eng.stats[k] - before[k] for k in COUNTERS}
    assert d["probe_cells"] == _live_cells(eng.tables.values(), q[8:])
    assert 0 < d["probe_live_cells"] <= d["probe_cells"]
    run = type("Run", (), {"stats_delta": d})
    assert probe_live_share.read(run) == 100.0


def test_a_get_answered_by_memtables_launches_no_probe():
    eng = _engine()
    eng.put_batch(np.arange(4, dtype=np.uint32), np.arange(4, dtype=np.int32))
    eng.get_batch(np.arange(4, dtype=np.uint32))
    assert {k: eng.stats[k] for k in COUNTERS} == dict.fromkeys(COUNTERS, 0)


def test_counters_are_in_stats_and_the_fleet_rollup():
    assert list(_engine().stats)[-2:] == list(COUNTERS)
    fleet = LSMFleet(2, lambda i: _engine(), parallel=False)
    keys = np.arange(0, 8 * MEMTABLE, 2, dtype=np.uint32)
    fleet.put_batch(keys, keys.astype(np.int32))
    fleet.drain()
    fleet.get_batch(keys)
    stats = fleet.stats
    assert stats["probe_cells"] >= stats["probe_live_cells"] > 0


@pytest.mark.parametrize("n", [1, 1024, 1025, 2048, 3000, 5000])
def test_probe_batch_is_a_power_of_two_of_blocks(n):
    p = probe_batch(n)
    assert p >= n and p % 1024 == 0
    assert (p // 1024) & (p // 1024 - 1) == 0
    assert p // 2 < n or p == 1024
