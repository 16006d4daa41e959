#!/usr/bin/env python3
"""Smoke run of the LSM store's data plane, compiled on one TPU.

Drives one ``LSMEngine`` through the calls a user makes: ``put_batch``,
``delete_batch``, ``pump`` and ``drain`` load 2**24 distinct uint32 keys
(128 MiB of key/value data) with updates and deletes through a
group-committed WAL under the greedy merge scheduler; ``get_batch`` then
serves 2**16 point reads (hits and misses), ``scan_range`` a few range
windows, and a ``BackgroundDriver`` phase serves concurrent writes, reads
and scans while the pump thread merges.  Every answer is checked against
a plain numpy newest-wins replay of the same operations, and every
data-plane dispatch decision must be ``compiled``.

    python chip_smoke.py [--seed 0]

The store is a LevelDB-style partitioned leveling tree (files of 2**20
entries, size ratio 10), so a read screens about 17 tables in one fused
Bloom probe.  Keys, values and the operation mix come from ``--seed``.
The times printed describe this one smoke run; they are not benchmark
numbers.  The script exits non-zero, printing no result line, unless
JAX's first device is a TPU.  Its last line is the JSON result.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

LOG2_KEYS = 24                 # distinct keys loaded
LOG2_READS = 16                # point reads in the read phase
MEMTABLE = 1 << 18
FILE_ENTRIES = 1 << 20
TOMBSTONE = np.int32(-2 ** 31)


def _counting_backend(base, **kw):
    """An ``ExecBackend`` subclass instance that counts every ``decide``
    result per (op, mode); the foreground and the pump thread share it."""

    class Counting(base):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.decisions = collections.Counter()
            self._count_lock = threading.Lock()

        def decide(self, op, size):
            mode = super().decide(op, size)
            with self._count_lock:
                self.decisions[(op, mode)] += 1
            return mode

    return Counting(**kw)


def _newest(ks: np.ndarray, vs: np.ndarray):
    """Sorted unique keys with the value of each key's LAST write."""
    order = np.argsort(ks, kind="stable")
    ks, vs = ks[order], vs[order]
    last = np.ones(len(ks), bool)
    last[:-1] = ks[1:] != ks[:-1]
    return ks[last], vs[last]


def _lookup(ks: np.ndarray, vs: np.ndarray, keys: np.ndarray):
    i = np.minimum(np.searchsorted(ks, keys), max(len(ks) - 1, 0))
    found = ks[i] == keys if len(ks) else np.zeros(len(keys), bool)
    return found, np.where(found, vs[i] if len(ks) else 0, 0)


class Reference:
    """Plain numpy newest-wins replay of every admitted operation: a
    settled base (deleted keys dropped) plus the newest write per key
    since, tombstones kept so they can mask the base."""

    def __init__(self):
        self.keys: list[np.ndarray] = []
        self.vals: list[np.ndarray] = []
        self._based = 0                     # log entries in the base
        empty = (np.empty(0, np.uint32), np.empty(0, np.int32))
        self.base = self.recent = empty

    def record(self, keys, vals) -> None:
        self.keys.append(np.array(keys, np.uint32))
        self.vals.append(np.array(vals, np.int32))

    def settle(self) -> None:
        """Fold the whole log into the base."""
        ks, vs = _newest(np.concatenate(self.keys),
                         np.concatenate(self.vals))
        live = vs != TOMBSTONE
        self.base = ks[live], vs[live]
        self._based = len(self.keys)
        self.recent = (np.empty(0, np.uint32), np.empty(0, np.int32))

    def settle_recent(self) -> None:
        """Resolve only what was logged since the last ``settle``."""
        if len(self.keys) > self._based:
            self.recent = _newest(np.concatenate(self.keys[self._based:]),
                                  np.concatenate(self.vals[self._based:]))

    def get(self, keys):
        keys = np.asarray(keys, np.uint32)
        rf, rv = _lookup(*self.recent, keys)
        bf, bv = _lookup(*self.base, keys)
        found = np.where(rf, rv != TOMBSTONE, bf)
        return found, np.where(found, np.where(rf, rv, bv), 0)

    def scan(self, lo, hi):
        window = {}
        for ks, vs in (self.base, self.recent):
            a, b = np.searchsorted(ks, np.array([lo, hi], np.uint32))
            window.update(zip(ks[a:b].tolist(), vs[a:b].tolist()))
        items = sorted((k, v) for k, v in window.items()
                       if v != TOMBSTONE)
        return (np.array([k for k, _ in items], np.uint32),
                np.array([v for _, v in items], np.int32))


def _distinct_keys(rng, n: int) -> np.ndarray:
    """``n`` distinct uint32 keys below the reserved sentinel 2**32-1,
    in random order."""
    keys = np.unique(rng.integers(0, 2 ** 32 - 1, int(n * 1.05) + 1024,
                                  dtype=np.uint32))
    return rng.permutation(keys)[:n]


def _values(rng, n: int) -> np.ndarray:
    """int32 values, never the reserved tombstone."""
    return rng.integers(-2 ** 31 + 1, 2 ** 31, n, dtype=np.int32)


def _admit(eng, ref, budget: int, keys, vals=None) -> int:
    """Admit the whole batch, pumping while the write path stalls;
    returns how many pumps the stalls cost."""
    done = stalls = 0
    while done < len(keys):
        if vals is None:
            n = eng.delete_batch(keys[done:])
            ref.record(keys[done:done + n],
                       np.full(n, TOMBSTONE, np.int32))
        else:
            n = eng.put_batch(keys[done:], vals[done:])
            ref.record(keys[done:done + n], vals[done:done + n])
        done += n
        if done < len(keys):
            eng.pump(budget)
            stalls += 1
    return stalls


def _check_reads(eng, ref, queries, batch: int) -> int:
    """Point reads through ``get_batch`` in batches; returns mismatches."""
    bad = 0
    for s in range(0, len(queries), batch):
        q = queries[s:s + batch]
        found, vals = eng.get_batch(q)
        wf, wv = ref.get(q)
        bad += int((found != wf).sum() + (found & (vals != wv)).sum())
    return bad


def _check_scan(eng, ref, lo: int, hi: int) -> tuple[int, int]:
    ks, vs = eng.scan_range(lo, hi)
    wk, wv = ref.scan(lo, hi)
    ok = np.array_equal(ks, wk) and np.array_equal(vs, wv)
    return (0 if ok else 1), len(wk)


def run(seed: int, backend, log2_keys: int = LOG2_KEYS,
        log2_reads: int = LOG2_READS, memtable: int = MEMTABLE,
        file_entries: int = FILE_ENTRIES, log=print) -> dict:
    """Load, read, scan and serve one store; returns the counters the
    result line is decided from."""
    from repro.core.constraints import NoConstraint
    from repro.core.engine import BackgroundDriver, LSMEngine
    from repro.core.policies import PartitionedLevelingPolicy
    from repro.core.scheduler import GreedyScheduler
    from repro.core.wal import WriteAheadLog

    rng = np.random.default_rng(seed)
    n_keys = 1 << log2_keys
    chunk = max(n_keys >> 8, 64)             # put_batch size
    budget = 8 * chunk                       # background I/O per pump
    t0 = time.perf_counter()
    keys = _distinct_keys(rng, n_keys)
    vals = _values(rng, n_keys)
    out = {"mismatches": 0}
    with tempfile.TemporaryDirectory(prefix="lsm-smoke-") as tmp:
        wal = WriteAheadLog(Path(tmp) / "wal", segment_entries=1 << 20)
        policy = PartitionedLevelingPolicy(
            10, memtable, n_keys, file_entries=file_entries,
            l1_capacity=4 * file_entries)
        eng = LSMEngine(policy, GreedyScheduler(), NoConstraint(),
                        memtable_entries=memtable, unique_keys=n_keys,
                        wal=wal, group_commit_entries=1 << 16,
                        backend=backend)
        ref = Reference()
        out["setup_s"] = time.perf_counter() - t0

        # -- load: inserts, with updates and deletes of loaded keys -----
        t0 = time.perf_counter()
        stalls = updates = deletes = 0
        for c, s in enumerate(range(0, n_keys, chunk)):
            stalls += _admit(eng, ref, budget, keys[s:s + chunk],
                             vals[s:s + chunk])
            if c % 8 == 7:
                upd = keys[rng.integers(0, s + chunk, chunk // 8)]
                stalls += _admit(eng, ref, budget, upd,
                                 _values(rng, len(upd)))
                dele = keys[rng.integers(0, s + chunk, chunk // 16)]
                stalls += _admit(eng, ref, budget, dele)
                updates += len(upd)
                deletes += len(dele)
            eng.pump(budget)
            if c % 32 == 31:
                log(f"  loaded {s + chunk} keys "
                    f"({time.perf_counter() - t0:.1f} s)", flush=True)
        eng.drain(budget)
        out["load_s"] = time.perf_counter() - t0
        ref.settle()
        tables = list(eng.tables.values())
        out.update(loaded=n_keys, updates=updates, deletes=deletes,
                   stall_pumps=stalls, tables=len(tables),
                   tables_on_device=sum(t.device_resident for t in tables),
                   entries_in_tables=sum(len(t) for t in tables),
                   live_keys=len(ref.base[0]), stats=eng.stats)

        # -- read: point reads (hits, deleted keys, misses) and scans ---
        t0 = time.perf_counter()
        n_reads = 1 << log2_reads
        hits = keys[rng.integers(0, n_keys, n_reads // 2)]
        misses = rng.integers(0, 2 ** 32 - 1, n_reads - len(hits),
                              dtype=np.uint32)
        queries = rng.permutation(np.concatenate([hits, misses]))
        out["read_mismatches"] = _check_reads(eng, ref, queries,
                                              batch=n_reads // 4)
        span = (2 ** 32 // n_keys) * min(4096, n_keys >> 4)  # keys/window
        scan_bad = scanned = 0
        for lo in rng.integers(0, 2 ** 32 - span, 8):
            bad, n = _check_scan(eng, ref, int(lo), int(lo) + span)
            scan_bad += bad
            scanned += n
        out.update(scan_mismatches=scan_bad, scanned_keys=scanned,
                   read_s=time.perf_counter() - t0, reads=n_reads)

        # -- serve: concurrent writes/reads/scans under the pump thread -
        t0 = time.perf_counter()
        served_bad = served_ops = 0
        drv = BackgroundDriver(eng, bandwidth_bytes_per_s=1024 * 4e6,
                               quantum_s=0.01)
        drv.start()
        try:
            for _ in range(24):
                new = _distinct_keys(rng, chunk // 4)
                old = keys[rng.integers(0, n_keys, chunk // 4)]
                with eng.lock():
                    _admit(eng, ref, budget, new, _values(rng, len(new)))
                    _admit(eng, ref, budget, old, _values(rng, len(old)))
                    _admit(eng, ref, budget,
                           keys[rng.integers(0, n_keys, chunk // 64)])
                    ref.settle_recent()
                    q = np.concatenate([new[:2048], old[:2048],
                                        rng.integers(0, 2 ** 32 - 1, 2048,
                                                     dtype=np.uint32)])
                    served_bad += _check_reads(eng, ref, q, batch=len(q))
                    lo = int(rng.integers(0, 2 ** 32 - span))
                    served_bad += _check_scan(eng, ref, lo, lo + span)[0]
                served_ops += 2 * len(new) + chunk // 64 + len(q) + 1
        finally:
            drv.stop()
        eng.drain(budget)
        served_bad += _check_reads(eng, ref, keys[:n_reads], batch=n_reads)
        out.update(served_mismatches=served_bad, served_ops=served_ops,
                   serve_s=time.perf_counter() - t0)
        eng.close()
    out["mismatches"] = (out["read_mismatches"] + out["scan_mismatches"]
                         + out["served_mismatches"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the keys, values and operation mix")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.core.backend import COMPILED, ExecBackend

    cache = enable_compile_cache()
    compile_s = collections.Counter()

    def on_event(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s["secs"] += secs
            compile_s["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}; compile cache {cache}", flush=True)
    backend = _counting_backend(ExecBackend, mode="auto")
    out = run(args.seed, backend)

    print(f"loaded {out['loaded']} distinct keys (+{out['updates']} "
          f"updates, {out['deletes']} deletes; {out['stall_pumps']} "
          f"stall pumps); {out['live_keys']} keys live")
    print(f"tables: {out['tables']} ({out['tables_on_device']} device-"
          f"resident), {out['entries_in_tables']} entries; "
          f"flushes={out['stats']['flushes']} "
          f"merges={out['stats']['merges']} "
          f"wal_syncs={out['stats']['wal_syncs']}")
    decisions = sorted(backend.decisions.items())
    for (op, mode), n in decisions:
        print(f"decision {op} -> {mode}: {n}")
    print(f"point reads: {out['reads']}, mismatches "
          f"{out['read_mismatches']}; scans: 8 windows, "
          f"{out['scanned_keys']} keys, mismatches "
          f"{out['scan_mismatches']}; served phase: {out['served_ops']} "
          f"ops, mismatches {out['served_mismatches']}")
    print(f"smoke run times (not benchmark numbers): setup "
          f"{out['setup_s']:.1f} s, load {out['load_s']:.1f} s, read "
          f"{out['read_s']:.1f} s, serve {out['serve_s']:.1f} s; "
          f"{compile_s['n']} compilations took {compile_s['secs']:.1f} s")
    not_compiled = [(op, mode) for (op, mode), _ in decisions
                    if mode != COMPILED]
    ok = (out["mismatches"] == 0 and not not_compiled and decisions
          and out["loaded"] >= 1 << LOG2_KEYS)
    if not ok:
        print(f"FAILED: mismatches={out['mismatches']} "
              f"non-compiled decisions={not_compiled}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
