"""The LSM engine as a standalone key-value store: write a workload through
the greedy scheduler under an I/O budget, then query it — Bloom probes
and merges run wherever the engine's execution backend sends them
(compiled Pallas kernels on a TPU). A second phase serves the same store
behind the wall-clock ``BackgroundDriver``: the pump thread holds the
engine lock around each quantum, and the foreground read/write path
takes the same lock (``with eng.lock():``) so serving traffic never
races background I/O. A third phase serves the SAME workload through a
4-shard ``LSMFleet``: the batched router scatters keys across shards,
the ``FleetBackgroundDriver`` splits one global I/O budget via the fair
arbiter, and no external locking is needed — engines lock internally. A
final phase makes the store durable: writes (and tombstoned deletes) go
through a group-committed WAL, a snapshot is taken mid-workload, the
process is "killed" at a fault-injection crash point with a torn WAL
tail, and a fresh engine recovers — snapshot restore + budgeted replay —
to a state bit-identical to a reference fed the durable prefix.

    PYTHONPATH=src python examples/lsm_store.py
"""
import tempfile
import time
from pathlib import Path

import numpy as np

from repro.checkpoint import EngineSnapshotStore
from repro.core.constraints import GlobalConstraint
from repro.core.engine import BackgroundDriver, LSMEngine
from repro.core.faults import (FaultInjector, SimulatedCrash, WorkloadLog,
                               apply_entries, apply_torn_tail,
                               assert_reads_equal)
from repro.core.fleet import FleetBackgroundDriver, LSMFleet
from repro.core.policies import TieringPolicy
from repro.core.scheduler import GreedyScheduler
from repro.core.wal import RecoverySession, WriteAheadLog


def main():
    rng = np.random.default_rng(0)
    eng = LSMEngine(TieringPolicy(3, 512, 8192), GreedyScheduler(),
                    GlobalConstraint(48), memtable_entries=512,
                    unique_keys=8192, merge_block=128)
    ref = {}
    stalls = 0
    keys = rng.integers(0, 8192, 10_000).astype(np.uint32)
    vals = rng.integers(0, 1 << 30, 10_000).astype(np.int32)
    # bulk admission: slice-at-a-time, pumping only when admission stalls
    done = 0
    while done < len(keys):
        chunk_k, chunk_v = keys[done:done + 512], vals[done:done + 512]
        n = eng.put_batch(chunk_k, chunk_v)
        ref.update(zip(chunk_k[:n].tolist(), chunk_v[:n].tolist()))
        done += n
        if n < len(chunk_k):
            stalls += 1
        eng.pump(512)                 # background I/O quantum
    eng.drain()
    qs = rng.choice(8192, 500, replace=False).astype(np.uint32)
    found, got = eng.get_batch(qs)    # one fused multi-table probe
    wrong = sum((int(got[i]) if found[i] else None) != ref.get(int(k))
                for i, k in enumerate(qs))
    print(f"writes={eng.stats['puts']} flushes={eng.stats['flushes']} "
          f"merges={eng.stats['merges']} components={eng.num_components()} "
          f"write-stall-retries={stalls}")
    print(f"point lookups: {len(qs)} queried, {wrong} wrong; "
          f"bloom skipped {eng.stats['bloom_skips']} component probes")
    sk, sv = eng.scan_range(1000, 1100)    # one k-way newest-wins merge
    want = {k: v for k, v in ref.items() if 1000 <= k < 1100}
    scan_ok = dict(zip(sk.tolist(), sv.tolist())) == want
    print(f"range scan [1000,1100): {len(sk)} keys, correct={scan_ok}")
    assert wrong == 0 and scan_ok

    # ---- serve the store behind the wall-clock background driver ----
    drv = BackgroundDriver(eng, bandwidth_bytes_per_s=8e6, quantum_s=0.002)
    drv.start()
    served_wrong = 0
    try:
        for k in rng.integers(0, 8192, 2000).astype(np.uint32):
            v = int(rng.integers(0, 1 << 30))
            with eng.lock():              # foreground vs pump thread
                if eng.put(int(k), v):
                    ref[int(k)] = v
        qs = rng.choice(8192, 200, replace=False).astype(np.uint32)
        with eng.lock():
            found, got = eng.get_batch(qs)
            sk, sv = eng.scan_range(4000, 4200)
        served_wrong = sum(
            (int(got[i]) if found[i] else None) != ref.get(int(k))
            for i, k in enumerate(qs))
        want = {k: v for k, v in ref.items() if 4000 <= k < 4200}
        served_wrong += dict(zip(sk.tolist(), sv.tolist())) != want
    finally:
        drv.stop()
    print(f"served phase: {served_wrong} wrong under concurrent pump")
    assert served_wrong == 0

    # ---- the same store as a key-partitioned fleet behind the router ----
    # Four shards, one global I/O budget split by the fair arbiter; the
    # router scatters each batch by hash(key) % 4 and serves shards on a
    # worker pool, so NO external locking is needed (engines lock
    # internally).
    fleet = LSMFleet(4, lambda s: LSMEngine(
        TieringPolicy(3, 512, 8192), GreedyScheduler(),
        GlobalConstraint(48), memtable_entries=512, unique_keys=8192,
        merge_block=128), arbiter="fair")
    fdrv = FleetBackgroundDriver(fleet, bandwidth_bytes_per_s=8e6,
                                 quantum_s=0.002)
    fdrv.start()
    fref = {}
    try:
        with fleet:
            # a stalled shard rejects only ITS sub-batch, so the
            # admitted set is not a prefix of the caller's batch:
            # retry by mask, keeping rejected keys ahead of the rest
            # (preserves per-key write order)
            pend = np.arange(len(keys))
            while len(pend):
                sel = pend[:512]
                mask = fleet.put_batch_admitted(keys[sel], vals[sel])
                ok = sel[mask]
                fref.update(zip(keys[ok].tolist(), vals[ok].tolist()))
                pend = np.concatenate([sel[~mask], pend[512:]])
                if not mask.all():  # stalled shard: the driver drains it
                    time.sleep(0.001)
            found, got = fleet.get_batch(qs)
            fleet_wrong = sum(
                (int(got[i]) if found[i] else None) != fref.get(int(k))
                for i, k in enumerate(qs))
            sk, sv = fleet.scan_range(4000, 4200)
            want = {k: v for k, v in fref.items() if 4000 <= k < 4200}
            fleet_wrong += dict(zip(sk.tolist(), sv.tolist())) != want
    finally:
        fdrv.stop()
    st = fleet.stats
    print(f"fleet phase (4 shards): {fleet_wrong} wrong, "
          f"{st['flushes']} flushes, {st['merges']} merges fleet-wide")
    assert fleet_wrong == 0

    # ---- kill -9 and recover: WAL + snapshot + fault injection ----
    # The WAL logs every admitted write/delete in order (group commit:
    # one fsync per 256 entries or per pump epoch, the sync charged to
    # the same I/O budget as flushes and merges).  A crash loses at
    # most the unsynced tail; recovery = restore the snapshot's tables,
    # then replay the WAL suffix under a budgeted session.
    with tempfile.TemporaryDirectory() as td:
        tmp = Path(td)
        faults = FaultInjector()
        mk = lambda w, f=None: LSMEngine(
            TieringPolicy(3, 512, 8192), GreedyScheduler(),
            GlobalConstraint(48), memtable_entries=512, unique_keys=8192,
            merge_block=128, wal=w, group_commit_entries=256, faults=f)
        eng = mk(WriteAheadLog(tmp / "wal"), faults)
        store = EngineSnapshotStore(tmp / "snap")
        log = WorkloadLog()           # admitted history, in order

        def feed(ks, vs=None):        # record exactly what was admitted
            done = 0
            try:
                while done < len(ks):
                    if vs is None:
                        n = eng.delete_batch(ks[done:])
                        log.record_deletes(ks[done:done + n])
                    else:
                        n = eng.put_batch(ks[done:], vs[done:])
                        log.record(ks[done:done + n], vs[done:done + n])
                    done += n
                    if done < len(ks):
                        eng.pump(512)
            except SimulatedCrash:    # unacked tail: WAL holds a prefix
                log.record(ks[done:], vs[done:]) if vs is not None \
                    else log.record_deletes(ks[done:])
                raise

        try:
            for r in range(12):
                feed(rng.integers(0, 8192, 400, dtype=np.uint32),
                     rng.integers(0, 1 << 30, 400, dtype=np.int32))
                feed(rng.integers(0, 8192, 80, dtype=np.uint32))  # deletes
                eng.pump(1024)
                if r == 5:
                    eng.snapshot(store)   # fsync + persist + truncate WAL
                if r == 8:
                    faults.arm("pre-flush")   # next flush never finishes
        except SimulatedCrash as e:
            print(f"durability phase: simulated crash at {e.point!r} "
                  f"after {log.n} admitted ops")
        apply_torn_tail(eng.wal, 0.5)     # half the unsynced tail survives

        eng2 = mk(WriteAheadLog(tmp / "wal"))
        sess = RecoverySession(eng2, store)
        epochs = sess.run(budget_per_epoch=2048)
        rec = eng2._lsn
        assert eng2.wal.synced_lsn <= rec <= log.n
        # a reference store fed exactly the recovered prefix must agree
        ref = mk(None)
        ks, vs = log.prefix(rec)
        apply_entries(ref, ks, vs)
        ref.drain()
        assert_reads_equal(eng2, ref, 8192)
        print(f"recovered {rec}/{log.n} ops in {epochs} budgeted epochs "
              f"(replayed {eng2.stats['replayed']} from WAL, "
              f"{eng2.live_entries()} keys live); reads match the "
              f"durable prefix")
        eng2.close()
    print("OK")


if __name__ == "__main__":
    main()
