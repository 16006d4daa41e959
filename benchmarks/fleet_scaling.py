"""Fleet scaling: the sharded serving plane vs the single engine
(ISSUE 6).

Section A (closed-loop admission): a batched writer drives
``LSMFleet.put_batch`` as fast as admission allows at shard counts
{1, 2, 4, 8} under one GLOBAL wall-clock background budget
(``FleetBackgroundDriver``), in two regimes:

* A1, burst window — a fixed window at a modest paced budget.  Each
  shard owns its own memtable group, so the fleet absorbs N× more
  in-flight writes before its first stall while background I/O drains
  at the same global budget either way; admitted throughput over the
  window scales with shard count.  Bar: >= 2x admitted at 4 shards vs
  1 shard.  (On a multi-core host the worker pool adds background
  flush/merge parallelism on top; this container is single-CPU, so the
  cell isolates the buffering term — the artifact records
  ``cpu_count`` alongside.)
* A2, sustained — a long window at a budget far below admission speed.
  The paper's invariant, fleet-wide: steady-state throughput equals
  the global I/O budget over the write amplification, so shard count
  must NOT buy sustained throughput — the arbiter conserves one global
  budget.  Bar: 4-shard/1-shard sustained ratio within [0.75, 1.35].

Section B (open-loop tail): the ``latency_tail.py`` methodology —
coordinated-omission-free scheduled arrivals, writer ``put_batch`` +
reader ``scan_range`` against a live background plane over a preloaded
cascading merge workload.  Total resources are held CONSTANT across
shard counts: each shard gets its key-routed preload slice and 1/N of
the memtable capacity, so the comparison isolates the router, not extra
buffer.  Bar: writer p99 at 4 shards within 3x of the single-engine
baseline (a plain ``LSMEngine`` driven exactly like
``latency_tail.py``), measured as the MEDIAN of 5 paired back-to-back
ratios (the box freezes intermittently for tens of ms; pairing cancels
slow phases, the median drops a poisoned rep).  The measured clean
median ratio is ~2.5x and is a single-core artifact: the harness (like
``latency_tail.py``) interleaves ops on one client thread, every 8th op
is a scan that fans to all N shards with N-fold per-run snapshot
overhead, and there is no second core for the pool to hide it on — the
artifact records ``cpu_count``.  Two fleet scan-plane optimizations are
load-bearing here and regression-pinned by this bar: adaptive inline
dispatch (no pool handoff for narrow ops) and the flat one-pass gather
merge (``engine.scan_runs``), which together took the 4-shard writer
p99 from ~4x the baseline to ~2.5x.

Section C (starved global budget): 4 shards preloaded with SKEWED merge
debt, pumped in deterministic epochs at a tiny global budget.  The
paper's scheduler comparison, fleet-wide: the fair arbiter apportions
every epoch across all indebted shards (largest remainder by debt), the
greedy arbiter drains the fewest-remaining-bytes shard first — so
greedy finishes its first shard strictly earlier while fair spreads
grants across strictly more shards per epoch.

Section D: a miniature fleet-vs-single-engine differential (the full
version lives in ``tests/test_fleet.py``) — bit-identical get/scan
results on a shared random trace.

    PYTHONPATH=src python -m benchmarks.fleet_scaling [--quick]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import time

import numpy as np

from repro.core.engine import BackgroundDriver, LSMEngine
from repro.core.fleet import FleetBackgroundDriver, LSMFleet
from repro.core.metrics import LatencyRecorder
from repro.core.policies import TieringPolicy
from repro.core.scheduler import FairScheduler
from repro.core.sstable import SSTable

from .common import save

KEY_SPACE = 1 << 22
MEMTABLE = 32_768


def _mk_engine(_shard: int = 0) -> LSMEngine:
    return LSMEngine(TieringPolicy(4, MEMTABLE, KEY_SPACE), FairScheduler(),
                     None, memtable_entries=MEMTABLE, num_memtables=4,
                     unique_keys=KEY_SPACE, use_kernels=False)


def _mk_engine_scaled(n_shards: int):
    """Shard factory holding TOTAL resources constant: each of N shards
    gets 1/N of the single engine's memtable capacity, so the tail cells
    compare equal-footprint configurations (a scan's memtable-window
    extraction touches the same total buffer at every shard count)."""
    per = max(2048, MEMTABLE // n_shards)

    def factory(_shard: int = 0) -> LSMEngine:
        return LSMEngine(TieringPolicy(4, per, KEY_SPACE), FairScheduler(),
                         None, memtable_entries=per, num_memtables=4,
                         unique_keys=KEY_SPACE, use_kernels=False)
    return factory


def _inject_table(eng: LSMEngine, keys: np.ndarray, level: int) -> None:
    vals = keys.astype(np.int32)
    table = SSTable.build(np.sort(keys), vals, level=level,
                          created_at=eng.now)
    eng._bind_table(table)


# ---------------------------------------------------------------- section A
def _closed_loop(n_shards: int, duration: float, batch: int,
                 bw_bytes: float) -> dict:
    fleet = LSMFleet(n_shards, _mk_engine, arbiter="fair")
    drv = FleetBackgroundDriver(fleet, bw_bytes, quantum_s=0.005)
    rng = np.random.default_rng(n_shards)
    # pre-generate the write stream: the foreground loop should measure
    # admission + routing, not RNG cost
    pool_n = 1 << 21
    kpool = rng.integers(0, KEY_SPACE, pool_n, dtype=np.uint32)
    vpool = rng.integers(0, 1 << 30, pool_n, dtype=np.int32)
    admitted = 0
    off = 0
    drv.start()
    t0 = time.monotonic()
    try:
        while time.monotonic() - t0 < duration:
            if off + batch > pool_n:
                off = 0
            n = fleet.put_batch(kpool[off:off + batch],
                                vpool[off:off + batch])
            admitted += n
            off += batch
            if n < batch:
                time.sleep(1e-3)        # stalled: let background drain
    finally:
        elapsed = time.monotonic() - t0
        drv.stop()
        stats = fleet.stats
        fleet.close()
    return {"shards": n_shards, "admitted": admitted, "elapsed_s": elapsed,
            "puts_per_s": admitted / elapsed, "flushes": stats["flushes"],
            "merges": stats["merges"], "stalls": stats["stall_events"]}


# ---------------------------------------------------------------- section B
def _preload_cascade(store, n_shards: int, level_sizes: list[int],
                     rng) -> None:
    """3 tables per level per shard, key-routed so each shard holds only
    its own partition; TOTAL entries per level are constant across shard
    counts (each shard gets ~1/N of every table)."""
    engines = store.engines if isinstance(store, LSMFleet) else [store]
    for level, n in enumerate(level_sizes):
        for _ in range(3):
            keys = np.unique(rng.integers(0, KEY_SPACE, int(n * 1.3),
                                          dtype=np.uint32))[:n]
            if isinstance(store, LSMFleet):
                sid = store.shard_ids(keys)
                for s, eng in enumerate(engines):
                    _inject_table(eng, keys[sid == s], level)
            else:
                _inject_table(engines[0], keys, level)


def _open_loop(store, driver, duration: float, rate_ops: float,
               batch: int, read_every: int) -> dict:
    """The latency_tail discipline: ops fire at fixed SCHEDULED times;
    latency is completion - scheduled (no coordinated omission); a
    stalled write retries until its whole batch lands."""
    wrec, rrec = LatencyRecorder(), LatencyRecorder()
    rng = np.random.default_rng(7)
    interval = 1.0 / rate_ops
    driver.start()
    try:
        t0 = time.monotonic()
        i = 0
        while True:
            sched = t0 + i * interval
            lag = sched - time.monotonic()
            if lag > 0:
                time.sleep(lag)
            if time.monotonic() - t0 >= duration:
                break
            if read_every and i % read_every == read_every - 1:
                lo = int(rng.integers(0, KEY_SPACE - 4096))
                store.scan_range(lo, lo + 4096)
                rrec.observe(time.monotonic() - sched)
            else:
                keys = rng.integers(0, KEY_SPACE, batch, dtype=np.uint32)
                vals = rng.integers(0, 1 << 30, batch, dtype=np.int32)
                done = 0
                while done < batch:
                    took = store.put_batch(keys[done:], vals[done:])
                    done += took
                    if took == 0:
                        time.sleep(2e-4)
                wrec.observe(time.monotonic() - sched)
            i += 1
    finally:
        driver.stop()
    stats = store.stats
    return {"writer": wrec.summary(), "reader": rrec.summary(),
            "merges": stats["merges"], "flushes": stats["flushes"]}


def _tail_cell(n_shards: int | None, duration: float,
               level_sizes: list[int], bw_bytes: float, rate_ops: float,
               batch: int, read_every: int) -> dict:
    """``n_shards=None`` is the single-engine baseline (plain LSMEngine +
    BackgroundDriver, exactly the latency_tail.py harness shape)."""
    rng = np.random.default_rng(42)
    if n_shards is None:
        eng = _mk_engine()
        _preload_cascade(eng, 1, level_sizes, rng)
        out = _open_loop(eng, BackgroundDriver(eng, bw_bytes,
                                               quantum_s=0.005),
                         duration, rate_ops, batch, read_every)
        out["shards"] = 0           # 0 == no router, the raw engine
        return out
    fleet = LSMFleet(n_shards, _mk_engine_scaled(n_shards), arbiter="fair")
    try:
        _preload_cascade(fleet, n_shards, level_sizes, rng)
        out = _open_loop(fleet, FleetBackgroundDriver(fleet, bw_bytes,
                                                      quantum_s=0.005),
                         duration, rate_ops, batch, read_every)
    finally:
        fleet.close()
    out["shards"] = n_shards
    return out


# ---------------------------------------------------------------- section C
def _starved_cell(policy: str, shard_table_sizes: list[int],
                  epoch_budget: int, max_epochs: int = 4000) -> dict:
    """Deterministic epochs under a starved global budget: shard i is
    preloaded with 4 same-size L0 tables of ``shard_table_sizes[i]``
    entries (an immediate 4-way merge per shard), then the arbiter splits
    ``epoch_budget`` each epoch until every shard drains."""
    n = len(shard_table_sizes)
    fleet = LSMFleet(n, _mk_engine, arbiter=policy, parallel=False)
    rng = np.random.default_rng(9)
    for s, size in enumerate(shard_table_sizes):
        for _ in range(4):
            keys = np.unique(rng.integers(0, KEY_SPACE, int(size * 1.3),
                                          dtype=np.uint32))[:size]
            _inject_table(fleet.engines[s], keys, 0)
    drain_epoch: dict[int, int] = {}
    nonzero_counts: list[int] = []
    spent_total = 0
    for epoch in range(1, max_epochs + 1):
        debts = fleet.pending_debts()
        for s, d in enumerate(debts):
            if d == 0 and s not in drain_epoch:
                drain_epoch[s] = epoch - 1
        if len(drain_epoch) == n:
            break
        grants = fleet.arbiter.allocate(debts, epoch_budget)
        assert sum(grants) <= epoch_budget
        nonzero_counts.append(sum(1 for g in grants if g > 0))
        for s, g in enumerate(grants):
            if g > 0:
                spent_total += fleet.engines[s].pump(g)
    fleet.close()
    return {"policy": policy, "epoch_budget": epoch_budget,
            "shard_table_sizes": shard_table_sizes,
            "drain_epoch_per_shard": [drain_epoch.get(s)
                                      for s in range(n)],
            "first_drain_epoch": min(drain_epoch.values()),
            "last_drain_epoch": max(drain_epoch.values()),
            "mean_shards_granted_per_epoch":
                float(np.mean(nonzero_counts)) if nonzero_counts else 0.0,
            "spent_total": spent_total}


# ---------------------------------------------------------------- section D
def _mini_differential(n_shards: int = 4) -> bool:
    rng = np.random.default_rng(123)
    eng = _mk_engine()
    fleet = LSMFleet(n_shards, _mk_engine, arbiter="fair")
    try:
        for _ in range(4):
            keys = rng.integers(0, KEY_SPACE, 8192, dtype=np.uint32)
            vals = rng.integers(0, 1 << 30, 8192, dtype=np.int32)
            assert eng.put_batch(keys, vals) == 8192
            assert fleet.put_batch(keys, vals) == 8192
            eng.pump(8192)
            fleet.pump(8192)
        eng.drain()
        fleet.drain()
        qs = rng.integers(0, KEY_SPACE, 4096, dtype=np.uint32)
        f1, v1 = eng.get_batch(qs)
        f2, v2 = fleet.get_batch(qs)
        lo = int(rng.integers(0, KEY_SPACE // 2))
        k1, x1 = eng.scan_range(lo, lo + (1 << 18))
        k2, x2 = fleet.scan_range(lo, lo + (1 << 18))
        return bool((f1 == f2).all() and (v1[f1] == v2[f2]).all()
                    and np.array_equal(k1, k2) and np.array_equal(x1, x2))
    finally:
        fleet.close()


def run(quick: bool = False) -> dict:
    if quick:
        shard_counts = [1, 2, 4]
        burst_dur, burst_bw = 2.0, 4.0e7
        sustained_dur, sustained_bw = 2.0, 1.5e9
        tput_bar = 2.0
        tail_dur, tail_sizes, tail_bw = 2.5, [24_576, 98_304], 2.5e8
        tail_bar = 3.5
        starved_sizes, starved_budget = [512, 2048, 8192, 16_384], 512
    else:
        shard_counts = [1, 2, 4, 8]
        burst_dur, burst_bw = 4.0, 4.0e7
        sustained_dur, sustained_bw = 6.0, 1.5e9
        tput_bar = 2.0
        tail_dur, tail_sizes, tail_bw = 8.0, [98_304, 393_216], 4.0e8
        tail_bar = 3.0
        starved_sizes, starved_budget = [2048, 8192, 32_768, 65_536], 1024
    closed_batch = 8192

    # PAIRED tail claim cells FIRST (before this benchmark's own
    # CPU-saturating closed-loop cells disturb the box): baseline and
    # fleet-4 alternate back to back, 5 reps, and the claim compares the
    # MEDIAN of per-rep ratios.  This shared box intermittently freezes
    # the whole process for tens of ms (observed: the same cell
    # measuring 2 ms and 83 ms minutes apart); pairing cancels
    # slow-machine phases and the median drops poisoned reps.
    pairs = []
    for _ in range(5):
        gc.collect()
        b = _tail_cell(None, tail_dur, tail_sizes, tail_bw,
                       rate_ops=400.0, batch=128, read_every=8)
        gc.collect()
        f = _tail_cell(4, tail_dur, tail_sizes, tail_bw,
                       rate_ops=400.0, batch=128, read_every=8)
        pairs.append((f["writer"]["p99"] / max(b["writer"]["p99"], 1e-9),
                      b, f))
    pairs.sort(key=lambda p: p[0])
    tail_ratio, baseline, fleet4 = pairs[len(pairs) // 2]
    tails = [fleet4 if n == 4 else
             _tail_cell(n, tail_dur, tail_sizes, tail_bw, rate_ops=400.0,
                        batch=128, read_every=8) for n in shard_counts]

    closed = [_closed_loop(n, burst_dur, closed_batch, burst_bw)
              for n in shard_counts]
    tput = {c["shards"]: c["puts_per_s"] for c in closed}
    sustained = [_closed_loop(n, sustained_dur, closed_batch, sustained_bw)
                 for n in (1, 4)]
    sus = {c["shards"]: c["puts_per_s"] for c in sustained}

    starved = {p: _starved_cell(p, starved_sizes, starved_budget)
               for p in ("fair", "greedy")}
    diff_ok = _mini_differential()

    out = {"closed_loop_burst": closed, "closed_loop_sustained": sustained,
           "open_loop_baseline": baseline, "open_loop": tails,
           "starved_budget": starved, "tput_bar": tput_bar,
           "tail_bar": tail_bar,
           "cpu_count": len(os.sched_getaffinity(0)), "claims": {}}
    out["claims"]["burst_window_4shard_admits_2x_single"] = \
        tput.get(4, 0.0) >= tput_bar * tput[1]
    out["claims"]["sustained_tput_budget_bound_not_shard_bound"] = \
        0.75 * sus[1] <= sus[4] <= 1.35 * sus[1]
    out["tail_ratio_median"] = tail_ratio
    out["claims"]["open_loop_writer_p99_within_bar_of_single"] = \
        tail_ratio <= tail_bar
    out["claims"]["fleet_ran_background"] = all(
        c["flushes"] > 0 for c in closed)
    out["claims"]["greedy_drains_first_shard_before_fair"] = \
        starved["greedy"]["first_drain_epoch"] < \
        starved["fair"]["first_drain_epoch"]
    out["claims"]["fair_spreads_grants_across_more_shards"] = \
        starved["fair"]["mean_shards_granted_per_epoch"] > \
        starved["greedy"]["mean_shards_granted_per_epoch"]
    out["claims"]["fleet_single_differential_ok"] = diff_ok
    save("fleet_scaling", out)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    res = run(quick=ap.parse_args().quick)
    for c in res["closed_loop_burst"]:
        print(f"[fleet] burst-window {c['shards']:2d} shards: "
              f"{c['puts_per_s']:10.0f} puts/s  ({c['flushes']} flushes, "
              f"{c['merges']} merges, {c['stalls']} stalls)")
    for c in res["closed_loop_sustained"]:
        print(f"[fleet] sustained    {c['shards']:2d} shards: "
              f"{c['puts_per_s']:10.0f} puts/s  ({c['flushes']} flushes, "
              f"{c['merges']} merges)")
    b = res["open_loop_baseline"]
    print(f"[fleet] open-loop baseline (engine): writer p99 = "
          f"{b['writer']['p99']*1e3:8.2f} ms  p999 = "
          f"{b['writer']['p999']*1e3:8.2f} ms  reader p99 = "
          f"{b['reader']['p99']*1e3:8.2f} ms")
    for t in res["open_loop"]:
        w, r = t["writer"], t["reader"]
        print(f"[fleet] open-loop {t['shards']:2d} shards: writer p99 = "
              f"{w['p99']*1e3:8.2f} ms  p999 = {w['p999']*1e3:8.2f} ms  "
              f"reader p99 = {r['p99']*1e3:8.2f} ms")
    for p, s in res["starved_budget"].items():
        print(f"[fleet] starved {p:6s}: first drain @ epoch "
              f"{s['first_drain_epoch']:4d}, last @ "
              f"{s['last_drain_epoch']:4d}, mean shards granted/epoch "
              f"{s['mean_shards_granted_per_epoch']:.2f}")
    print(json.dumps(res["claims"], indent=1))
    raise SystemExit(0 if all(res["claims"].values()) else 1)
