"""Builds a configuration's store from the seed, through the program's
own constructors and its recovery path.

``build`` makes a ``StorageGroup`` with the config's policy, scheduler,
constraint, memtables and write-ahead log, and an ``ExecBackend`` that
counts its decisions.  The loaded records become sorted runs in the
shape ``shapes/<policy>.py`` gives, restored with
``StorageGroup.restore_tables`` — what a user's restart from a snapshot
does — rather than replayed through ``put_batch``, and placed on the
device, where a load's merges leave them.
"""
from __future__ import annotations

import collections
import importlib
import threading
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from .keys import record_keys

TOMBSTONE = -2 ** 31
#: threads that build the restored tables' Bloom filters
BUILD_THREADS = 8


def record_values(seed: int, n: int) -> np.ndarray:
    """int32 values of records ``0 .. n-1`` as loaded, never the
    reserved tombstone."""
    rng = np.random.default_rng([int(seed), 0xDA7A])
    return rng.integers(TOMBSTONE + 1, 2 ** 31, n, dtype=np.int32)


def _camel(name: str) -> str:
    return "".join(p.capitalize() for p in name.split("_"))


def counting_backend(mode: str = "auto"):
    """An ``ExecBackend`` that counts every decision per (op, mode) and
    the probe's launches; the client and the pump thread share it."""
    from repro.core.backend import ExecBackend

    class Counting(ExecBackend):
        def __init__(self, **kw):
            super().__init__(**kw)
            self._count_lock = threading.Lock()
            self.reset_counts()

        def reset_counts(self) -> None:
            with self._count_lock:
                self.decisions = collections.Counter()
                self.calls = collections.Counter()

        def decide(self, op, size):
            mode = super().decide(op, size)
            with self._count_lock:
                self.decisions[(op, mode)] += 1
            return mode

        def probe_multi(self, filts, meta, keys, filts_host=None):
            out = super().probe_multi(filts, meta, keys,
                                      filts_host=filts_host)
            with self._count_lock:
                self.calls["probe_multi"] += 1
            return out

    return Counting(mode=mode)


def build(cfg: dict, seed: int, backend, wal_dir: Path,
          drop_oldest: bool = False):
    """The config's store, holding its records in the starting shape,
    its tables on the device.  ``drop_oldest`` leaves the oldest run
    out: a store that lost loaded records, the check's control.
    Returns ``(group, wal_path)``."""
    from repro.core import constraints, policies, scheduler
    from repro.core.engine import StorageGroup
    from repro.core.wal import WriteAheadLog

    n = int(cfg["records"])
    mem = int(cfg["memtable_entries"])
    policy = policies.POLICIES[cfg["policy"]](
        memtable_entries=mem, unique_keys=n, **cfg["policy_args"])
    sched = getattr(scheduler, _camel(cfg["scheduler"]) + "Scheduler")()
    if cfg["constraint"] == "none":
        constraint = constraints.NoConstraint()
    else:
        constraint = getattr(constraints, _camel(cfg["constraint"])
                             + "Constraint")(**cfg.get("constraint_args",
                                                       {}))
    wal_path = Path(wal_dir) / "wal"
    wal = WriteAheadLog(wal_path,
                        segment_entries=int(cfg["wal"]["segment_entries"]))
    group = StorageGroup(
        policy, sched, constraint, memtable_entries=mem,
        num_memtables=int(cfg["num_memtables"]), unique_keys=n, wal=wal,
        group_commit_entries=int(cfg["wal"]["group_commit_entries"]),
        backend=backend)
    shape = importlib.import_module(f"{__package__}.shapes.{cfg['policy']}")
    vals = record_values(seed, n)
    tables = []
    for run in shape.layout(cfg)[int(drop_oldest):]:
        keys = record_keys(np.arange(run["lo"], run["hi"]))
        order = np.argsort(keys)
        keys, rvals = keys[order], vals[run["lo"]:run["hi"]][order]
        meta = {"level": run["level"], "created_at": 0.0,
                "stamp": run["stamp"]}
        for ks, vs in zip(np.array_split(keys, run["files"]),
                          np.array_split(rvals, run["files"])):
            tables.append((ks, vs, meta))
    stamp = max((m["stamp"] for _, _, m in tables), default=0)
    group.restore_tables(tables, {"stamp": stamp, "flushed_lsn": 0,
                                  "now": 0.0})
    # a store loaded through put_batch holds its merged tables on the
    # device; restored tables are placed there as a load would leave them,
    # and their Bloom filters, which the first read would build one by
    # one, are built here on a few threads
    import jax
    restored = [t for tree in group.trees for t in tree.tables.values()]
    with ThreadPoolExecutor(BUILD_THREADS) as pool:
        list(pool.map(lambda t: t.bloom_host(), restored))
    jax.block_until_ready([(t.keys, t.vals) for t in restored])
    return group, wal_path
