"""The one traffic generator: a mix file's parameters in, operations out.

A mix (``lsmbench/mixes/<name>.json``) states:

- ``rate_ops_per_s``: an open loop's offered rate; independent users
  arrive on a schedule, evenly spaced as YCSB's ``-target`` spaces
  them;
- ``ops``: the share of each operation kind (``read``, ``update``,
  ``insert``, ``scan``);
- ``key_dist``: how reads, updates and scan starts pick a record
  (``scrambled_zipfian`` with ``zipf_theta``, or ``uniform``); inserts
  always take the next new record number, YCSB's hashed insert order;
- ``scan_length``: ``min``/``max`` records of a scan, uniform;
- ``max_batch``: the most operations of one kind the front door puts in
  one call;
- ``warmup_burst_ops`` (optional): operations of the same traffic
  served back to back before the warm-up, so that its writes reach a
  flush and a merge;
- ``warmup_s``: seconds of the same traffic run before the window.

Every seed gets the same number of operations of each kind, the same
arrival times and the same multiset of scan lengths, in another order:
the seed changes which records are touched and the values written, not
the amount of work.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .keys import record_keys, scrambled_zipfian

READ, UPDATE, INSERT, SCAN = 0, 1, 2, 3
KINDS = {"read": READ, "update": UPDATE, "insert": INSERT, "scan": SCAN}
WRITES = (UPDATE, INSERT)
TOMBSTONE = -2 ** 31


@dataclass
class Ops:
    """A slice of operations: kind, key, value written (writes) and the
    exclusive upper key of a scan (scans; 0 otherwise)."""
    kind: np.ndarray      # int8
    key: np.ndarray       # uint32
    val: np.ndarray       # int32
    hi: np.ndarray        # int64

    def __len__(self) -> int:
        return len(self.kind)


class Traffic:
    """Operation source for one run of one mix.  ``records`` is how many
    records the store was loaded with; inserts continue from there."""

    def __init__(self, mix: dict, seed: int, records: int):
        self.mix = mix
        self.records = int(records)
        self.next_record = int(records)
        self.rng = np.random.default_rng([int(seed), 0x7AFF1C])
        shares = {KINDS[k]: float(v) for k, v in mix["ops"].items()}
        total = sum(shares.values())
        self.shares = {k: v / total for k, v in shares.items() if v > 0}
        self.theta = float(mix.get("zipf_theta", 0.99))
        sl = mix.get("scan_length", {"min": 1, "max": 1})
        self.scan_lengths = np.arange(int(sl["min"]), int(sl["max"]) + 1)

    @property
    def rate(self) -> float:
        return float(self.mix["rate_ops_per_s"])

    def arrivals(self, seconds: float) -> np.ndarray:
        """Scheduled offsets (s) of an open loop's operations over
        ``seconds``."""
        n = int(round(self.rate * seconds))
        return np.arange(n, dtype=np.float64) / self.rate

    def _counts(self, n: int) -> dict[int, int]:
        kinds = sorted(self.shares)
        counts = {k: int(self.shares[k] * n) for k in kinds}
        # hand the rounding remainder to the largest shares, in a fixed
        # order, so every seed gets the same counts
        for k in sorted(kinds, key=lambda k: -self.shares[k]):
            if sum(counts.values()) >= n:
                break
            counts[k] += 1
        return counts

    def _existing(self, n: int) -> np.ndarray:
        if self.mix.get("key_dist") == "uniform":
            return self.rng.integers(0, self.records, n)
        return scrambled_zipfian(self.rng, n, self.records, self.theta)

    def take(self, n: int) -> Ops:
        """The next ``n`` operations."""
        counts = self._counts(n)
        kind = np.concatenate([np.full(c, k, np.int8)
                               for k, c in sorted(counts.items())])
        kind = self.rng.permutation(kind)
        rec = np.zeros(n, np.int64)
        old = kind != INSERT
        rec[old] = self._existing(int(old.sum()))
        ins = np.flatnonzero(kind == INSERT)
        rec[ins] = self.next_record + np.arange(len(ins))
        self.next_record += len(ins)
        key = record_keys(rec)
        val = self.rng.integers(TOMBSTONE + 1, 2 ** 31, n, dtype=np.int32)
        val[~np.isin(kind, WRITES)] = 0
        hi = np.zeros(n, np.int64)
        scans = np.flatnonzero(kind == SCAN)
        if len(scans):
            lengths = self.rng.permutation(
                np.resize(self.scan_lengths, len(scans)))
            # a scan of L records spans L mean key gaps of the live set
            gap = 2 ** 32 / self.next_record
            span = np.maximum(1, np.round(lengths * gap)).astype(np.int64)
            hi[scans] = np.minimum(key[scans].astype(np.int64) + span,
                                   2 ** 32 - 1)
        return Ops(kind, key, val, hi)
