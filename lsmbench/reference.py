"""The plain reference: a numpy newest-wins replay of every admitted
operation, independent of the store under test.

``Reference`` is a copy of the repo's ``chip_smoke.Reference``: a
settled base plus the newest write per key since.  ``Loaded`` is the
same base for a store loaded with records ``0 .. n-1``: it finds a
key's record through the inverse of the key hash, so that a run need
not sort every loaded record to check its answers.  ``History``'s
``as_of`` and ``scan_as_of`` answer a read or a scan as the store had
to answer it when the call was made: over the base and the first
``pos`` admitted writes, in admission order.
"""
from __future__ import annotations

from functools import cached_property

import numpy as np

from .keys import record_keys, record_numbers

TOMBSTONE = np.int32(-2 ** 31)


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


class Loaded:
    """The loaded records as a settled base: record ``r`` has the key
    ``record_keys(r)`` and the value ``vals[r]``."""

    def __init__(self, vals):
        self.vals = np.asarray(vals, np.int32)

    def get(self, keys):
        rec = record_numbers(keys)
        found = rec < len(self.vals)
        i = np.where(found, rec, 0)
        return found, np.where(found, self.vals[i] if len(self.vals)
                               else 0, 0)

    @cached_property
    def base(self):
        """Sorted (keys, values), for scans."""
        keys = record_keys(np.arange(len(self.vals)))
        order = np.argsort(keys)
        return keys[order], self.vals[order]


class History:
    """A settled base (a settled ``Reference`` or ``Loaded``) and the
    admitted writes after it, in admission order: write ``i`` is the
    ``i``-th entry any ``put_batch`` admitted."""

    def __init__(self, ref: Reference, wkeys: np.ndarray,
                 wvals: np.ndarray):
        self.ref = ref
        self.wkeys = np.asarray(wkeys, np.uint32)
        self.wvals = np.asarray(wvals, np.int32)
        pos = np.arange(len(self.wkeys), dtype=np.uint64)
        comp = (self.wkeys.astype(np.uint64) << np.uint64(32)) | pos
        order = np.argsort(comp, kind="stable")
        self._comp = comp[order]
        self._skeys = self.wkeys[order]
        self._spos = pos[order].astype(np.int64)
        self._svals = self.wvals[order]

    def as_of(self, keys, pos):
        """(found, value) of each key after the first ``pos`` writes
        (``pos`` per key)."""
        keys = np.asarray(keys, np.uint32)
        pos = np.broadcast_to(np.asarray(pos, np.int64), keys.shape)
        q = (keys.astype(np.uint64) << np.uint64(32)) | pos.astype(np.uint64)
        i = np.searchsorted(self._comp, q, side="left") - 1
        ic = np.maximum(i, 0)
        hit = (i >= 0) & (self._skeys[ic] == keys) if len(self._comp) \
            else np.zeros(len(keys), bool)
        wv = self._svals[ic] if len(self._comp) else np.zeros(len(keys),
                                                              np.int32)
        bf, bv = self.ref.get(keys)
        found = np.where(hit, wv != TOMBSTONE, bf)
        return found, np.where(found, np.where(hit, wv, bv), 0)

    def scan_as_of(self, lo: int, hi: int, pos: int):
        """Sorted live (keys, values) in ``[lo, hi)`` after the first
        ``pos`` writes."""
        bk, bv = self.ref.base
        a, b = np.searchsorted(bk, np.array([lo, min(hi, 2 ** 32 - 1)],
                                            np.uint32))
        bk, bv = bk[a:b], bv[a:b]
        c, d = np.searchsorted(self._skeys, np.array(
            [lo, min(hi, 2 ** 32 - 1)], np.uint32))
        wk, wp, wv = self._skeys[c:d], self._spos[c:d], self._svals[c:d]
        keep = wp < pos
        wk, wv = wk[keep], wv[keep]
        if len(wk) == 0:
            return bk, bv
        last = np.ones(len(wk), bool)       # sorted by (key, pos): the
        last[:-1] = wk[1:] != wk[:-1]       # last of a key is its newest
        ks = np.concatenate([wk[last], bk])
        vs = np.concatenate([wv[last], bv])
        ks, first = np.unique(ks, return_index=True)   # writes come first
        vs = vs[first]
        live = vs != TOMBSTONE
        return ks[live], vs[live]
