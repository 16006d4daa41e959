"""The comparison that decides ``correct``: what the timed path returned,
against the plain reference replayed over the same admitted writes.

Each number counts answers that differ from the reference; each limit
is 0, since the store's answers are exact.
"""
from __future__ import annotations

import numpy as np

from .reference import History, Loaded, Reference

#: every compared number and its limit, in the order they are printed
LIMITS = {"read_mismatches": 0, "scan_mismatches": 0,
          "readback_mismatches": 0, "wal_mismatches": 0,
          "unanswered": 0, "not_compiled": 0}


def history(loaded_keys, loaded_vals, wkeys, wvals) -> History:
    """The reference: loaded records settled as the base, the admitted
    writes after them."""
    ref = Reference()
    ref.record(loaded_keys, loaded_vals)
    ref.settle()
    return History(ref, wkeys, wvals)


def loaded_history(loaded_vals, wkeys, wvals) -> History:
    """``history`` for records ``0 .. n-1`` loaded with ``loaded_vals``,
    without sorting them."""
    return History(Loaded(loaded_vals), wkeys, wvals)


def read_mismatches(hist: History, reads) -> int:
    """Reads ``(pos, keys, found, vals)`` whose answer differs from the
    reference after the first ``pos`` writes."""
    if not reads:
        return 0
    keys = np.concatenate([r[1] for r in reads])
    pos = np.concatenate([np.full(len(r[1]), r[0], np.int64)
                          for r in reads])
    found = np.concatenate([r[2] for r in reads]).astype(bool)
    vals = np.concatenate([r[3] for r in reads])
    wf, wv = hist.as_of(keys, pos)
    bad = (found != wf) | (found & (vals != wv))
    return int(bad.sum())


def scan_mismatches(hist: History, scans) -> int:
    """Scans ``(pos, lo, hi, keys, vals)`` whose keys or values differ
    from the reference."""
    bad = 0
    for pos, lo, hi, ks, vs in scans:
        wk, wv = hist.scan_as_of(lo, hi, pos)
        if not (np.array_equal(ks, wk) and np.array_equal(vs, wv)):
            bad += 1
    return bad


def wal_mismatches(wal_keys, wal_vals, wkeys, wvals) -> int:
    """Admitted writes missing from the reopened log, or logged
    differently, plus log entries no write accounts for."""
    n = min(len(wal_keys), len(wkeys))
    differ = (wal_keys[:n] != wkeys[:n]) | (wal_vals[:n] != wvals[:n])
    return int(differ.sum()) + abs(len(wal_keys) - len(wkeys))
