"""Broken stores, to show that the check catches what it must.

Each fault wraps a ``StorageGroup``'s entry points in place, below the
front door, so the rest of a run is unchanged:

- ``unchanged_state``: a write is acknowledged but the store keeps
  nothing of it;
- ``half_batch``: half of every batch is left out — the second half of
  a read batch reports not found, of a write batch is acknowledged
  unstored, of a scan's answer is dropped;
- ``altered_answer``: one answer is altered where it is produced — the
  first value a read or a scan returns, the first value a write stores.

The control breaks a guarantee the configuration states instead: with
``drop_oldest`` the store is built without its oldest run, so loaded
records it was given are gone.
"""
from __future__ import annotations

import numpy as np


def unchanged_state(group) -> None:
    def put_batch(keys, values):
        return len(keys)
    group.put_batch = put_batch


def half_batch(group) -> None:
    get, put, scan = group.get_batch, group.put_batch, group.scan_range

    def get_batch(keys):
        h = (len(keys) + 1) // 2
        f, v = get(keys[:h])
        pad = len(keys) - h
        return (np.concatenate([f, np.zeros(pad, bool)]),
                np.concatenate([v, np.zeros(pad, np.int32)]))

    def put_batch(keys, values):
        h = (len(keys) + 1) // 2
        n = put(keys[:h], values[:h])
        return n if n < h else len(keys)

    def scan_range(lo, hi):
        ks, vs = scan(lo, hi)
        h = (len(ks) + 1) // 2
        return ks[:h], vs[:h]

    group.get_batch, group.put_batch, group.scan_range = \
        get_batch, put_batch, scan_range


def altered_answer(group) -> None:
    get, put, scan = group.get_batch, group.put_batch, group.scan_range

    def get_batch(keys):
        f, v = get(keys)
        v = v.copy()
        if len(v):
            v[0] ^= 1
        return f, v

    def put_batch(keys, values):
        values = np.array(values, np.int32)
        if len(values):
            values[0] ^= 1
        return put(keys, values)

    def scan_range(lo, hi):
        ks, vs = scan(lo, hi)
        vs = vs.copy()
        if len(vs):
            vs[0] ^= 1
        return ks, vs

    group.get_batch, group.put_batch, group.scan_range = \
        get_batch, put_batch, scan_range


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "altered_answer": altered_answer}
