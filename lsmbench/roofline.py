"""The least bytes each data-plane operation needs, and the chip's peaks.

A roofline share is the least time the chip could take for the work,
the bytes it needs over the HBM peak, divided by the device time the
kernel took.  The byte counts are of the work the operation needs,
whatever implements it: padding, extra hash lanes or rows that could
not hold a key count for nothing, so a leaner kernel raises the share
and none takes it past 100%.  Every operation here is bound by bytes:
none does arithmetic worth a compute roof.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

KEY_BYTES = 4
WORD_BYTES = 4           # one filter word per hash lane


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; a device not in the
    table is an error."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json")
    return table["devices"][device_kind]


def probe_bytes(keys, lo, hi, k) -> int:
    """Least bytes a Bloom probe of ``keys`` needs: each key once, and
    for each table whose key range ``[lo, hi]`` holds the key, the
    ``k`` filter words its hash lanes select."""
    keys = np.asarray(keys, np.uint32)[:, None]
    holds = (keys >= np.asarray(lo, np.uint32)[None, :]) & \
            (keys <= np.asarray(hi, np.uint32)[None, :])
    words = (holds * np.asarray(k, np.int64)[None, :]).sum()
    return int(len(keys) * KEY_BYTES + words * WORD_BYTES)


def share(nbytes: int, device_s: float, bytes_per_s: float):
    """Roofline share in percent, or None when the kernel did not run."""
    if device_s <= 0 or nbytes <= 0:
        return None
    return 100.0 * (nbytes / bytes_per_s) / device_s


class ProbeBytes:
    """Counts ``probe_bytes`` over the point reads of a traced run: the
    tables' key ranges are read before each ``get_batch``, and the call
    counts only if the backend launched a probe for it."""

    def __init__(self, group, backend):
        self.group = group
        self.backend = backend
        self.total = 0
        self._pending = 0
        self._calls = 0

    def before(self, keys) -> None:
        with self.group.lock():
            tables = [t for t in self.group.tables.values() if len(t)]
            lo = [t.keys_np[0] for t in tables]
            hi = [t.keys_np[-1] for t in tables]
            k = [t.k_hashes for t in tables]
        self._pending = probe_bytes(keys, lo, hi, k) if tables else 0
        self._calls = self.backend.calls["probe_multi"]

    def after(self) -> None:
        if self.backend.calls["probe_multi"] > self._calls:
            self.total += self._pending
