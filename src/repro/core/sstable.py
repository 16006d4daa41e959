"""Immutable sorted-run component for the real engine.

Bloom filters are built on the host and probed by the fused Pallas
kernel, point lookups are vectorized sorted searches, and merges (in
engine.py) run through the execution backend (``core/backend.py``).  One
SSTable corresponds to one scheduling-plane ``Component`` so the paper's
policies/schedulers drive real bytes.

Residency contract: the HOST mirrors (``keys_np``/``vals_np``) are the
authoritative storage — the read plane ``np.searchsorted``s them without
a device sync per lookup, merges stage their windows from them, and
``build`` never copies them.  The DEVICE arrays (``keys``/``vals``
properties) materialize LAZILY on first use, or are adopted at build
when the engine's streaming merge ran on the device (``dev=``: the
output lands there once, at the merge's finish), so a table only ever
touched by host-path ops never pays for a device copy at all.

Point lookups never probe one table at a time: the engine's batch plane
screens every table in one fused multi-table probe (``ExecBackend``) and
calls ``search`` only for survivors.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.kernels.bloom.ops import bloom_build, filter_params
from .component import Component
from .iostack import data_crc32
from .memtable import scan_window, sorted_lookup


@dataclass
class SSTable:
    keys_np: np.ndarray                # (n,) uint32, sorted asc, unique —
    vals_np: np.ndarray                # authoritative host mirrors
    n_bits: int = 0
    k_hashes: int = 0
    component: Optional[Component] = None
    data_stamp: int = 0                # data age: strictly increasing at
                                       # flush; max over inputs at merge
    stack_slot: int = -1               # row in the engine's persistent
                                       # filter stack (set by its sync)
    crc32: Optional[int] = None        # content checksum sealed at bind
                                       # (``data_crc32``); the scrub pass
                                       # re-verifies it to catch bit-rot
    bloom_np: Optional[np.ndarray] = None  # uint32 filter words, built
                                           # LAZILY by ``bloom_host`` — never
                                           # on the background (flush/merge)
                                           # path, whose quanta must stay
                                           # O(quantum)
    _keys_dev: Optional[jnp.ndarray] = field(default=None, repr=False)
    _vals_dev: Optional[jnp.ndarray] = field(default=None, repr=False)

    @classmethod
    def build(cls, keys, vals, level: int = 0, created_at: float = 0.0,
              fpr: float = 0.01, dev: Optional[tuple] = None) -> "SSTable":
        # Host-first: the flush/merge call sites already hold numpy
        # arrays (``MemTable.seal`` output / the streaming merge's
        # preallocated output buffer), so component bounds come from the
        # host copy and the read plane's mirrors are adopted for free.
        # No device upload happens here AT ALL: device arrays either
        # arrive via ``dev`` (output already living on device — the
        # engine's device-resident merge plane) or materialize lazily on
        # the first kernel launch that needs them.
        keys_np = np.asarray(keys, np.uint32)
        vals_np = np.asarray(vals, np.int32)
        n = int(keys_np.shape[0])
        n_bits, k_hashes = filter_params(n, fpr)
        # the Bloom filter itself is NOT built here: flush/merge
        # completions run under the engine lock in scheduler quanta, and
        # an O(n) filter build there is exactly the compute cliff the
        # bounded background plane forbids.  ``bloom_host`` builds it
        # on the first probe (point-read paths only — scans never pay).
        lo = float(keys_np[0]) / 2**32 if n else 0.0
        hi = (float(keys_np[-1]) + 1) / 2**32 if n else 1.0
        comp = Component(size=float(n), level=level, key_lo=lo, key_hi=hi,
                         created_at=created_at)
        dk, dv = dev if dev is not None else (None, None)
        return cls(keys_np=keys_np, vals_np=vals_np, n_bits=n_bits,
                   k_hashes=k_hashes, component=comp, _keys_dev=dk,
                   _vals_dev=dv)

    def __len__(self) -> int:
        return int(self.keys_np.shape[0])

    # -- residency ------------------------------------------------------------
    @property
    def keys(self) -> jnp.ndarray:
        """Device-resident keys, materialized lazily from the host mirror
        (or adopted from a device-side merge output at build)."""
        if self._keys_dev is None:
            self._keys_dev = jnp.asarray(self.keys_np)
        return self._keys_dev

    @property
    def vals(self) -> jnp.ndarray:
        if self._vals_dev is None:
            self._vals_dev = jnp.asarray(self.vals_np)
        return self._vals_dev

    @property
    def device_resident(self) -> bool:
        """True when the device arrays already exist (no upload pending)."""
        return self._keys_dev is not None and self._vals_dev is not None

    def _host(self) -> tuple[np.ndarray, np.ndarray]:
        """Host-side (keys, vals) mirrors — the authoritative storage."""
        return self.keys_np, self.vals_np

    # -- integrity ------------------------------------------------------------
    def seal_checksum(self) -> int:
        """Seal the content CRC (called when the table binds into a
        read view — flush, merge completion, snapshot restore).  O(n),
        but so was producing the run; the scrub pass amortizes
        RE-verification across pump quanta instead."""
        self.crc32 = int(data_crc32(self.keys_np, self.vals_np))
        return self.crc32

    def verify_checksum(self) -> bool:
        """True when the host mirrors still match the sealed CRC (an
        unsealed table vacuously passes)."""
        if self.crc32 is None:
            return True
        return int(data_crc32(self.keys_np, self.vals_np)) == self.crc32

    def bloom_host(self) -> np.ndarray:
        """Host-side filter words, built/materialized once on first use
        (the engine's incremental filter stack syncs new tables' words
        from here — one O(filter) cost on the first point read after a
        flush/merge, zero on the background quanta themselves)."""
        if self.bloom_np is None:
            self.bloom_np = bloom_build(self.keys_np, self.n_bits,
                                        self.k_hashes)
        return self.bloom_np

    # -- queries --------------------------------------------------------------
    def search(self, keys) -> tuple[np.ndarray, np.ndarray]:
        """Sorted-search lookup WITHOUT the bloom screen: (found mask,
        values).  The engine's batch plane calls this only for keys the
        fused multi-table probe said may be present."""
        keys = np.asarray(keys, np.uint32)
        n = len(self)
        if n == 0 or len(keys) == 0:
            return np.zeros(len(keys), bool), np.zeros(len(keys), np.int32)
        sk, sv = self._host()
        return sorted_lookup(sk, sv, keys)

    def scan_range(self, lo: int, hi: int) -> tuple[np.ndarray, np.ndarray]:
        """All (key, value) with lo <= key < hi — a zero-copy
        ``scan_window`` over the host mirrors; this is the per-table
        slice the engine's k-way range merge consumes (no Bloom screen:
        range scans probe the run directly)."""
        sk, sv = self._host()
        return scan_window(sk, sv, lo, hi)
