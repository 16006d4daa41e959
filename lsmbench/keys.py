"""Record keys and YCSB's key choosers, vectorized in numpy.

A record is named by its YCSB record number.  Its key is the 32-bit
murmur3 finalizer of that number: a bijection on uint32, so distinct
records keep distinct keys (YCSB hashes the number with 64-bit FNV; the
store takes uint32 keys).  The one record number that maps to the
store's reserved key 2**32-1 lies far past any record count a cell uses
(``SENTINEL_RECORD``).

``scrambled_zipfian`` is YCSB's ``ScrambledZipfianGenerator``: a
Zipfian draw over 10**10 items with YCSB's precomputed zeta, hashed with
64-bit FNV and folded onto the record range, so popular records are
spread over the key space.
"""
from __future__ import annotations

import numpy as np

#: the record number whose key is the store's reserved sentinel 2**32-1
SENTINEL_RECORD = 857_579_651

_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)
#: YCSB's ScrambledZipfianGenerator constants (theta 0.99)
ZIPF_ITEMS = 10_000_000_000
ZIPF_ZETAN = 26.46902820178302
ZIPF_THETA = 0.99


def record_keys(records) -> np.ndarray:
    """uint32 keys of the given record numbers (murmur3 ``fmix32``)."""
    r = np.asarray(records, np.int64)
    if len(r) and (r.min() < 0 or r.max() >= SENTINEL_RECORD):
        raise ValueError("record numbers must lie in [0, SENTINEL_RECORD)")
    h = r.astype(np.uint32)
    h ^= h >> np.uint32(16)
    h *= np.uint32(0x85EBCA6B)
    h ^= h >> np.uint32(13)
    h *= np.uint32(0xC2B2AE35)
    h ^= h >> np.uint32(16)
    return h


def record_numbers(keys) -> np.ndarray:
    """Record numbers (int64) of uint32 keys: the inverse of
    ``record_keys``."""
    h = np.asarray(keys, np.uint32).copy()
    h ^= h >> np.uint32(16)
    h *= np.uint32(0x7ED1B41D)          # inverse of 0xC2B2AE35 mod 2**32
    h ^= (h >> np.uint32(13)) ^ (h >> np.uint32(26))
    h *= np.uint32(0xA5CB9243)          # inverse of 0x85EBCA6B mod 2**32
    h ^= h >> np.uint32(16)
    return h.astype(np.int64)


def fnv64(values) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` (FNV-1a over the 8 little-endian
    bytes), returned as the non-negative int64 YCSB's ``Math.abs``
    gives."""
    v = np.asarray(values, np.uint64).copy()
    h = np.full(v.shape, _FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            h *= _FNV_PRIME
            v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def zipfian(rng, n: int, items: int = ZIPF_ITEMS,
            zetan: float = ZIPF_ZETAN, theta: float = ZIPF_THETA):
    """``n`` draws of YCSB's ``ZipfianGenerator`` over ``items`` items
    (item 0 most popular), with ``zetan`` = zeta(items, theta)."""
    alpha = 1.0 / (1.0 - theta)
    zeta2 = 1.0 + 0.5 ** theta
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(n)
    uz = u * zetan
    tail = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    return np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, tail))


def scrambled_zipfian(rng, n: int, records: int,
                      theta: float = ZIPF_THETA) -> np.ndarray:
    """``n`` record numbers in ``[0, records)`` from YCSB's
    ``ScrambledZipfianGenerator``."""
    if theta != ZIPF_THETA:
        raise ValueError("YCSB's scrambled zipfian fixes theta at 0.99")
    return fnv64(zipfian(rng, n)) % np.int64(records)
