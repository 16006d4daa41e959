"""Public Bloom-filter API: build (host numpy, once per component) +
probe (Pallas kernel, the per-lookup hot path)."""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .bloom import (LANES, bloom_probe_multi_kernel, reciprocal,
                    stack_width)
from .ref import _hash_np


def filter_params(n_keys: int, fpr: float = 0.01) -> tuple[int, int]:
    """(n_bits, k_hashes) for a target false-positive rate (1% in the
    paper's setup, Section 3.1)."""
    n_keys = max(n_keys, 1)
    n_bits = int(math.ceil(-n_keys * math.log(fpr) / (math.log(2) ** 2)))
    n_bits = max(128, (n_bits + 127) // 128 * 128)
    k = max(1, round(n_bits / n_keys * math.log(2)))
    return n_bits, min(k, 16)


def bloom_build(keys, n_bits: int, k_hashes: int) -> np.ndarray:
    """Build the filter as uint32 words (bit ``p`` is bit ``p % 32`` of
    word ``p // 32``).  Built on the host: a table's filter is made once,
    on the first point read after the table appears, and its words go to
    the host mirror of the filter stack and, by one row write, to the
    device stack — so a device build would only add a scatter compiled
    per filter geometry and a copy back."""
    keys = np.asarray(keys, np.uint32)
    h1 = _hash_np(keys, 0x9E3779B9)
    h2 = _hash_np(keys, 0x85EBCA6B) | np.uint32(1)     # odd stride
    i = np.arange(k_hashes, dtype=np.uint32)[:, None]
    pos = (h1[None, :] + i * h2[None, :]) % np.uint32(n_bits)
    bits = np.zeros(n_bits, np.uint8)
    bits[pos.reshape(-1)] = 1
    return np.packbits(bits, bitorder="little").view("<u4").astype(np.uint32)


def bloom_probe(filt, keys, n_bits: int, k_hashes: int, block: int = 1024,
                interpret: bool = False):
    """Probe keys against one filter; returns a bool maybe-present mask
    (no false negatives).  A one-row stack through the fused kernel."""
    meta = np.array([[n_bits, k_hashes]], np.uint32)
    return bloom_probe_multi(jnp.asarray(filt)[None], meta, keys,
                             block=block, interpret=interpret)[0]


def stack_filters(filters, n_bits_list, k_hashes_list):
    """Pad per-table filters to a common word count and pack their
    geometry: returns (filts (T, W) uint32, meta (T, 2) uint32) ready for
    ``bloom_probe_multi``.  ``meta`` stays host-side numpy so callers can
    derive the static k_max without a device sync."""
    t = len(filters)
    w = max((f.shape[0] for f in filters), default=1)
    filts = np.zeros((t, max(w, 1)), np.uint32)
    meta = np.zeros((t, 2), np.uint32)
    for i, (f, nb, kh) in enumerate(zip(filters, n_bits_list,
                                        k_hashes_list)):
        f = np.asarray(f, np.uint32)
        filts[i, :f.shape[0]] = f
        meta[i] = (nb, kh)
    return filts, meta


def device_stack(stk: np.ndarray):
    """The device copy of a host (tables, ``stack_width``) uint32 word
    stack, as the kernel reads it: int32 words shaped (tables, rows,
    128), so a launch takes the stack in place rather than copying it."""
    return jnp.array(stk.view(np.int32).reshape(len(stk), -1, LANES))


@functools.partial(jax.jit, donate_argnums=(0,))
def _set_row_donated(filts, row, slot):
    return filts.at[slot].set(row)


def set_stack_row(filts, row_words, slot):
    """Write one filter's words into row ``slot`` of a stacked device
    filter array, donating the input buffer so backends that support
    input-output aliasing update the row IN PLACE — O(row) instead of the
    O(tables * width) restack-and-reupload of ``stack_filters``.  This is
    the engine's incremental read-view maintenance primitive: one call
    per flush output / merge output.  ``row_words`` (host numpy uint32)
    shorter than the stack width must be pre-padded by the caller.  The
    donated input array is consumed — callers must replace every
    reference with the returned array.  Operands cross the jit boundary
    raw (the row as host words in the stack's shape, the slot as a
    Python int): explicit ``jnp.asarray``/``jnp.int32`` staging costs an
    order of magnitude more dispatch than the row write itself."""
    return _set_row_donated(filts, np.asarray(row_words, np.uint32)
                            .view(np.int32).reshape(filts.shape[1:]),
                            int(slot))


class ProbeHits(NamedTuple):
    """Maybe-present (row, key) pairs of a pruned probe, rows ascending:
    ``rows`` are stack rows, ``keys`` indices into the probed batch."""
    rows: np.ndarray
    keys: np.ndarray


def _live_pairs(meta, keys):
    """Every (row, key) pair whose row's ``[lo, hi]`` (``meta`` columns 2
    and 3, uint32 compares; ``lo > hi`` holds none) holds the key.  The
    live keys of a row are one window of the sorted batch, found by one
    search per bound.  Returns the batch's sort order, each row's window
    ``(start, count)``, and the pairs as (rows ascending, sorted key
    positions), in O(pairs + rows) past the sort."""
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    start = np.searchsorted(sk, meta[:, 2], "left")
    count = np.maximum(np.searchsorted(sk, meta[:, 3], "right") - start, 0)
    rows = np.repeat(np.arange(len(count)), count)
    base = np.repeat(start - np.cumsum(count) + count, count)
    return order, start, count, rows, base + np.arange(len(rows))


def _probe_pairs_host(filts_np, meta, keys, rows, qs) -> np.ndarray:
    """Maybe-present flag of key ``keys[qs[p]]`` in stack row
    ``rows[p]``, for each pair ``p``: the kernel's double hashing over
    the host mirror, lanes beyond a row's own k passing."""
    key = np.asarray(keys, np.uint32)[qs]
    h1 = _hash_np(key, 0x9E3779B9)
    h2 = _hash_np(key, 0x85EBCA6B) | np.uint32(1)
    n_bits = meta[rows, 0].astype(np.uint32)
    k = meta[rows, 1].astype(np.int64)
    out = np.ones(len(rows), bool)
    for i in range(int(k.max(initial=0))):
        pos = ((h1 + np.uint32(i) * h2) % n_bits).astype(np.int64)
        bit = (filts_np[rows, pos >> 5] >> (pos & 31).astype(np.uint32)) \
            & np.uint32(1)
        out &= (bit == 1) | (i >= k)
    return out


def bloom_probe_multi_host(filts_np: np.ndarray, meta: np.ndarray,
                           keys: np.ndarray) -> np.ndarray:
    """Host twin of ``bloom_probe_multi``: the same double-hashing probe
    over the HOST mirror of the stacked filter words, pure numpy (bit-
    identical to the kernel by construction: same hash family, same
    per-row geometry semantics, unused hash lanes pass).

    ``filts_np`` is (tables, words) uint32, ``meta`` (tables, 2) uint32
    rows of (n_bits, k_hashes).  Returns a (tables, keys) bool matrix."""
    t, q = int(filts_np.shape[0]), len(keys)
    rows, qs = np.divmod(np.arange(t * q), max(q, 1))
    meta = np.asarray(meta, np.uint32)
    return _probe_pairs_host(filts_np, meta, keys, rows, qs).reshape(t, q)


def probe_batch(n: int, block: int = 1024) -> int:
    """Keys one fused probe launch takes for a batch of ``n``: the
    batch padded to a power-of-two number of ``block``-key blocks, so a
    serving loop compiles O(log batch) programs, not one per size."""
    return block << max(-(-n // block) - 1, 0).bit_length()


def _launch(filts, meta, keys, start, count, block, interpret):
    """One kernel launch over ``keys`` padded to ``probe_batch``;
    returns the (tables, padded keys / 32) maybe-present bits."""
    meta = np.asarray(meta, np.uint32)
    kp = np.zeros(probe_batch(len(keys), block), np.uint32)
    kp[:len(keys)] = keys
    table = np.column_stack([meta[:, :2], reciprocal(meta[:, 0]), start,
                             count]).astype(np.uint32)
    return np.asarray(bloom_probe_multi_kernel(
        jnp.asarray(filts), table, kp, k_max=int(meta[:, 1].max()),
        block=block, interpret=interpret))


def bloom_probe_multi(filts, meta, keys, block: int = 1024,
                      interpret: bool = False):
    """Probe one key batch against a stack of padded filters (see
    ``stack_filters``) in a single fused launch, every row against every
    key; returns a (tables, keys) bool maybe-present matrix (no false
    negatives per table)."""
    t = filts.shape[0]
    n = len(keys)
    if t == 0 or n == 0:
        return np.zeros((t, n), bool)
    bits = _launch(filts, meta, np.asarray(keys, np.uint32),
                   np.zeros(t, np.int32), np.full(t, n, np.int32),
                   block, interpret)
    return np.unpackbits(np.ascontiguousarray(bits).view(np.uint8), axis=1,
                         count=n, bitorder="little").astype(bool)


def bloom_probe_pruned(filts, meta, keys, block: int = 1024,
                       interpret: bool = False) -> tuple[ProbeHits, int]:
    """The fused probe pruned by key range: ``meta`` rows are (n_bits,
    k_hashes, lo, hi), and a row probes only the keys inside its table's
    ``[lo, hi]`` — a table whose key range misses a key cannot hold it,
    so the answer is exact.  Launches the kernel over each row's window
    of the sorted batch and reads back one bit per (row, padded key).
    Returns the maybe-present pairs and the (row, key) cells probed."""
    keys = np.asarray(keys, np.uint32)
    meta = np.asarray(meta, np.uint32)
    order, start, count, rows, qs = _live_pairs(meta, keys)
    cells = len(rows)
    if cells:
        bits = _launch(filts, meta, keys[order], start, count, block,
                       interpret)
        hit = (bits[rows, qs >> 5] >> (qs & 31).astype(np.uint32)) & 1 == 1
        rows, qs = rows[hit], qs[hit]
    return ProbeHits(rows, order[qs]), cells


def bloom_probe_pruned_host(filts_np: np.ndarray, meta: np.ndarray,
                            keys: np.ndarray) -> tuple[ProbeHits, int]:
    """Host twin of ``bloom_probe_pruned``: the same pairs, probed over
    the host mirror."""
    keys = np.asarray(keys, np.uint32)
    meta = np.asarray(meta, np.uint32)
    order, _, _, rows, qs = _live_pairs(meta, keys)
    qs = order[qs]
    hit = _probe_pairs_host(filts_np, meta, keys, rows, qs)
    return ProbeHits(rows[hit], qs[hit]), len(rows)
