"""Public Bloom-filter API: build (host numpy, once per component) +
probe (Pallas kernel, the per-lookup hot path)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .bloom import bloom_probe_multi_kernel, reciprocal
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


@functools.partial(jax.jit, donate_argnums=(0,))
def _set_row_donated(filts, row, slot):
    return filts.at[slot].set(row)


def set_stack_row(filts, row_words, slot):
    """Write one filter's words into row ``slot`` of a stacked device
    filter array, donating the input buffer so backends that support
    input-output aliasing update the row IN PLACE — O(row) instead of the
    O(tables * width) restack-and-reupload of ``stack_filters``.  This is
    the engine's incremental read-view maintenance primitive: one call
    per flush output / merge output.  ``row_words`` shorter than the
    stack width must be pre-padded by the caller.  The donated input
    array is consumed — callers must replace every reference with the
    returned array.  Operands cross the jit boundary raw (the row as
    host uint32 words, the slot as a Python int): explicit
    ``jnp.asarray``/``jnp.int32`` staging costs an order of magnitude
    more dispatch than the row write itself."""
    return _set_row_donated(filts, row_words, int(slot))


def bloom_probe_multi_host(filts_np: np.ndarray, meta: np.ndarray,
                           keys: np.ndarray) -> np.ndarray:
    """Host twin of ``bloom_probe_multi``: the same double-hashing probe
    over the HOST mirror of the stacked filter words, pure numpy — the
    execution backend's CPU fast path for the fused probe (bit-identical
    to the kernel by construction: same hash family, same per-row
    geometry semantics, unused hash lanes pass).

    ``filts_np`` is (tables, words) uint32, ``meta`` (tables, 2) uint32
    rows of (n_bits, k_hashes).  Returns a (tables, keys) bool matrix.
    Rows iterate in Python (tables are tens, keys are the batch — the
    inner work is vectorized numpy over (k, q))."""
    keys = np.asarray(keys, np.uint32)
    t, q = int(filts_np.shape[0]), len(keys)
    out = np.zeros((t, q), bool)
    if t == 0 or q == 0:
        return out
    h1 = _hash_np(keys, 0x9E3779B9)
    h2 = _hash_np(keys, 0x85EBCA6B) | np.uint32(1)
    i_max = np.arange(int(meta[:, 1].max()), dtype=np.uint32)[:, None]
    for r in range(t):
        n_bits = np.uint32(meta[r, 0])
        k = int(meta[r, 1])
        pos = ((h1[None, :] + i_max[:k] * h2[None, :]) % n_bits) \
            .astype(np.int64)                           # (k, q)
        words = filts_np[r, pos >> 5]
        bits = (words >> (pos & 31).astype(np.uint32)) & np.uint32(1)
        out[r] = bits.min(axis=0).astype(bool)
    return out


def probe_batch(n: int, block: int = 1024) -> int:
    """Keys one fused probe launch screens for a batch of ``n``: the
    batch padded to a power-of-two number of ``block``-key blocks, so a
    serving loop compiles O(log batch) programs, not one per size."""
    return block << max(-(-n // block) - 1, 0).bit_length()


def bloom_probe_multi(filts, meta, keys, block: int = 1024,
                      interpret: bool = False):
    """Probe one key batch against a stack of padded filters (see
    ``stack_filters``) in a single fused launch; returns a (tables, keys)
    bool maybe-present matrix (no false negatives per table).  The batch
    is padded on the host to ``probe_batch(len(keys), block)`` keys."""
    t = filts.shape[0]
    n = len(keys)
    if t == 0 or n == 0:
        return np.zeros((t, n), bool)
    meta = np.asarray(meta, np.uint32)
    kp = np.zeros(probe_batch(n, block), np.uint32)
    kp[:n] = np.asarray(keys, np.uint32)
    meta = np.concatenate([meta, reciprocal(meta[:, :1])], axis=1)
    out = bloom_probe_multi_kernel(jnp.asarray(filts), meta,
                                   kp, k_max=int(meta[:, 1].max()),
                                   block=block, interpret=interpret)
    return np.asarray(out)[:, :n].astype(bool)
