"""Pallas TPU kernel: fused multi-table Bloom-filter probe (Section 2.1 —
the point-lookup filter the paper's query experiments lean on).

The SSD-era idiom pokes single bits through byte addressing.  A TPU has
no vector gather from VMEM, so the probe splits at the one thing each
side does well: XLA computes every (table, hash, key) bit position as a
dense vectorized pass (double hashing, each table modulo its own
``n_bits``), and the kernel holds one table's filter in VMEM as
``(rows, 128)`` words and resolves positions from SMEM with
scalar-addressed row loads — one row load, one lane rotate and one
masked AND per probe.  Filters are built on the host (ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: filter rows are padded to whole (8, 128) word tiles
TILE_WORDS = 8 * LANES
#: VMEM the probe may claim: a double-buffered filter plus slack, within
#: the 128 MiB of a v5e core
_VMEM_CAP = 100 << 20


def hash_u32(x, seed: int):
    """xorshift-multiply finalizer on uint32 lanes."""
    x = x.astype(jnp.uint32) ^ jnp.uint32(seed)
    x = (x ^ (x >> 16)) * jnp.uint32(0x45D9F3B)
    x = (x ^ (x >> 16)) * jnp.uint32(0x45D9F3B)
    return x ^ (x >> 16)


def reciprocal(n_bits: np.ndarray) -> np.ndarray:
    """Host-side ``floor((2**32 - 1) / n_bits)`` per table: the multiplier
    of ``_mod``'s Barrett reduction."""
    return (np.uint64(0xFFFFFFFF)
            // np.asarray(n_bits, np.uint64)).astype(np.uint32)


def _mulhi(a, b):
    """High 32 bits of the uint32 product, from 16-bit limbs."""
    a0, a1 = a & 0xFFFF, a >> 16
    b0, b1 = b & 0xFFFF, b >> 16
    lo, m1, m2 = a0 * b0, a1 * b0, a0 * b1
    carry = ((lo >> 16) + (m1 & 0xFFFF) + (m2 & 0xFFFF)) >> 16
    return a1 * b1 + (m1 >> 16) + (m2 >> 16) + carry


def _mod(x, d, recip):
    """Exact ``x % d`` for uint32 lanes with a per-lane divisor ``d`` and
    its ``reciprocal``.  XLA's TPU lowering of a non-constant uint32
    remainder takes tens of seconds to compile; this Barrett reduction
    is a handful of multiplies (the quotient estimate is at most 2
    low, so two conditional subtractions finish it)."""
    r = x - _mulhi(x, recip) * d
    r = jnp.where(r >= d, r - d, r)
    return jnp.where(r >= d, r - d, r)


def _probe_kernel(pos_ref, filt_ref, out_ref, *, k_max: int, block: int):
    """One grid step probes ``block`` keys against ONE table's filter.

    ``pos_ref`` (SMEM) holds the key block's bit positions per hash lane,
    -1 for lanes beyond this table's own k (they cannot veto).  Each
    probe loads the filter row holding its word, rotates the word's lane
    onto the key's output lane and ANDs the bit in."""
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def out_row(r, carry):
        def one_key(j, acc):
            q = r * LANES + j

            def one_hash(i, acc):
                p = pos_ref[0, i, q]
                w = jnp.maximum(p, 0) >> 5
                row = filt_ref[0, pl.ds(w >> 7, 1), :]
                s = (j - (w & (LANES - 1))) & (LANES - 1)
                # the rolled iota says where the word's lane landed, so
                # the pick is independent of the roll direction
                at = pltpu.roll(lane, s, 1) == (w & (LANES - 1))
                word = jnp.where(at, pltpu.roll(row, s, 1),
                                 pltpu.roll(row, (LANES - s) & (LANES - 1),
                                            1))
                bit = lax.shift_right_logical(word, p & 31) & 1
                keep = (lane != j) | (p < 0)
                return acc & jnp.where(keep, 1, bit)

            return lax.fori_loop(0, k_max, one_hash, acc)

        acc = lax.fori_loop(0, LANES, one_key,
                            jnp.ones((1, LANES), jnp.int32))
        out_ref[0, pl.ds(r, 1), :] = acc
        return carry

    lax.fori_loop(0, block // LANES, out_row, 0)


@functools.partial(jax.jit, static_argnames=("k_max", "block", "interpret"))
def bloom_probe_multi_kernel(filts, meta, keys, k_max: int,
                             block: int = 1024, interpret: bool = False):
    """Fused probe of one key batch against a STACK of filters.

    ``filts`` is (tables, words) uint32 — each row a filter zero-padded to
    the common word count; ``meta`` is (tables, 3) uint32 rows of
    (n_bits, k_hashes, ``reciprocal(n_bits)``); ``keys`` is (n,) uint32
    with ``n % block == 0``.
    Returns (tables, n) int32 maybe-present flags (0/1) from one launch
    over a (tables, key-blocks) grid — the hot path for batched point
    lookups across a whole LSM tree.  Per-table geometry arrives as data,
    so tables with heterogeneous filters share the launch.
    """
    t, w = filts.shape
    n = keys.shape[0]
    if n % block or block % LANES:
        raise ValueError("pad keys to a multiple of block (a multiple "
                         "of 128) in ops.py")
    rows = -(-max(w, 1) // TILE_WORDS) * TILE_WORDS // LANES
    words = jnp.pad(filts.astype(jnp.uint32), ((0, 0), (0, rows * LANES - w)))
    words = lax.bitcast_convert_type(words, jnp.int32).reshape(t, rows, LANES)
    meta = jnp.asarray(meta, jnp.uint32)
    n_bits, k, recip = (meta[:, c][:, None, None] for c in range(3))
    h1 = hash_u32(keys, 0x9E3779B9)
    h2 = hash_u32(keys, 0x85EBCA6B) | jnp.uint32(1)   # odd stride
    i = jnp.arange(k_max, dtype=jnp.uint32)[None, :, None]
    pos = _mod(h1[None, None, :] + i * h2[None, None, :], n_bits, recip)
    pos = jnp.where(i < k, pos.astype(jnp.int32), -1)  # (t, k_max, n)
    vmem = min(_VMEM_CAP, 2 * rows * LANES * 4 + (8 << 20))
    return pl.pallas_call(
        functools.partial(_probe_kernel, k_max=k_max, block=block),
        grid=(t, n // block),
        in_specs=[
            pl.BlockSpec((1, k_max, block), lambda i, j: (i, 0, j),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, rows, LANES), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block // LANES, LANES),
                               lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((t, n // LANES, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
    )(pos, words).reshape(t, n)
