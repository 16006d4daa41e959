"""Pallas TPU kernel: fused multi-table Bloom-filter probe (Section 2.1 —
the point-lookup filter the paper's query experiments lean on).

The SSD-era idiom pokes single bits through byte addressing.  A TPU has
no vector gather from VMEM, so the probe splits at the one thing each
side does well: XLA computes every (table, hash, key) bit position as a
dense vectorized pass (double hashing, each table modulo its own
``n_bits``), and the kernel holds one table's filter in VMEM as
``(rows, 128)`` words and resolves positions from SMEM with
scalar-addressed row loads — one row load, one lane rotate and one
masked AND per probe.  A launch probes each table only for its window
of the sorted key batch, the keys inside the table's key range (a table
whose range misses a key cannot hold it), so tables and keys that
cannot match cost no probe work.  Filters are built on the host
(ops.py).
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


def stack_width(words: int) -> int:
    """Words of a filter stack row that holds ``words``: whole (8, 128)
    word tiles, the shape of the kernel's VMEM block."""
    return -(-max(words, 1) // TILE_WORDS) * TILE_WORDS


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


def _probe_kernel(fetch_ref, start_ref, count_ref, pos_ref, filt_ref,
                  out_ref, *, k_max: int, block: int):
    """Grid step ``(i, j)`` probes the keys of row ``i``'s live window
    ``[start, start + count)`` of the sorted batch that fall in key
    block ``j``, against the filter of the row ``fetch_ref[i]`` names
    (the row itself when it holds a live key).  A step outside the
    window does no probe work and writes nothing.

    ``pos_ref`` (SMEM) holds the key block's bit positions per hash lane,
    -1 for lanes beyond this table's own k (they cannot veto).  Each
    probe loads the filter row holding its word, rotates the word's lane
    onto the key's output lane and ANDs the bit in."""
    del fetch_ref                        # used by the index maps only
    i, jb = pl.program_id(0), pl.program_id(1)
    lo = jnp.maximum(start_ref[i] - jb * block, 0)
    hi = jnp.minimum(start_ref[i] + count_ref[i] - jb * block, block)
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def out_row(r, carry):
        def one_key(j, acc):
            q = r * LANES + j

            def one_hash(h, acc):
                p = pos_ref[0, h, q]
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

        first = jnp.maximum(lo - r * LANES, 0)
        last = jnp.minimum(hi - r * LANES, LANES)
        acc = lax.fori_loop(first, last, one_key,
                            jnp.ones((1, LANES), jnp.int32))
        out_ref[0, pl.ds(r, 1), :] = acc
        return carry

    # output rows of 128 keys that hold a live key; none when hi <= lo
    r0 = lo >> 7
    lax.fori_loop(r0, jnp.where(hi > lo, (hi + LANES - 1) >> 7, r0),
                  out_row, 0)


@functools.partial(jax.jit, static_argnames=("k_max", "block", "interpret"))
def bloom_probe_multi_kernel(filts, table, keys, k_max: int,
                             block: int = 1024, interpret: bool = False):
    """Fused probe of one key batch against a STACK of filters, pruned to
    each row's live window of keys.

    ``filts`` is (tables, words) uint32 — each row a filter zero-padded to
    the common word count — or, read in place, the same words as int32
    (tables, rows, 128), rows of ``stack_width`` words; ``table`` is
    (tables, 5) uint32 rows of (n_bits, k_hashes, ``reciprocal(n_bits)``,
    start, count); ``keys`` is (n,) uint32 with ``n % block == 0``.  Row
    ``r`` probes only keys ``start .. start + count - 1`` of the batch;
    the engine sorts the batch so that a row's window is the keys inside
    its table's key range.  (One operand for the rows and one for the
    keys: each host array an operand costs a transfer.)

    Returns (tables, n // 32) uint32 maybe-present bits, bit ``q % 32``
    of word ``q // 32`` for key ``q``, zero outside each row's window,
    from one launch over a (tables, key-blocks) grid.  A row holding no
    live key does no probe work and, as its filter block, keeps the
    previous live row's (the first live row's before any), so each live
    row's filter crosses into VMEM once and no other does.  Per-table
    geometry arrives as data, so tables with heterogeneous filters share
    the launch.
    """
    n = keys.shape[0]
    if n % block or block % LANES:
        raise ValueError("pad keys to a multiple of block (a multiple "
                         "of 128) in ops.py")
    if filts.ndim == 2:                  # a copy into whole word tiles
        t, w = filts.shape
        filts = jnp.pad(filts.astype(jnp.uint32),
                        ((0, 0), (0, stack_width(w) - w)))
        filts = lax.bitcast_convert_type(filts, jnp.int32).reshape(t, -1,
                                                                   LANES)
    t, rows, _ = filts.shape
    table = jnp.asarray(table, jnp.uint32)
    n_bits, k, recip = (table[:, c][:, None, None] for c in range(3))
    h1 = hash_u32(keys, 0x9E3779B9)
    h2 = hash_u32(keys, 0x85EBCA6B) | jnp.uint32(1)   # odd stride
    i = jnp.arange(k_max, dtype=jnp.uint32)[None, :, None]
    pos = _mod(h1[None, None, :] + i * h2[None, None, :], n_bits, recip)
    pos = jnp.where(i < k, pos.astype(jnp.int32), -1)  # (t, k_max, n)
    # per-step tables: a dead row fetches the last live row before it
    # (the first live row if none), and anchors its blocks at that row's
    # last (first) key block, so its steps repeat the block indices
    # of the step before (after) them and Pallas copies nothing
    start = table[:, 3].astype(jnp.int32)
    count = table[:, 4].astype(jnp.int32)
    live = count > 0
    prev = lax.cummax(jnp.where(live, jnp.arange(t, dtype=jnp.int32), -1))
    fetch = jnp.where(prev >= 0, prev, jnp.argmax(live).astype(jnp.int32))
    anchor = jnp.where(prev >= 0,
                       start[fetch] + jnp.maximum(count[fetch], 1) - 1,
                       start[fetch])
    anchor = jnp.clip(jnp.where(live, start, anchor), 0, n - 1)
    step_count = jnp.where(live, count, 0)

    def blocks(i, j, fetch_ref, start_ref, count_ref):
        a = start_ref[i]
        b = a + jnp.maximum(count_ref[i], 1) - 1
        return fetch_ref[i], jnp.minimum(jnp.maximum(j, a // block),
                                         b // block)

    def pos_map(i, j, *refs):
        f, kb = blocks(i, j, *refs)
        return f, 0, kb

    def filt_map(i, j, fetch_ref, *_):
        return fetch_ref[i], 0, 0

    def out_map(i, j, *refs):
        f, kb = blocks(i, j, *refs)
        return f, kb, 0

    vmem = min(_VMEM_CAP, 2 * rows * LANES * 4 + (8 << 20))
    flags = pl.pallas_call(
        functools.partial(_probe_kernel, k_max=k_max, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(t, n // block),
            in_specs=[
                pl.BlockSpec((1, k_max, block), pos_map,
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, rows, LANES), filt_map),
            ],
            out_specs=pl.BlockSpec((1, block // LANES, LANES), out_map),
        ),
        out_shape=jax.ShapeDtypeStruct((t, n // LANES, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
    )(fetch, anchor, step_count, pos, filts).reshape(t, n)
    # a step writes whole 128-key rows and a row's skipped blocks are
    # never written: keep the live window only, then pack 32 keys a word
    q = jnp.arange(n, dtype=jnp.int32)[None, :]
    flags = jnp.where((q >= start[:, None]) & (q < (start + count)[:, None]),
                      flags, 0).astype(jnp.uint32)
    shift = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(flags.reshape(t, n // 32, 32) << shift, axis=2,
                   dtype=jnp.uint32)
