"""jit'd public API for the sorted-merge kernel: run staging, co-rank
planning, the tournament of Pallas launches, and newest-wins dedup.

Entry points: ``merge_sorted``/``merge_dedup`` (one pairwise compaction
step) and ``merge_dedup_kway``/``merge_dedup_kway_window`` (a balanced
tournament reduction over the age-carrying pairwise kernel — the k-way
merge behind the engine's range plane and multi-input compactions).

Runs are staged on the host into one ``(runs, rows, 128)`` stack per
call: every run padded with the sentinel to a common power-of-two
capacity plus the kernel's ``2 * block`` tail, and the run count padded
to a power of two with empty runs.  Each tournament round is then ONE
launch over every pair, and the compiled programs are keyed only by
(runs, capacity) buckets, so a stream of differently-sized merge windows
compiles O(log^2) programs rather than one per window.  Results come
back to the host, which holds every table's authoritative copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .merge import AGE_PAD, KEY_PAD, LANES, TILE, merge_path_merge_age

_FLIP = np.uint32(0x80000000)


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def kernel_block(block: int) -> int:
    """The kernel's output block: ``block`` rounded up to a power-of-two
    number of whole (8, 128) tiles."""
    return max(TILE, _next_pow2(block))


def _to_stored(keys: np.ndarray) -> np.ndarray:
    """Keys as the kernel stores them: int32 in the same order (uint32
    keys have their top bit flipped; int32 keys are kept)."""
    keys = np.asarray(keys)
    if keys.dtype == np.uint32:
        return (keys ^ _FLIP).view(np.int32)
    return keys.astype(np.int32)


def _stage(runs, block: int):
    """Pack ``[(keys, ages, vals), ...]`` into the padded round stack:
    host ``(keys, ages, vals)`` of shape (runs, rows, 128) and ``lens``."""
    m = _next_pow2(max(len(runs), 2))
    cap = max(block, _next_pow2(max(len(r[0]) for r in runs)))
    width = cap + 2 * block
    ks = np.full((m, width), KEY_PAD, np.int32)
    ags = np.full((m, width), AGE_PAD, np.int32)
    vs = np.zeros((m, width), np.int32)
    lens = np.zeros(m, np.int32)
    for i, (k, a, v) in enumerate(runs):
        n = len(k)
        ks[i, :n] = _to_stored(k)
        ags[i, :n] = a
        vs[i, :n] = np.asarray(v, np.int32)
        lens[i] = n
    shape = (m, width // LANES, LANES)
    return (ks.reshape(shape), ags.reshape(shape), vs.reshape(shape)), lens


def _coranks(keys, lens, block: int, blocks: int):
    """Merge-path co-ranks of every output block of every pair, by a
    vectorized binary search along each block's diagonal: co-rank ``i``
    of diagonal ``d`` is the number of run-A entries among the first
    ``d`` merged, with equal keys taking run A (the newer) first."""
    runs = keys.shape[0]
    flat = keys.reshape(runs, -1)
    size = flat.shape[1]
    ka, kb = flat[0::2], flat[1::2]
    na, nb = lens[0::2][:, None], lens[1::2][:, None]
    d = jnp.minimum(jnp.arange(blocks, dtype=jnp.int32)[None, :] * block,
                    na + nb)
    lo = jnp.maximum(0, d - nb)
    hi = jnp.minimum(d, na)
    for _ in range(size.bit_length() + 1):
        mid = (lo + hi) // 2
        a = jnp.take_along_axis(ka, jnp.clip(mid, 0, size - 1), axis=1)
        b = jnp.take_along_axis(kb, jnp.clip(d - mid - 1, 0, size - 1),
                                axis=1)
        live = lo < hi
        a_first = a <= b
        lo, hi = (jnp.where(live & a_first, mid + 1, lo),
                  jnp.where(live & ~a_first, mid, hi))
    return jnp.stack([lo, d - lo], axis=-1).reshape(-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _round(keys, ages, vals, lens, block: int, interpret: bool):
    """One tournament round: merge pairs (2p, 2p + 1) in one launch."""
    rows = keys.shape[1]
    blocks = 2 * rows * LANES // block - 2
    parts = _coranks(keys, lens, block, blocks)
    ko, ao, vo = merge_path_merge_age(keys, ages, vals, parts, block=block,
                                      interpret=interpret)
    return ko, ao, vo, lens[0::2] + lens[1::2]


def _tournament(stack, lens, block: int, interpret: bool):
    keys, ages, vals = stack
    lens = jnp.asarray(lens)
    while keys.shape[0] > 1:
        keys, ages, vals, lens = _round(keys, ages, vals, lens, block=block,
                                        interpret=interpret)
    return keys[0].reshape(-1), ages[0].reshape(-1), vals[0].reshape(-1), \
        lens[0]


def merge_sorted(keys_a, vals_a, keys_b, vals_b, block: int = 256,
                 interpret: bool = False):
    """Merge two sorted runs; A is the newer run (wins ties).

    Returns (keys, vals, src, valid_len) where the first ``valid_len``
    entries are the merged output (entries beyond are sentinel padding).
    """
    block = kernel_block(block)
    ka, kb = np.asarray(keys_a), np.asarray(keys_b)
    runs = [(ka, np.zeros(len(ka), np.int32), vals_a),
            (kb, np.ones(len(kb), np.int32), vals_b)]
    stack, lens = _stage(runs, block)
    mk, ms, mv, _ = _tournament(stack, lens, block, interpret)
    if ka.dtype == np.uint32:
        mk = lax.bitcast_convert_type(mk, jnp.uint32) ^ jnp.uint32(_FLIP)
    return mk, mv, ms, len(ka) + len(kb)


def dedup_newest(keys, vals, srcs, valid_len):
    """Newest-wins dedup of a merged run (A-entries sort before equal
    B-entries): keep an entry iff it is the first of its equal-key group."""
    n = keys.shape[0]
    idx = jnp.arange(n)
    prev_same = jnp.concatenate([jnp.array([False]),
                                 keys[1:] == keys[:-1]])
    keep = (~prev_same) & (idx < valid_len)
    return keep


def merge_dedup(keys_a, vals_a, keys_b, vals_b, block: int = 256,
                interpret: bool = False):
    """Full compaction step: merge + newest-wins dedup.

    Returns (keys, vals, keep_mask, valid_len); callers typically compact
    with ``jnp.where`` + host-side slicing."""
    mk, mv, ms, valid = merge_sorted(keys_a, vals_a, keys_b, vals_b,
                                     block=block, interpret=interpret)
    keep = dedup_newest(mk, mv, ms, valid)
    return mk, mv, keep, valid


# --------------------------------------------------------------- k-way
def merge_dedup_kway(runs, block: int = 256, interpret: bool = False,
                     drop_value: int | None = None):
    """K-way newest-wins merge of sorted unique runs (NEWEST run first).

    A balanced tournament reduction over the age-carrying pairwise
    merge-path kernel: each element enters tagged with its run index as an
    age (smaller = newer), adjacent pairs are merged per round (left run
    newer — list order keeps age groups contiguous, so every A-age < every
    B-age and the pairwise tie rule stays exact), and duplicates survive
    until ONE final compaction pass masks every non-first element of each
    equal-key group.  O(n log k) merged entries vs O(n*k) for the
    sequential pairwise fold.  The compaction runs on the host as the
    merged run is copied back: on the chip a prefix-sum compaction
    compiles for seconds per output size.

    ``drop_value`` fuses tombstone reclamation into the compaction mask:
    an equal-key group whose NEWEST (winning) version carries this value
    is dropped entirely — the read plane passes the engine's tombstone
    sentinel here for scans, and bottom-level merges pass it to reclaim
    deleted keys (older shadowed versions fall to the dedup mask
    regardless, so only the winner's value needs testing).

    Returns compacted (keys, vals) numpy arrays, sorted ascending, keys in
    the input dtype.
    """
    block = kernel_block(block)
    staged = [(np.asarray(k), np.full(len(k), i, np.int32), np.asarray(v))
              for i, (k, v) in enumerate(runs) if len(k)]
    if not staged:
        return np.empty(0, np.uint32), np.empty(0, np.int32)
    stack, lens = _stage(staged, block)
    keys, _, vals, _ = _tournament(stack, lens, block, interpret)
    n = int(lens.sum())
    keys = np.asarray(keys)[:n]
    vals = np.asarray(vals)[:n]
    first = np.ones(n, bool)
    first[1:] = keys[1:] != keys[:-1]
    if drop_value is not None:
        first &= vals != np.int32(drop_value)
    keys = keys[first]
    if staged[0][0].dtype == np.uint32:
        keys = keys.view(np.uint32) ^ _FLIP
    return keys, vals[first]


def merge_dedup_kway_window(runs, starts, stops, block: int = 256,
                            interpret: bool = False,
                            drop_value: int | None = None):
    """Streaming-quantum (block-stepped) variant of ``merge_dedup_kway``:
    merge only the ``[starts[i], stops[i])`` window of each run.

    The engine's streaming merge cursor cuts windows at a GLOBAL key
    boundary (no equal-key group straddles a cut), so per-window
    newest-wins dedup composes exactly: concatenating successive windows'
    outputs is bit-identical to ``merge_dedup_kway`` over the full runs.
    Run-list order is still newest-first, and an empty window keeps its
    position's age rank (``merge_dedup_kway`` tags ages by list index),
    so the tournament's tie-breaking is unchanged.  Per call the kernel
    touches O(k * capacity) entries, where the capacity is the largest
    window rounded up to a power of two — the bounded-lock-hold contract
    of the engine's background plane.
    """
    windows = [(np.asarray(k)[s:e], np.asarray(v)[s:e])
               for (k, v), s, e in zip(runs, starts, stops)]
    return merge_dedup_kway(windows, block=block, interpret=interpret,
                            drop_value=drop_value)
