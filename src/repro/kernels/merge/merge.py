"""Pallas TPU kernel: pairwise sorted merge via merge-path partitioning.

This is the compaction inner loop the paper's schedulers meter out I/O to.
The CPU/GPU idiom (an iterator heap) is scalar and branchy; the TPU
adaptation splits each pair's output into fixed-size blocks of ``block``
entries whose input windows are located by a *merge-path* co-rank search
(done once, vectorized, in ops.py) and merges each window pair with
bitonic compare-exchange networks — pure VPU selects plus lane/sublane
rolls, no data-dependent control flow.

Layout: every run lives in HBM as ``(rows, 128)`` int32 tiles (keys are
stored order-preserving as int32, see ``ops``).  A grid step copies, by
DMA, the ``2 * block`` entries starting at the 128-aligned row that holds
its co-rank: the chip can only copy whole rows at a data-dependent
offset.  The ``off = corank % 128`` entries in front of the co-rank
belong to earlier blocks; they are masked to the +inf sentinel, which
leaves each window a bitonic sequence.  A bitonic half-cleaner then
keeps the window's ``block`` smallest entries and a bitonic merge sorts
them (run A ascending, run B descending), so no reversal or dynamic
shift is ever needed; one more half-cleaner + merge of the two sorted
halves yields the block.  Everything compares (key, age)
lexicographically: ages are original run indices (smaller = newer), so
equal keys from different runs stay totally ordered and the downstream
newest-wins dedup is a pure adjacent-key mask.

Grid: ``(pairs, blocks)`` — one launch merges every adjacent run pair of
a tournament round.  The co-ranks arrive as scalar prefetch (SMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: the smallest block: one (8, 128) int32 tile
TILE = 8 * LANES
KEY_PAD = jnp.iinfo(jnp.int32).max      # sentinel key (stored form)
AGE_PAD = jnp.iinfo(jnp.int32).max      # sentinel age (oldest possible)


def _less(ka, aa, kb, ab):
    """(ka, aa) < (kb, ab) in (key, age) lexicographic order."""
    return (ka < kb) | ((ka == kb) & (aa < ab))


def _half_clean(lo, hi):
    """Element-wise min of two tiles in (key, age) order: applied to the
    two halves of a bitonic sequence, the result holds its smaller half
    (itself bitonic)."""
    take_hi = _less(hi[0], hi[1], lo[0], lo[1])
    return tuple(jnp.where(take_hi, h, l) for l, h in zip(lo, hi))


def _bitonic_merge(xs, descending: bool):
    """Sort a bitonic ``(rows, 128)`` tile triple (key, age, val) whose
    element order is row-major.  Stage ``d`` compare-exchanges element
    ``e`` with ``e ^ d``; the partner is fetched with two rolls and a
    select on where a rolled iota landed, which is independent of the
    roll's direction convention."""
    rows = xs[0].shape[0]
    d = rows * LANES // 2
    while d >= 1:
        if d < LANES:
            axis, n, dist = 1, LANES, d
        else:
            axis, n, dist = 0, rows, d // LANES
        iota = lax.broadcasted_iota(jnp.int32, (rows, LANES), axis)
        fwd = pltpu.roll(iota, dist, axis) == (iota ^ dist)
        # the lower element of a pair keeps the min (ascending)
        upper = ((iota & dist) == 0) if descending else ((iota & dist) != 0)
        part = [jnp.where(fwd, pltpu.roll(x, dist, axis),
                          pltpu.roll(x, n - dist, axis)) for x in xs]
        p_less = _less(part[0], part[1], xs[0], xs[1])
        s_less = _less(xs[0], xs[1], part[0], part[1])
        take = (upper & s_less) | (~upper & p_less)
        xs = tuple(jnp.where(take, p, x) for p, x in zip(part, xs))
        d //= 2
    return xs


def _merge_kernel(parts_ref, k_hbm, a_hbm, v_hbm, ko_ref, ao_ref, vo_ref,
                  buf, sem, *, block: int, blocks: int):
    p = pl.program_id(0)
    b = pl.program_id(1)
    base = 2 * (p * blocks + b)
    starts = (parts_ref[base], parts_ref[base + 1])
    wrows = 2 * block // LANES
    copies = []
    for side in range(2):
        row0 = starts[side] // LANES
        for j, src in enumerate((k_hbm, a_hbm, v_hbm)):
            cp = pltpu.make_async_copy(
                src.at[2 * p + side, pl.ds(row0, wrows)],
                buf.at[side, j], sem.at[side, j])
            cp.start()
            copies.append(cp)
    for cp in copies:
        cp.wait()
    pos = lax.broadcasted_iota(jnp.int32, (wrows, LANES), 0) * LANES + \
        lax.broadcasted_iota(jnp.int32, (wrows, LANES), 1)
    srows = block // LANES
    halves = []
    for side in range(2):
        # entries in front of the co-rank belong to earlier blocks: +inf
        dead = pos < starts[side] % LANES
        w = (jnp.where(dead, KEY_PAD, buf[side, 0]),
             jnp.where(dead, AGE_PAD, buf[side, 1]),
             jnp.where(dead, 0, buf[side, 2]))
        low = _half_clean(tuple(x[:srows] for x in w),
                          tuple(x[srows:] for x in w))
        halves.append(_bitonic_merge(low, descending=side == 1))
    out = _bitonic_merge(_half_clean(*halves), descending=False)
    ko_ref[...] = out[0]
    ao_ref[...] = out[1]
    vo_ref[...] = out[2]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def merge_path_merge_age(keys, ages, vals, parts, block: int = TILE,
                         interpret: bool = False):
    """Merge adjacent run pairs ``(2p, 2p + 1)`` of a stacked round.

    ``keys``/``ages``/``vals`` are ``(runs, rows, 128)`` int32 with each
    run's valid prefix (key, age)-sorted and a sentinel tail of at least
    ``2 * block`` entries; every age of run ``2p`` must be smaller than
    every age of run ``2p + 1`` (the tournament pairs adjacent
    newest-first groups, which guarantees this).  ``parts`` is the flat
    ``(pairs * blocks * 2,)`` co-rank table from ``ops``, where
    ``blocks = rows * 128 * 2 / block - 2``.  Returns three
    ``(pairs, blocks * block / 128, 128)`` arrays: each pair's merged
    run followed by a sentinel tail of ``2 * block`` entries.
    """
    if block % TILE:
        raise ValueError(f"block must be a multiple of {TILE}")
    runs, rows, _ = keys.shape
    pairs = runs // 2
    blocks = 2 * rows * LANES // block - 2
    out_rows = blocks * block // LANES
    out_spec = pl.BlockSpec((None, block // LANES, LANES),
                            lambda p, b, parts: (p, b, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pairs, blocks),
        in_specs=[any_spec] * 3,
        out_specs=[out_spec] * 3,
        scratch_shapes=[
            pltpu.VMEM((2, 3, 2 * block // LANES, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 3)),
        ],
    )
    shape = jax.ShapeDtypeStruct((pairs, out_rows, LANES), jnp.int32)
    return pl.pallas_call(
        functools.partial(_merge_kernel, block=block, blocks=blocks),
        grid_spec=grid_spec,
        out_shape=[shape] * 3,
        interpret=interpret,
    )(parts, keys, ages, vals)
