"""Pure-jnp oracle for the sorted-merge kernel."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np



def merge_sorted_ref(keys_a, vals_a, keys_b, vals_b):
    """Stable two-run merge: ties prefer run A.  Returns (keys, vals, src)."""
    keys = jnp.concatenate([keys_a, keys_b])
    vals = jnp.concatenate([vals_a, vals_b])
    srcs = jnp.concatenate([jnp.zeros(keys_a.shape, jnp.int32),
                            jnp.ones(keys_b.shape, jnp.int32)])
    order = jnp.lexsort((srcs, keys))
    return keys[order], vals[order], srcs[order]


def merge_dedup_ref(keys_a, vals_a, keys_b, vals_b):
    """Oracle for merge + newest-wins dedup, via a plain dict (numpy)."""
    d = {}
    for k, v in zip(np.asarray(keys_b), np.asarray(vals_b)):
        d[int(k)] = v
    for k, v in zip(np.asarray(keys_a), np.asarray(vals_a)):
        d[int(k)] = v          # A (newer) overwrites B
    items = sorted(d.items())
    ks = np.array([k for k, _ in items])
    vs = np.array([v for _, v in items])
    return ks, vs


def merge_dedup_kway_ref(runs):
    """Oracle for the k-way tournament (runs NEWEST first): replay the
    runs oldest -> newest into a dict so later (newer) writes win."""
    d = {}
    for ks, vs in reversed(list(runs)):
        for k, v in zip(np.asarray(ks), np.asarray(vs)):
            d[int(k)] = int(v)
    items = sorted(d.items())
    ks = np.array([k for k, _ in items], np.uint32)
    vs = np.array([v for _, v in items], np.int32)
    return ks, vs
