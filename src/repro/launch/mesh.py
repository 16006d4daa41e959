"""Production mesh construction (assignment-specified shapes).

TPU v5e constants used by the roofline analysis live here too, so every
consumer (dry-run, benchmarks, EXPERIMENTS.md generators) agrees on them.
"""
from __future__ import annotations

import jax

# TPU v5e per-chip hardware constants (assignment-specified).
PEAK_BF16_FLOPS = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link


def _auto(n: int) -> tuple:
    """``Auto`` axes: the sharding rules place arrays with
    ``with_sharding_constraint``, which ``jax.make_mesh``'s default
    ``Explicit`` axes refuse."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh():
    """Whatever devices exist locally, as a 1x1 (data, model) mesh slice —
    used by CPU examples/tests so the same step code paths run anywhere."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"), axis_types=_auto(2))
