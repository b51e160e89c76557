"""Production mesh definitions (assignment §MULTI-POD DRY-RUN).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (data, model).
Multi-pod: 2x16x16 = 512 chips (pod, data, model); the pod axis carries pure
data parallelism (gradient all-reduce, optionally int8-compressed).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over the locally available devices (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# TPU v5e hardware constants (roofline targets; assignment §ROOFLINE)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (~per chip, one direction)
HBM_BYTES = 16 * 2 ** 30      # 16 GiB HBM per v5e chip
