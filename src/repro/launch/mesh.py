"""Production mesh builders.

TPU v5e pod targets: single pod = 16x16 (256 chips) with (data, model)
axes; multi-pod = 2 pods x 256 chips with a leading 'pod' axis (DCN
data-parallel dimension).  Functions, not module constants — importing
this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e roofline constants (per chip)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link (~45-50 GB/s on v5e)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes: ``repro.dist.sharding`` places
    arrays by rules under ``sharding_ctx``, which Explicit axes (the
    default of ``jax.make_mesh``) reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pods: int = 0):
    """Small mesh for subprocess tests (forced host devices)."""
    if pods:
        return make_mesh((pods, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
