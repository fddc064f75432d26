"""Production mesh construction.

Target hardware: TPU v5e pods — 256 chips/pod, (data=16, model=16) per pod;
the multi-pod mesh adds a leading "pod" axis (2 pods = 512 chips).  Defined
as a FUNCTION so importing this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    # Auto axes: the dry-run steps place arrays with with_sharding_constraint,
    # which jax.make_mesh's default Explicit axes reject
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over however many (host) devices exist — used by tests."""
    return _auto_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link
