"""Production mesh builders.

Target: TPU v5e, 256 chips per pod. Single pod = (16, 16) over (data, model);
multi-pod = (2, 16, 16) over (pod, data, model). A FUNCTION (not a module-level
constant) so importing never touches jax device state.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence[jax.Device]] = None
              ) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the runtime places arrays
    with ``NamedSharding`` + ``with_sharding_constraint`` and lets GSPMD
    propagate, which the ``Explicit`` default of newer JAX would refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# Hardware constants for the roofline (TPU v5e)
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link
