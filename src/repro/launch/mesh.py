"""Production mesh construction (assignment MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module constant: importing this module never touches
jax device state.  Single pod = (16, 16) v5e = ("data", "model");
multi-pod = (2, 16, 16) = ("pod", "data", "model") — the pod axis carries
pure data parallelism across pods (DCN-ish), `data` carries FSDP + batch,
`model` carries TP/EP/SP.

Every axis is ``Auto``: the models place their activations with
``with_sharding_constraint`` and let GSPMD propagate the rest, which
``Explicit`` axes (``jax.make_mesh``'s default since jax 0.7) refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# TPU v5e hardware constants (roofline denominators; assignment §Roofline).
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (effective, one link)
HBM_PER_CHIP = 16 * 1024 ** 3     # 16 GiB
