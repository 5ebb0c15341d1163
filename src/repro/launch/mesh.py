"""Production mesh construction.

`make_production_mesh` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The single-pod production mesh
is 16x16 = 256 chips (one TPU v5e pod-slice); the multi-pod mesh adds a
leading "pod" axis (2 pods = 512 chips) whose collectives ride DCN.

Every axis is `AxisType.Auto`: the model code places activations with
`with_sharding_constraint` (`ShardingRules.cs`) and lets GSPMD propagate
the rest, which `jax.make_mesh`'s default Explicit axes would refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axis_names):
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    if multi_pod:
        return auto_mesh((2, 16, 16), ("pod", "data", "model"))
    return auto_mesh((16, 16), ("data", "model"))


def make_host_mesh(data: int = 1, model: int = 1):
    """A small mesh over however many (host) devices exist — for tests."""
    return auto_mesh((data, model), ("data", "model"))
