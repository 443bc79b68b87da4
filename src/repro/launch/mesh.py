"""Production mesh construction (functions, never module-level constants —
importing this module must not touch jax device state).

Every axis is ``AxisType.Auto``: the sharding rules (``parallel/rules.py``)
pin layouts with ``with_sharding_constraint`` and leave the rest to GSPMD,
which Explicit axes (``jax.make_mesh``'s default) would refuse."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.parallel import ParallelCtx


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (GSPMD-propagated)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def make_mesh(data: int = 1, model: int = 1):
    """General (data, model) mesh — THE mesh-construction entry for launchers
    and serving (tracecheck TC405 pins `jax.make_mesh` to this module)."""
    return auto_mesh((data, model), ("data", "model"))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_ctx(mesh, *, moe_impl: str = "a2a") -> ParallelCtx:
    axes = mesh.axis_names
    data_axes = tuple(a for a in axes if a in ("pod", "data"))
    return ParallelCtx(mesh=mesh, data_axes=data_axes, model_axis="model",
                       moe_impl=moe_impl)
