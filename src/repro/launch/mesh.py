"""Production mesh construction.

``make_production_mesh`` is a function (never a module-level constant) so that
importing this module does not touch jax device state.
"""
from __future__ import annotations

import jax


def make_mesh_auto(shape, axes, **kw):
    """``jax.make_mesh`` with Auto axis types on every axis."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Small mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return make_mesh_auto((n // model_axis, model_axis), ("data", "model"))
