"""Production mesh construction.

``make_production_mesh`` is a function (importing this module never touches
jax device state).  Meshes:

  single-pod   (16, 16)      axes ("data", "model")         — 256 chips
  multi-pod    (2, 16, 16)   axes ("pod", "data", "model")  — 512 chips

The "pod" axis is the slowest (DCN between pods); "model" is innermost (ICI
ring) — tensor-parallel collectives stay on-pod, only data-parallel gradient
reductions cross the DCN, matching the v5e network hierarchy.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axes (jax's default is Explicit): the
    sharding rules here place arrays and leave propagation to the compiler."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"))
