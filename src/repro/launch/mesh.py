"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (required so smoke tests see 1 CPU device while the dry-run
sees 512 forced host devices).

Every mesh is built with ``AxisType.Auto`` axes: the sharding layer
(:mod:`repro.parallel.sharding`) places arrays through logical-axis rules and
``with_sharding_constraint``, leaving the compiler to propagate shardings
through gathers and contractions. ``jax.make_mesh`` defaults to explicit
axes, under which every such op would need a hand-written ``out_sharding``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: Optional[int] = None) -> Mesh:
    """Small mesh over whatever devices exist (CPU tests)."""
    n = len(jax.devices())
    model = model or 1
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
