"""Mesh construction (assignment-specified shapes).

Functions, not module-level constants: importing this module must never
touch jax device state (smoke tests see 1 device; only dryrun.py forces 512).

Every mesh has Auto axes: all shardings in this codebase are passed
explicitly as NamedShardings, and ``jax.make_mesh`` would otherwise make
Explicit (sharding-in-types) axes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` over the visible devices, with Auto axis types."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = data or (n // model)
    return make_mesh((data, model), ("data", "model"))
