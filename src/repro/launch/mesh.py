"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips.
Multi-pod: (pod=2, data=16, model=16) = 512 chips — the `pod` axis is pure
data parallelism (gradient all-reduce over DCN, int8-compressible via
training.grad_compression).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import AxisType


def auto_mesh(shape: Tuple[int, ...], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis Auto-sharded, so lowering does not
    follow a changing default (the hlo_cost walker depends on it)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int = 1):
    """Small mesh over whatever devices exist (tests on CPU)."""
    n = len(jax.devices())
    model = min(model, n)
    data = max(min(data, n // model), 1)
    return auto_mesh((data, model), ("data", "model"))
