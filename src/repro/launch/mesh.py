"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state — the dry-run sets XLA_FLAGS before any jax initialisation.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the pod axis is pure
    DP (params replicated across pods, gradient all-reduce over DCI)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for multi-device tests (host platform device count)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         **_axis_kwargs(2))
