"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before any jax import.

Mesh shapes (TPU v5e):
  single-pod : (16, 16)    axes (data, model)        = 256 chips
  multi-pod  : (2, 16, 16) axes (pod, data, model)   = 512 chips

``data`` doubles as the FL client axis (DESIGN.md §3); ``pod`` is the
cross-pod (DCN) data/client axis — hierarchical aggregation reduces within
pods over ICI first, then across pods.

Every axis is ``Auto``: the partitioner propagates shardings from the
parameter/batch specs and ``dist.sharding.act_hint`` constraints, which JAX
only accepts on Auto axes (``jax.make_mesh`` defaults to Explicit).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
               devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1, devices=None):
    """Small mesh over the actually-available devices (or ``devices``)."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    return _auto_mesh((n // model_parallel, model_parallel),
                      ("data", "model"), devices=devices)
