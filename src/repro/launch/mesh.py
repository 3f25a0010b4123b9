"""Mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state.  Every mesh in the repo is built by
:func:`make_mesh`: its axes are ``Auto``, which the model layers'
``with_sharding_constraint`` calls and the embedding gather on a
model-sharded table assume.  Single-pod: 16×16 = 256 chips (v5e pod);
multi-pod: 2×16×16 = 512 chips with a leading "pod" axis for cross-pod
data parallelism (hierarchical DP: fast in-pod ICI, slow DCN across).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(data, 1)))
    return make_mesh((data, model), ("data", "model"))


def device_pool(n: int | None = None, *, mesh=None):
    """The device tuple a :class:`~repro.core.streams.Dispatcher`
    places streams over: the first ``n`` host devices (all of them when
    ``n`` is None), or — given a ``mesh`` — that mesh's devices in
    flat order, so stream placement and sharded launches draw from the
    same pool.  Run under ``XLA_FLAGS=--xla_force_host_platform_
    device_count=N`` to get N CPU devices."""
    if mesh is not None:
        devs = tuple(mesh.devices.flat)
    else:
        devs = tuple(jax.devices())
    return devs if n is None else devs[:n]
