"""Pluggable grid-execution backends for the COX launcher.

A backend turns a :class:`~repro.core.backends.plan.LaunchPlan` into a
jitted ``exe(globals_, scalars) -> globals_`` callable via ``build``,
and exposes the same launcher un-jitted via ``build_fn`` so the graph
tracer (``repro.core.graphs``) can inline whole launches into one fused
XLA program:

* ``scan``    — loop-carried baseline: one ``lax.scan`` over block ids
                (minimal memory, fully serialized grid);
* ``vmap``    — block-parallel: ``jax.vmap`` runs chunks of blocks
                simultaneously, reconciled by the shared write-mask /
                atomic-delta merge (``merge.py``);
* ``sharded`` — shard_map over a mesh axis × the same vmap executor
                within each device, psum merge across devices.

``repro.core.flat.choose_backend`` is the backend heuristic (kernel
features + grid size + mesh → backend name); ``get_backend`` resolves a
name to its module.
"""
from __future__ import annotations

from . import block_vmap, scan, sharded
from .plan import LaunchPlan  # noqa: F401

BACKENDS = {
    scan.name: scan,
    block_vmap.name: block_vmap,
    sharded.name: sharded,
}


def available_backends():
    return tuple(BACKENDS)


def get_backend(name: str):
    try:
        return BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown launch backend {name!r}; "
                         f"available: {sorted(BACKENDS)}") from None
