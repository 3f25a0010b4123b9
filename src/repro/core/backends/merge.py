"""Unified write-mask / atomic-delta merge semantics.

Every execution level that runs CUDA code on *copies* of memory — a
vmap chunk of blocks on one device, one device's slice of the grid
under shard_map, or the per-warp copies of shared/global memory under
warp-batched execution (``execute.py``'s ``(n_warps, W)`` lane plane)
— reconciles those copies here, under one contract:

* **plain stores** are single-writer: the CUDA race-freedom contract
  guarantees at most one copy stores to a given element between syncs,
  so the merged value is *the* writer's value, transported bit-exactly
  (:func:`select_writer`: payload bits moved through a masked integer
  sum whose other terms are zero — merged stores are bitwise-identical
  to serial execution);
* **atomics** are order-free reductions: each copy accumulates its own
  delta buffer and deltas are summed across copies (and ``psum``-ed
  across devices) — a *stronger* story than the paper, which has no
  multi-device atomics at all;
* elements nobody touched keep the carried-in value.

A vmap wave of blocks (``block_vmap._merge_wave``) hands this module
copies only of the arrays that need them: a global no instruction
writes has no copy, an atomic-only target brings its deltas alone, and
a wave-logged array (``execute.wave_logged``: stored, never reloaded)
comes as (index, value) store logs that the wave scatters onto the
carry itself — the same single-writer contract, without a per-block
copy of the array.

Delta buffers live in the "numeric image" of the array dtype
(:func:`num` — bool promotes to int32 so masks/flags can be atomic
targets); :func:`denum` maps merged values back.

Semantics note: within one merge scope (a chunk, or a device between
merges) blocks do not observe each other's atomic updates.  For
order-free reductions that never inspect intermediate state this is
unobservable.  It IS observable to kernels that capture atomic old
values (the atomicAdd ticket pattern — valid and deterministic on CUDA,
where old values are unique across blocks), so those kernels are
rejected by the vmap/sharded builds (``LaunchPlan.check_mergeable``)
and kept on the serial scan backend by the ``auto`` heuristic.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax.numpy as jnp
from jax import lax


def num(x):
    """Numeric image of an array (bool -> int32) for delta arithmetic."""
    return x.astype(jnp.int32) if x.dtype == jnp.bool_ else x


def denum(x, dt):
    """Inverse of :func:`num` for a target dtype."""
    return (x != 0) if dt == jnp.bool_ else x.astype(dt)


def zeros_masks(globals_: Dict[str, Any]) -> Dict[str, Any]:
    return {k: jnp.zeros(v.shape, jnp.bool_) for k, v in globals_.items()}


def zeros_deltas(globals_: Dict[str, Any]) -> Dict[str, Any]:
    """Accumulator buffers, already in the numeric image."""
    return {k: jnp.zeros(v.shape, num(v).dtype) for k, v in globals_.items()}


def _to_bits(x):
    """Bit image of an array for exact payload transport: floats bitcast
    to same-width unsigned ints, bool widens to int32, ints pass."""
    if x.dtype == jnp.bool_:
        return x.astype(jnp.int32)
    if jnp.issubdtype(x.dtype, jnp.floating):
        nbits = jnp.dtype(x.dtype).itemsize * 8
        return lax.bitcast_convert_type(x, jnp.dtype(f"uint{nbits}"))
    return x


def _from_bits(b, dt):
    """Inverse of :func:`_to_bits`."""
    if dt == jnp.bool_:
        return b != 0
    if jnp.issubdtype(dt, jnp.floating):
        return lax.bitcast_convert_type(b, dt)
    return b


def select_writer(carry, copies, masks, *, axis: int = 0):
    """Single-writer selection along ``axis`` of ``copies``: the merged
    value at each element is *the* writing copy's value; untouched
    elements keep ``carry``.  Returns ``(merged, wrote_any)``.

    The payload is transported **bit-exactly**: values are bitcast to
    integers and moved through a masked sum (all other terms are zero —
    exact because integer addition with zero is the identity and the
    CUDA race-freedom contract guarantees at most one writer per
    element).  The masked sum is pure vector arithmetic, an order of
    magnitude cheaper on CPU than the equivalent argmax +
    ``take_along_axis`` gather; every bit pattern (-0.0, NaN payloads)
    survives unchanged.  A *racy* kernel (two writers between syncs)
    would get a garbage sum instead of an arbitrary winner — both are
    outside the contract.

    ``axis`` is the copy axis — axis 0 for a chunk of blocks or a warp
    plane merged at trace level; an inner axis when the caller merges an
    already-batched stack of copies (e.g. a (chunk, n_warps, N) plane
    merged over warps while the chunk axis stays batched).
    """
    cb = _to_bits(carry)
    xb = _to_bits(copies)
    stored = jnp.where(masks, xb, jnp.zeros_like(xb)).sum(
        axis=axis, dtype=cb.dtype)
    any_w = jnp.any(masks, axis=axis)
    return _from_bits(jnp.where(any_w, stored, cb), carry.dtype), any_w


def merge_chunk(g: Dict[str, Any], chunk_g: Dict[str, Any],
                chunk_m: Dict[str, Any], chunk_d: Dict[str, Any],
                *, fold_deltas: bool, axis: int = 0
                ) -> Tuple[Dict[str, Any], Dict[str, Any], Dict[str, Any]]:
    """Merge an ``axis``-batched set of per-copy memories into carry
    ``g``.  The copy axis is a chunk of blocks (grid backends) or the
    warp axis of a batched (n_warps, W) plane (``execute.py``).

    Only the arrays that have copies (``chunk_g``, with masks in
    ``chunk_m``) or deltas (``chunk_d``) are merged; an array with
    deltas and no copies — an atomic-only target in a vmap wave — keeps
    the carry and takes the summed deltas, with no mask or select.

    Returns ``(g_new, wrote_any, delta_sum)`` for the merged arrays:
    ``wrote_any`` is the per-array union of the copies' write masks (for
    arrays with copies) and ``delta_sum`` the per-array summed deltas
    (numeric image; empty when the kernel has no atomics).  With
    ``fold_deltas=True`` the summed deltas are applied to ``g_new``
    directly (single-device semantics); with ``False`` the caller owns
    them (the cross-device ``psum`` path, or the grid backends'
    mask/delta accumulators above a warp-plane merge).
    """
    out: Dict[str, Any] = {}
    wrote: Dict[str, Any] = {}
    dsum: Dict[str, Any] = {}
    for k in g:
        if k in chunk_g:
            new, wrote[k] = select_writer(g[k], chunk_g[k], chunk_m[k],
                                          axis=axis)
        elif k in chunk_d:
            new = g[k]
        else:
            continue
        if k in chunk_d:
            d = jnp.sum(num(chunk_d[k]), axis=axis)
            dsum[k] = d
            if fold_deltas:
                new = denum(num(new) + d, g[k].dtype)
        out[k] = new
    return out, wrote, dsum


def cross_device_merge(g0: Dict[str, Any], g: Dict[str, Any],
                       masks: Dict[str, Any], deltas: Dict[str, Any],
                       axis: str) -> Dict[str, Any]:
    """Reconcile per-device global-memory copies inside shard_map:
    single-writer stores land via masked psum (disjoint by contract),
    atomics via psum of the delta buffers (numeric image)."""
    merged = {}
    for k in g0:
        stored = lax.psum(jnp.where(masks[k], num(g[k]), 0), axis)
        cnt = lax.psum(masks[k].astype(jnp.int32), axis)
        val = jnp.where(cnt > 0, stored.astype(num(g[k]).dtype), num(g0[k]))
        if k in deltas:
            val = val + lax.psum(deltas[k], axis)
        merged[k] = denum(val, g0[k].dtype)
    return merged
