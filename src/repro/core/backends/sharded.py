"""`sharded` backend — shard_map over devices × vmap within device.

Blocks are dealt round-robin-contiguously over a mesh axis; each device
runs its slice of the grid with the same chunked block-parallel executor
as the single-device `vmap` backend (so the multi-device path owns no
execution or merge logic of its own), then the per-device copies of
global memory are reconciled with the shared write-mask / psum-delta
merge.  Straggler note for the 1000-node posture: blocks are pure
functions of (bid, inputs), so any chunk can be re-executed anywhere —
the -1-padded per-device bid table is the re-dispatchable unit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..execute import make_block_fn
from . import merge
from .block_vmap import run_chunked, run_phase_wave, run_strided
from .plan import LaunchPlan, check_donate_supported

name = "sharded"


def build_fn(plan: LaunchPlan, mesh=None, axis: str = "data"):
    """Return the *raw* traceable ``run(globals_, scalars) -> globals_``
    launcher — the un-jitted form the graph tracer (``repro.core.
    graphs``) inlines into one fused program.  :func:`build` wraps it in
    ``jax.jit`` for standalone dispatch."""
    if mesh is None:
        raise ValueError("the sharded backend needs a mesh")
    plan.check_mergeable(name)
    if plan.n_phases > 1:
        return _build_phased_fn(plan, mesh, axis)
    ndev = mesh.shape[axis]
    block_fn = make_block_fn(plan.ck, n_warps=plan.n_warps, mode=plan.mode,
                             simd=plan.simd, track_writes=True,
                             warp_exec=plan.warp_exec,
                             block_dim=plan.block_dim, grid_dim=plan.grid_dim)
    if plan.schedule == "grid_stride":
        return _build_strided_fn(plan, mesh, axis, block_fn)
    bid_table = jnp.asarray(plan.device_bid_table(ndev))

    def device_fn(dev_bids, g0, scalars):
        # local view of the sharded (ndev, per) table is (1, per):
        # reshape to this device's (n_chunks, chunk) work units
        bid_chunks = dev_bids.reshape(-1, plan.chunk)
        g, masks, deltas = run_chunked(plan, block_fn, bid_chunks, g0,
                                       scalars, fold_deltas=False)
        return merge.cross_device_merge(g0, g, masks, deltas, axis)

    fn = jax.shard_map(device_fn, mesh=mesh,
                       in_specs=(P(axis), P(), P()), out_specs=P(),
                       check_vma=False)

    def run(globals_, scalars):
        return fn(bid_table, globals_, scalars)

    return run


def _build_strided_fn(plan: LaunchPlan, mesh, axis: str, block_fn):
    """Grid-stride over a mesh: the resident slots stripe across
    devices — device *d* owns the contiguous ids ``[d·per, (d+1)·per)``
    (the same round-robin-contiguous deal as ``device_bid_table``, so
    results match the chunked schedule bitwise) and loops its slice in
    waves of ``n_resident`` with ids computed from ``lax.axis_index``
    inside the staged program.  No ``(ndev, per)`` table is built or
    shipped; the per-device working set is ``n_resident × |globals|``
    regardless of grid size."""
    ndev = mesh.shape[axis]
    per = -(-plan.grid // ndev)

    def device_fn(g0, scalars):
        base = lax.axis_index(axis) * per
        g, masks, deltas = run_strided(plan, block_fn, g0, scalars,
                                       fold_deltas=False, base=base,
                                       total=per)
        return merge.cross_device_merge(g0, g, masks, deltas, axis)

    fn = jax.shard_map(device_fn, mesh=mesh,
                       in_specs=(P(), P()), out_specs=P(),
                       check_vma=False)

    def run(globals_, scalars):
        return fn(globals_, scalars)

    return run


def build(plan: LaunchPlan, mesh=None, axis: str = "data",
          donate: bool = False):
    """Return a jitted ``exe(globals_, scalars) -> globals_`` launcher."""
    if donate:
        check_donate_supported(name, plan.ck.kernel.name)
    return jax.jit(build_fn(plan, mesh=mesh, axis=axis))


def _build_phased_fn(plan: LaunchPlan, mesh, axis: str):
    """Cooperative launch over a mesh: each device keeps its slice of
    the grid resident across the whole phase sequence (per-block carried
    state never leaves its device — blocks are pinned, the bid table is
    identical every phase), and global memory is reconciled with the
    masked-psum / delta-psum merge at **every phase boundary**, so a
    phase-*p+1* block on one device observes phase-*p* writes made on
    any other device — the grid barrier's guarantee."""
    if plan.schedule == "grid_stride":
        return _build_phased_strided_fn(plan, mesh, axis)
    ndev = mesh.shape[axis]
    fns = plan.block_fns(track_writes=True)
    per = -(-plan.grid // ndev)
    table = np.full((ndev, per), -1, np.int32)
    flat = np.arange(plan.grid, dtype=np.int32)
    for d in range(ndev):
        mine = flat[d * per:(d + 1) * per]
        table[d, :len(mine)] = mine
    bid_table = jnp.asarray(table)

    def device_fn(dev_bids, g0, scalars):
        dev_bids = dev_bids.reshape(-1)        # this device's resident wave
        g = g0
        state = plan.init_persist(n_blocks=dev_bids.shape[0])
        for fn in fns:
            g2, wrote, dsum, state = run_phase_wave(
                plan, fn, dev_bids, g, scalars, state, fold_deltas=False)
            g = merge.cross_device_merge(g, g2, wrote, dsum, axis)
        return g

    fn = jax.shard_map(device_fn, mesh=mesh,
                       in_specs=(P(axis), P(), P()), out_specs=P(),
                       check_vma=False)

    def run(globals_, scalars):
        return fn(bid_table, globals_, scalars)

    return run


def _build_phased_strided_fn(plan: LaunchPlan, mesh, axis: str):
    """Cooperative grid-stride over a mesh: each device pages its
    contiguous slice of the grid through waves of ``n_resident`` blocks
    per phase (ids from ``lax.axis_index``, no bid table), accumulating
    write masks and atomic deltas across its waves, then global memory
    reconciles with the masked-psum / delta-psum merge at **every phase
    boundary** — all waves on all devices complete phase *p* before any
    block starts *p+1*, the grid barrier's guarantee, now without the
    all-resident capacity limit.  Per-block persistent state stays
    device-local in stacked planes windowed by ``dynamic_slice``."""
    ndev = mesh.shape[axis]
    fns = plan.block_fns(track_writes=True)
    R = plan.n_resident
    per = -(-plan.grid // ndev)
    n_waves = max(1, -(-per // R))
    tmap = jax.tree_util.tree_map

    def device_fn(g0, scalars):
        base = lax.axis_index(axis) * per
        limit = jnp.minimum(jnp.asarray(base, jnp.int32) + jnp.int32(per),
                            jnp.int32(plan.grid))
        g = g0
        state = plan.init_persist(n_blocks=n_waves * R)
        for fn in fns:
            masks0 = merge.zeros_masks(g)
            deltas0 = merge.zeros_deltas(g) if plan.has_atomics else {}

            def wave(i, carry, fn=fn):
                g, st, m_acc, d_acc = carry
                bids = plan.stride_bids(i, base=base, limit=limit)
                st_i = tmap(lambda a: lax.dynamic_slice_in_dim(
                    a, i * R, R, 0), st)
                g2, wrote, dsum, st2 = run_phase_wave(
                    plan, fn, bids, g, scalars, st_i, fold_deltas=False)
                st = tmap(lambda a, v: lax.dynamic_update_slice_in_dim(
                    a, v, i * R, 0), st, st2)
                m_acc = {k: m_acc[k] | wrote[k] for k in m_acc}
                d_acc = {k: d_acc[k] + dsum[k] for k in d_acc}
                return g2, st, m_acc, d_acc

            g2, state, masks, deltas = lax.fori_loop(
                0, n_waves, wave, (g, state, masks0, deltas0))
            g = merge.cross_device_merge(g, g2, masks, deltas, axis)
        return g

    fn = jax.shard_map(device_fn, mesh=mesh,
                       in_specs=(P(), P()), out_specs=P(),
                       check_vma=False)

    def run(globals_, scalars):
        return fn(globals_, scalars)

    return run
