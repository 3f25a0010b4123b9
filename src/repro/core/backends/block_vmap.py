"""`vmap` backend — block-parallel execution.

COX's host runtime (paper §4) forks one pthread per CUDA block because
blocks are independent between grid-wide syncs.  This backend is the
XLA rendition of that observation: ``jax.vmap`` over the compiled block
function runs a *chunk* of blocks simultaneously, and an outer
``lax.scan`` (or grid-stride ``fori_loop``) walks the chunks.  Each
block of a wave returns only what it can write: a copy and write mask
of each array it stores to through the copy path, delta buffers of its
atomic targets, and (index, value) store logs of its wave-logged arrays
(``execute.wave_logged``: stored, never reloaded).  The wave merges
them onto the carried globals — copies by the shared merge module's
single-writer select, deltas summed, logs scattered in program order —
and read-only globals pass through untouched.  A wave's working set is
chunk × |copy-path arrays| plus chunk × the logged stores.

The chunk axis is what exposes inter-block parallelism to the host
scheduler: XLA sees wide batched array ops instead of a length-`grid`
sequential loop of narrow ones.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..execute import make_block_fn
from . import merge
from .plan import LaunchPlan

name = "vmap"


def _merge_wave(plan: LaunchPlan, block_fn, bids, carry,
                scalars: Dict[str, Any], *, fold_deltas: bool):
    """One vmap wave over ``bids`` (-1 marking pad slots) and its merge
    into ``carry = (globals, mask_acc, delta_acc)`` — the body both the
    chunk-table walk and the grid-stride loop run, so the two schedules
    are the same computation over the same wave contents.

    ``block_fn`` is a tracked single-phase block function, whose
    outputs are the wave form of ``execute.make_block_fn``.  Copy-path
    arrays merge by single-writer select, atomic targets by summed
    deltas, and each wave-logged store instruction lands as one flat
    scatter over the chunk's lanes, in program order (the single-writer
    contract makes different blocks' entries disjoint; a later store of
    the same block overwrites an earlier one).  Under tracking
    (``fold_deltas=False``) the accumulators gather the wave's masks —
    a logged store's indices set its mask with one scatter of ``True``
    — and deltas for ``merge.cross_device_merge``."""
    g, m_acc, d_acc = carry
    u = plan.uniforms(bids, scalars)                # bid: (chunk,)
    u_axes = {k: (0 if k == "bid" else None) for k in u}
    g2, m2, d2, logs = jax.vmap(block_fn, in_axes=(u_axes, None))(u, g)
    # pad slots (bid < 0) ran with garbage indices; their writes are
    # discarded by zeroing the masks/deltas before the merge and by
    # pointing their log entries one past the end, where they drop
    valid = bids >= 0
    m2 = {k: v & valid[:, None] for k, v in m2.items()}
    d2 = {k: jnp.where(valid[:, None], v, 0) for k, v in d2.items()}
    with jax.named_scope("cox_merge"):
        g_new, wrote, dsum = merge.merge_chunk(g, g2, m2, d2,
                                               fold_deltas=fold_deltas)
    g = {**g, **g_new}
    if not fold_deltas:
        m_acc = {**m_acc, **{k: m_acc[k] | w for k, w in wrote.items()}}
        d_acc = {**d_acc, **{k: d_acc[k] + d for k, d in dsum.items()}}
    with jax.named_scope("cox_wave_log"):
        for k, entries in logs.items():
            for idx, val in entries:            # (chunk, n_warps, W) each
                flat = jnp.where(valid[:, None, None], idx,
                                 jnp.int32(g[k].shape[0])).reshape(-1)
                g[k] = g[k].at[flat].set(val.reshape(-1), mode="drop")
                if not fold_deltas:
                    m_acc[k] = m_acc[k].at[flat].set(True, mode="drop")
    return g, m_acc, d_acc


def _accumulators(plan: LaunchPlan, globals_, fold_deltas: bool):
    """The wave loop's starting carry: the globals, plus zero masks and
    deltas where the caller tracks them (the sharded backend)."""
    if fold_deltas:
        return globals_, {}, {}
    return (globals_, merge.zeros_masks(globals_),
            merge.zeros_deltas(globals_) if plan.has_atomics else {})


def run_chunked(plan: LaunchPlan, block_fn, bid_chunks, globals_,
                scalars: Dict[str, Any], *, fold_deltas: bool
                ) -> Tuple[Dict[str, Any], Dict[str, Any], Dict[str, Any]]:
    """scan-over-chunks × vmap-within-chunk block executor.

    ``bid_chunks`` is a (n_chunks, chunk) int32 table, -1 marking pad
    slots.  Returns ``(globals, masks, deltas)`` where masks/deltas are
    the union/sum over every executed block — the sharded backend feeds
    them to :func:`merge.cross_device_merge`; the single-device caller
    ignores them (``fold_deltas=True`` applies deltas in-line).
    """
    def chunk_step(carry, bids):
        return _merge_wave(plan, block_fn, bids, carry, scalars,
                           fold_deltas=fold_deltas), None

    (g, m, d), _ = lax.scan(chunk_step,
                            _accumulators(plan, globals_, fold_deltas),
                            jnp.asarray(bid_chunks))
    return g, m, d


def run_strided(plan: LaunchPlan, block_fn, globals_,
                scalars: Dict[str, Any], *, fold_deltas: bool,
                base=0, total: Optional[int] = None
                ) -> Tuple[Dict[str, Any], Dict[str, Any], Dict[str, Any]]:
    """Grid-stride block executor: a counted ``lax.fori_loop`` over
    resident waves, each wave a vmap over ``n_resident`` block slots
    whose ids are computed in-graph (``plan.stride_bids``) — no
    ``(n_chunks, chunk)`` table is ever materialized, so the working
    set is one wave's regardless of grid size.

    Wave *i* covers the contiguous ids ``base + [i·R, (i+1)·R)`` —
    exactly row *i* of the chunk table a chunked plan with ``chunk=R``
    would walk, so the two schedules produce bitwise-equal results.
    ``base``/``total`` scope the loop to one device's slice of the grid
    (``base`` may be a traced ``axis_index`` offset); the defaults
    cover the whole grid.  Returns ``(globals, masks, deltas)`` exactly
    like :func:`run_chunked`."""
    total = plan.grid if total is None else int(total)
    n_waves = plan.n_stride_waves(total)
    limit = jnp.minimum(jnp.asarray(base, jnp.int32) + jnp.int32(total),
                        jnp.int32(plan.grid))

    def wave_step(i, carry):
        bids = plan.stride_bids(i, base=base, limit=limit)
        return _merge_wave(plan, block_fn, bids, carry, scalars,
                           fold_deltas=fold_deltas)

    return lax.fori_loop(0, n_waves, wave_step,
                         _accumulators(plan, globals_, fold_deltas))


def run_phase_wave(plan: LaunchPlan, fn, bids, globals_, scalars, state,
                   *, fold_deltas: bool):
    """One cooperative phase as a single all-resident ``jax.vmap`` wave
    over ``bids`` (the plan pins ``chunk == grid``, so there is exactly
    one wave — CUDA's cooperative-launch residency rule).  Per-block
    carried state rides the batch axis; -1 pad slots (sharded backend's
    idle lanes) get their masks/deltas zeroed exactly like
    :func:`run_chunked`'s pad handling.  Returns
    ``(globals, wrote_masks, delta_sums, state)`` with masks/deltas
    merged over the wave (``fold_deltas=True`` applies them in-line)."""
    u = plan.uniforms(bids, scalars)
    u_axes = {k: (0 if k == "bid" else None) for k in u}
    g2, m2, d2, st2 = jax.vmap(
        lambda uu, gg, ss: fn(uu, gg, state=ss),
        in_axes=(u_axes, None, 0))(u, globals_, state)
    valid = (bids >= 0)[:, None]
    m2 = {k: v & valid for k, v in m2.items()}
    d2 = {k: jnp.where(valid, v, 0) for k, v in d2.items()}
    g, wrote, dsum = merge.merge_chunk(globals_, g2, m2, d2,
                                       fold_deltas=fold_deltas)
    return g, wrote, dsum, st2


def build_fn(plan: LaunchPlan, mesh=None, axis: str = "data"):
    """Return the *raw* traceable ``run(globals_, scalars) -> globals_``
    launcher — the un-jitted form the graph tracer (``repro.core.
    graphs``) inlines into one fused program.  :func:`build` wraps it in
    ``jax.jit`` for standalone dispatch."""
    plan.check_mergeable(name)
    if plan.n_phases > 1:
        return _build_phased_fn(plan)
    block_fn = make_block_fn(plan.ck, n_warps=plan.n_warps, mode=plan.mode,
                             simd=plan.simd, track_writes=True,
                             warp_exec=plan.warp_exec,
                             block_dim=plan.block_dim, grid_dim=plan.grid_dim)
    if plan.schedule == "grid_stride":
        def run(globals_, scalars):
            g, _, _ = run_strided(plan, block_fn, globals_, scalars,
                                  fold_deltas=True)
            return g

        return run
    bid_chunks = plan.chunked_bids()

    def run(globals_, scalars):
        g, _, _ = run_chunked(plan, block_fn, bid_chunks, globals_, scalars,
                              fold_deltas=True)
        return g

    return run


def build(plan: LaunchPlan, mesh=None, axis: str = "data",
          donate: bool = False):
    """Return a jitted ``exe(globals_, scalars) -> globals_`` launcher.
    ``donate=True`` donates the globals dict (argnum 0) — every input
    buffer aliases its same-shape output, so the chunked merge carry
    starts in place instead of on a copy."""
    return jax.jit(build_fn(plan, mesh=mesh, axis=axis),
                   donate_argnums=(0,) if donate else ())


def _build_phased_fn(plan: LaunchPlan):
    """Cooperative launch: one all-resident vmap wave per phase, globals
    merged (single-writer select + summed atomic deltas) at every phase
    boundary so phase *p+1* observes all of phase *p*'s writes."""
    if plan.schedule == "grid_stride":
        return _build_phased_strided_fn(plan)
    fns = plan.block_fns(track_writes=True)
    bids = jnp.arange(plan.grid, dtype=jnp.int32)

    def run(globals_, scalars):
        g = globals_
        state = plan.init_persist()
        for fn in fns:
            g, _, _, state = run_phase_wave(plan, fn, bids, g, scalars,
                                            state, fold_deltas=True)
        return g

    return run


def _build_phased_strided_fn(plan: LaunchPlan):
    """Cooperative grid-stride: each phase runs as a ``fori_loop`` over
    resident waves of ``n_resident`` blocks, with every block's
    persistent state paged through ``dynamic_slice`` windows of the
    stacked O(grid) planes.  All waves of phase *p* complete before
    phase *p+1* starts (the loop is inside the per-phase step), so the
    grid barrier's guarantee holds beyond the all-resident capacity —
    the lowering CUDA itself uses for occupancy-sized cooperative
    launches.  Single-writer stores and summed deltas make the result
    equal to the one-wave schedule regardless of wave grouping."""
    fns = plan.block_fns(track_writes=True)
    R = plan.n_resident
    n_waves = plan.n_stride_waves()
    tmap = jax.tree_util.tree_map

    def run(globals_, scalars):
        g = globals_
        # padded to whole waves: pad slots run with bid=-1 and have
        # their masks/deltas zeroed by run_phase_wave, so the garbage
        # state they write back is never observed
        state = plan.init_persist(n_blocks=n_waves * R)
        for fn in fns:
            def wave(i, carry, fn=fn):
                g, st = carry
                bids = plan.stride_bids(i)
                st_i = tmap(lambda a: lax.dynamic_slice_in_dim(
                    a, i * R, R, 0), st)
                g2, _, _, st2 = run_phase_wave(plan, fn, bids, g, scalars,
                                               st_i, fold_deltas=True)
                st = tmap(lambda a, v: lax.dynamic_update_slice_in_dim(
                    a, v, i * R, 0), st, st2)
                return g2, st

            g, state = lax.fori_loop(0, n_waves, wave, (g, state))
        return g

    return run
