"""COX runtime: grid launch (the paper §4 host side).

The paper forks one pthread per CUDA block.  Here the grid is functional
and the schedule is a pluggable *backend* (``repro.core.backends``):

* ``scan``    — single device, ``lax.scan`` over block indices carrying
  global memory (a legal schedule: CUDA guarantees nothing about
  cross-block ordering between grid-wide syncs);
* ``vmap``    — single device, chunks of blocks run simultaneously via
  ``jax.vmap`` over the block function; per-block copies of global
  memory are reconciled with single-writer write-masks + summed atomic
  deltas (``backends/merge.py``);
* ``sharded`` — blocks dealt round-robin-contiguously over a mesh axis
  with ``shard_map``; within each device the same vmap executor runs,
  and device copies merge with masked ``psum`` stores + ``psum`` of
  atomic deltas (a *stronger* story than the paper, which has none).

``backend="auto"`` (default) applies ``flat.choose_backend``'s
heuristic.  Straggler note for the 1000-node posture: blocks are pure
functions of (bid, inputs), so any chunk can be re-executed anywhere;
``chunk`` slices the grid into re-dispatchable work units.
"""
from __future__ import annotations

import dataclasses
import operator
from typing import Any, Dict, Optional, Sequence

import jax.numpy as jnp
from jax.sharding import Mesh

from . import backends as _backends
from . import flat as _flat
from .backends.plan import DEFAULT_CHUNK, LaunchPlan
from .execute import CompiledKernel
from .types import (COOP_MAX_RESIDENT_BLOCKS, CoxUnsupported, Dim3, as_dim3,
                    check_launch_geometry)


@dataclasses.dataclass(frozen=True)
class ResolvedLaunch:
    """Launch knobs after dim3 normalization and 'auto' resolution —
    the single canonical form every caller (``KernelFn.launch``'s cache
    key, :func:`build_launcher`, tests) derives from.  The heuristics
    key on the normalized *totals*, so ``grid=4`` and ``grid=(4,1,1)``
    resolve identically.

    ``chunk``/``chunk_source`` carry the resolved vmap-wave width and
    *where it came from*: ``'explicit'`` (caller passed ``chunk=``; the
    footprint verdict never swaps the schedule underneath it),
    ``'heuristic'`` (defaulted to ``min(grid, DEFAULT_CHUNK)``), or
    ``'cooperative'`` (pinned to ``grid`` by the all-resident grid-sync
    rule).

    ``schedule``/``n_resident``/``schedule_source`` mirror that design
    for the *launch schedule*: ``'chunked'`` walks a materialized
    ``(n_chunks, chunk)`` block-id table; ``'grid_stride'`` runs a
    fixed wave of ``n_resident`` block slots that loop over the grid
    with in-graph block ids (``bid = wave × n_resident + slot``), so no
    O(grid) table ever exists — CUDA's grid-stride-loop idiom.  The
    provenance values follow ``chunk_source``: ``'explicit'`` (caller
    passed ``schedule=``), ``'heuristic'`` (the footprint verdict,
    applied once argument shapes are bound), ``'cooperative'`` (a
    multi-phase grid beyond the resident capacity, grid-strided instead
    of rejected)."""
    grid: Dim3
    block: Dim3
    backend: str    # 'scan' | 'vmap' | 'sharded'
    mode: str       # 'normal' | 'jit'
    warp_exec: str  # 'serial' | 'batched'
    n_warps: int
    chunk: Optional[int] = None  # resolved blocks-per-wave (None: plan default)
    chunk_source: str = "heuristic"  # 'explicit'|'heuristic'|'cooperative'
    schedule: str = "chunked"    # 'chunked' | 'grid_stride'
    n_resident: Optional[int] = None  # grid-stride wave width (None: chunked)
    schedule_source: str = "heuristic"  # same provenance set as chunk_source


def resolve_chunk(ck: CompiledKernel, grid: int, chunk) -> tuple:
    """Resolve the ``chunk`` knob to ``(value, source)`` — the one place
    the explicit-vs-defaulted distinction is decided.  ``chunk`` accepts
    an int >= 1 (explicit — clamped to the grid but otherwise honored
    verbatim) or ``None`` (the ``min(grid, DEFAULT_CHUNK)`` heuristic);
    anything else raises ``ValueError``.  Cooperative launches pin
    ``chunk == grid`` exactly as ``LaunchPlan.build`` enforces."""
    auto = chunk is None
    if not auto:
        try:
            c = operator.index(chunk)   # ints only: no 'auto', no floats
        except TypeError:
            c = 0
        if c < 1:
            raise ValueError(f"chunk must be an int >= 1 or None, got "
                             f"{chunk!r}")
    if ck.n_phases > 1:
        if not auto and c < grid:
            raise CoxUnsupported(
                f"cooperative launch of '{ck.kernel.name}': chunk={chunk} "
                f"would split the grid into waves, but a grid barrier "
                f"needs every block resident per phase — drop chunk= "
                f"(the plan schedules all {grid} blocks as one wave)")
        return grid, "cooperative"
    if auto:
        return min(grid, DEFAULT_CHUNK), "heuristic"
    return min(c, grid), "explicit"


def resolve_launch(ck: CompiledKernel, *, grid, block,
                   mode: str = "auto", backend: str = "auto",
                   warp_exec: str = "auto", chunk=None,
                   schedule: str = "auto",
                   n_resident: Optional[int] = None,
                   mesh: Optional[Mesh] = None) -> ResolvedLaunch:
    """Normalize ``grid``/``block`` (``int | (x, y[, z])``) to canonical
    dim3, enforce CUDA's launch limits, and resolve the 'auto' knobs via
    the ``repro.core.flat`` heuristics.  This is the one place launch
    knobs are resolved — dim3 normalization happens exactly once.

    ``schedule`` accepts ``'auto'`` (the footprint verdict picks, once
    argument shapes are known — :func:`resolve_schedule`),
    ``'chunked'``, or ``'grid_stride'``; ``n_resident`` sizes the
    grid-stride wave (``None``: cost-model default) and implies
    ``schedule='grid_stride'``.  Cooperative grids beyond the resident
    capacity lower to a grid-strided phase wave instead of raising —
    the CUDA analogue of occupancy-sizing a cooperative launch — unless
    the caller explicitly pins ``schedule='chunked'``."""
    grid3 = as_dim3(grid, "grid")
    block3 = as_dim3(block, "block")
    check_launch_geometry(grid3, block3)
    if schedule not in ("auto", "chunked", "grid_stride"):
        raise ValueError(
            f"schedule must be 'auto', 'chunked' or 'grid_stride', "
            f"got {schedule!r}")
    if n_resident is not None:
        n_resident = int(n_resident)
        if n_resident < 1:
            raise ValueError(f"n_resident must be >= 1, got {n_resident}")
        if schedule == "chunked":
            raise ValueError(
                "n_resident= only applies to schedule='grid_stride' "
                "(the chunked schedule sizes waves with chunk=)")
        schedule = "grid_stride"  # n_resident implies the strided schedule
    sched = "chunked" if schedule == "auto" else schedule
    sched_src = "heuristic" if schedule == "auto" else "explicit"
    n_res = n_resident
    bname = _flat.choose_backend(ck.kernel, grid=grid3.total, mesh=mesh,
                                 requested=backend)
    n_warps = -(-block3.total // ck.warp_size)
    mode = _flat.choose_mode(ck.kernel, n_warps=n_warps, requested=mode)
    machines = (ck.machine if not ck.phases
                else tuple(p.machine for p in ck.phases))
    warp_exec = _flat.choose_warp_exec(ck.kernel, n_warps=n_warps,
                                       requested=warp_exec,
                                       machine=machines)
    ch, ch_src = resolve_chunk(ck, grid3.total, chunk)
    if ck.n_phases > 1:
        # CUDA's cooperative-launch constraint (cudaLaunchCooperativeKernel
        # rejects grids beyond SMs × maxBlocksPerSM): a grid barrier needs
        # every block resident per phase.  Beyond the capacity we lower to
        # a grid-strided phase wave — COOP_MAX_RESIDENT_BLOCKS slots loop
        # over the grid within each phase, every wave of phase p completing
        # before phase p+1 starts, so the barrier guarantee holds with
        # per-block carried state paged through the resident wave.
        if grid3.total > COOP_MAX_RESIDENT_BLOCKS:
            if schedule == "chunked":
                raise CoxUnsupported(
                    f"cooperative launch of '{ck.kernel.name}': grid="
                    f"{grid3.total} blocks exceeds the resident capacity "
                    f"({COOP_MAX_RESIDENT_BLOCKS}) and schedule='chunked' "
                    f"pins the all-resident wave — drop schedule= to let "
                    f"the grid-stride lowering page blocks through "
                    f"{COOP_MAX_RESIDENT_BLOCKS} resident slots")
            sched = "grid_stride"
            if sched_src != "explicit":
                sched_src = "cooperative"
            n_res = min(n_res or COOP_MAX_RESIDENT_BLOCKS,
                        COOP_MAX_RESIDENT_BLOCKS)
            ch, ch_src = n_res, "cooperative"
        elif sched == "grid_stride":
            n_res = min(n_res or grid3.total, grid3.total,
                        COOP_MAX_RESIDENT_BLOCKS)
            ch, ch_src = n_res, "cooperative"
    elif sched == "grid_stride" and n_res is not None:
        n_res = min(n_res, grid3.total)
    return ResolvedLaunch(grid3, block3, bname, mode, warp_exec, n_warps,
                          ch, ch_src, sched, n_res, sched_src)


def resolve_schedule(ck: CompiledKernel, rl: ResolvedLaunch,
                     shapes: Dict[str, tuple], *,
                     budget: Optional[int] = None) -> ResolvedLaunch:
    """Apply the footprint verdict to an otherwise-resolved launch.
    Needs the *bound* argument shapes (the footprint model keys on
    global-memory bytes), so it runs after ``plan.bind_args`` /
    ``bind_kernel_args`` rather than inside :func:`resolve_launch`.

    Explicit schedules are honored verbatim (an explicit
    ``'grid_stride'`` without ``n_resident=`` gets the cost-model wave
    width filled in), and so is an explicit ``chunk=`` — the caller
    asked for that exact wave geometry, so the verdict never swaps the
    schedule underneath it; cooperative lowering decided in
    :func:`resolve_launch` is kept; everything else asks
    ``costmodel.schedule_verdict`` whether the chunk-table schedule
    fits ``FOOTPRINT_BUDGET`` and routes to grid-stride when it does
    not."""
    from . import costmodel as _costmodel
    if rl.schedule == "grid_stride":
        if rl.n_resident is None:
            n_res = _costmodel.resident_slots(
                ck, shapes, grid=rl.grid.total, n_warps=rl.n_warps,
                warp_exec=rl.warp_exec, budget=budget)
            return dataclasses.replace(
                rl, n_resident=min(n_res, rl.grid.total))
        return rl
    if (rl.schedule_source == "explicit"
            or rl.chunk_source == "explicit" or ck.n_phases > 1):
        return rl
    sched, n_res = _costmodel.schedule_verdict(
        ck, shapes, grid=rl.grid.total,
        chunk=rl.chunk if rl.chunk else DEFAULT_CHUNK,
        n_warps=rl.n_warps, warp_exec=rl.warp_exec,
        backend=rl.backend, budget=budget)
    if sched == "grid_stride":
        return dataclasses.replace(rl, schedule="grid_stride",
                                   n_resident=n_res,
                                   schedule_source="heuristic")
    return rl


def build_traceable(ck: CompiledKernel, rl: ResolvedLaunch, *,
                    simd: bool = True, mesh: Optional[Mesh] = None,
                    axis: str = "data", chunk: Optional[int] = None):
    """Build the plan and the *raw* (un-jitted) launcher for an
    already-resolved launch.  Returns ``(plan, fn)`` with
    ``fn(globals_, scalars) -> {name: flat array}`` traceable inside a
    larger jitted program — the form ``repro.core.graphs`` inlines when
    staging a captured launch DAG as one fused executable.

    ``chunk=`` overrides the resolved ``rl.chunk`` when given (legacy
    call shape; the resolved field is the canonical source)."""
    plan = LaunchPlan.build(ck, grid=rl.grid, block=rl.block, mode=rl.mode,
                            simd=simd,
                            chunk=chunk if chunk is not None else rl.chunk,
                            warp_exec=rl.warp_exec, schedule=rl.schedule,
                            n_resident=rl.n_resident)
    fn = _backends.get_backend(rl.backend).build_fn(plan, mesh=mesh,
                                                    axis=axis)
    return plan, fn


def build_resolved(ck: CompiledKernel, rl: ResolvedLaunch, *,
                   simd: bool = True, mesh: Optional[Mesh] = None,
                   axis: str = "data", chunk: Optional[int] = None,
                   donate: bool = False):
    """Build the plan and stage the jitted executable for an
    already-resolved launch.  Returns ``(plan, exe)`` with
    ``exe(globals_, scalars) -> {name: flat array}``.

    ``donate=True`` stages the executable with its global-memory inputs
    donated (``jax.jit(..., donate_argnums=...)``): XLA reuses the input
    buffers for the outputs instead of copying, so an in-order stream
    re-launching over the same globals stops paying the copy.  The
    caller must treat the passed arrays as *consumed* — JAX deletes
    donated buffers, and re-using one raises."""
    plan = LaunchPlan.build(ck, grid=rl.grid, block=rl.block, mode=rl.mode,
                            simd=simd,
                            chunk=chunk if chunk is not None else rl.chunk,
                            warp_exec=rl.warp_exec, schedule=rl.schedule,
                            n_resident=rl.n_resident)
    exe = _backends.get_backend(rl.backend).build(plan, mesh=mesh, axis=axis,
                                                  donate=donate)
    return plan, exe


def build_launcher(ck: CompiledKernel, *, grid, block,
                   mode: str = "auto", simd: bool = True,
                   mesh: Optional[Mesh] = None, axis: str = "data",
                   backend: str = "auto", chunk: Optional[int] = None,
                   warp_exec: str = "auto", schedule: str = "auto",
                   n_resident: Optional[int] = None, donate: bool = False):
    """:func:`resolve_launch` + :func:`build_resolved` in one call.

    No argument shapes here, so ``schedule='auto'`` stays chunked (the
    footprint verdict can't run); :func:`launch` and the stream layer
    bind args first and get the full :func:`resolve_schedule` pass."""
    rl = resolve_launch(ck, grid=grid, block=block, mode=mode,
                        backend=backend, warp_exec=warp_exec, chunk=chunk,
                        schedule=schedule, n_resident=n_resident, mesh=mesh)
    if rl.schedule == "grid_stride" and rl.n_resident is None:
        rl = resolve_schedule(ck, rl, {})  # cost-model default wave width
    return build_resolved(ck, rl, simd=simd, mesh=mesh, axis=axis,
                          donate=donate)


def launch(ck: CompiledKernel, *, grid, block, args: Sequence[Any],
           mode: str = "auto", simd: bool = True,
           mesh: Optional[Mesh] = None, axis: str = "data",
           backend: str = "auto", chunk: Optional[int] = None,
           warp_exec: str = "auto", schedule: str = "auto",
           n_resident: Optional[int] = None,
           donate: bool = False) -> Dict[str, jnp.ndarray]:
    """Run ``kernel<<<grid, block>>>(*args)``; returns {array name: value}.
    ``grid`` and ``block`` accept ``int | (x, y[, z])`` dim3 geometry.

    mode='auto' (default) resolves to loop-carried 'normal' execution
    for multi-warp blocks — on XLA the trace is already
    shape-specialized, so the paper's JIT mode (grid/block burned in,
    loops unrolled) only bloats the program; the Fig-13 advantage does
    NOT transfer (EXPERIMENTS.md §Benchmarks) — and to 'jit' for
    single-warp blocks, where unrolling is free.  mode='jit'/'normal'
    remain available for the comparison.

    warp_exec='auto' (default) batches the inter-warp loop into one
    (n_warps, W) lane plane whenever the block has more than one warp
    and the per-warp shared-memory copies fit the budget
    (``flat.choose_warp_exec``); 'serial'/'batched' force either path.

    ``donate=True`` donates the flat global-memory buffers to the
    executable (buffer reuse instead of copy-on-write); the bound
    arrays are consumed — note that for already-1-D inputs the flat
    binding aliases the caller's array, which JAX then deletes.
    Donation is unsupported on the ``sharded`` backend
    (``CoxUnsupported``): its replicated cross-device buffers cannot
    alias a single donated input.

    This is the uncached entry point; ``KernelFn.launch`` adds a
    launch-level compile cache (now owned by the stream dispatcher,
    ``repro.core.streams``) so repeat launches skip retracing.
    """
    from .backends.plan import bind_kernel_args
    rl = resolve_launch(ck, grid=grid, block=block, mode=mode,
                        backend=backend, warp_exec=warp_exec, chunk=chunk,
                        schedule=schedule, n_resident=n_resident, mesh=mesh)
    globals_, shapes, scalars = bind_kernel_args(ck, args)
    rl = resolve_schedule(ck, rl, shapes)
    _, exe = build_resolved(ck, rl, simd=simd, mesh=mesh, axis=axis,
                            donate=donate)
    out = exe(globals_, scalars)
    return {k: v.reshape(shapes[k]) for k, v in out.items()}
