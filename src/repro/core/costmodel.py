"""Wave footprint model — the chunked-vs-grid-stride schedule verdict.

``chunk_footprint``/``stride_footprint`` are the wave residency models
— per-block copies of global memory plus per-warp shared copies, with
the chunked schedule additionally charged for its materialized O(grid)
block-id table — and ``schedule_verdict`` turns them into the
chunked-vs-grid-stride lowering decision against ``FOOTPRINT_BUDGET``
(tests monkeypatch the constant to force the stride path on small
inputs).  Everything here is counted from kernel parameters and bound
argument shapes: nothing compiles or traces.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from . import flat as _flat
from .execute import CompiledKernel

# residency budget for a chunked wave's schedule-dependent footprint —
# the chunk× copies of global memory plus the materialized O(grid)
# block-id table — sized to a desktop L3.  Launches whose chunked
# footprint blows it are lowered to the grid-stride schedule
# (schedule_verdict below).
FOOTPRINT_BUDGET = 64 << 20

# wave widths the residency sizer considers, widest first
RESIDENT_CANDIDATES = (32, 16, 8, 4, 2, 1)


def global_bytes(ck: CompiledKernel, shapes: Dict[str, tuple]) -> int:
    """Total bytes of the bound global-memory arrays."""
    total = 0
    from .types import ArraySpec
    for spec in ck.kernel.params:
        if not isinstance(spec, ArraySpec):
            continue
        shape = shapes.get(spec.name)
        if shape is None:
            continue
        n = 1
        for d in shape:
            n *= int(d)
        total += n * np.dtype(spec.dtype.jnp).itemsize
    return total


def _per_block_bytes(ck: CompiledKernel, shapes: Dict[str, tuple], *,
                     n_warps: int, warp_exec: str) -> int:
    """One block's resident bytes in a vmap wave: its copy of global
    memory (the write-mask merge's cost) plus its shared memory — per
    warp when the batched plane copies it."""
    shared = _flat.shared_footprint(ck.kernel)
    per_block = global_bytes(ck, shapes)
    per_block += shared * (n_warps if warp_exec == "batched" else 1)
    return per_block


def bid_table_bytes(grid: int, chunk: int) -> int:
    """Bytes of the materialized ``(n_chunks, chunk)`` -1-padded block-id
    table the chunked schedule scans over (``LaunchPlan.chunked_bids``)
    — the O(grid) term the grid-stride schedule eliminates."""
    chunk = max(1, int(chunk))
    n_chunks = -(-int(grid) // chunk)
    return n_chunks * chunk * 4          # int32 entries


def chunk_footprint(ck: CompiledKernel, shapes: Dict[str, tuple], *,
                    chunk: int, n_warps: int,
                    warp_exec: str = "serial",
                    grid: Optional[int] = None) -> int:
    """Schedule-dependent resident bytes of the *chunked* schedule:
    ``chunk`` per-block copies of global memory plus shared memory, and
    — when the caller supplies ``grid`` — the materialized O(grid)
    block-id table the chunk walk scans over.  The table term is what a
    smaller chunk cannot shrink (``ceil(grid/chunk) × chunk`` entries ≈
    grid regardless of chunk), which is exactly why an over-budget
    verdict routes to grid-stride instead of clamping."""
    per_block = _per_block_bytes(ck, shapes, n_warps=n_warps,
                                 warp_exec=warp_exec)
    total = int(chunk) * per_block
    if grid is not None:
        total += bid_table_bytes(grid, chunk)
    return total


def stride_footprint(ck: CompiledKernel, shapes: Dict[str, tuple], *,
                     n_resident: int, n_warps: int,
                     warp_exec: str = "serial") -> int:
    """Resident bytes of one grid-stride wave: ``n_resident`` slot
    copies, no table term — block ids are computed in-graph
    (``bid = wave × n_resident + slot``), so the footprint is
    grid-independent."""
    return int(n_resident) * _per_block_bytes(ck, shapes, n_warps=n_warps,
                                              warp_exec=warp_exec)


def resident_slots(ck: CompiledKernel, shapes: Dict[str, tuple], *,
                   grid: int, n_warps: int, warp_exec: str = "serial",
                   budget: Optional[int] = None) -> int:
    """Cost-model-sized grid-stride wave width: the widest
    ``RESIDENT_CANDIDATES`` entry whose :func:`stride_footprint` fits
    the budget, floored at ``min(grid, DEFAULT_CHUNK)``.

    The floor matters: one copy of global memory is live under *every*
    schedule (scan included), so once ``per_block`` alone exceeds the
    budget, shrinking the wave below the default width stops saving
    real memory while multiplying the per-wave merge passes — the
    clamped-chunk fallback's failure mode.  Grid-stride keeps the wave
    useful and spends the budget where width actually helps."""
    from .backends.plan import DEFAULT_CHUNK
    budget = FOOTPRINT_BUDGET if budget is None else int(budget)
    floor = min(int(grid), DEFAULT_CHUNK)
    for width in RESIDENT_CANDIDATES:
        if width <= floor:
            break
        if width <= grid and stride_footprint(
                ck, shapes, n_resident=width, n_warps=n_warps,
                warp_exec=warp_exec) <= budget:
            return width
    return max(1, floor)


def schedule_verdict(ck: CompiledKernel, shapes: Dict[str, tuple], *,
                     grid: int, chunk: int, n_warps: int,
                     warp_exec: str = "serial", backend: str = "vmap",
                     budget: Optional[int] = None
                     ) -> Tuple[str, Optional[int]]:
    """Pick the launch schedule from the footprint model: ``('chunked',
    None)`` when the materialized chunk-table schedule fits the budget
    (or the grid is a single wave — there is no table to speak of),
    else ``('grid_stride', n_resident)`` with the wave width sized by
    :func:`resident_slots`.  Pure policy — the caller threads the
    verdict into ``ResolvedLaunch`` with provenance.

    ``backend='scan'`` keys on the block-id sequence alone: scan holds
    one copy of global memory under every schedule, so its only O(grid)
    materialized state is the ``arange(grid)`` it scans over — the
    grid-stride form replaces it with a counted ``fori_loop`` (width 1
    by construction)."""
    grid = int(grid)
    chunk = max(1, int(chunk))
    budget = FOOTPRINT_BUDGET if budget is None else int(budget)
    if backend == "scan":
        if bid_table_bytes(grid, 1) > budget:
            return "grid_stride", 1
        return "chunked", None
    if grid <= chunk:
        return "chunked", None
    fits = chunk_footprint(ck, shapes, chunk=chunk, n_warps=n_warps,
                           warp_exec=warp_exec, grid=grid) <= budget
    if fits:
        return "chunked", None
    return "grid_stride", resident_slots(ck, shapes, grid=grid,
                                         n_warps=n_warps,
                                         warp_exec=warp_exec, budget=budget)
