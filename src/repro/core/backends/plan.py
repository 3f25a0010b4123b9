"""LaunchPlan — everything a grid-execution backend needs, precomputed.

A plan captures the launch geometry (grid, block, warps), the execution
flavor (mode, simd), the chunking of block ids into re-dispatchable work
units, and the arg-binding convention (arrays flattened to CUDA-pointer
1-D views, scalars split off as block-uniform parameters).  Backends are
pure functions of a plan; none of them re-derive this state.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .. import kernel_ir as K
from ..execute import CompiledKernel, make_block_fn, walk_instrs, wave_logged
from ..types import (COOP_MAX_RESIDENT_BLOCKS, ArraySpec, CoxUnsupported,
                     Dim3, DType, GraphRef, as_dim3, check_launch_geometry)

DEFAULT_CHUNK = 8  # blocks run simultaneously per vmap step


def check_donate_supported(backend: str, kernel_name: str) -> None:
    """Donation aliases each global's single device buffer; the sharded
    backend has none to alias (globals enter shard_map replicated and
    leave through a cross-device psum merge).  One shared check so the
    eager rejection in ``api.KernelFn.make_request`` and the build-time
    rejection in ``backends.sharded`` can never drift apart."""
    if backend == "sharded":
        raise CoxUnsupported(
            f"kernel '{kernel_name}': donate=True is unsupported on the "
            f"sharded backend — replicated cross-device globals have no "
            f"single buffer to reuse; drop donate= or launch without a "
            f"mesh")


def bind_kernel_args(ck: CompiledKernel, args: Sequence[Any]
                     ) -> Tuple[Dict[str, Any], Dict[str, tuple],
                                Dict[str, Any]]:
    """Split positional args into (globals dict, shapes, scalar
    uniforms); arrays are flattened (CUDA pointer semantics).  A module
    function (not only a plan method) because the stream dispatch layer
    binds args at *enqueue* time, before any plan is staged.

    A :class:`~repro.core.types.GraphRef` (a captured launch's output
    placeholder, only meaningful during stream capture) binds
    symbolically: its shape is recorded and the value passes through
    untouched for the graph tracer to resolve — the dtype cast and
    flatten happen *inside* the staged graph program, exactly where the
    eager path does them outside it."""
    if len(args) != len(ck.kernel.params):
        raise TypeError(f"kernel {ck.kernel.name} takes "
                        f"{len(ck.kernel.params)} args, "
                        f"got {len(args)}")
    globals_: Dict[str, Any] = {}
    shapes: Dict[str, tuple] = {}
    scalars: Dict[str, Any] = {}
    for spec, val in zip(ck.kernel.params, args):
        if isinstance(spec, ArraySpec):
            if isinstance(val, GraphRef):
                shapes[spec.name] = tuple(val.shape)
                globals_[spec.name] = val
                continue
            arr = jnp.asarray(val, spec.dtype.jnp)
            shapes[spec.name] = arr.shape
            globals_[spec.name] = arr.reshape(-1)
        else:
            if isinstance(val, GraphRef):
                raise CoxUnsupported(
                    f"kernel {ck.kernel.name}: scalar parameter "
                    f"'{spec.name}' bound to a captured array output "
                    f"({val!r}) — graph data edges carry global-memory "
                    f"arrays, not by-value uniforms")
            scalars[spec.name] = jnp.asarray(val, spec.dtype.jnp)
    return globals_, shapes, scalars


@dataclasses.dataclass(frozen=True)
class LaunchPlan:
    """Immutable description of one ``kernel<<<grid, block>>>`` launch.

    ``grid``/``block`` are the *linear totals* — everything downstream
    (chunk tables, warp counts, merge machinery, heuristics) keys on
    them, so ``grid=4`` and ``grid=(4, 1, 1)`` build identical plans.
    ``grid_dim``/``block_dim`` carry the canonical dim3 geometry for
    the executor's per-axis intrinsics only.
    """
    ck: CompiledKernel
    grid: int            # total blocks (grid_dim.total)
    block: int           # total threads per block (block_dim.total)
    n_warps: int
    mode: str            # 'normal' | 'jit' (resolved, never 'auto')
    simd: bool
    chunk: int           # blocks per vmap slice (1 = fully serial merge)
    has_atomics: bool
    captures_atomic_old: bool  # AtomicRMW with dst — serial-only
    warp_exec: str = "serial"  # 'serial' | 'batched' (resolved, never 'auto')
    grid_dim: Optional[Dim3] = None   # canonical dim3 (set by build)
    block_dim: Optional[Dim3] = None
    n_phases: int = 1          # >1 → cooperative (grid_sync) launch
    schedule: str = "chunked"  # 'chunked' | 'grid_stride'
    n_resident: Optional[int] = None  # grid-stride wave width (else None)

    @classmethod
    def build(cls, ck: CompiledKernel, *, grid, block,
              mode: str = "normal", simd: bool = True,
              chunk: Optional[int] = None,
              warp_exec: str = "serial", schedule: str = "chunked",
              n_resident: Optional[int] = None) -> "LaunchPlan":
        grid3 = as_dim3(grid, "grid")
        block3 = as_dim3(block, "block")
        check_launch_geometry(grid3, block3)
        grid, block = grid3.total, block3.total
        if mode not in ("normal", "jit"):
            raise ValueError(f"mode must be resolved to 'normal' or 'jit' "
                             f"before plan build, got {mode!r} "
                             f"(flat.choose_mode resolves 'auto')")
        if warp_exec not in ("serial", "batched"):
            raise ValueError(f"warp_exec must be resolved to 'serial' or "
                             f"'batched' before plan build, got "
                             f"{warp_exec!r} (flat.choose_warp_exec "
                             f"resolves 'auto')")
        if schedule not in ("chunked", "grid_stride"):
            raise ValueError(f"schedule must be resolved to 'chunked' or "
                             f"'grid_stride' before plan build, got "
                             f"{schedule!r} (runtime.resolve_schedule "
                             f"resolves 'auto')")
        n_warps = -(-block // ck.warp_size)
        n_phases = ck.n_phases
        if schedule == "grid_stride":
            # the wave width doubles as the merge chunk: wave i covers
            # the contiguous block ids [i·R, (i+1)·R), i.e. exactly row
            # i of the chunk table a chunked plan with chunk=R would
            # materialize — which is why the two schedules are bitwise
            # equal by construction
            n_resident = (min(grid, DEFAULT_CHUNK) if n_resident is None
                          else max(1, min(int(n_resident), grid)))
            if n_phases > 1 and n_resident > COOP_MAX_RESIDENT_BLOCKS:
                raise CoxUnsupported(
                    f"cooperative launch of '{ck.kernel.name}': "
                    f"n_resident={n_resident} exceeds the resident "
                    f"capacity ({COOP_MAX_RESIDENT_BLOCKS}) — the "
                    f"grid-stride wave is the resident set, exactly "
                    f"cudaLaunchCooperativeKernel's occupancy rule")
            chunk = n_resident
        elif n_phases > 1:
            # CUDA's cooperative-launch constraint: every block resident
            # per phase.  The chunked schedule may not split the grid —
            # each block's carried state (locals + shared memory) must
            # stay live across the whole phase sequence.  Grids beyond
            # the capacity take the grid-stride schedule above, which
            # pages carried state through a capacity-sized wave instead.
            if grid > COOP_MAX_RESIDENT_BLOCKS:
                raise CoxUnsupported(
                    f"cooperative launch of '{ck.kernel.name}': "
                    f"grid={grid} blocks exceeds the resident capacity "
                    f"({COOP_MAX_RESIDENT_BLOCKS}) — every block must be "
                    f"resident per phase for a grid barrier, exactly "
                    f"cudaLaunchCooperativeKernel's occupancy rule "
                    f"(schedule='grid_stride' pages blocks through a "
                    f"capacity-sized resident wave instead)")
            if chunk is not None and int(chunk) < grid:
                raise CoxUnsupported(
                    f"cooperative launch of '{ck.kernel.name}': "
                    f"chunk={chunk} would split the grid into waves, but "
                    f"a grid barrier needs every block resident per "
                    f"phase — drop chunk= (the plan schedules all "
                    f"{grid} blocks as one wave)")
            chunk = grid
        else:
            n_resident = None  # chunked plans carry no wave width
        if chunk is None:
            chunk = min(grid, DEFAULT_CHUNK)
        chunk = max(1, min(int(chunk), grid))
        atomics = [s for s in walk_instrs(ck) if isinstance(s, K.AtomicRMW)]
        plan = cls(ck, grid, block, n_warps, mode, simd, chunk,
                   has_atomics=bool(atomics),
                   captures_atomic_old=any(s.dst for s in atomics),
                   warp_exec=warp_exec, grid_dim=grid3, block_dim=block3,
                   n_phases=n_phases, schedule=schedule,
                   n_resident=n_resident)
        plan.check_warp_batchable()
        return plan

    def check_warp_batchable(self):
        """Reject launches whose semantics the per-warp copy merge of
        warp-batched execution cannot reproduce — the same ticket-
        pattern argument as :meth:`check_mergeable`, one level down:
        captured atomic old values are unique only under a serial warp
        order, and per-warp delta buffers would hand every warp of a
        block the same ticket."""
        if self.warp_exec == "batched" and self.captures_atomic_old:
            raise CoxUnsupported(
                f"kernel '{self.ck.kernel.name}' captures atomic old "
                f"values (atomic_add_old): old values are only unique "
                f"under a serial warp order, which warp-batched "
                f"execution's per-warp delta merge cannot reproduce — "
                f"use warp_exec='serial' (the 'auto' heuristic picks it)")

    def check_mergeable(self, backend: str):
        """Reject launches whose semantics the write-mask / atomic-delta
        merge cannot reproduce.  Captured atomic old values (the
        atomicAdd ticket pattern) are unique only under serial
        execution — per-copy delta buffers would hand every block the
        same ticket — so such kernels are scan-only."""
        if self.captures_atomic_old:
            raise CoxUnsupported(
                f"kernel '{self.ck.kernel.name}' captures atomic old "
                f"values (atomic_add_old): old values are only unique "
                f"under serial execution, which the {backend!r} "
                f"backend's delta merge cannot reproduce — launch "
                f"without a mesh and use backend='scan' (the "
                f"single-device 'auto' heuristic picks it)")

    @property
    def wave_logged(self) -> Tuple[str, ...]:
        """Globals a vmap wave of this plan carries as (index, value)
        store logs instead of per-block copies (``execute.wave_logged``;
        empty for cooperative launches, whose phase waves keep the copy
        path)."""
        return wave_logged(self.ck, n_warps=self.n_warps,
                           warp_exec=self.warp_exec)

    # ---------------- phase staging (cooperative grid sync) ----------------

    def persist_spec(self) -> Optional[Tuple[Tuple[str, ...],
                                             Tuple[str, ...]]]:
        """The per-block state a phase executable must thread through:
        ``(carried local names, shared-memory names)`` — or ``None`` for
        single-phase launches (no state, the pre-phase program)."""
        if self.n_phases == 1:
            return None
        return (tuple(self.ck.carried),
                tuple(s.name for s in self.ck.kernel.shared))

    def block_fns(self, *, track_writes: bool):
        """One compiled block function per phase (a single-entry list
        for ordinary kernels), all built with identical launch knobs."""
        persist = self.persist_spec()
        return [make_block_fn(sub, n_warps=self.n_warps, mode=self.mode,
                              simd=self.simd, track_writes=track_writes,
                              warp_exec=self.warp_exec,
                              block_dim=self.block_dim,
                              grid_dim=self.grid_dim, persist=persist)
                for sub in self.ck.phase_list()]

    def init_persist(self, n_blocks: Optional[int] = None):
        """Phase-0 per-block state, stacked over ``n_blocks`` (default:
        the whole grid): zeroed ``(n_blocks, n_warps, W)`` planes for
        carried locals and zeroed flat shared buffers — the same initial
        values a single-phase launch starts from."""
        nb = self.grid if n_blocks is None else int(n_blocks)
        W = self.ck.warp_size
        bv = {v: jnp.zeros((nb, self.n_warps, W),
                           self.ck.var_types.get(v, DType.f32).jnp)
              for v in self.ck.carried}
        sh = {s.name: jnp.zeros((nb, int(np.prod(s.shape))), s.dtype.jnp)
              for s in self.ck.kernel.shared}
        return {"bv": bv, "sh": sh}

    # ---------------- arg binding ----------------

    def bind_args(self, args: Sequence[Any]
                  ) -> Tuple[Dict[str, Any], Dict[str, tuple], Dict[str, Any]]:
        """Split positional args into (globals dict, shapes, scalar
        uniforms); arrays are flattened (CUDA pointer semantics)."""
        return bind_kernel_args(self.ck, args)

    def uniforms(self, bid, scalars: Dict[str, Any]) -> Dict[str, Any]:
        """The block-uniform environment for one block (or a batch of
        blocks when ``bid`` carries a leading chunk axis)."""
        u = {"bid": bid, "bdim": jnp.int32(self.block),
             "gdim": jnp.int32(self.grid)}
        u.update(scalars)
        return u

    # ---------------- grid-stride waves ----------------

    def n_stride_waves(self, total: Optional[int] = None) -> int:
        """How many resident waves a grid-stride launch runs:
        ``ceil(total / n_resident)`` (default: the whole grid; sharded
        passes its per-device block count)."""
        n = self.grid if total is None else int(total)
        return max(1, -(-n // self.n_resident))

    def stride_bids(self, wave, *, base=0, limit: Optional[int] = None):
        """In-graph block ids of one grid-stride wave: the contiguous
        slice ``base + wave·R … base + (wave+1)·R`` of width
        ``R = n_resident``, entries at/past ``limit`` (default: the
        grid) masked to -1 — exactly row ``wave`` of the chunk table
        the chunked schedule would materialize, except computed inside
        the staged program so no O(grid) host array ever exists.
        ``wave``/``base`` may be traced (``fori_loop`` index,
        ``axis_index`` device offset)."""
        R = self.n_resident
        limit = self.grid if limit is None else limit
        start = (jnp.asarray(base, jnp.int32)
                 + jnp.asarray(wave, jnp.int32) * jnp.int32(R))
        bids = start + jnp.arange(R, dtype=jnp.int32)
        return jnp.where(bids < jnp.int32(limit), bids, jnp.int32(-1))

    # ---------------- chunking ----------------

    def chunked_bids(self) -> np.ndarray:
        """The whole grid's block ids as a (n_chunks, chunk) table,
        -1-padded (the sharded backend instead reshapes its slice of
        :func:`device_bid_table`)."""
        n = self.grid
        n_chunks = -(-n // self.chunk)
        bids = np.full((n_chunks * self.chunk,), -1, np.int32)
        bids[:n] = np.arange(n, dtype=np.int32)
        return bids.reshape(n_chunks, self.chunk)

    def device_bid_table(self, ndev: int) -> np.ndarray:
        """Round-robin-contiguous block ids per device, shaped
        (ndev, per_padded) with per_padded a multiple of ``chunk`` and
        -1 marking idle-pad slots."""
        per = -(-self.grid // ndev)
        per_padded = -(-per // self.chunk) * self.chunk
        table = np.full((ndev, per_padded), -1, np.int32)
        flat = np.arange(self.grid, dtype=np.int32)
        for d in range(ndev):
            mine = flat[d * per:(d + 1) * per]
            table[d, :len(mine)] = mine
        return table
