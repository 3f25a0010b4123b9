"""Public COX API.

    from repro.core import cox

    @cox.kernel
    def vec_add(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32),
                b: cox.Array(cox.f32), n: cox.i32):
        i = c.block_idx() * c.block_dim() + c.thread_idx()
        if i < n:
            out[i] = a[i] + b[i]

    out = vec_add.launch(grid=4, block=256, args=(out, a, b, n))["out"]
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Sequence

from jax.profiler import annotate_function

from . import autotune  # noqa: F401  (cox.autotune — measured knob tuning)
from . import autotune as _autotune  # distinct alias: make_request's
#                                      autotune= knob shadows the module
from . import costmodel  # noqa: F401  (cox.costmodel — op/mem estimates)
from . import errors  # noqa: F401  (cox.errors — typed error hierarchy)
from . import faults  # noqa: F401  (cox.faults — fault injection)
from . import flat as _flat
from . import kernel_ir as K
from . import placement  # noqa: F401  (cox.placement — device policies)
from . import runtime as _runtime
from . import streams as _streams
from .backends.plan import bind_kernel_args, check_donate_supported
from .errors import (CoxCompileError, CoxDependencyError,  # noqa: F401
                     CoxDeviceError, CoxError, CoxLaunchError,
                     CoxTimeoutError)
from .execute import CompiledKernel, compile_kernel
from .frontend import Array, parse_kernel  # noqa: F401  (cox.Array re-export)
from .graphs import (Graph, GraphExec,  # noqa: F401  (cox.Graph capture API)
                     GraphNodeHandle)
from .streams import (Event, default_stream, synchronize,  # noqa: F401
                      LaunchHandle, Stream, get_dispatcher)
from .streams import (device_reset, get_last_error,  # noqa: F401
                      peek_at_last_error)  # cudaGetLastError analogues
from .streams import _mesh_key  # noqa: F401  (compat re-export for tests)
from .placement import (AffinityPlacement,  # noqa: F401  (placement API)
                        HealthAwarePlacement, PlacementPolicy,
                        RoundRobinPlacement)
from .types import (CoxUnsupported, DType, Dim3, WARP_SIZE,  # noqa: F401
                    GraphRef, as_dim3)  # Dim3 re-exported: launch geometry

# dtype shorthands (annotation + c.shared dtype arguments)
f32 = DType.f32
f16 = DType.f16
bf16 = DType.bf16
i32 = DType.i32
u32 = DType.u32
b1 = DType.b1


@dataclasses.dataclass
class KernelFn:
    """A parsed CUDA-style kernel plus the pass-pipeline cache
    (``compiled``).  The launch-level cache of staged executables lives
    behind the stream dispatcher (``repro.core.streams``) and is shared
    across every stream — ``_launch_cache`` below is a read view of this
    kernel's entries, keyed exactly as before."""
    ir: K.Kernel
    _cache: Dict[Any, CompiledKernel] = dataclasses.field(default_factory=dict)

    @property
    def _launch_cache(self) -> Dict[Any, Any]:
        """This kernel's staged ``(plan, exe)`` entries in the
        dispatcher's shared cache (backward-compatible key shape:
        compile token first, phase count second)."""
        return get_dispatcher().cache_view(self._cache.values())

    @property
    def name(self) -> str:
        return self.ir.name

    def _compile_key(self, *, collapse: str, warp_size: int,
                     block: Optional[int]) -> tuple:
        """The pass-pipeline cache key — also the stable per-compile
        token in launch-cache keys (``id(ck)`` would be recycled by the
        allocator if a compiled kernel were ever dropped)."""
        choice = _flat.choose_collapse(self.ir, collapse)
        if choice == "flat":
            if block is None:
                raise ValueError("flat collapsing specializes on block size; "
                                 "pass block=")
            ws = block
        else:
            ws = warp_size
        return (choice, ws)

    def _compiled_for(self, key: tuple) -> CompiledKernel:
        ck = self._cache.get(key)
        if ck is None:
            ck = self._cache[key] = compile_kernel(self.ir, warp_size=key[1])
        return ck

    def compiled(self, *, collapse: str = "hybrid",
                 warp_size: int = WARP_SIZE,
                 block=None) -> CompiledKernel:
        """Run the pass pipeline.  collapse='flat' uses warp_size=block
        (single block-wide loop; requires `block`, whose dim3 total is
        used); 'hier' is the paper's hierarchical collapsing; 'hybrid'
        picks automatically."""
        if block is not None:
            block = as_dim3(block, "block").total
        return self._compiled_for(self._compile_key(
            collapse=collapse, warp_size=warp_size, block=block))

    @functools.partial(annotate_function, name="cox.resolve")
    def make_request(self, *, grid, block, args: Sequence[Any],
                     collapse: str = "hybrid", mode: str = "auto",
                     simd: bool = True, warp_size: int = WARP_SIZE,
                     mesh=None, axis: str = "data", backend: str = "auto",
                     chunk=None, warp_exec: str = "auto",
                     schedule: str = "auto",
                     n_resident: Optional[int] = None,
                     donate: bool = False, device: Any = None,
                     autotune: Optional[bool] = None
                     ) -> _streams.LaunchRequest:
        """Resolve the launch knobs and bind the arguments into a
        :class:`~repro.core.streams.LaunchRequest` — the unit the stream
        dispatcher consumes.  Compilation (the pass pipeline) and knob
        resolution happen here, eagerly, so bad launches fail at the
        call site; staging and dispatch happen later, behind the
        dispatcher.

        ``chunk=`` accepts an int (explicit, never overridden by the
        autotuner), ``None`` (the heuristic default) or ``'auto'``
        (tune the chunk by measurement).  ``autotune=True`` measures
        every knob left on auto — candidate cells pruned by the cost
        model, winners persisted in the on-disk cache
        (``repro.core.autotune``) — and ``autotune=None`` defers to the
        ``COX_AUTOTUNE`` env (plus ``chunk='auto'``, which always
        tunes).

        ``schedule=`` picks the launch schedule: ``'auto'`` (default)
        lets the footprint verdict choose between the chunk-table walk
        and the grid-stride loop once argument shapes are bound;
        ``'chunked'``/``'grid_stride'`` force either (explicit, never
        overridden by the autotuner), and ``n_resident=`` sizes the
        grid-stride wave (implies ``schedule='grid_stride'``).

        ``device=`` pins the launch to one XLA device (multi-device
        placement; mutually exclusive with ``mesh``, which spans its
        own device set) — left ``None``, the dispatcher's placement
        policy assigns the stream a device when its pool is
        multi-device."""
        if device is not None and mesh is not None:
            raise CoxUnsupported(
                f"kernel '{self.name}': device= and mesh= are mutually "
                f"exclusive — a sharded launch spans the mesh's own "
                f"devices; placement applies to single-device launches")
        block3 = as_dim3(block, "block")
        token = self._compile_key(collapse=collapse, warp_size=warp_size,
                                  block=block3.total)
        ck = self._compiled_for(token)
        rl = _runtime.resolve_launch(ck, grid=grid, block=block3, mode=mode,
                                     backend=backend, warp_exec=warp_exec,
                                     chunk=chunk, schedule=schedule,
                                     n_resident=n_resident, mesh=mesh)
        globals_, shapes, scalars = bind_kernel_args(ck, args)
        rl = _runtime.resolve_schedule(ck, rl, shapes)
        tune = (autotune if autotune is not None
                else (chunk == "auto" or _autotune.enabled()))
        if tune:
            rl = _autotune.tune(ck, token, rl, shapes=shapes,
                                scalars=scalars, globals_=globals_,
                                simd=simd, mesh=mesh, req_backend=backend,
                                req_warp_exec=warp_exec)
        if donate:
            # fail at the call site, not at deferred staging
            check_donate_supported(rl.backend, ck.kernel.name)
        return _streams.LaunchRequest(
            ck=ck, token=token, rl=rl, simd=simd, chunk=rl.chunk, mesh=mesh,
            axis=axis, donate=donate, globals_=globals_, shapes=shapes,
            scalars=scalars, device=device,
            # pre-resolution knobs: the degradation ladder may only fall
            # back along rungs the caller left on 'auto'
            req_backend=backend, req_warp_exec=warp_exec)

    def launch(self, *, grid, block, args: Sequence[Any],
               collapse: str = "hybrid", mode: str = "auto",
               simd: bool = True, warp_size: int = WARP_SIZE,
               mesh=None, axis: str = "data", backend: str = "auto",
               chunk=None, warp_exec: str = "auto",
               schedule: str = "auto", n_resident: Optional[int] = None,
               donate: bool = False,
               device: Any = None, autotune: Optional[bool] = None,
               stream: Optional[Stream] = None) -> Dict[str, Any]:
        """Launch with backend dispatch (see ``repro.core.backends``):
        enqueue on the (default) stream and dispatch — the async CUDA
        ``kernel<<<...>>>()`` itself, with the outputs handed back as
        XLA futures.

        ``grid``/``block`` accept CUDA dim3 geometry — ``int | (x, y[,
        z])`` — normalized to one canonical form (missing axes are 1),
        so ``grid=4`` and ``grid=(4, 1, 1)`` share a cache entry.
        backend='auto'|'scan'|'vmap'|'sharded'; ``chunk`` bounds how many
        blocks the vmap-based backends run simultaneously;
        ``warp_exec='auto'|'serial'|'batched'`` picks between the serial
        inter-warp loop and the batched (n_warps, W) lane plane;
        ``mode='auto'|'normal'|'jit'`` picks loop-carried vs unrolled
        inter-warp iteration (all three resolved by ``repro.core.flat``
        heuristics when 'auto', keyed on the normalized totals).

        ``donate=True`` donates the flat global buffers to the staged
        executable (buffer reuse instead of copies — the bound arrays
        are consumed); ``device=`` pins the launch to one XLA device
        (see :meth:`make_request`); ``stream=`` enqueues on a
        non-default :class:`cox.Stream` instead.

        The returned arrays are XLA futures, exactly as before the
        stream refactor — the launch is *dispatched* (host errors
        surface here) but the host does not block on device completion,
        so back-to-back launches keep pipelining; use
        :meth:`launch_async` / ``stream.launch`` to also defer
        dispatch."""
        return self.launch_async(
            grid=grid, block=block, args=args, collapse=collapse,
            mode=mode, simd=simd, warp_size=warp_size, mesh=mesh,
            axis=axis, backend=backend, chunk=chunk, warp_exec=warp_exec,
            schedule=schedule, n_resident=n_resident,
            donate=donate, device=device, autotune=autotune,
            stream=stream).arrays()

    def launch_async(self, *, stream: Optional[Stream] = None,
                     **knobs) -> LaunchHandle:
        """Enqueue on ``stream`` (default: the legacy-sync default
        stream) and return a :class:`LaunchHandle` future immediately —
        the async CUDA launch.  Takes the same keyword knobs as
        :meth:`launch`."""
        st = stream if stream is not None else get_dispatcher().default
        return st.launch(self, **knobs)

    def uses_warp_features(self) -> bool:
        return K.uses_warp_features(self.ir)


def kernel(fn=None, *, name: Optional[str] = None):
    """Decorator: parse a restricted-Python CUDA-style kernel."""
    def wrap(f):
        return KernelFn(parse_kernel(f, name=name))
    if fn is None:
        return wrap
    return wrap(fn)
