"""CUDA streams & events: the async launch-dispatch layer.

CUDA programs overlap independent kernels by issuing them on *streams* —
in-order launch queues whose cross-stream ordering is constrained only
by *events* (and by the legacy default stream, which synchronizes with
everything).  COX's runtime (paper §4) stops at synchronous single-queue
launches; this module refactors every launch into an explicit
request/dispatch architecture and builds streams on top:

* :class:`LaunchRequest` — resolved knobs (:class:`~repro.core.runtime.
  ResolvedLaunch`) plus bound args, the unit the dispatcher consumes.
  ``api.KernelFn.launch`` is now "build a request, enqueue it on the
  default stream, dispatch" — the returned arrays stay XLA futures
  exactly as before the refactor (no host block), one launch path.
* :class:`Stream` — an in-order launch queue.  ``stream.launch(...)``
  returns a :class:`LaunchHandle` future immediately; ``.result()``
  materializes the outputs.
* :class:`Event` — ``record()`` captures a point in a stream's program
  order; ``wait(stream)`` makes another stream's *subsequent* launches
  depend on it; ``synchronize()`` blocks the host; ``elapsed(end)``
  reports wall-clock milliseconds between two recorded events.
* :class:`Dispatcher` — the host-side scheduler.  Every flush
  **topologically orders** the pending requests by stream program order
  plus event edges and dispatches each staged executable through XLA's
  async dispatch — no ``block_until_ready`` inside the graph, so the
  host issues launch *B* while *A* is still executing (stream launches
  flush eagerly, like a CUDA launch; handles defer only the *wait*).
  The launch-level executable cache lives here (not on the kernel), so
  **all streams share staged executables**: identical geometry launched
  from two streams stages exactly once.

What maps to what (see README "Streams & events" for the full table):
in-stream order and event edges become host *dispatch order*; overlap
comes from XLA's async dispatch (a dispatched executable runs while the
host binds and dispatches the next request).  A single XLA device
executes one computation at a time, so two streams overlap host work
with device work — the CUDA H2D/compute-overlap story, not two
simultaneous device queues.  Buffer donation (``donate=True``) lets an
in-order stream re-launching over the same globals reuse their buffers
instead of copying (``jax.jit(..., donate_argnums=...)``).

**Error model** (README "Error model & fault tolerance"): failures are
typed (``repro.core.errors``) and follow CUDA's contract — a failed
launch surfaces its error at *its own* sync, its DAG descendants
(stream program order + event edges + ``handle.outputs`` data edges,
the same edge set graph capture records) fail fast with
:class:`~repro.core.errors.CoxDependencyError` instead of dispatching
on stale inputs, the failing stream is poisoned until the error is
surfaced (or ``stream.reset()``), sticky errors
(:class:`~repro.core.errors.CoxDeviceError`) poison every enqueue
until :func:`device_reset`, and ``get_last_error()`` /
``peek_at_last_error()`` are the ``cudaGetLastError`` /
``cudaPeekAtLastError`` analogues.  Transient staging failures get a
bounded retry-with-backoff; non-transient failures on auto-chosen
knobs walk a graceful-degradation ladder (batched→serial warp
execution, vmap→scan backend — each rung re-staged, bitwise-correct by
the backend-equivalence contract, and logged as a structured
degradation event).  A per-launch deadline (``launch_deadline_s``,
enforced through :class:`~repro.ft.watchdog.StepWatchdog`) turns a
hung launch into :class:`~repro.core.errors.CoxTimeoutError` at sync.

**Multi-device placement & priorities** (README "Multi-device
placement"): when the dispatcher's device pool holds more than one
device, each non-default stream is *placed* on one
(``repro.core.placement`` policies: round-robin, affinity-by-resident-
buffers, health-aware) so independent streams execute concurrently on
different XLA devices — true CUDA multi-queue concurrency, not just
host/device pipelining.  Placement happens at dispatch: inputs are
``jax.device_put`` to the stream's device (a no-op for already-resident
buffers, an explicit async transfer node when a cross-stream data/event
edge crosses devices), staged executables are per-device (the stage key
carries the target device), and sticky :class:`~repro.core.errors.
CoxDeviceError` is scoped to the failing device — placement routes new
work around a poisoned device; ``device_reset(device=...)`` revives
one.  ``Stream(priority=...)`` biases the Kahn ready-set: among
simultaneously-ready requests, lower priority numbers dispatch first
(CUDA's convention — ``cudaStreamCreateWithPriority``'s
``greatestPriority`` is the most negative).  The default stream, mesh
(sharded) launches, and single-device pools keep the exact legacy
dispatch path.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
import itertools
import threading
import time
import weakref
from collections import OrderedDict, deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation, annotate_function

from . import costmodel as _costmodel
from . import errors as _errors
from . import faults as _faults
from . import placement as _placement
from . import runtime as _runtime
from ..ft.watchdog import StepWatchdog
from .errors import (CoxDependencyError, CoxTimeoutError)
from .types import CoxUnsupported, GraphRef

# staged-executable LRU bound: far above any real working set (every
# distinct (kernel, geometry, knobs) combination is one entry); evicted
# entries are simply re-staged on next use
STAGE_CACHE_SIZE = 1024

# dispatch_log retention: the log is introspection/test surface, not an
# audit trail — a long-lived serving process must not grow per-launch
# state, so the log is a bounded ``deque(maxlen=...)`` holding only the
# most recent dispatches (older entries fall off structurally)
DISPATCH_LOG_MAX = 8192

# errored-request retention: a caller that drops a failed handle without
# syncing must not leak its request forever — errored entries move to a
# bounded OrderedDict (newest kept, oldest evicted) surfaced via
# ``get_last_error()`` / ``Dispatcher.error_log``
ERROR_LOG_MAX = 256

# structured degradation events (ladder fallbacks) — bounded the same way
DEGRADATION_LOG_MAX = 1024

# transient-failure retry knobs: attempts beyond the first, and the
# exponential-backoff base (sleep = base * 2**attempt)
RETRY_LIMIT = 3
RETRY_BACKOFF_S = 0.005

# deadline-wait poll period: the watchdog timer marks the deadline; the
# waiter polls readiness at this granularity (host-side, no device cost)
DEADLINE_POLL_S = 0.001

# per-stage-key telemetry retention (launch counts, dispatch time, cost
# estimates) — bounded like every other long-lived dispatcher structure
TELEMETRY_MAX = 512


def _is_deleted(x) -> bool:
    """True for a jax.Array whose buffer was donated away (a later
    ``donate=True`` launch consumed it).  Deleted outputs are
    unwaitable — and vacuously complete: deletion happens when a
    downstream consumer was dispatched, and that consumer's own data
    dependency covers the producer."""
    try:
        return bool(x.is_deleted())
    except AttributeError:
        return False


def _outputs_ready(outputs: Dict[str, Any]) -> bool:
    """Non-blocking readiness over an output dict, donation-aware."""
    try:
        return all(_is_deleted(o) or o.is_ready() for o in outputs.values())
    except AttributeError:      # jax without Array.is_ready
        return True


def _block_outputs(outputs: Dict[str, Any]) -> None:
    """``block_until_ready`` over an output dict, skipping buffers that
    a donating relaunch already consumed."""
    for o in outputs.values():
        if not _is_deleted(o):
            jax.block_until_ready(o)


def _dev_id(dev) -> Optional[int]:
    """A stable hashable stand-in for a device in cache keys and the
    per-device sticky map (``None`` = unplaced / legacy path)."""
    return None if dev is None else dev.id


def _to_device(val, dev):
    """``jax.device_put`` to ``dev`` unless the value is already
    resident there — the identity-preserving transfer node.  Returning
    the original array for already-resident buffers is load-bearing:
    a donating relaunch over the same globals must see the *same*
    buffers to alias them instead of copying."""
    try:
        if val.devices() == {dev}:
            return val
    except (AttributeError, TypeError):
        pass
    return jax.device_put(val, dev)


def _mesh_key(mesh) -> Any:
    """A hashable stand-in for the mesh in staging-cache keys, built
    from stable content (axis names/sizes + device ids).  Object
    identity is NOT a safe key: ``id()`` of a garbage-collected mesh can
    be recycled by a new mesh, which would then hit a stale executable
    closed over the old devices."""
    if mesh is None:
        return None
    try:
        return ("mesh", tuple(mesh.shape.items()),
                tuple(d.id for d in mesh.devices.flat))
    except (AttributeError, TypeError):
        pass
    try:
        hash(mesh)
        return mesh
    except TypeError:
        return ("unhashable-mesh", id(mesh), repr(mesh))


@dataclasses.dataclass
class LaunchRequest:
    """One ``kernel<<<grid, block, stream>>>(*args)`` as data: the
    resolved launch knobs plus the bound arguments.  This is the unit
    the :class:`Dispatcher` consumes — ``KernelFn.make_request`` builds
    one, a :class:`Stream` enqueues it, the dispatcher stages and
    dispatches it."""
    ck: Any                      # CompiledKernel
    token: tuple                 # pass-pipeline cache key (stable per ck)
    rl: Any                      # runtime.ResolvedLaunch
    simd: bool
    chunk: Optional[int]
    mesh: Any
    axis: str
    donate: bool
    globals_: Optional[Dict[str, Any]]   # dropped after dispatch
    shapes: Dict[str, tuple]
    scalars: Optional[Dict[str, Any]]
    # the *requested* (pre-resolution) knobs — the degradation ladder
    # only falls back along rungs the user left on 'auto'; explicitly
    # requested knobs are honored and fail as requested
    req_backend: str = "auto"
    req_warp_exec: str = "auto"
    # target device: an explicit ``device=`` knob pins it here; else the
    # dispatcher's placement policy fills it at dispatch (stays None on
    # the single-device / default-stream / mesh legacy paths)
    device: Any = None
    # dispatch priority, inherited from the stream at enqueue — lower
    # numbers dispatch first among simultaneously-ready requests
    priority: int = 0
    # dispatcher bookkeeping (set at enqueue / dispatch)
    seq: int = -1
    stream: Optional["Stream"] = None
    deps: Tuple[int, ...] = ()
    data_deps: Tuple[int, ...] = ()            # handle.outputs edges
    outputs: Optional[Dict[str, Any]] = None   # raw flat arrays (futures)
    dispatched: bool = False
    error: Optional[BaseException] = None
    surfaced: bool = False       # error raised to (or consumed by) the caller
    injected_hang: bool = False  # timeout-site fault: outputs never ready
    out_ids: List[int] = dataclasses.field(default_factory=list)

    def fn_key(self) -> tuple:
        """Everything that determines the request's *traced program* —
        the raw launcher's identity, shared between eager staging and
        graph staging.  Donation is a jit-wrapper property (buffer
        aliasing), not a trace property, so it lives only in
        :meth:`stage_key`."""
        rl = self.rl
        return (self.token, self.ck.n_phases, rl.backend, rl.mode,
                rl.grid.astuple(), rl.block.astuple(), rl.n_warps,
                self.simd, self.chunk, rl.warp_exec, rl.schedule,
                rl.n_resident, _mesh_key(self.mesh), self.axis)

    def stage_key(self) -> tuple:
        """The staging-cache key *without* the kernel-identity element
        (the dispatcher prepends it).  Same layout as the old
        ``KernelFn._launch_cache`` key — the compile token first, the
        phase count second — with ``donate`` and the target device
        appended: a donating executable aliases its input buffers and
        must never be handed a launch that expects copies, and a placed
        executable runs on committed inputs so its compiled program is
        per-device."""
        return self.fn_key() + (self.donate, _dev_id(self.device))


class LaunchHandle:
    """Future for an enqueued launch.  ``.result()`` flushes the
    dispatcher, blocks until this launch's outputs are ready, and
    returns them reshaped — the synchronous endpoint.  ``.outputs`` is
    the async endpoint: it only guarantees the launch has been
    *dispatched* and hands back the raw flat arrays (still XLA futures),
    the currency for chaining dependent launches without a host sync."""

    __slots__ = ("_req", "_disp")

    def __init__(self, req: LaunchRequest, disp: "Dispatcher"):
        self._req = req
        self._disp = disp

    @property
    def stream(self) -> "Stream":
        return self._req.stream

    @property
    def request(self) -> LaunchRequest:
        return self._req

    def done(self) -> bool:
        """True once the launch has been dispatched and its outputs are
        ready (never blocks)."""
        req = self._req
        if req.error is not None:
            return True
        if not req.dispatched or req.injected_hang:
            return False
        return _outputs_ready(req.outputs)

    @property
    def outputs(self) -> Dict[str, Any]:
        """Raw flat output arrays (async: dispatched, not awaited)."""
        self._disp.dispatch_through(self._req)
        if self._req.error is not None:
            # surfacing the error reclaims the bookkeeping entry, same
            # as an explicit sync would — no leak on the launch() path
            self._disp.forget(self._req)
            raise self._req.error
        return self._req.outputs

    def _reshaped(self) -> Dict[str, Any]:
        req = self._req
        for k, v in req.outputs.items():
            if _is_deleted(v):
                raise CoxUnsupported(
                    f"launch output '{k}' was donated to a later "
                    f"donate=True launch and its buffer is gone — "
                    f"materialize the handle before donating its "
                    f"outputs, or keep the downstream handle instead")
        return {k: v.reshape(req.shapes[k]) for k, v in req.outputs.items()}

    def arrays(self) -> Dict[str, Any]:
        """Reshaped outputs *without* a host sync — still XLA futures,
        exactly what the pre-stream ``KernelFn.launch`` returned.  The
        launch (and everything it depends on) is dispatched first."""
        outs = self.outputs      # dispatch + surface this request's error
        del outs
        return self._reshaped()

    def result(self) -> Dict[str, Any]:
        """Materialize: flush, block on this launch, reshape outputs."""
        self._disp.sync_request(self._req)
        return self._reshaped()


class Stream:
    """An in-order launch queue (CUDA ``cudaStream_t``).

    Launches enqueued on one stream dispatch in program order; launches
    on different streams are unordered unless an :class:`Event` edge —
    or the legacy default stream — connects them.  The **default
    stream** has CUDA's legacy-sync semantics: a launch on it is ordered
    after the current tail of *every* stream, and every stream's next
    launch is ordered after the default stream's tail.

    While a stream is **capturing** into a :class:`~repro.core.graphs.
    Graph` (``begin_capture()``/``end_capture()``, CUDA's
    ``cudaStreamBeginCapture``), launches record graph nodes instead of
    dispatching, and host-blocking operations (``synchronize``, waiting
    on eager events) raise :class:`CoxUnsupported` — exactly the set of
    operations cudaStreamCapture invalidates a capture over."""

    _names = itertools.count()

    def __init__(self, name: Optional[str] = None,
                 dispatcher: Optional["Dispatcher"] = None, *,
                 priority: int = 0, device: Any = None,
                 _default: bool = False):
        self._disp = dispatcher if dispatcher is not None else get_dispatcher()
        self._default = _default
        self.name = name or ("default" if _default
                             else f"stream{next(self._names)}")
        # dispatch priority (CUDA cudaStreamCreateWithPriority): lower
        # numbers dispatch first among simultaneously-ready requests
        self.priority = int(priority)
        # placement: an explicit device pins every launch on this stream
        # to it; otherwise the dispatcher's placement policy assigns one
        # on first dispatch (multi-device pools only) and the stream
        # keeps it — device affinity — until it is poisoned
        self._device = device
        self._device_pinned = device is not None
        self._wait_deps: List[int] = []   # event edges for the next launch
        self._capture = None              # Graph while capturing, else None
        self._capture_deps: List[int] = []   # captured event edges (node idx)
        # first un-surfaced failure on this stream: while set, subsequent
        # launches on the stream fail fast with CoxDependencyError (they
        # are program-order descendants of the failed request).  Cleared
        # when the error is surfaced to the caller, or by reset().
        self._error: Optional[BaseException] = None

    def __repr__(self):
        return f"Stream({self.name!r})"

    @property
    def is_default(self) -> bool:
        return self._default

    @property
    def device(self) -> Any:
        """The device this stream's launches run on: its pin, the
        placement policy's assignment, or ``None`` (unplaced — the
        legacy single-device path)."""
        return self._device

    @property
    def dispatcher(self) -> "Dispatcher":
        return self._disp

    def launch(self, kern, *, grid, block, args, **knobs) -> LaunchHandle:
        """Enqueue ``kern<<<grid, block>>>(*args)`` on this stream and
        return a :class:`LaunchHandle` immediately.  ``kern`` is an
        ``api.KernelFn``; ``knobs`` are the usual launch knobs
        (``backend=``, ``warp_exec=``, ``donate=``, ...).

        Dispatch is **eager**, exactly like a CUDA launch: the request
        (and anything still pending) goes straight through the
        dispatcher's topological flush into XLA's async dispatch, so
        the kernel starts executing while the host issues the next
        launch — the handle only defers the *wait*, never the work.
        Enqueue order is always a legal linearization (an event edge
        requires its ``record`` to precede the ``wait``), so eager
        dispatch can never violate a dependency.

        While capturing, the request is recorded as a graph node instead
        of dispatching, and the returned handle's ``.outputs`` /
        ``.arrays()`` hand back :class:`~repro.core.types.GraphRef`
        placeholders for chaining captured launches."""
        req = kern.make_request(grid=grid, block=block, args=args, **knobs)
        if self._capture is not None:
            return self._capture.add_request(req, stream=self)
        handle = self._disp.enqueue(req, self)
        self._disp.flush()
        return handle

    # ---------------- stream capture (CUDA graphs) ----------------

    def begin_capture(self, graph=None):
        """Start capturing this stream's schedule into ``graph`` (a new
        :class:`~repro.core.graphs.Graph` when ``None``) — CUDA
        ``cudaStreamBeginCapture``.  Returns the graph."""
        from . import graphs as _graphs      # late: graphs imports streams
        if self._capture is not None:
            raise CoxUnsupported(
                f"{self!r} is already capturing into "
                f"{self._capture!r} — end_capture() first")
        g = graph if graph is not None else _graphs.Graph()
        g._attach_stream(self)
        self._capture = g
        self._capture_deps = []
        self._disp._capturing.add(self)
        return g

    def end_capture(self):
        """End capture and return the captured graph (CUDA
        ``cudaStreamEndCapture``)."""
        if self._capture is None:
            raise CoxUnsupported(
                f"{self!r}.end_capture() without begin_capture()")
        g = self._capture
        g._detach_stream(self)
        self._capture = None
        self._capture_deps = []
        self._disp._capturing.discard(self)
        return g

    @property
    def capturing(self) -> bool:
        return self._capture is not None

    def wait_event(self, event: "Event") -> None:
        """All *subsequent* launches on this stream wait for ``event``
        (CUDA ``cudaStreamWaitEvent``).  Waiting on an unrecorded event
        is a no-op, as on CUDA."""
        event.wait(self)

    def record_event(self, event: Optional["Event"] = None) -> "Event":
        """Record (a new) event at this stream's current tail."""
        ev = event if event is not None else Event()
        ev.record(self)
        return ev

    def synchronize(self) -> None:
        """Block the host until every launch enqueued on this stream has
        completed.  Idempotent — synchronizing an already-idle stream is
        a no-op.  Illegal during capture (a capture records a schedule,
        it runs nothing — there is nothing to wait for, and CUDA
        invalidates the capture)."""
        if self._capture is not None:
            raise CoxUnsupported(
                f"{self!r}.synchronize() during stream capture — a "
                f"capture records the schedule without running it; "
                f"end_capture() first (cudaStreamSynchronize in a "
                f"capture invalidates it)")
        self._disp.sync_stream(self)

    # ---------------- error state (stream poisoning) ----------------

    @property
    def error(self) -> Optional[BaseException]:
        """The stream's first un-surfaced failure, or ``None`` when the
        stream is healthy.  While set, every subsequent launch on this
        stream fails fast with :class:`~repro.core.errors.
        CoxDependencyError` — CUDA's stream-poisoning behavior.  The
        state clears when the error is surfaced (a sync/``result()``/
        ``outputs`` raises it, or ``get_last_error()`` consumes it) or
        via :meth:`reset`."""
        return self._error

    def reset(self) -> "Stream":
        """Clear the stream's non-sticky error state and pending event
        edges so new work can be enqueued — the recovery point for a
        caller that dropped a failed handle without surfacing it.  A
        sticky device error is *not* cleared (only
        :func:`device_reset` is the ``cudaDeviceReset`` analogue)."""
        if self._capture is not None:
            raise CoxUnsupported(
                f"{self!r}.reset() during stream capture — "
                f"end_capture() first")
        self._error = None
        self._wait_deps = []
        # retire this stream's retained failed requests too: the next
        # launch's program-order tail points at them, and an un-surfaced
        # failure there would re-poison the fresh start
        self._disp.release_stream_errors(self)
        return self

    def _consume_wait_deps(self) -> List[int]:
        deps, self._wait_deps = self._wait_deps, []
        return deps

    def _consume_capture_deps(self) -> List[int]:
        deps, self._capture_deps = self._capture_deps, []
        return deps


class Event:
    """CUDA-style event: a recorded point in a stream's program order.

    ``record(stream)`` captures the stream's current tail;
    ``wait(stream)`` orders another stream's subsequent launches after
    that point; ``synchronize()`` blocks the host until the recorded
    work completed and stamps the completion time; ``elapsed(end)``
    returns milliseconds between two events' stamps.  Timing caveat:
    the stamp is taken when completion is first *observed* (at a
    ``synchronize()``), not at true device completion — synchronize
    promptly for tight timings."""

    def __init__(self):
        self._req: Optional[LaunchRequest] = None
        self._disp: Optional[Dispatcher] = None
        self._recorded = False
        self._t_done: Optional[float] = None
        self._graph = None                 # capture graph, when recorded there
        self._gnode = None                 # captured tail node (None: idle)

    def record(self, stream: Optional[Stream] = None) -> "Event":
        stream = stream if stream is not None else get_dispatcher().default
        self._disp = stream.dispatcher
        if stream._capture is not None:
            # capture-recorded: the event marks the stream's captured
            # tail node — a schedule edge, not a completion point
            self._graph = stream._capture
            self._gnode = stream._capture._tail_node(stream)
            self._req = None
            self._recorded = True
            self._t_done = None
            return self
        self._graph = self._gnode = None
        self._req = self._disp.tail_request(stream)   # None: empty stream
        self._recorded = True
        # recording on an idle stream completes immediately (CUDA: an
        # event completes once all preceding stream work has) — stamp now
        self._t_done = None if self._req is not None else time.perf_counter()
        return self

    def wait(self, stream: Stream) -> None:
        if not self._recorded:
            return                       # CUDA: wait-before-record is a no-op
        if self._graph is not None:      # capture-recorded event
            if stream._capture is None:
                raise CoxUnsupported(
                    f"eager stream {stream.name!r} cannot wait on an "
                    f"event recorded during capture — the captured "
                    f"schedule has not run; wait inside the same "
                    f"capture or replay the graph first")
            if stream._capture is not self._graph:
                raise CoxUnsupported(
                    f"stream {stream.name!r} is capturing into a "
                    f"different graph than the one this event was "
                    f"recorded in — cross-graph event edges are not "
                    f"capturable")
            if self._gnode is not None:
                stream._capture_deps.append(self._gnode.idx)
            return
        if stream._capture is not None:
            raise CoxUnsupported(
                f"capturing stream {stream.name!r} cannot wait on an "
                f"event recorded outside its capture — CUDA invalidates "
                f"the capture; record the event inside the capture")
        if self._req is None:
            return
        stream._wait_deps.append(self._req.seq)

    def query(self) -> bool:
        """True when the recorded work has completed (never blocks).
        Illegal for a capture-recorded event — captured work never runs
        until replay, so completion is not a meaningful question."""
        if self._graph is not None:
            raise CoxUnsupported(
                "Event.query() on an event recorded during stream "
                "capture — the captured schedule runs only at "
                "graph.replay(); a capture event is a schedule edge, "
                "not a completion point")
        if not self._recorded:
            return True
        if self._req is None:
            return True
        if not self._req.dispatched or self._req.injected_hang:
            return False
        if self._req.error is not None:
            return True                  # failed work is "complete"
        return _outputs_ready(self._req.outputs)

    def synchronize(self) -> "Event":
        """Block until the recorded work completed; idempotent.  The
        first call stamps the event's completion time."""
        if self._graph is not None:
            raise CoxUnsupported(
                "Event.synchronize() on an event recorded during stream "
                "capture — the captured schedule runs only at "
                "graph.replay()")
        if not self._recorded:
            raise CoxUnsupported("Event.synchronize() before record()")
        if self._req is not None:
            self._disp.sync_request(self._req)
        if self._t_done is None:
            self._t_done = time.perf_counter()
        return self

    def elapsed(self, end: "Event") -> float:
        """Milliseconds between this (start) event and ``end`` — CUDA
        ``cudaEventElapsedTime``.  Synchronizes both events."""
        self.synchronize()
        end.synchronize()
        return (end._t_done - self._t_done) * 1e3

    elapsed_time = elapsed   # cupy-style alias


class Dispatcher:
    """Host-side launch scheduler + the shared staging cache.

    :meth:`flush` topologically orders the pending request graph —
    stream program order plus event edges, FIFO (enqueue-order)
    tie-break — and dispatches each request's staged executable via
    XLA's **async dispatch**: the ``exe(...)`` call returns futures
    immediately, so a dispatched kernel executes while the host binds
    and dispatches later requests.  Stream launches flush eagerly (a
    CUDA launch starts the kernel, it does not queue it on the host);
    requests can still sit pending between ``enqueue`` and ``flush``
    when the dispatcher is driven directly.  Nothing in the dispatch
    path calls ``block_until_ready``.

    Staged executables are cached here, keyed on kernel identity plus
    the request's resolved geometry/knobs (``LaunchRequest.stage_key``),
    so every stream — and the synchronous ``KernelFn.launch`` path —
    shares one staging per distinct launch shape."""

    def __init__(self, stage_cache_size: int = STAGE_CACHE_SIZE,
                 dispatch_log_max: int = DISPATCH_LOG_MAX, *,
                 launch_deadline_s: Optional[float] = None,
                 max_strikes: int = 8,
                 error_log_max: int = ERROR_LOG_MAX,
                 retry_limit: int = RETRY_LIMIT,
                 retry_backoff_s: float = RETRY_BACKOFF_S,
                 devices: Optional[Tuple[Any, ...]] = None,
                 placement: Optional[Any] = None):
        # _lock guards the queues/caches and is only ever held briefly;
        # _dispatch_lock serializes whole flush drains so concurrent
        # flushes cannot interleave dispatch out of dependency order,
        # while staging (JAX trace/compile) runs with only it held —
        # other threads' enqueues/syncs never wait on a compile
        self._lock = threading.RLock()
        self._dispatch_lock = threading.Lock()
        self._stage_cache_size = stage_cache_size
        self._staged: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._staged_fns: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._pending: "OrderedDict[int, LaunchRequest]" = OrderedDict()
        self._inflight: Dict[int, LaunchRequest] = {}
        # stream -> weakref to its tail request.  Both sides are weak on
        # purpose: a pending/in-flight request is kept alive by
        # _pending/_inflight (and keeps its stream alive via req.stream),
        # while a *completed* request whose handle was dropped may be
        # collected — ordering against completed work is vacuous, so a
        # dead tail simply means "no edge needed".
        self._tails: "weakref.WeakKeyDictionary[Stream, Any]" = \
            weakref.WeakKeyDictionary()
        self._seq = itertools.count()
        # bounded structurally: maxlen evicts the oldest entries, so a
        # long-lived serving loop cannot grow per-launch host state
        self.dispatch_log: Deque[int] = deque(maxlen=dispatch_log_max)
        self.stage_hits = 0
        self.stage_misses = 0
        self.stage_fn_hits = 0
        self.stage_fn_misses = 0
        # ---- telemetry (README "Autotune & telemetry") ----
        # per-stage-key live counters: launches, host dispatch seconds,
        # estimated bytes/FLOPs (repro.core.costmodel) —
        # benchmarks/roofline.py computes roofline position from these
        # real counters instead of dry-run JSON
        self._telemetry: "OrderedDict[tuple, Dict[str, Any]]" = OrderedDict()
        self._capturing: "weakref.WeakSet[Stream]" = weakref.WeakSet()
        # ---- fault tolerance (README "Error model & fault tolerance") ----
        # errored requests whose handle was dropped without a sync move
        # here (bounded, oldest evicted) so a long-lived serving loop
        # stays bounded under repeated failures; surfaced via
        # get_last_error() / error_log
        self.error_log_max = error_log_max
        self._errored: "OrderedDict[int, LaunchRequest]" = OrderedDict()
        # id(output array) -> (weakref-or-None, producer seq): the data
        # edges behind handle.outputs chaining.  An entry lives exactly
        # as long as its producer sits in _inflight/_errored — the
        # producer's req.outputs holds the array strongly, so the id
        # cannot be recycled while the entry exists.
        self._out_producers: Dict[int, Tuple[Any, int]] = {}
        # device-poisoning errors, scoped per device: key = device id of
        # the placed request that faulted, or None for an unplaced
        # (legacy single-device / default-stream / mesh) fault — the
        # process-wide CUDA behavior.  Placement routes new work around
        # poisoned devices; enqueue only fails once *no* healthy device
        # remains (which on a one-device pool is the first sticky fault,
        # exactly the old contract).
        self._sticky: "OrderedDict[Optional[int], BaseException]" = \
            OrderedDict()
        self._last_error: Optional[BaseException] = None   # cudaGetLastError
        # ---- multi-device placement (repro.core.placement) ----
        # the device pool is lazy: this constructor runs at module import
        # (the default dispatcher singleton) and must not initialize jax
        self._devices = tuple(devices) if devices is not None else None
        self.placement = placement       # policy; defaults to round-robin
        # per-device dispatch counters: str(device) (or "default" for
        # unplaced work) -> {dispatches, failures, degradations}
        self._dev_counters: Dict[str, Dict[str, int]] = {}
        # device-id -> display name, learned as devices pass through
        # (labels sticky-map keys without resolving the lazy pool)
        self._dev_names: Dict[int, str] = {}
        self.launch_deadline_s = launch_deadline_s
        self.max_strikes = max_strikes
        self.retry_limit = retry_limit
        self.retry_backoff_s = retry_backoff_s
        self.failures = 0        # requests that ended with an error
        self.retries = 0         # transient-failure retry attempts
        self.degradations = 0    # ladder fallbacks taken
        self.timeouts = 0        # launches killed by the deadline
        self.degradation_log: Deque[Dict[str, Any]] = \
            deque(maxlen=DEGRADATION_LOG_MAX)
        self.watchdog: Optional[StepWatchdog] = None   # lazily armed
        self._wd_lock = threading.Lock()   # serializes deadline awaits
        self.default = Stream(dispatcher=self, _default=True)

    # ---------------- placement (multi-device scale-out) ----------------

    @property
    def devices(self) -> Tuple[Any, ...]:
        """The device pool streams are placed over (default: every jax
        device, resolved lazily so constructing a dispatcher — including
        the import-time singleton — never initializes jax)."""
        devs = self._devices
        if devs is None:
            devs = self._devices = tuple(jax.devices())
        return devs

    def _healthy_devices(self) -> List[Any]:
        with self._lock:
            poisoned = set(self._sticky) - {None}
        return [d for d in self.devices if d.id not in poisoned]

    def _sticky_blocking(self) -> Optional[BaseException]:
        """The sticky error that must fail an enqueue/sync outright:
        an unplaced (device-less) sticky fault poisons the process —
        the CUDA contract — while placed faults only block once every
        device in the pool is poisoned (placement routes around
        anything less)."""
        with self._lock:
            if not self._sticky:
                return None
            glob = self._sticky.get(None)
            if glob is not None:
                return glob
            if not self._healthy_devices():
                return next(iter(self._sticky.values()))
            return None

    def _sticky_for(self, device) -> Optional[BaseException]:
        """The sticky error covering a request bound for ``device``:
        its own device's, or — for unplaced work, which runs on the
        pool's first device — that device's.  Caller holds ``_lock``."""
        glob = self._sticky.get(None)
        if glob is not None:
            return glob
        if not self._sticky:
            return None
        if device is not None:
            return self._sticky.get(device.id)
        devs = self.devices
        return self._sticky.get(devs[0].id) if devs else None

    def _place(self, req: LaunchRequest) -> None:
        """Assign the request a target device (fills ``req.device``).
        Explicitly placed requests, mesh (sharded) launches, default-
        stream launches, and single-device pools keep ``device=None`` —
        the exact legacy dispatch path, no transfers.  Raises the first
        sticky error when no healthy device remains."""
        if req.device is not None or req.mesh is not None:
            return
        devices = self.devices
        if len(devices) <= 1:
            return
        s = req.stream
        if s is None or s.is_default:
            return                   # CUDA: default stream = current device
        if s._device_pinned:
            req.device = s._device
            return
        healthy = self._healthy_devices()
        if not healthy:
            err = self._sticky_blocking()
            if err is not None:
                raise err
            healthy = list(devices)      # racing device_reset: pool is back
        pol = self.placement
        if pol is None:
            pol = self.placement = _placement.RoundRobinPlacement()
        req.device = pol.place(req, healthy, self)

    @staticmethod
    def _dev_of(req: "LaunchRequest"):
        """The device a request's counters attribute to: its placement,
        else its stream's (a descendant failed *before* placement still
        belongs to its stream's device), else None (unplaced)."""
        if req.device is not None:
            return req.device
        s = req.stream
        return s._device if s is not None else None

    def _bump_dev(self, device, key: str) -> None:
        """Per-device health counter bump.  Caller holds ``_lock``."""
        name = str(device) if device is not None else "default"
        c = self._dev_counters.get(name)
        if c is None:
            c = self._dev_counters[name] = {
                "dispatches": 0, "failures": 0, "degradations": 0}
        c[key] += 1

    def device_health(self) -> Dict[str, Dict[str, int]]:
        """Per-device dispatch counters, keyed by ``str(device)``
        (``"default"`` collects unplaced work) — what
        :class:`~repro.core.placement.HealthAwarePlacement` reads."""
        with self._lock:
            return {k: dict(v) for k, v in self._dev_counters.items()}

    # ---------------- enqueue ----------------

    @functools.partial(annotate_function, name="cox.enqueue")
    def enqueue(self, req: LaunchRequest, stream: Stream) -> LaunchHandle:
        """Assign the request its place in the launch order: program
        order on its stream, pending event edges, and the default
        stream's legacy-sync edges."""
        if req.globals_:
            for name, val in req.globals_.items():
                if isinstance(val, GraphRef):
                    raise CoxUnsupported(
                        f"kernel '{req.ck.kernel.name}': argument "
                        f"'{name}' is a capture placeholder ({val!r}) "
                        f"that escaped its graph — captured outputs "
                        f"only exist inside the capture; replay the "
                        f"graph and use its real outputs instead")
        blocking = self._sticky_blocking()
        if blocking is not None:
            # CUDA: after a sticky error every launch fails synchronously
            # with that error until cudaDeviceReset (device_reset here).
            # With a multi-device pool this only fires once every device
            # is poisoned — placement routes around anything less.
            raise blocking
        with self._lock:
            req.seq = next(self._seq)
            req.stream = stream
            req.priority = stream.priority
            if req.device is None and stream._device_pinned:
                req.device = stream._device
            deps = []
            tail = self.tail_request(stream)
            if tail is not None:
                deps.append(tail.seq)            # in-order within the stream
            if stream.is_default:
                # legacy sync: the default stream is ordered after the
                # current tail of every other stream
                for s in list(self._tails):
                    if s is stream:
                        continue
                    t = self._tails[s]()
                    if t is not None:
                        deps.append(t.seq)
            else:
                dt = self.tail_request(self.default)
                if dt is not None:
                    deps.append(dt.seq)          # ...and every stream after it
            deps.extend(stream._consume_wait_deps())
            req.deps = tuple(sorted(set(deps)))
            if req.globals_:
                # handle.outputs data edges: an argument that is a live
                # launch output makes this request a DAG descendant of
                # its producer
                ddeps = {self._producer_seq(v) for v in req.globals_.values()}
                ddeps.discard(None)
                req.data_deps = tuple(sorted(ddeps))
            self._pending[req.seq] = req
            self._tails[stream] = weakref.ref(req)
            return LaunchHandle(req, self)

    def _producer_seq(self, val) -> Optional[int]:
        """The in-flight/errored producer seq of ``val``, if ``val`` is
        one of its raw output arrays (identity-checked — ``id()`` alone
        is not trusted across object lifetimes)."""
        try:
            entry = self._out_producers.get(id(val))
        except TypeError:
            return None
        if entry is None:
            return None
        ref, seq = entry
        if ref is not None and ref() is not val:
            return None
        return seq

    def tail_request(self, stream: Stream) -> Optional[LaunchRequest]:
        with self._lock:
            ref = self._tails.get(stream)
            return ref() if ref is not None else None

    # ---------------- staging (the shared launch cache) ----------------

    def stage(self, req: LaunchRequest):
        """Resolve the request to a staged ``(plan, exe)``, shared
        across streams.  ``id(ck)`` is safe in the key because the
        cached plan holds a strong reference to the same ck — the id
        cannot be recycled while the entry lives.

        The executable is the jit wrap of the raw launcher from
        :meth:`stage_fn`, so eager staging and graph staging share one
        trace recipe per launch shape — a graph capturing a kernel the
        streams already launched re-traces nothing, and vice versa."""
        key = (id(req.ck),) + req.stage_key()
        with self._lock:
            hit = self._staged.get(key)
            if hit is not None:
                self._staged.move_to_end(key)
                self.stage_hits += 1
                return hit
        with TraceAnnotation("cox.restage"):
            plan, fn = self.stage_fn(req)
            staged = (plan, jax.jit(fn, donate_argnums=(0,) if req.donate
                                    else ()))
        with self._lock:
            self.stage_misses += 1
            self._staged[key] = staged
            while len(self._staged) > self._stage_cache_size:
                self._staged.popitem(last=False)
        return staged

    def stage_fn(self, req: LaunchRequest):
        """Resolve the request to its *raw* (un-jitted) launcher,
        ``(plan, fn)`` — the form the graph tracer inlines.  Cached
        separately from :meth:`stage` (an fn is a trace recipe, an exe
        is a compiled program) but shared across every graph that
        captures the same launch shape, so two graphs over the same
        kernel trace it once."""
        key = (id(req.ck),) + req.fn_key()
        with self._lock:
            hit = self._staged_fns.get(key)
            if hit is not None:
                self._staged_fns.move_to_end(key)
                self.stage_fn_hits += 1
                return hit
        with TraceAnnotation("cox.restage"):
            plan, fn = _runtime.build_traceable(
                req.ck, req.rl, simd=req.simd, mesh=req.mesh, axis=req.axis,
                chunk=req.chunk)
        # the launcher is built for this stage alone; its name is the one
        # jax.jit gives the XLA module (jit_cox_<kernel>)
        fn.__name__ = fn.__qualname__ = f"cox_{req.ck.kernel.name}"
        staged = (plan, fn)
        with self._lock:
            self.stage_fn_misses += 1
            self._staged_fns[key] = staged
            while len(self._staged_fns) > self._stage_cache_size:
                self._staged_fns.popitem(last=False)
        return staged

    def stage_graph(self, key: tuple, builder):
        """Stage a captured graph's fused executable in the shared LRU.
        ``key`` starts with the literal ``"graph"`` tag (so
        :meth:`cache_view`'s kernel-id filter never surfaces graph
        entries) followed by the captured DAG's per-node stage keys —
        two structurally identical captures hit the same executable.
        ``builder()`` runs without the queue lock (it traces)."""
        with self._lock:
            hit = self._staged.get(key)
            if hit is not None:
                self._staged.move_to_end(key)
                self.stage_hits += 1
                return hit
        with TraceAnnotation("cox.restage"):
            staged = builder()
        with self._lock:
            self.stage_misses += 1
            self._staged[key] = staged
            while len(self._staged) > self._stage_cache_size:
                self._staged.popitem(last=False)
        return staged

    def cache_view(self, cks) -> Dict[tuple, tuple]:
        """The staged entries for the given compiled kernels, keyed
        without the kernel-identity element — the backward-compatible
        ``KernelFn._launch_cache`` shape."""
        ids = {id(ck) for ck in cks}
        with self._lock:
            return {k[1:]: v for k, v in self._staged.items() if k[0] in ids}

    # ---------------- dispatch ----------------

    def _toposorted(self) -> List[LaunchRequest]:
        """Kahn's algorithm over the pending graph: edges are stream
        program order + event edges (``req.deps``, restricted to
        still-pending requests).  The ready-set is a priority heap:
        among simultaneously-ready requests the lowest stream priority
        number dispatches first (latency-sensitive streams preempt bulk
        work in the issue order), with FIFO enqueue-order tie-break so
        dispatch stays deterministic."""
        pending = self._pending
        indeg = {seq: sum(1 for d in r.deps if d in pending)
                 for seq, r in pending.items()}
        ready = [(pending[seq].priority, seq)
                 for seq, n in indeg.items() if n == 0]
        out: List[LaunchRequest] = []
        fwd: Dict[int, List[int]] = {}
        for seq, r in pending.items():
            for d in r.deps:
                if d in pending:
                    fwd.setdefault(d, []).append(seq)
        heapq.heapify(ready)
        while ready:
            _, seq = heapq.heappop(ready)
            out.append(pending[seq])
            for nxt in fwd.get(seq, ()):
                indeg[nxt] -= 1
                if indeg[nxt] == 0:
                    heapq.heappush(ready, (pending[nxt].priority, nxt))
        if len(out) != len(pending):     # impossible by construction:
            raise AssertionError("cycle in launch-dependency graph")
        return out

    @functools.partial(annotate_function, name="cox.dispatch")
    def _dispatch(self, req: LaunchRequest) -> None:
        name = req.ck.kernel.name
        if req.error is not None:         # already failed fast (descendant)
            self._finish_failed(req)
            return
        with self._lock:
            dep_err = self._first_dep_error(req)
        if dep_err is not None:
            # fail fast: never dispatch on a failed upstream's stale
            # outputs — CUDA's poisoned stream simply never runs these
            root = _errors.root_of(dep_err)
            self._fail_request(req, CoxDependencyError(
                f"kernel '{name}' (seq {req.seq}) not dispatched: "
                f"upstream failure {type(root).__name__}: {root}",
                root=root))
            return
        try:
            self._place(req)             # fills req.device (policy/pin)
        except Exception as e:
            self._fail_request(req, e)
            return
        with self._lock:
            sticky = self._sticky_for(req.device)
        if sticky is not None:
            # the request's target device is poisoned (explicit pin, or
            # unplaced work on a poisoned first device): fail it with
            # the device's sticky error — placement never *chooses* a
            # poisoned device, so a policy-placed request cannot land here
            self._fail_request(req, sticky)
            return
        try:
            outputs = self._run_attempts(req, name)   # stage + async dispatch
        except Exception as e:            # surfaces at *this* request's sync
            self._fail_request(req, e)
            return
        req.outputs = outputs
        req.dispatched = True
        req.globals_ = None               # release (or donated) inputs
        req.scalars = None
        with self._lock:
            for o in outputs.values():
                try:
                    ref = weakref.ref(o)
                except TypeError:
                    ref = None
                self._out_producers[id(o)] = (ref, req.seq)
                req.out_ids.append(id(o))
            self._inflight[req.seq] = req
            self.dispatch_log.append(req.seq)   # deque: maxlen-bounded
            self._bump_dev(self._dev_of(req), "dispatches")

    def _first_dep_error(self, req: LaunchRequest) -> Optional[BaseException]:
        """The first un-surfaced failure among the request's DAG parents
        (program order + event edges + data edges) or on its stream.
        Caller holds ``_lock``."""
        for d in sorted(set(req.deps) | set(req.data_deps)):
            r = (self._inflight.get(d) or self._errored.get(d)
                 or self._pending.get(d))
            if r is not None and r.error is not None and not r.surfaced:
                return r.error
        s = req.stream
        if s is not None and s._error is not None:
            return s._error
        return None

    def _fail_request(self, req: LaunchRequest, err: BaseException) -> None:
        req.error = err
        self._finish_failed(req)

    def _finish_failed(self, req: LaunchRequest) -> None:
        """Bookkeeping for a request that failed at (or before) dispatch:
        record it, poison its stream, update the error registers."""
        req.dispatched = True
        req.globals_ = None
        req.scalars = None
        with self._lock:
            self._inflight[req.seq] = req
            self.dispatch_log.append(req.seq)
            self._last_error = req.error
            self.failures += 1
            self._bump_dev(self._dev_of(req), "failures")
            if _errors.is_sticky(req.error):
                self._note_sticky_locked(req.device, req.error)
            if req.stream is not None and req.stream._error is None:
                req.stream._error = req.error

    # -------- attempts: retry ladder + graceful degradation --------

    def _ladder(self, req: LaunchRequest) -> List[Tuple[Any, str]]:
        """The fallback rungs for this request, most-capable first.
        Only knobs the caller left on ``'auto'`` may degrade — an
        explicitly requested backend/warp_exec is honored and fails as
        requested.  Every rung computes bitwise-identical outputs by
        the backend-equivalence contract (scan/serial is the reference
        semantics every other cell is tested against)."""
        rungs: List[Tuple[Any, str]] = [(req.rl, "as-resolved")]
        rl = req.rl
        if rl.warp_exec == "batched" and req.req_warp_exec == "auto":
            rl = dataclasses.replace(rl, warp_exec="serial")
            rungs.append((rl, "warp_exec=serial"))
        if rl.backend == "vmap" and req.req_backend == "auto":
            rl = dataclasses.replace(rl, backend="scan")
            rungs.append((rl, "backend=scan"))
        return rungs

    def _run_attempts(self, req: LaunchRequest, name: str) -> Dict[str, Any]:
        """Try the request down its degradation ladder; each rung gets
        the bounded transient retry.  A sticky error aborts the ladder
        (the device is gone, no rung can help)."""
        rungs = self._ladder(req)
        last: Optional[BaseException] = None
        for i, (rl, tag) in enumerate(rungs):
            req.rl = rl                  # re-stage on this rung's knobs
            try:
                return self._attempt_with_retry(req, name)
            except Exception as e:
                if _errors.is_sticky(e):
                    raise
                last = e
                if i + 1 < len(rungs):
                    event = {"kernel": name, "seq": req.seq,
                             "from": tag, "to": rungs[i + 1][1],
                             "error": repr(e)}
                    with self._lock:
                        self.degradations += 1
                        self._bump_dev(self._dev_of(req), "degradations")
                        self.degradation_log.append(event)
        assert last is not None
        raise last

    def _attempt_with_retry(self, req: LaunchRequest,
                            name: str) -> Dict[str, Any]:
        attempt = 0
        while True:
            try:
                return self._attempt(req, name)
            except Exception as e:
                if (_errors.is_sticky(e) or not _errors.is_transient(e)
                        or attempt >= self.retry_limit):
                    raise
                with self._lock:
                    self.retries += 1
                time.sleep(self.retry_backoff_s * (2 ** attempt))
                attempt += 1

    def _attempt(self, req: LaunchRequest, name: str) -> Dict[str, Any]:
        """One stage+dispatch attempt, with the fault-injection consults
        (``repro.core.faults``) at each lifecycle site.  Injected
        dispatch faults fire *before* the executable runs, so a donating
        request's buffers survive for the fallback rung."""
        fault = _faults.consume("stage", name)
        if fault is not None:
            raise fault
        try:
            _, exe = self.stage(req)      # may trace/compile — no _lock
        except Exception as e:
            raise _errors.classify(e, site="stage", what=f"kernel '{name}'")
        fault = _faults.consume("sticky-device", name)
        if fault is not None:
            raise fault
        fault = _faults.consume("dispatch", name)
        if fault is not None:
            raise fault
        if req.device is not None:
            # the explicit transfer node: commit inputs to the placed
            # device (async device_put; a no-op returning the same
            # buffer when already resident, preserving donation
            # aliasing).  This is where a cross-stream data edge whose
            # producer landed on another device becomes a D2D copy.
            # Written back onto the request so retry/ladder rungs — and
            # a donating relaunch — reuse the transferred buffers.
            try:
                req.globals_ = {k: _to_device(v, req.device)
                                for k, v in req.globals_.items()}
                if req.scalars:
                    req.scalars = {k: _to_device(v, req.device)
                                   for k, v in req.scalars.items()}
            except Exception as e:
                raise _errors.classify(e, site="dispatch",
                                       what=f"kernel '{name}'")
        try:
            t0 = time.perf_counter()
            with TraceAnnotation("cox.xla_call"):
                outputs = exe(req.globals_, req.scalars)   # async dispatch
            dispatch_s = time.perf_counter() - t0
        except Exception as e:
            raise _errors.classify(e, site="dispatch",
                                   what=f"kernel '{name}'")
        self._note_telemetry(req, dispatch_s)
        if _faults.consume("timeout", name) is not None:
            req.injected_hang = True      # outputs never report ready
        return outputs

    def flush(self) -> None:
        """Dispatch every pending request in topological order.  The
        drain loop holds only the dispatch lock; the queue lock is
        taken just to snapshot a batch, so concurrent enqueues (and
        already-staged launches) never wait on a first-launch compile."""
        with self._dispatch_lock:
            while True:
                with self._lock:
                    if not self._pending:
                        break
                    order = self._toposorted()
                    self._pending = OrderedDict()
                for req in order:
                    self._dispatch(req)
            with self._lock:
                self._prune_inflight()

    def dispatch_through(self, req: LaunchRequest) -> None:
        """Ensure ``req`` (and, by topological order, everything it
        depends on) has been dispatched."""
        if not req.dispatched:
            self.flush()

    def _prune_inflight(self) -> None:
        # DAG descendants of a still-hung launch must stay resident even
        # if their own outputs report ready (only possible under a
        # simulated hang): when the hang resolves into CoxTimeoutError,
        # _fail_descendants_locked has to find them to fail them.  Deps
        # point at earlier seqs and _inflight iterates in seq order, so
        # one pass tracks hang-blocked seqs transitively.
        blocked: set = set()
        for seq in list(self._inflight):
            r = self._inflight[seq]
            if r.error is not None:
                # retained (bounded) until surfaced — a dropped handle
                # must not leak its request forever
                del self._inflight[seq]
                self._retain_errored(r)
                continue
            if r.injected_hang:
                blocked.add(seq)
                continue                 # "hung": never reports ready
            if blocked and not blocked.isdisjoint((*r.deps, *r.data_deps)):
                blocked.add(seq)
                continue
            if _outputs_ready(r.outputs):
                del self._inflight[seq]
                self._drop_producers(r)

    def _retain_errored(self, r: LaunchRequest) -> None:
        self._errored[r.seq] = r
        while len(self._errored) > self.error_log_max:
            _, old = self._errored.popitem(last=False)
            self._drop_producers(old)

    def _drop_producers(self, req: LaunchRequest) -> None:
        for i in req.out_ids:
            entry = self._out_producers.get(i)
            if entry is not None and entry[1] == req.seq:
                del self._out_producers[i]
        req.out_ids = []

    # ---------------- synchronization ----------------

    def _surface_locked(self, req: LaunchRequest) -> None:
        """The request's error reached the caller: mark it surfaced and
        un-poison its stream if this error is what poisoned it — a
        surfaced non-sticky error leaves the stream usable, exactly
        CUDA's cudaGetLastError contract.  Caller holds ``_lock``."""
        req.surfaced = True
        s = req.stream
        if s is not None and s._error is req.error:
            s._error = None

    def forget(self, req: LaunchRequest) -> None:
        """Drop a request from the in-flight/errored sets (its
        error/result has been surfaced to the caller)."""
        with self._lock:
            self._inflight.pop(req.seq, None)
            self._errored.pop(req.seq, None)
            self._drop_producers(req)
            if req.error is not None:
                self._surface_locked(req)

    def sync_request(self, req: LaunchRequest) -> None:
        """Flush, then block until this request's outputs are ready.
        A failed request raises its typed error *here* — at its own
        sync — and surfacing it reclaims the bookkeeping entry."""
        self.dispatch_through(req)
        if req.error is None:
            self._await_request(req)
        self.forget(req)
        if req.error is not None:
            raise req.error

    def _await_request(self, req: LaunchRequest,
                       extra: Optional[List[LaunchRequest]] = None) -> None:
        """Block until the dispatched request's outputs are ready,
        enforcing the per-launch deadline when configured.  On failure
        (deadline, or an async error surfacing in the wait) the error is
        recorded on ``req`` and its DAG descendants fail fast."""
        deadline = self.launch_deadline_s
        if deadline is None and req.injected_hang:
            deadline = 0.0               # a hang with no deadline would spin
        name = req.ck.kernel.name
        if deadline is None:
            try:
                with TraceAnnotation("cox.wait"):
                    _block_outputs(req.outputs)
            except Exception as e:
                err = _errors.classify(e, site="dispatch",
                                       what=f"kernel '{name}'")
                self._record_async_failure(req, err, extra)
            return
        with self._wd_lock, TraceAnnotation("cox.wait"):   # one at a time
            wd = self.watchdog
            if wd is None or wd.deadline_s != deadline:
                wd = StepWatchdog(deadline_s=deadline,
                                  max_strikes=self.max_strikes)
                self.watchdog = wd
            wd.start(step=req.seq)
            try:
                while True:
                    if not req.injected_hang and _outputs_ready(req.outputs):
                        try:
                            _block_outputs(req.outputs)
                        except Exception as e:
                            err = _errors.classify(e, site="dispatch",
                                                   what=f"kernel '{name}'")
                            self._record_async_failure(req, err, extra)
                        return
                    if wd.fired:
                        err = CoxTimeoutError(
                            f"kernel '{name}' (seq {req.seq}) exceeded "
                            f"its launch deadline of {deadline}s")
                        with self._lock:
                            self.timeouts += 1
                        self._record_async_failure(req, err, extra)
                        return
                    time.sleep(DEADLINE_POLL_S)
            finally:
                wd.stop()

    def _record_async_failure(self, req: LaunchRequest, err: BaseException,
                              extra: Optional[List[LaunchRequest]] = None,
                              ) -> None:
        """A failure detected *after* dispatch (deadline expiry, async
        error in the wait): record it and fail the DAG descendants."""
        with self._lock:
            req.error = err
            self._last_error = err
            self.failures += 1
            self._bump_dev(self._dev_of(req), "failures")
            if _errors.is_sticky(err):
                self._note_sticky_locked(req.device, err)
            if req.stream is not None and req.stream._error is None:
                req.stream._error = err
            self._fail_descendants_locked(req, err, extra)

    def _fail_descendants_locked(self, req: LaunchRequest,
                                 err: BaseException,
                                 extra: Optional[List[LaunchRequest]] = None,
                                 ) -> None:
        """Mark every (transitive) DAG descendant of ``req`` failed with
        :class:`CoxDependencyError` — their outputs were computed from
        (or will depend on) a failed launch.  Deps always point to
        earlier seqs, so one ascending pass reaches the fixpoint."""
        root = _errors.root_of(err)
        failed = {req.seq}
        pool: Dict[int, LaunchRequest] = {}
        for r in list(self._pending.values()) + list(self._inflight.values()) \
                + list(extra or ()):
            pool[r.seq] = r
        for seq in sorted(pool):
            r = pool[seq]
            if seq in failed or r.error is not None:
                continue
            if (set(r.deps) | set(r.data_deps)) & failed:
                r.error = CoxDependencyError(
                    f"kernel '{r.ck.kernel.name}' (seq {seq}) depends on "
                    f"failed launch seq {req.seq}: "
                    f"{type(root).__name__}: {root}", root=root)
                if r.stream is not None and r.stream._error is None:
                    r.stream._error = r.error
                failed.add(seq)

    def _take_inflight(self, stream: Optional[Stream]) -> List[LaunchRequest]:
        """Atomically remove (and return, seq-ordered) the in-flight —
        and retained errored — requests of ``stream``, or of every
        stream when ``None``.  The caller blocks on them *outside* the
        lock, so concurrent enqueues/flushes never wait on device
        completion."""
        with self._lock:
            taken = []
            for pool in (self._inflight, self._errored):
                for seq in list(pool):
                    r = pool[seq]
                    if stream is None or r.stream is stream:
                        del pool[seq]
                        taken.append(r)
                        self._drop_producers(r)
            return sorted(taken, key=lambda r: r.seq)

    def sync_stream(self, stream: Optional[Stream]) -> None:
        """Block until every launch enqueued on ``stream`` completed
        (``None``: on any stream).  The *earliest* deferred launch error
        of the synced set is raised, CUDA's async-error-at-sync
        analogue; every error in the set counts as surfaced (the stream
        is left usable unless the error was sticky).  Illegal while any
        stream of this dispatcher is capturing — CUDA invalidates an
        active capture on a device-wide sync."""
        if stream is not None and stream._capture is not None:
            raise CoxUnsupported(
                f"cannot synchronize {stream!r} during stream capture — "
                f"end_capture() first")
        if stream is None and self._capturing:
            names = sorted(s.name for s in self._capturing)
            raise CoxUnsupported(
                f"device-wide synchronize while stream(s) {names} are "
                f"capturing — a capture records the schedule without "
                f"running it; end_capture() first")
        self.flush()
        taken = self._take_inflight(stream)
        for r in taken:
            if r.error is None:
                # a failure here marks descendants in `taken` via extra
                self._await_request(r, extra=taken)
        pairs = [(r.seq, r.error) for r in taken if r.error is not None]
        with self._lock:
            for r in taken:
                if r.error is not None:
                    self._surface_locked(r)
            if stream is not None and stream._error is not None:
                # the poisoning request was evicted/collected — surface
                # the bare stream error so reset-by-sync still works
                pairs.append((float("inf"), stream._error))
                stream._error = None
        if pairs:
            raise min(pairs, key=lambda p: p[0])[1]
        blocking = self._sticky_blocking()
        if blocking is not None:
            raise blocking               # CUDA: sticky errors never clear

    def sync_all(self) -> None:
        """Device-wide barrier (CUDA ``cudaDeviceSynchronize``)."""
        self.sync_stream(None)

    # ------------- error surface (cudaGetLastError analogues) -------------

    @property
    def error_log(self) -> List[LaunchRequest]:
        """The retained (un-surfaced, handle-dropped) failed requests,
        oldest first — bounded at ``error_log_max``."""
        with self._lock:
            return list(self._errored.values())

    def get_last_error(self) -> Optional[BaseException]:
        """Return and *clear* the last launch error (``cudaGetLastError``).
        A sticky error is returned but never cleared — only
        :meth:`device_reset` recovers a poisoned device.  Consuming an
        error counts as surfacing it: matching retained requests are
        marked surfaced and their streams un-poisoned."""
        with self._lock:
            if self._sticky:
                return next(iter(self._sticky.values()))
            err = self._last_error
            self._last_error = None
            if err is not None:
                for pool in (self._errored, self._inflight):
                    for r in list(pool.values()):
                        if r.error is err:
                            self._surface_locked(r)
            return err

    def peek_at_last_error(self) -> Optional[BaseException]:
        """The last launch error without clearing it
        (``cudaPeekAtLastError``)."""
        with self._lock:
            return (next(iter(self._sticky.values())) if self._sticky
                    else self._last_error)

    def release_stream_errors(self, stream: Stream) -> None:
        """Retire (mark surfaced, drop retention for) every failed
        request of ``stream`` — the dispatcher half of
        ``stream.reset()``."""
        with self._lock:
            for pool in (self._inflight, self._errored):
                for seq in list(pool):
                    r = pool[seq]
                    if r.stream is stream and r.error is not None:
                        del pool[seq]
                        self._drop_producers(r)
                        r.surfaced = True
            for r in self._pending.values():
                if r.stream is stream and r.error is not None:
                    r.surfaced = True

    def device_reset(self, device: Any = None) -> "Dispatcher":
        """The ``cudaDeviceReset`` analogue.  With ``device=None``:
        clear every sticky error, the last-error register, every
        retained failed request, and every stream's poisoned state.
        With ``device=`` a device (or device id): clear only *that
        device's* sticky state, so placement resumes routing to it —
        the recovery point for a single poisoned device in a
        multi-device pool (everything else is left untouched).
        In-flight successful work is never disturbed (we have no
        device contexts to tear down)."""
        if device is not None:
            did = device if isinstance(device, int) else device.id
            with self._lock:
                self._sticky.pop(did, None)
            return self
        with self._lock:
            self._sticky.clear()
            self._last_error = None
            for r in self._errored.values():
                self._drop_producers(r)
                r.surfaced = True
            self._errored.clear()
            for seq in list(self._inflight):
                r = self._inflight[seq]
                if r.error is not None:
                    self._drop_producers(r)
                    r.surfaced = True
                    del self._inflight[seq]
            for r in self._pending.values():
                if r.error is not None:
                    r.surfaced = True
            for s in set(self._tails) | {self.default}:
                s._error = None
        return self

    def _note_sticky_locked(self, device, err: BaseException) -> None:
        """Record a sticky error against its device (``None`` = the
        process-wide CUDA contract), remembering the device's display
        name while we hold the object.  Caller holds ``_lock``."""
        did = _dev_id(device)
        self._sticky.setdefault(did, err)
        if did is not None:
            self._dev_names[did] = str(device)

    def _dev_label(self, did: Optional[int]) -> str:
        """Human-readable name for a sticky-map key.  Caller holds
        ``_lock`` (reads ``_devices`` without resolving the lazy pool —
        a health probe must not initialize jax)."""
        if did is None:
            return "unplaced"
        name = self._dev_names.get(did)
        if name is not None:
            return name
        for d in (self._devices or ()):
            if d.id == did:
                return str(d)
        return f"device:{did}"

    # ---------------- telemetry (per-stage-key live counters) --------------

    @staticmethod
    def _telemetry_key(req: LaunchRequest) -> tuple:
        """Human-readable stage identity: one row per distinct
        (kernel, backend, warp_exec, chunk, schedule, geometry,
        device, argument shapes)."""
        rl = req.rl
        return (req.ck.kernel.name, rl.backend, rl.warp_exec,
                rl.chunk, rl.schedule, rl.n_resident,
                rl.grid.astuple(), rl.block.astuple(),
                _dev_id(req.device), tuple(req.shapes.items()))

    @staticmethod
    def _telemetry_row(req: LaunchRequest) -> Dict[str, Any]:
        """A fresh stage-key row.  Its cost estimate is fixed for the
        row's key, so it is taken once, here, from ``repro.core.
        costmodel`` ('static' by default — ``COX_COSTMODEL=xla``
        upgrades to the compiled program's own cost analysis)."""
        try:
            est = _costmodel.estimate_request(req)
        except Exception:       # pragma: no cover - estimate never raises
            est = None
        return {
            "launches": 0, "dispatch_s": 0.0, "bytes": 0.0, "flops": 0.0,
            "op_estimate": est.op_estimate if est else 0.0,
            "mem_estimate": est.mem_estimate if est else 0.0,
            "estimate_source": est.source if est else None,
            "chunk_source": getattr(req.rl, "chunk_source", "heuristic"),
            "schedule_source":
                getattr(req.rl, "schedule_source", "heuristic"),
        }

    def _note_telemetry(self, req: LaunchRequest, dispatch_s: float) -> None:
        """Record one dispatched launch against its stage-key row.
        Never raises: telemetry must not be able to fail a launch."""
        key = self._telemetry_key(req)
        with self._lock:
            rec = self._telemetry.get(key)
        if rec is None:
            rec = self._telemetry_row(req)    # the estimate: no _lock held
        with self._lock:
            rec = self._telemetry.setdefault(key, rec)
            self._telemetry.move_to_end(key)
            while len(self._telemetry) > TELEMETRY_MAX:
                self._telemetry.popitem(last=False)
            rec["launches"] += 1
            rec["dispatch_s"] += dispatch_s
            rec["bytes"] += rec["mem_estimate"]
            rec["flops"] += rec["op_estimate"]

    def telemetry(self) -> List[Dict[str, Any]]:
        """The per-stage-key counter rows, with GFLOPS and GB/s derived
        from host dispatch time (a lower bound on a launch's time)."""
        with self._lock:
            rows = [(k, dict(v)) for k, v in self._telemetry.items()]
        out: List[Dict[str, Any]] = []
        for (name, backend, warp_exec, chunk, schedule, n_resident,
             grid, block, dev, shapes), rec in rows:
            rec.update(kernel=name, backend=backend, warp_exec=warp_exec,
                       chunk=chunk, schedule=schedule,
                       n_resident=n_resident, grid=grid, block=block,
                       device=dev, shapes=dict(shapes))
            per = rec["dispatch_s"] / max(1, rec["launches"])
            rec["s_per_launch"] = per
            rec["gflops"] = (rec["op_estimate"] / per / 1e9) if per else 0.0
            rec["gbps"] = (rec["mem_estimate"] / per / 1e9) if per else 0.0
            out.append(rec)
        return out

    def health(self) -> Dict[str, Any]:
        """Counters for monitoring a long-lived dispatcher — the serving
        layer and the benchmark gate read these.  ``devices`` carries
        the per-device dispatch/failure/degradation counters (the
        chaos drill asserts a fault stays confined to one device);
        ``sticky_devices`` the currently-poisoned devices; ``sticky``
        stays the first sticky error's repr (or None) for backward
        compatibility.  ``telemetry_keys``/``dispatch_s``/``bytes``
        summarize the live per-stage-key counters (full rows via
        :meth:`telemetry`); ``stage_hits``/``stage_misses`` count the
        staging cache's lookups (a miss builds an executable, or a
        graph's); ``autotune`` carries the knob-tuner's
        hit/miss/measurement counters."""
        with self._lock:
            first_sticky = (repr(next(iter(self._sticky.values())))
                            if self._sticky else None)
            schedules: Dict[str, int] = {}
            for k in self._telemetry:        # k[4] is the schedule
                schedules[k[4]] = schedules.get(k[4], 0) + 1
            return {
                "failures": self.failures,
                "retries": self.retries,
                "degradations": self.degradations,
                "timeouts": self.timeouts,
                "errored_retained": len(self._errored),
                "inflight": len(self._inflight),
                "pending": len(self._pending),
                "sticky": first_sticky,
                "sticky_devices": {self._dev_label(k): repr(v)
                                   for k, v in self._sticky.items()},
                "devices": {k: dict(v)
                            for k, v in self._dev_counters.items()},
                "watchdog_strikes": (self.watchdog.strikes
                                     if self.watchdog else 0),
                "telemetry_keys": len(self._telemetry),
                "schedules": schedules,
                "dispatch_s": sum(r["dispatch_s"]
                                  for r in self._telemetry.values()),
                "bytes": sum(r["bytes"] for r in self._telemetry.values()),
                "stage_hits": self.stage_hits,
                "stage_misses": self.stage_misses,
                "autotune": _autotune_stats(),
            }


def _autotune_stats() -> Dict[str, int]:
    """The knob-tuner's counters (lazy import: autotune pulls in the
    cost model, which health probes must not pay for eagerly)."""
    try:
        from . import autotune as _autotune
        return _autotune.stats()
    except Exception:           # pragma: no cover - import always works
        return {}


# ---------------------------------------------------------------------------
# module singletons — the process-wide dispatcher and its default stream
# ---------------------------------------------------------------------------

_DISPATCHER = Dispatcher()
default_stream = _DISPATCHER.default


def get_dispatcher() -> Dispatcher:
    return _DISPATCHER


def synchronize() -> None:
    """Device-wide barrier over the default dispatcher."""
    _DISPATCHER.sync_all()


def get_last_error() -> Optional[BaseException]:
    """Return-and-clear the default dispatcher's last launch error —
    the ``cudaGetLastError`` analogue (sticky errors are returned but
    never cleared)."""
    return _DISPATCHER.get_last_error()


def peek_at_last_error() -> Optional[BaseException]:
    """The default dispatcher's last launch error, not cleared — the
    ``cudaPeekAtLastError`` analogue."""
    return _DISPATCHER.peek_at_last_error()


def device_reset(device: Any = None) -> Dispatcher:
    """Clear sticky/poisoned error state on the default dispatcher —
    the ``cudaDeviceReset`` analogue.  ``device=`` scopes the reset to
    one device's sticky state (see :meth:`Dispatcher.device_reset`)."""
    return _DISPATCHER.device_reset(device)
