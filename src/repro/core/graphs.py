"""CUDA graphs: stream capture → instantiate → replay.

CUDA's answer to per-launch overhead is ``cudaGraph_t``: record a
stream's schedule once (``cudaStreamBeginCapture``), bake it into an
executable (``cudaGraphInstantiate``), then relaunch the whole DAG with
one host call (``cudaGraphLaunch``).  This module is the XLA rendition,
and it lands a bigger win than CUDA's: the captured DAG is staged as
**one jitted program** — every captured launch's raw (un-jitted)
backend launcher (``backends.*.build_fn``) is inlined into a single
trace, producer outputs thread *directly* into consumer bindings, so
consumed intermediates never materialize as device buffers and XLA
fuses across launch boundaries.  CUDA graphs amortize launch overhead;
a fused XLA graph also deletes the memory traffic between launches.

* :class:`~repro.core.types.GraphRef` — capture-time placeholder for a
  captured launch's output; passing one to a later captured launch
  records a *data edge*.
* :class:`GraphNode` / :class:`GraphNodeHandle` — one captured
  ``LaunchRequest`` and its handle (the capture-mode stand-in for
  :class:`~repro.core.streams.LaunchHandle`, so ``kern.launch(...)``
  composes unchanged under capture).
* :class:`Graph` — ``capture()`` context manager (or drive
  ``stream.begin_capture()`` / ``end_capture()`` directly),
  ``instantiate()``, ``replay(**bindings)``.
* :class:`GraphExec` — an instantiated graph: the staged fused
  executable plus this instantiation's current input bindings (CUDA
  ``cudaGraphExec_t``; rebinding at replay is
  ``cudaGraphExecKernelNodeSetParams``).

The fused executable joins the dispatcher's shared staging LRU, keyed
by the captured DAG's per-node stage keys — two structurally identical
captures (same kernels, geometry, knobs, and edge structure) trace and
compile once.  The per-launch raw traces themselves are shared with
eager staging through ``Dispatcher.stage_fn``, so a graph over a kernel
the streams already launched re-traces nothing.

Replay semantics follow CUDA: inputs not rebound keep their captured
values, rebindings persist across replays, and replay is pure — it
never mutates the bound arrays, it returns fresh outputs (the
functional analogue of relaunching over the same device buffers).
"""
from __future__ import annotations

import contextlib
import functools
import itertools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import annotate_function

from . import errors as _errors
from . import faults as _faults
from .types import ArraySpec, CoxTypeError, CoxUnsupported, GraphRef

_names = itertools.count()


class GraphNode:
    """One captured launch: the request plus its schedule edges (stream
    program order + captured event edges + data edges), as node-index
    deps.  Capture order is a topological order by construction — every
    dep precedes its node — so instantiation never re-sorts."""

    __slots__ = ("graph", "idx", "req", "deps", "label")

    def __init__(self, graph: "Graph", idx: int, req, deps: Tuple[int, ...],
                 label: str):
        self.graph = graph
        self.idx = idx
        self.req = req
        self.deps = deps
        self.label = label

    def __repr__(self):
        return f"GraphNode({self.idx}:{self.label})"


class GraphNodeHandle:
    """Capture-mode stand-in for :class:`~repro.core.streams.
    LaunchHandle`: ``.outputs`` / ``.arrays()`` hand back
    :class:`~repro.core.types.GraphRef` placeholders (flat / reshaped,
    mirroring the eager handle's two endpoints) so dependent launches
    chain identically whether the stream is capturing or not.
    ``result()`` / ``done()`` raise — captured work has no results
    until the graph replays."""

    __slots__ = ("node",)

    def __init__(self, node: GraphNode):
        self.node = node

    @property
    def request(self):
        return self.node.req

    @property
    def graph(self) -> "Graph":
        return self.node.graph

    @property
    def stream(self):
        return self.node.req.stream

    def _refs(self, flat: bool) -> Dict[str, GraphRef]:
        req = self.node.req
        out = {}
        for s in req.ck.kernel.params:
            if not isinstance(s, ArraySpec):
                continue
            shape = tuple(req.shapes[s.name])
            if flat:
                shape = (int(np.prod(shape)),) if shape else (1,)
            out[s.name] = GraphRef(self.node, s.name, shape, s.dtype)
        return out

    @property
    def outputs(self) -> Dict[str, GraphRef]:
        """Flat placeholders — the async chaining endpoint."""
        return self._refs(flat=True)

    def arrays(self) -> Dict[str, GraphRef]:
        """Reshaped placeholders — what ``kern.launch`` returns."""
        return self._refs(flat=False)

    def done(self) -> bool:
        raise CoxUnsupported(
            f"{self.node!r} was captured, not launched — captured work "
            f"runs only at graph.replay(); there is no completion to "
            f"query")

    def result(self):
        raise CoxUnsupported(
            f"{self.node!r} was captured, not launched — captured work "
            f"runs only at graph.replay(); take outputs from the "
            f"replay's return value")


class Graph:
    """A captured launch DAG (CUDA ``cudaGraph_t``).

    Build one with :meth:`capture` (or ``stream.begin_capture(graph)``);
    :meth:`instantiate` stages the whole DAG as one fused executable;
    :meth:`replay` runs it with optionally rebound inputs.  A graph is
    immutable once instantiated — capture again into a fresh graph to
    change the schedule."""

    def __init__(self, name: Optional[str] = None):
        self.name = name or f"graph{next(_names)}"
        self.nodes: List[GraphNode] = []
        self._tails: Dict[Any, GraphNode] = {}   # stream -> captured tail
        self._streams: set = set()               # currently capturing
        self._disp = None
        self._exec: Optional["GraphExec"] = None
        self._frozen = False               # set by instantiate()

    def __repr__(self):
        return f"Graph({self.name!r}, nodes={len(self.nodes)})"

    def __len__(self):
        return len(self.nodes)

    # ------------- capture bookkeeping (driven by Stream) -------------

    def _attach_stream(self, stream) -> None:
        if self._frozen:
            raise CoxUnsupported(
                f"{self!r} is already instantiated — an instantiated "
                f"graph is immutable; capture into a fresh Graph")
        if self._disp is None:
            self._disp = stream.dispatcher
        elif stream.dispatcher is not self._disp:
            raise CoxUnsupported(
                f"{self!r}: all capturing streams must share one "
                f"dispatcher")
        self._streams.add(stream)

    def _detach_stream(self, stream) -> None:
        self._streams.discard(stream)

    def _tail_node(self, stream) -> Optional[GraphNode]:
        return self._tails.get(stream)

    @contextlib.contextmanager
    def capture(self, *streams):
        """Capture launches issued on ``streams`` (default: the default
        stream) into this graph for the duration of the ``with`` block —
        ``cudaStreamBeginCapture`` / ``cudaStreamEndCapture`` as a
        context manager."""
        from . import streams as _streams
        if not streams:
            streams = (_streams.get_dispatcher().default,)
        for s in streams:
            s.begin_capture(self)
        try:
            yield self
        finally:
            for s in streams:
                if s._capture is self:
                    s.end_capture()

    def add_request(self, req, *, stream) -> GraphNodeHandle:
        """Record one launch as a graph node (called by
        ``Stream.launch`` while capturing).  Schedule edges: the
        stream's captured tail plus any pending captured event edges;
        data edges: every :class:`GraphRef` argument."""
        if req.donate:
            raise CoxUnsupported(
                f"kernel '{req.ck.kernel.name}': donate=True is not "
                f"capturable — a replayed graph elides consumed "
                f"intermediates entirely (fusion already gives the "
                f"buffer reuse donation buys), and donating an external "
                f"input would consume the caller's buffer on every "
                f"replay")
        deps = []
        tail = self._tails.get(stream)
        if tail is not None:
            deps.append(tail.idx)
        deps.extend(stream._consume_capture_deps())
        for pname, val in (req.globals_ or {}).items():
            if isinstance(val, GraphRef):
                if val.node.graph is not self:
                    raise CoxUnsupported(
                        f"kernel '{req.ck.kernel.name}': argument "
                        f"'{pname}' references a launch captured in "
                        f"{val.node.graph!r}, not {self!r} — data edges "
                        f"cannot cross graphs")
                deps.append(val.node.idx)
        req.stream = stream
        node = GraphNode(self, len(self.nodes), req,
                         tuple(sorted(set(deps))), req.ck.kernel.name)
        self.nodes.append(node)
        self._tails[stream] = node
        return GraphNodeHandle(node)

    # ------------------------- instantiate -------------------------

    def instantiate(self, dispatcher=None, *, device=None) -> "GraphExec":
        """Stage the captured DAG as one fused executable and return a
        fresh :class:`GraphExec` bound to the captured input values.

        The executable joins the dispatcher's shared staging LRU keyed
        by the DAG's per-node stage keys, so instantiating twice — or
        instantiating a structurally identical second capture — traces
        and compiles exactly once (the second call is a stage hit);
        each :class:`GraphExec` still carries its *own* rebindable
        input state.

        ``device=`` places the instantiated graph: every replay commits
        its inputs to that device and runs there (CUDA: a graph
        launches into a stream on one device).  Left ``None``, the
        graph inherits the placed/pinned device of a capturing stream,
        if any — so a DAG captured on a placed stream replays where the
        stream's eager launches would have run."""
        if self._streams:
            raise CoxUnsupported(
                f"{self!r} is still capturing on "
                f"{sorted(s.name for s in self._streams)} — "
                f"end_capture() first")
        if not self.nodes:
            raise CoxUnsupported(
                f"{self!r} is empty — capture at least one launch "
                f"before instantiating")
        from . import streams as _streams
        disp = dispatcher or self._disp or _streams.get_dispatcher()
        spec = _binding_spec(self.nodes)
        key = ("graph",) + tuple(_node_sig(n, spec) for n in self.nodes)
        nodes = self.nodes

        def builder():
            return _trace_graph(disp, nodes, spec, self.name)

        exe, raw_fn = disp.stage_graph(key, builder)
        self._frozen = True                # the DAG is baked in; no edits
        if device is None:
            # inherit a capturing stream's placement (pin or policy
            # assignment) — replay runs where eager issue would have
            device = next((s._device for s in self._tails
                           if getattr(s, "_device", None) is not None),
                          None)
        return GraphExec(self, disp, exe, raw_fn, spec, device=device)

    def replay(self, **bindings) -> Dict[str, Any]:
        """Instantiate lazily (once), then replay — the one-call CUDA
        ``cudaGraphLaunch`` convenience.  Rebindings persist across
        replays on the underlying :class:`GraphExec`."""
        if self._exec is None:
            self._exec = self.instantiate()
        return self._exec.replay(**bindings)


def _binding_spec(nodes: List[GraphNode]) -> Dict[str, Any]:
    """Resolve the captured DAG's dataflow into a static spec:

    * ``node_bindings`` — per node, per param: ``('ref', producer_idx,
      out_name)`` (a data edge) or ``('ext'|'sext', canonical_name)``
      (an external array / scalar input);
    * ``inputs`` — canonical input name → (node idx, param name, kind);
    * ``dtypes`` — canonical input name → DType (the in-trace cast);
    * ``outputs`` — canonical output name → (node idx, out name) over
      the *terminal* outputs (never consumed by a later node —
      consumed intermediates are elided from the fused program);
    * ``aliases`` — bare param name → every canonical input it names.

    Canonical names are the bare param name when it is unique among
    external inputs, else ``{param}_n{node_idx}`` — derived purely from
    DAG structure, so structurally identical captures agree on names
    (a requirement for sharing the staged executable)."""
    ext_counts: Dict[str, int] = {}
    for n in nodes:
        req = n.req
        for s in req.ck.kernel.params:
            if isinstance(s, ArraySpec) and isinstance(
                    req.globals_[s.name], GraphRef):
                continue
            ext_counts[s.name] = ext_counts.get(s.name, 0) + 1

    def canon(pname: str, idx: int) -> str:
        return pname if ext_counts[pname] == 1 else f"{pname}_n{idx}"

    inputs: Dict[str, tuple] = {}
    dtypes: Dict[str, Any] = {}
    aliases: Dict[str, List[str]] = {}
    node_bindings: List[tuple] = []
    consumed = set()
    for n in nodes:
        req = n.req
        binds = []
        for s in req.ck.kernel.params:
            if isinstance(s, ArraySpec):
                v = req.globals_[s.name]
                if isinstance(v, GraphRef):
                    binds.append((s.name, ("ref", v.node.idx, v.name)))
                    consumed.add((v.node.idx, v.name))
                    continue
                c = canon(s.name, n.idx)
                binds.append((s.name, ("ext", c)))
                inputs[c] = (n.idx, s.name, "array")
            else:
                c = canon(s.name, n.idx)
                binds.append((s.name, ("sext", c)))
                inputs[c] = (n.idx, s.name, "scalar")
            dtypes[c] = s.dtype
            aliases.setdefault(s.name, []).append(c)
        node_bindings.append(tuple(binds))

    term = [(n.idx, s.name) for n in nodes for s in n.req.ck.kernel.params
            if isinstance(s, ArraySpec) and (n.idx, s.name) not in consumed]
    tcounts: Dict[str, int] = {}
    for _, nm in term:
        tcounts[nm] = tcounts.get(nm, 0) + 1
    outputs = {(nm if tcounts[nm] == 1 else f"{nm}_n{i}"): (i, nm)
               for i, nm in term}
    return {"node_bindings": tuple(node_bindings), "inputs": inputs,
            "dtypes": dtypes, "outputs": outputs, "aliases": aliases}


def _node_sig(node: GraphNode, spec: Dict[str, Any]) -> tuple:
    """One node's contribution to the graph stage key: kernel identity
    (``id(ck)`` — safe because the staged executable closes over the
    nodes, keeping every ck alive), the raw-launcher key (geometry +
    knobs sans donate), and the binding structure.  Schedule-only edges
    are deliberately absent: values flow exclusively through data
    edges, so captures differing only in event edges run the same
    program."""
    req = node.req
    return ((id(req.ck),) + req.fn_key()
            + spec["node_bindings"][node.idx])


def _trace_graph(disp, nodes: List[GraphNode], spec: Dict[str, Any],
                 name: str):
    """Build the fused executable: one ``jax.jit`` program that walks
    the nodes in capture (= topological) order, threading producer
    outputs straight into consumer bindings.  External inputs arrive as
    one dict pytree; the eager path's dtype-cast + flatten happens
    *inside* the trace (a no-op for the captured defaults, the
    conversion point for rebound values).  Returns only terminal
    outputs — consumed intermediates exist solely as values inside the
    trace, free for XLA to fuse away.  Returns ``(jitted, raw)`` — the
    fused executable, jitted as ``cox_graph_<name>`` (XLA module
    ``jit_cox_graph_<name>``), plus the un-jitted trace function, the
    replay → eager fallback rung of the degradation ladder.

    A node that fails to stage fails the whole instantiation with *its
    own* typed error (:func:`~repro.core.errors.classify`, naming the
    node) — there is no partial graph."""
    staged = []                                      # [(plan, fn)] raw
    for n in nodes:
        fault = _faults.consume("stage", n.label)
        if fault is not None:
            raise fault
        try:
            staged.append(disp.stage_fn(n.req))
        except Exception as e:
            raise _errors.classify(
                e, site="stage",
                what=f"graph node {n.idx} (kernel '{n.label}')")
    node_bindings = spec["node_bindings"]
    outputs = spec["outputs"]
    dtypes = spec["dtypes"]

    def graph_fn(ext):
        vals: Dict[tuple, Any] = {}
        for (_, fn), n, binds in zip(staged, nodes, node_bindings):
            g, s = {}, {}
            for pname, b in binds:
                if b[0] == "ref":
                    g[pname] = vals[(b[1], b[2])]
                elif b[0] == "ext":
                    g[pname] = jnp.asarray(ext[b[1]],
                                           dtypes[b[1]].jnp).reshape(-1)
                else:
                    s[pname] = jnp.asarray(ext[b[1]], dtypes[b[1]].jnp)
            out = fn(g, s)
            for k, v in out.items():
                vals[(n.idx, k)] = v
        return {c: vals[t] for c, t in outputs.items()}

    graph_fn.__name__ = graph_fn.__qualname__ = f"cox_graph_{name}"
    return jax.jit(graph_fn), graph_fn


class GraphExec:
    """An instantiated graph (CUDA ``cudaGraphExec_t``): the shared
    fused executable plus *this* instantiation's input bindings.

    ``replay(**bindings)`` updates named inputs (bare param name when
    unambiguous, ``{param}_n{node}`` to address one node's binding —
    a bare name naming several bindings updates all of them) and runs
    the staged program: one dict update and one executable call, zero
    per-launch host work.  Un-rebound inputs keep their current values;
    rebindings persist across replays
    (``cudaGraphExecKernelNodeSetParams`` semantics)."""

    def __init__(self, graph: Graph, disp, exe, raw_fn,
                 spec: Dict[str, Any], *, device=None):
        self._graph = graph
        self._disp = disp
        self._exe = exe
        self._raw_fn = raw_fn        # un-jitted fallback (eager rung)
        self._device = device        # placed replay target (None: legacy)
        self._aliases = spec["aliases"]
        self._outputs = spec["outputs"]
        self._vals = {}
        for c, (nidx, pname, kind) in spec["inputs"].items():
            req = graph.nodes[nidx].req
            self._vals[c] = (req.globals_[pname] if kind == "array"
                             else req.scalars[pname])
        self._out_shapes = {c: tuple(graph.nodes[i].req.shapes[nm])
                            for c, (i, nm) in spec["outputs"].items()}

    @property
    def graph(self) -> Graph:
        return self._graph

    @property
    def device(self):
        """The device replays run on (``None``: unplaced legacy path)."""
        return self._device

    @property
    def input_names(self) -> Tuple[str, ...]:
        return tuple(self._vals)

    @property
    def output_names(self) -> Tuple[str, ...]:
        return tuple(self._outputs)

    @functools.partial(annotate_function, name="cox.graph.replay")
    def replay(self, **bindings) -> Dict[str, Any]:
        for name, val in bindings.items():
            if name in self._vals:
                self._vals[name] = val
            elif name in self._aliases:
                for c in self._aliases[name]:
                    if c in self._vals:
                        self._vals[c] = val
            else:
                raise KeyError(
                    f"graph {self._graph.name!r} has no input {name!r}; "
                    f"inputs: {sorted(self._vals)}")
        gname = self._graph.name
        dev = self._device
        if dev is not None:
            from .streams import _to_device
            with self._disp._lock:
                sticky = self._disp._sticky_for(dev)
            if sticky is not None:
                # a placed graph replays on *its* device — a poisoned
                # device fails the replay with its sticky error (route-
                # around is a placement-time decision, not a replay one)
                raise sticky
            # the transfer node: commit inputs to the placed device
            # (no-op for already-resident buffers) and keep the
            # committed arrays so later replays skip the put
            self._vals = {k: _to_device(v, dev)
                          for k, v in self._vals.items()}
        fault = _faults.consume("dispatch", gname)
        try:
            if fault is not None:
                raise fault
            flat = self._exe(self._vals)
        except Exception as e:
            err = _errors.classify(e, site="dispatch",
                                   what=f"graph '{gname}'")
            if (_errors.is_sticky(err)
                    or isinstance(err, (CoxUnsupported, CoxTypeError))):
                raise err            # user/device errors: no fallback
            # graph-replay → eager fallback: the last ladder rung — run
            # the same trace un-jitted (bitwise-identical by
            # construction), and log the degradation on the dispatcher
            disp = self._disp
            event = {"kernel": gname, "seq": -1,
                     "from": "graph-replay", "to": "eager",
                     "error": repr(err)}
            with disp._lock:
                disp.degradations += 1
                disp.degradation_log.append(event)
            flat = self._raw_fn(dict(self._vals))
        with self._disp._lock:
            self._disp._bump_dev(dev, "dispatches")
        return {c: v.reshape(self._out_shapes[c]) for c, v in flat.items()}

    __call__ = replay
