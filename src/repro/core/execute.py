"""Two-level vectorized executor for hierarchically-collapsed kernels.

Generated-code shape (paper Code 3):

    for each block-level PR:                 # block machine node
        for wid in range(n_warps):           # inter-warp loop
            run the PR's warp-level machine  # warp PRs + peeled branches
              — every warp PR evaluates all W lanes at once (intra-warp
                "loop" == the vector lane axis; AVX in the paper, VPU
                lanes on TPU, XLA-vectorized on CPU)

Loop peeling (paper §3.3.1 / Code 3 line 10): branch conditions are
evaluated by *all* lanes (side effects preserved) but the branch
direction is taken from lane 0 (warp level) or warp 0 lane 0 (block
level) — sound under the aligned-barrier assumption.

Modes:
* ``jit``    — inter-warp loops unrolled at trace time (block size burned
               in; the paper's JIT mode, Fig. 13) and static-trip
               predicated loops unrolled;
* ``normal`` — `lax.fori_loop` inter-warp loop, one trace serves any
               grid; block size still static per JAX shape rules (the
               runtime-configuration analogue).

Warp execution (``warp_exec``, orthogonal to the mode):
* ``serial``  — the inter-warp loop above: one warp at a time threads
                through each block-level PR (the paper's Code 3 shape);
* ``batched`` — COX's guarantee that warps are independent *between
                barriers* is exposed to XLA: all ``n_warps`` warps of a
                block-level PR run simultaneously as one ``(n_warps, W)``
                lane plane (``jax.vmap`` over the warp axis of the
                warp-level machine walk).  Each warp runs on its own copy
                of shared memory and global memory with write-mask /
                atomic-delta tracking; the copies are reconciled at every
                block-level PR boundary (== every block barrier) by the
                same bit-exact single-writer select merge the grid backends
                use (``backends/merge.py``) — bitwise-identical to serial
                execution for race-free kernels.  Block-replicated vars
                are handed to each warp as its own (W,) row; the stacked
                rows are the merged plane.  Warp-peel branch directions become
                per-warp (each warp's lane 0 decides; divergent warps
                advance their PC machines independently under vmap's
                masked while/switch batching).

``simd=False`` switches warp collectives to per-lane loop emulation
(Table 2's "w/o AVX" baseline).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import collectives
from . import kernel_ir as K
from .cfg import CFG, WarpBufCompute, WarpBufStore
from .lower import lower_kernel
from .passes import (insert_extra_barriers, lower_warp_intrinsics,
                     split_blocks_at_barriers)
from .regions import (EXIT, BlockPR, Machine, WarpPR, build_machine,
                      replication_classes, warp_peel_count)
from .typeinfer import infer
from .types import (ArraySpec, CoxUnsupported, DType, ScalarSpec,
                    dim3_tuple)

_UNROLL_LIMIT = 64  # static-trip predicated loops up to this are unrolled in jit mode


def _prod(shape) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out


# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CompiledKernel:
    """Result of the full pass pipeline, ready to stage into JAX.

    A kernel with grid-wide barriers (``c.grid_sync()``) compiles to a
    *multi-phase* container: ``phases`` holds one ordinary single-phase
    CompiledKernel per inter-sync program segment (``repro.core.phases``)
    and ``cfg``/``machine``/``classes`` of the container itself are
    unset — backends build one executable per phase and thread global
    memory plus the ``carried`` per-block state between them.  Kernels
    without a grid sync compile exactly as before (``phases == ()``).
    """
    kernel: K.Kernel
    cfg: Optional[CFG]
    machine: Optional[Machine]
    var_types: Dict[str, DType]
    classes: Dict[str, str]            # var -> 'block' | 'warp'
    warp_bufs: Dict[str, DType]
    warp_size: int
    phases: Tuple["CompiledKernel", ...] = ()   # per-phase compilations
    carried: Tuple[str, ...] = ()               # locals live across phases

    @property
    def n_phases(self) -> int:
        return len(self.phases) or 1

    def phase_list(self) -> Tuple["CompiledKernel", ...]:
        """The executable phase sequence — ``(self,)`` for the ordinary
        single-phase case."""
        return self.phases or (self,)

    @property
    def array_params(self) -> List[ArraySpec]:
        return [p for p in self.kernel.params if isinstance(p, ArraySpec)]

    @property
    def scalar_params(self) -> List[ScalarSpec]:
        return [p for p in self.kernel.params if isinstance(p, ScalarSpec)]

    def summary(self) -> str:
        if self.phases:
            inner = "; ".join(p.summary() for p in self.phases)
            return (f"kernel {self.kernel.name}: {len(self.phases)} "
                    f"grid-sync phases, {len(self.carried)} carried "
                    f"locals [{inner}]")
        n_bpr = sum(isinstance(n, BlockPR) for n in self.machine.nodes)
        n_wpr = sum(
            sum(isinstance(w, WarpPR) for w in n.warp.nodes)
            for n in self.machine.nodes if isinstance(n, BlockPR))
        n_peel = warp_peel_count(self.machine)
        return (f"kernel {self.kernel.name}: {len(self.cfg.blocks)} blocks, "
                f"{n_bpr} block-level PRs, {n_wpr} warp-level PRs, "
                f"{n_peel} warp peels, "
                f"{len([v for v, c in self.classes.items() if c == 'block'])} "
                f"block-replicated vars")


def compile_kernel(kernel: K.Kernel, warp_size: int = 32) -> CompiledKernel:
    """Run the hierarchical-collapsing pipeline (paper Fig. 4 steps 1-5).

    Kernels using ``c.grid_sync()`` are phase-split first (see
    ``repro.core.phases``): type inference runs once over the full
    kernel so cross-phase locals agree, each phase runs the unchanged
    pipeline, and locals live across phase boundaries are forced to the
    'block' replication class in every phase so their ``(n_warps, W)``
    planes can be carried between phase executables."""
    from .phases import carried_locals, split_phases
    phase_kernels = split_phases(kernel)
    if len(phase_kernels) == 1:
        return _compile_one(kernel, warp_size, infer(kernel), ())
    var_types = infer(kernel)          # full-kernel: cross-phase var types
    carried = tuple(sorted(carried_locals(kernel, phase_kernels)))
    compiled = tuple(_compile_one(pk, warp_size, dict(var_types), carried)
                     for pk in phase_kernels)
    uniforms = {p.name for p in kernel.params if isinstance(p, ScalarSpec)}
    vt = {v: t for v, t in var_types.items() if v not in uniforms}
    return CompiledKernel(kernel, None, None, vt, {}, {}, warp_size,
                          phases=compiled, carried=carried)


def _compile_one(kernel: K.Kernel, warp_size: int,
                 var_types: Dict[str, DType],
                 force_block: Tuple[str, ...]) -> CompiledKernel:
    """The single-phase pipeline (paper Fig. 4 steps 1-5)."""
    cfg = lower_kernel(kernel)
    warp_bufs = lower_warp_intrinsics(cfg, var_types)
    for b, dt in warp_bufs.items():
        var_types[b] = dt
    insert_extra_barriers(cfg)
    split_blocks_at_barriers(cfg)
    cfg.verify()
    machine = build_machine(cfg)
    uniforms = {p.name for p in kernel.params if isinstance(p, ScalarSpec)}
    for u in uniforms:  # scalar params are block-uniform, never replicated
        var_types.pop(u, None)
    classes = replication_classes(machine, uniforms)
    # every var assigned anywhere must have a class; default to warp-local
    for v in var_types:
        classes.setdefault(v, "warp")
    # cross-phase locals must live in the carried (n_warps, W) plane
    for v in force_block:
        classes[v] = "block"
    return CompiledKernel(kernel, cfg, machine, var_types, classes,
                          warp_bufs, warp_size)


# ---------------------------------------------------------------------------
# Environment
# ---------------------------------------------------------------------------


class _Env:
    """Mutable view over the machine state for one (block, warp) context."""

    def __init__(self, ck: CompiledKernel, *, wid, n_warps: int,
                 uniforms: Dict[str, Any], warp_vars: Dict[str, Any],
                 block_vars: Dict[str, Any], shmem: Dict[str, Any],
                 globals_: Dict[str, Any], simd: bool,
                 track_writes: bool = False,
                 store_masks: Optional[Dict[str, Any]] = None,
                 atomic_deltas: Optional[Dict[str, Any]] = None,
                 shared_masks: Optional[Dict[str, Any]] = None,
                 block_rows: bool = False,
                 log_arrays: Optional[Set[str]] = None,
                 block_dim3: Optional[Tuple[int, int, int]] = None,
                 grid_dim3: Optional[Tuple[int, int, int]] = None):
        self.ck = ck
        # static dim3 extents for the per-axis intrinsics; None means a
        # 1-D launch whose extents live in the uniforms (tid_x/bid_x are
        # the linear ids, y/z are zero)
        self.block_dim3 = block_dim3
        self.grid_dim3 = grid_dim3
        self.W = ck.warp_size
        self.wid = wid
        self.n_warps = n_warps
        self.uniforms = uniforms
        # Pre-allocate every warp-replicated local so lax control-flow
        # carries have a stable pytree structure.
        if not warp_vars:
            warp_vars = {
                v: jnp.zeros((self.W,), ck.var_types.get(v, DType.f32).jnp)
                for v, c in ck.classes.items()
                if c == "warp" and v not in uniforms}
        self.warp_vars = warp_vars
        self.block_vars = block_vars
        self.shmem = shmem
        self.globals = globals_
        self.simd = simd
        self.track_writes = track_writes
        self.store_masks = store_masks if store_masks is not None else {}
        self.atomic_deltas = atomic_deltas if atomic_deltas is not None else {}
        # shared-memory write masks: tracked only under warp-batched
        # execution, where each warp runs on its own copy of shared
        # memory and the copies merge at block-level PR boundaries
        self.track_shared = shared_masks is not None
        self.shared_masks = shared_masks if shared_masks is not None else {}
        # batched warp plane: block-replicated vars are handed to each
        # warp as its own (W,) row (a warp never touches another warp's
        # row, so the full (n_warps, W) plane would only buy every
        # write a batched scatter); serial mode keeps the plane and
        # indexes it with wid
        self.block_rows = block_rows
        # store log (batched warp plane): stores to arrays this PR never
        # reads skip the copy/mask machinery entirely — each executed
        # StoreGlobal appends its (safe idx, value) lane vectors here and
        # the plane runner replays them onto the carried array with one
        # flat scatter per store instruction, O(n_warps × W) instead of
        # O(n_warps × |array|)
        self.log_arrays = log_arrays if log_arrays is not None else set()
        self.store_log: List[Tuple[str, Any, Any]] = []
        self.lane = jnp.arange(self.W, dtype=jnp.int32)

    @property
    def base_mask(self):
        tid = jnp.asarray(self.wid, jnp.int32) * self.W + self.lane
        return tid < jnp.asarray(self.uniforms["bdim"], jnp.int32)

    # ---------------- state snapshot (for lax control flow) ----------------

    def state(self) -> Dict[str, Any]:
        return {"wv": dict(self.warp_vars), "bv": dict(self.block_vars),
                "sh": dict(self.shmem), "g": dict(self.globals),
                "sm": dict(self.store_masks), "ad": dict(self.atomic_deltas),
                "shm": dict(self.shared_masks)}

    def load(self, st: Dict[str, Any]):
        self.warp_vars = dict(st["wv"])
        self.block_vars = dict(st["bv"])
        self.shmem = dict(st["sh"])
        self.globals = dict(st["g"])
        self.store_masks = dict(st["sm"])
        self.atomic_deltas = dict(st["ad"])
        self.shared_masks = dict(st["shm"])

    # ---------------- variables ----------------

    def _dtype(self, name: str) -> DType:
        return self.ck.var_types.get(name, DType.f32)

    def read_var(self, name: str):
        if name in self.uniforms:
            return jnp.asarray(self.uniforms[name])
        cls = self.ck.classes.get(name, "warp")
        if cls == "warp":
            return self.warp_vars[name]
        if self.block_rows:
            return self.block_vars[name]
        return self.block_vars[name][self.wid]

    def write_var(self, name: str, value, mask=None):
        dt = self._dtype(name).jnp
        value = jnp.broadcast_to(jnp.asarray(value).astype(dt), (self.W,))
        if mask is not None:
            value = jnp.where(mask, value, self.read_var(name))
        cls = self.ck.classes.get(name, "warp")
        if cls == "warp":
            self.warp_vars[name] = value
        elif self.block_rows:
            self.block_vars[name] = value
        else:
            self.block_vars[name] = self.block_vars[name].at[self.wid].set(value)


# ---------------------------------------------------------------------------
# Expression evaluation (vectorized across the warp's lanes)
# ---------------------------------------------------------------------------

_BINOPS = {
    "+": jnp.add, "-": jnp.subtract, "*": jnp.multiply,
    "%": jnp.remainder, "&": None, "|": None, "^": None,
    "<<": jnp.left_shift, ">>": jnp.right_shift,
    "min": jnp.minimum, "max": jnp.maximum,
}

_CMPS = {"<": jnp.less, "<=": jnp.less_equal, ">": jnp.greater,
         ">=": jnp.greater_equal, "==": jnp.equal, "!=": jnp.not_equal}


def eval_expr(e: K.Expr, env: _Env):
    if isinstance(e, K.Const):
        return jnp.asarray(e.value, (e.dtype or DType.f32).jnp)
    if isinstance(e, K.Var):
        return env.read_var(e.name)
    if isinstance(e, K.Special):
        return _eval_special(e, env)
    if isinstance(e, K.BinOp):
        a, b = eval_expr(e.lhs, env), eval_expr(e.rhs, env)
        if e.op == "/":
            return jnp.true_divide(a.astype(jnp.float32), b.astype(jnp.float32)) \
                if not (jnp.issubdtype(a.dtype, jnp.floating)
                        or jnp.issubdtype(b.dtype, jnp.floating)) \
                else jnp.true_divide(a, b)
        if e.op == "//":
            return jnp.floor_divide(a, b)
        if e.op in ("&", "|", "^"):
            if a.dtype == jnp.bool_ or b.dtype == jnp.bool_:
                f = {"&": jnp.logical_and, "|": jnp.logical_or,
                     "^": jnp.logical_xor}[e.op]
                return f(a, b)
            f = {"&": jnp.bitwise_and, "|": jnp.bitwise_or,
                 "^": jnp.bitwise_xor}[e.op]
            return f(a, b)
        return _BINOPS[e.op](a, b)
    if isinstance(e, K.CmpOp):
        return _CMPS[e.op](eval_expr(e.lhs, env), eval_expr(e.rhs, env))
    if isinstance(e, K.BoolOp):
        vals = [eval_expr(a, env).astype(jnp.bool_) for a in e.args]
        out = vals[0]
        for v in vals[1:]:
            out = jnp.logical_and(out, v) if e.op == "and" else jnp.logical_or(out, v)
        return out
    if isinstance(e, K.UnOp):
        v = eval_expr(e.operand, env)
        if e.op == "neg":
            return -v
        if e.op == "not":
            return jnp.logical_not(v.astype(jnp.bool_))
        if e.op == "abs":
            return jnp.abs(v)
        if e.op in ("f32", "i32", "f16", "bf16", "u32"):
            return v.astype(DType(e.op).jnp)
        if e.op == "rsqrt":
            return lax.rsqrt(v.astype(jnp.float32))
        if e.op == "sigmoid":
            return jax.nn.sigmoid(v.astype(jnp.float32))
        fn = {"exp": jnp.exp, "log": jnp.log, "sqrt": jnp.sqrt,
              "tanh": jnp.tanh, "floor": jnp.floor}[e.op]
        return fn(v.astype(jnp.float32) if v.dtype in
                  (jnp.int32, jnp.bool_) else v)
    if isinstance(e, K.Select):
        return jnp.where(eval_expr(e.cond, env).astype(jnp.bool_),
                         eval_expr(e.on_true, env), eval_expr(e.on_false, env))
    if isinstance(e, K.LoadGlobal):
        idx = eval_expr(e.index, env).astype(jnp.int32)
        arr = env.globals[e.array]
        val = arr.at[idx].get(mode="fill", fill_value=0)
        if env.track_writes and e.array in env.atomic_deltas:
            val = val + env.atomic_deltas[e.array].at[idx].get(
                mode="fill", fill_value=0)
        return val
    if isinstance(e, K.LoadShared):
        idx = eval_expr(e.index, env).astype(jnp.int32)
        return env.shmem[e.array].at[idx].get(mode="fill", fill_value=0)
    raise CoxUnsupported(f"cannot evaluate {e!r}")


_AXIS_IX = {"x": 0, "y": 1, "z": 2}


def _decompose(lin, extents, axis: str):
    """x-fastest dim3 decomposition of a linear id against static
    extents, with degenerate-axis shortcuts that keep 1-D launches free
    of mod/div ops and 2-D launches down to one op per axis (lanes past
    the logical extent — the partial last warp — produce out-of-range
    components exactly as the linear path always has; their stores are
    masked off)."""
    dx, dy, dz = extents
    if axis == "x":
        return lin if dy == 1 and dz == 1 else lin % dx
    if axis == "y":
        if dy == 1:
            return jnp.zeros_like(lin)
        return lin // dx if dz == 1 else (lin // dx) % dy
    return jnp.zeros_like(lin) if dz == 1 else lin // (dx * dy)


def _eval_special(e: K.Special, env: _Env):
    """Thread-identity intrinsics.  The schedule is linear (warps over
    the x-fastest linearized block, a lax walk over linear block ids);
    per-axis values are cheap decompositions against the launch's
    static dim3 extents — per-lane (tx, ty, tz) vectors and per-block
    (bx, by, bz) uniforms."""
    if e.kind == "lane":
        return env.lane
    if e.kind == "wid":
        return jnp.broadcast_to(jnp.asarray(env.wid, jnp.int32), (env.W,))
    if e.kind == "wsize":
        return jnp.asarray(env.W, jnp.int32)
    axis = getattr(e, "axis", "x")
    if e.kind == "tid":
        lin = jnp.asarray(env.wid, jnp.int32) * env.W + env.lane
        if env.block_dim3 is None:  # direct make_block_fn caller: 1-D
            return lin if axis == "x" else jnp.zeros_like(lin)
        return _decompose(lin, env.block_dim3, axis)
    if e.kind == "bid":
        bid = jnp.asarray(env.uniforms["bid"], jnp.int32)
        if env.grid_dim3 is None:
            return bid if axis == "x" else jnp.zeros_like(bid)
        return _decompose(bid, env.grid_dim3, axis)
    if e.kind == "bdim":
        if env.block_dim3 is None:
            return jnp.asarray(env.uniforms["bdim"], jnp.int32)
        return jnp.asarray(env.block_dim3[_AXIS_IX[axis]], jnp.int32)
    if e.kind == "gdim":
        if env.grid_dim3 is None:
            return jnp.asarray(env.uniforms["gdim"], jnp.int32)
        return jnp.asarray(env.grid_dim3[_AXIS_IX[axis]], jnp.int32)
    return jnp.asarray(env.uniforms[e.kind], jnp.int32)


# ---------------------------------------------------------------------------
# Instruction execution (with predication masks for barrier-free divergence)
# ---------------------------------------------------------------------------


def _store_mask(env: _Env, mask):
    m = env.base_mask
    return m if mask is None else (m & mask)


def _safe_idx(idx, m, size):
    idx = jnp.broadcast_to(idx.astype(jnp.int32), m.shape)
    return jnp.where(m, idx, jnp.int32(size))  # size == one-past-end → dropped


def exec_instrs(instrs: List, env: _Env, mask, *, jit_mode: bool):
    for ins in instrs:
        exec_instr(ins, env, mask, jit_mode=jit_mode)


def exec_instr(ins, env: _Env, mask, *, jit_mode: bool):
    if isinstance(ins, K.Assign):
        env.write_var(ins.name, eval_expr(ins.value, env), mask)
    elif isinstance(ins, K.StoreGlobal):
        m = _store_mask(env, mask)
        arr = env.globals[ins.array]
        idx = _safe_idx(eval_expr(ins.index, env), m, arr.shape[0])
        val = jnp.broadcast_to(
            jnp.asarray(eval_expr(ins.value, env)).astype(arr.dtype), m.shape)
        if ins.array in env.log_arrays:
            env.store_log.append((ins.array, idx, val))
            return
        env.globals[ins.array] = arr.at[idx].set(val, mode="drop")
        if env.track_writes:
            sm = env.store_masks[ins.array]
            env.store_masks[ins.array] = sm.at[idx].set(True, mode="drop")
    elif isinstance(ins, K.StoreShared):
        m = _store_mask(env, mask)
        arr = env.shmem[ins.array]
        idx = _safe_idx(eval_expr(ins.index, env), m, arr.shape[0])
        val = jnp.broadcast_to(
            jnp.asarray(eval_expr(ins.value, env)).astype(arr.dtype), m.shape)
        env.shmem[ins.array] = arr.at[idx].set(val, mode="drop")
        if env.track_shared:
            shm = env.shared_masks[ins.array]
            env.shared_masks[ins.array] = shm.at[idx].set(True, mode="drop")
    elif isinstance(ins, K.AtomicRMW):
        m = _store_mask(env, mask)
        if env.track_writes:
            tgt = env.atomic_deltas[ins.array]
        else:
            tgt = env.globals[ins.array]
        idx = _safe_idx(eval_expr(ins.index, env), m, tgt.shape[0])
        val = jnp.broadcast_to(
            jnp.asarray(eval_expr(ins.value, env)).astype(tgt.dtype), m.shape)
        if ins.dst:
            if env.track_writes:
                # tgt is the per-block delta buffer (zeroed per block),
                # NOT the value a serial execution would observe, and
                # cross-block uniqueness of captured old values (ticket
                # patterns) cannot hold under delta merging at all.
                # LaunchPlan.check_mergeable rejects such launches
                # before tracing; this guard catches any future
                # make_block_fn caller that skips it.
                raise CoxUnsupported(
                    "atomic old-value capture under write-tracking: "
                    "captured old values are only exact under serial "
                    "execution — use the scan backend")
            old = tgt.at[jnp.where(m, idx, 0)].get(mode="fill", fill_value=0)
            env.write_var(ins.dst, old, mask)
        if ins.op == "add":
            new = tgt.at[idx].add(val, mode="drop")
        elif ins.op == "max":
            new = tgt.at[idx].max(val, mode="drop")
        else:
            new = tgt.at[idx].min(val, mode="drop")
        if env.track_writes:
            env.atomic_deltas[ins.array] = new
        else:
            env.globals[ins.array] = new
    elif isinstance(ins, K.Barrier):
        pass  # structural only — ordering is preserved by lane vectorization
    elif isinstance(ins, WarpBufStore):
        if mask is not None:
            raise CoxUnsupported(
                "warp collective inside divergent (predicated) control flow — "
                "dynamic-mask collectives are outside the supported set "
                "(paper §2.2.3)")
        env.write_var(ins.buf, eval_expr(ins.value, env), None)
    elif isinstance(ins, WarpBufCompute):
        if mask is not None:
            raise CoxUnsupported("warp collective inside divergent control flow")
        buf = env.read_var(ins.buf)
        fn = collectives.dispatch(ins.func, env.simd)
        extra = [eval_expr(a, env) for a in ins.args]
        res = fn(buf, *extra, W=env.W, width=ins.width, mask=env.base_mask)
        env.write_var(ins.dst, res, None)
    elif isinstance(ins, K.If):
        cond = eval_expr(ins.cond, env).astype(jnp.bool_)
        cond = jnp.broadcast_to(cond, (env.W,))
        m_t = cond if mask is None else (mask & cond)
        exec_instrs(ins.then_body, env, m_t, jit_mode=jit_mode)
        if ins.else_body:
            m_f = ~cond if mask is None else (mask & ~cond)
            exec_instrs(ins.else_body, env, m_f, jit_mode=jit_mode)
    elif isinstance(ins, K.While):
        _exec_masked_while(ins, env, mask, jit_mode=jit_mode)
    elif isinstance(ins, K.Return):
        raise CoxUnsupported("return must terminate the kernel")
    else:
        raise CoxUnsupported(f"cannot execute {ins!r}")


def _written_names(instrs) -> Tuple[Set[str], Set[str], Set[str], Set[str]]:
    """(variables, global arrays, shared arrays, atomic targets) a
    statement list may write, descending into If/While — the minimal lax
    carry for a loop, and the minimal per-warp copy/merge set for the
    batched warp plane.  Atomic targets are also members of the global
    set; they are reported separately because they merge by delta sum,
    not writer selection."""
    wv: Set[str] = set()
    arrays: Set[str] = set()
    sh: Set[str] = set()
    atomics: Set[str] = set()
    stack = list(instrs)
    while stack:
        s = stack.pop()
        if isinstance(s, K.Assign):
            wv.add(s.name)
        elif isinstance(s, K.StoreGlobal):
            arrays.add(s.array)
        elif isinstance(s, K.StoreShared):
            sh.add(s.array)
        elif isinstance(s, K.AtomicRMW):
            arrays.add(s.array)
            atomics.add(s.array)
            if s.dst:
                wv.add(s.dst)
        elif isinstance(s, WarpBufStore):
            wv.add(s.buf)
        elif isinstance(s, WarpBufCompute):
            wv.add(s.dst)
        elif isinstance(s, K.If):
            stack.extend(s.then_body)
            stack.extend(s.else_body)
        elif isinstance(s, K.While):
            stack.extend(s.body)
    return wv, arrays, sh, atomics


def _instr_exprs(s):
    """Every expression an instruction evaluates (not descending into
    nested statements)."""
    if isinstance(s, K.Assign):
        return [s.value]
    if isinstance(s, (K.StoreGlobal, K.StoreShared)):
        return [s.index, s.value]
    if isinstance(s, K.AtomicRMW):
        return [s.index, s.value]
    if isinstance(s, WarpBufStore):
        return [s.value]
    if isinstance(s, WarpBufCompute):
        return list(s.args)
    if isinstance(s, K.If):
        return [s.cond]
    if isinstance(s, K.While):
        return [s.cond]
    return []


def _loaded_globals(instrs) -> Set[str]:
    """Global arrays any expression in ``instrs`` may read."""
    out: Set[str] = set()
    stack = list(instrs)
    estack: List[K.Expr] = []
    while stack:
        s = stack.pop()
        estack.extend(_instr_exprs(s))
        if isinstance(s, K.If):
            stack.extend(s.then_body)
            stack.extend(s.else_body)
        elif isinstance(s, K.While):
            stack.extend(s.body)
    while estack:
        e = estack.pop()
        if isinstance(e, K.LoadGlobal):
            out.add(e.array)
        estack.extend(K.expr_children(e))
    return out


def _stored_in_while(instrs, in_while: bool = False) -> Set[str]:
    """Global arrays stored from inside a While body — their stores
    execute inside a lax.while trace, so they cannot use the store log
    (log entries must escape to the post-vmap replay)."""
    out: Set[str] = set()
    for s in instrs:
        if isinstance(s, K.StoreGlobal) and in_while:
            out.add(s.array)
        elif isinstance(s, K.If):
            out |= _stored_in_while(s.then_body, in_while)
            out |= _stored_in_while(s.else_body, in_while)
        elif isinstance(s, K.While):
            out |= _stored_in_while(s.body, True)
    return out


@dataclasses.dataclass(frozen=True)
class _PRPlan:
    """Static per-block-level-PR execution plan for the batched warp
    plane: what to copy/mask/merge, and which stores can go through the
    replay log instead."""
    block_vars: Tuple[str, ...]   # block-replicated vars written
    shared: Tuple[str, ...]       # shared arrays written (mask+merge)
    masked: Tuple[str, ...]       # globals on the copy/mask/merge path
    atomics: Tuple[str, ...]      # atomic targets (delta merge)
    logged: Tuple[str, ...]       # globals on the store-log path


def _pr_plan(ck: CompiledKernel, node: BlockPR) -> _PRPlan:
    """Write sets + store-log eligibility of one block-level PR.

    An array's stores go through the log when the warp graph is linear
    (log entries inside ``lax.switch`` branches cannot escape), every
    store to it sits outside While bodies, the PR never *loads* it (a
    logged store skips the per-warp copy, so a same-lane reload would
    read stale data), and it is not an atomic target in this PR."""
    wv: Set[str] = set()
    g: Set[str] = set()
    sh: Set[str] = set()
    at: Set[str] = set()
    loads: Set[str] = set()
    in_while: Set[str] = set()
    for bname in node.blocks:
        instrs = ck.cfg.blocks[bname].instrs
        w, a, s, t = _written_names(instrs)
        wv |= w
        g |= a
        sh |= s
        at |= t
        loads |= _loaded_globals(instrs)
        in_while |= _stored_in_while(instrs)
    bvw = {v for v in wv if ck.classes.get(v) == "block"}
    logged: Set[str] = set()
    if _try_linear(node.warp) is not None:
        logged = (g - at) - loads - in_while
    return _PRPlan(tuple(sorted(bvw)), tuple(sorted(sh)),
                   tuple(sorted((g - logged))), tuple(sorted(at)),
                   tuple(sorted(logged)))


def wave_logged(ck: CompiledKernel, *, n_warps: int,
                warp_exec: str) -> Tuple[str, ...]:
    """Globals a vmap wave carries as (index, value) store logs instead
    of per-block copies of global memory.

    An array qualifies when every block-level PR that stores to it puts
    it on the batched plane's store-log path (``_PRPlan.logged``), no PR
    loads it and it is no atomic target: no block ever reads it back, so
    the merged array is the carry with each block's logged stores
    applied in program order.  The block-level machine must be linear —
    log entries made inside its PC machine's ``lax.switch`` branches
    cannot escape the while loop."""
    if ck.phases or warp_exec != "batched" or n_warps <= 1:
        return ()
    if _try_linear_block(ck.machine) is None:
        return ()
    plans = [_pr_plan(ck, n) for n in ck.machine.nodes
             if isinstance(n, BlockPR)]
    logged = {a for p in plans for a in p.logged}
    copied = {a for p in plans for a in p.masked}
    loads: Set[str] = set()
    for blk in ck.cfg.blocks.values():
        loads |= _loaded_globals(blk.instrs)
    return tuple(sorted(logged - copied - loads))


def _exec_masked_while(ins: K.While, env: _Env, mask, *, jit_mode: bool):
    """Barrier-free loop with potentially lane-divergent trip counts:
    iterate while any lane is active, with per-lane masking (the
    whole-function-vectorization treatment of divergent loops).

    The lax carry holds only the state the body can write — carrying the
    full env (in particular the global-memory dict) would make every
    batched/vmapped execution of the loop select over whole arrays per
    iteration just to freeze finished instances."""
    if jit_mode and ins.static_trip is not None and ins.static_trip <= _UNROLL_LIMIT:
        for _ in range(ins.static_trip):
            cond = jnp.broadcast_to(
                eval_expr(ins.cond, env).astype(jnp.bool_), (env.W,))
            m = cond if mask is None else (mask & cond)
            exec_instrs(ins.body, env, m, jit_mode=jit_mode)
        return

    mask_in = jnp.ones((env.W,), jnp.bool_) if mask is None else mask
    wv, arrays, sh, _ = _written_names(ins.body)

    def snap():
        return {
            "wv": {k: v for k, v in env.warp_vars.items() if k in wv},
            "bv": {k: v for k, v in env.block_vars.items() if k in wv},
            "sh": {k: env.shmem[k] for k in sh if k in env.shmem},
            "g": {k: env.globals[k] for k in arrays if k in env.globals},
            "sm": {k: env.store_masks[k] for k in arrays
                   if k in env.store_masks},
            "ad": {k: env.atomic_deltas[k] for k in arrays
                   if k in env.atomic_deltas},
            "shm": {k: env.shared_masks[k] for k in sh
                    if k in env.shared_masks},
        }

    def load(st):
        env.warp_vars.update(st["wv"])
        env.block_vars.update(st["bv"])
        env.shmem.update(st["sh"])
        env.globals.update(st["g"])
        env.store_masks.update(st["sm"])
        env.atomic_deltas.update(st["ad"])
        env.shared_masks.update(st["shm"])

    def active(st) -> Any:
        load(st)
        cond = jnp.broadcast_to(
            eval_expr(ins.cond, env).astype(jnp.bool_), (env.W,))
        return mask_in & cond

    def cond_f(st):
        return jnp.any(active(st))

    def body_f(st):
        m = active(st)  # load(st) happened inside
        exec_instrs(ins.body, env, m, jit_mode=jit_mode)
        return snap()

    st = lax.while_loop(cond_f, body_f, snap())
    load(st)


# ---------------------------------------------------------------------------
# Warp-level machine (runs one warp through one block-level PR)
# ---------------------------------------------------------------------------


def _peel0(v):
    return v[0].astype(jnp.bool_)


def run_warp_graph(node: BlockPR, env: _Env, *, jit_mode: bool):
    """Execute the block-level PR's warp-level region graph for env.wid.
    Returns the exit index (which block-level successor to take)."""
    g = node.warp
    linear = _try_linear(g)
    if linear is not None:
        for wnode in linear:
            exec_instrs_of_warp_pr(wnode, env, jit_mode=jit_mode)
        return jnp.asarray(linear[-1].succ[1], jnp.int32)

    # general case: PC-dispatch machine
    EXITPC = len(g.nodes)

    def mk_fn(wnode):
        def fn(st):
            env.load(st["env"])
            if isinstance(wnode, WarpPR):
                exec_instrs_of_warp_pr(wnode, env, jit_mode=jit_mode)
                kind, val = wnode.succ
                if kind == "node":
                    pc, ex = jnp.int32(val), st["exit_ix"]
                else:
                    pc, ex = jnp.int32(EXITPC), jnp.int32(val)
            else:  # WarpPeel — loop peeling: lane 0 decides (paper §3.3.1)
                flag = _peel0(env.read_var(wnode.cond))
                def enc(tgt):
                    kind, val = tgt
                    if kind == "node":
                        return jnp.int32(val), st["exit_ix"]
                    return jnp.int32(EXITPC), jnp.int32(val)
                tp, te = enc(wnode.on_true)
                fp, fe = enc(wnode.on_false)
                pc = jnp.where(flag, tp, fp)
                ex = jnp.where(flag, te, fe)
            return {"pc": pc, "exit_ix": ex, "env": env.state()}
        return fn

    fns = [mk_fn(w) for w in g.nodes]

    def cond_f(st):
        return st["pc"] != EXITPC

    def body_f(st):
        return lax.switch(jnp.clip(st["pc"], 0, EXITPC - 1), fns, st)

    st0 = {"pc": jnp.int32(g.entry), "exit_ix": jnp.int32(0), "env": env.state()}
    st = lax.while_loop(cond_f, body_f, st0)
    env.load(st["env"])
    return st["exit_ix"]


def exec_instrs_of_warp_pr(wnode: WarpPR, env: _Env, *, jit_mode: bool):
    for bname in wnode.blocks:
        exec_instrs(env.ck.cfg.blocks[bname].instrs, env, None, jit_mode=jit_mode)


def _try_linear(g) -> Optional[List[WarpPR]]:
    """Fast path: the warp graph is a pure chain of PRs ending at exit 0
    (no peels, no cycles) — the shape every warp-feature-free PR has."""
    out: List[WarpPR] = []
    seen = set()
    cur = g.entry
    while True:
        node = g.nodes[cur]
        if not isinstance(node, WarpPR) or cur in seen:
            return None
        seen.add(cur)
        out.append(node)
        kind, val = node.succ
        if kind == "exit":
            return out
        cur = val


# ---------------------------------------------------------------------------
# Block-level machine
# ---------------------------------------------------------------------------


def make_block_fn(ck: CompiledKernel, *, n_warps: int, mode: str = "jit",
                  simd: bool = True, track_writes: bool = False,
                  warp_exec: str = "serial",
                  block_dim=None, grid_dim=None,
                  persist: Optional[Tuple[Tuple[str, ...],
                                          Tuple[str, ...]]] = None):
    """Build ``f(uniforms, globals[, masks, deltas]) -> (globals, masks,
    deltas)`` executing one CUDA block.  ``uniforms`` must contain bid,
    bdim, gdim and every scalar kernel parameter.

    ``persist=(var_names, shared_names)`` makes the block function one
    *phase* of a cooperative (grid-sync) kernel: it takes an extra
    ``state={"bv": {var: (n_warps, W)}, "sh": {name: flat}}`` argument
    holding this block's carried locals and shared memory from the
    previous phase (zeros for phase 0), and returns a fourth output with
    their final values.  Carried vars are 'block'-class in every phase
    (``compile_kernel`` forces this), so the state plugs straight into
    the block-replicated plane.

    ``block_dim``/``grid_dim`` are the launch's static dim3 extents
    (Dim3 or tuple); they feed only the per-axis intrinsics — the
    machine walk itself stays linear.  ``None`` (direct callers) means
    a 1-D launch: ``tid_x``/``bid_x`` are the linear ids, y/z are 0.

    ``warp_exec='batched'`` replaces the inter-warp loop with a
    ``jax.vmap`` over the warp axis: every block-level PR runs all
    ``n_warps`` warps at once as one ``(n_warps, W)`` lane plane, with
    per-warp copies of shared/global memory merged at each PR boundary
    (see the module docstring).  ``'serial'`` is the paper's Code 3
    inter-warp loop.

    ``track_writes=True`` without ``persist`` builds a vmap grid
    wave's block, which returns only what it can write: ``(globals,
    masks, deltas, logs)`` with globals and masks for the arrays on the
    copy path (plain stores, not wave-logged), deltas for the atomic
    targets, and ``logs = {array: [(idx, val), ...]}`` — the store logs
    of the :func:`wave_logged` arrays, in program order, left for the
    wave to apply.  Read-only globals are no output at all.
    """
    if warp_exec not in ("serial", "batched"):
        raise ValueError(f"unknown warp_exec {warp_exec!r}; "
                         f"expected 'serial' or 'batched'")
    if ck.phases:
        raise ValueError("make_block_fn runs one phase: pass a phase "
                         "CompiledKernel (ck.phase_list()), not the "
                         "multi-phase container")
    jit_mode = mode == "jit"
    W = ck.warp_size
    bdim3 = dim3_tuple(block_dim)
    gdim3 = dim3_tuple(grid_dim)
    all_atomics = [s for s in _all_instrs(ck) if isinstance(s, K.AtomicRMW)]
    has_atomics = bool(all_atomics)
    batch_warps = warp_exec == "batched" and n_warps > 1
    if batch_warps and any(s.dst for s in all_atomics):
        # defense in depth — LaunchPlan.check_warp_batchable rejects
        # these launches before tracing (see that docstring for why)
        raise CoxUnsupported(
            "atomic old-value capture under warp-batched execution: "
            "captured old values are only unique under serial warp "
            "order — use warp_exec='serial'")
    wave = track_writes and persist is None
    from .backends import merge  # deferred: backends imports execute
    pr_plans = ({n.id: _pr_plan(ck, n) for n in ck.machine.nodes
                 if isinstance(n, BlockPR)} if batch_warps else {})
    wave_log = (frozenset(wave_logged(ck, n_warps=n_warps,
                                      warp_exec=warp_exec))
                if wave else frozenset())
    copied = tuple(sorted(
        {s.array for s in _all_instrs(ck) if isinstance(s, K.StoreGlobal)}
        - wave_log))
    atomic_targets = tuple(sorted({s.array for s in all_atomics}))

    def block_fn(uniforms: Dict[str, Any], globals_: Dict[str, Any],
                 store_masks=None, atomic_deltas=None, state=None):
        block_vars = {
            v: jnp.zeros((n_warps, W), ck.var_types.get(v, DType.f32).jnp)
            for v, c in ck.classes.items() if c == "block"}
        shmem = {s.name: jnp.zeros((_prod(s.shape),), s.dtype.jnp)
                 for s in ck.kernel.shared}
        if persist is not None:
            if state is None:
                raise ValueError("persist block fn needs state= (carried "
                                 "per-block locals + shared memory)")
            block_vars.update({v: state["bv"][v] for v in persist[0]})
            shmem.update({s: state["sh"][s] for s in persist[1]})
        if track_writes:
            store_masks = store_masks if store_masks is not None else {
                k: jnp.zeros(v.shape, jnp.bool_) for k, v in globals_.items()}
            atomic_deltas = atomic_deltas if atomic_deltas is not None else ({
                k: jnp.zeros(v.shape, merge.num(v).dtype)
                for k, v in globals_.items()}
                if has_atomics else {})
        else:
            store_masks, atomic_deltas = {}, {}
        # wave-logged arrays' store logs, filled in program order along
        # the (linear) block machine and returned unreplayed
        wlog: Dict[str, List[Tuple[Any, Any]]] = {k: [] for k in wave_log}

        def run_warp_plane(node: BlockPR, bv, sh, g, sm, ad):
            """All warps of one block-level PR as a single (n_warps, W)
            lane plane: ``jax.vmap`` over the warp axis of the warp-level
            machine walk.  Sound because warps are independent between
            barriers (COX's hierarchical-collapsing guarantee) and every
            block-level PR boundary *is* a barrier boundary.

            Each warp runs on its own copy of shared/global memory with
            write-mask + atomic-delta tracking; the copies reconcile here
            via the backends' bit-exact single-writer select merge
            (masked integer-sum payload transport), so the
            merged state is bitwise-identical to the serial inter-warp
            loop for race-free kernels (atomic deltas sum order-free).
            Block-replicated vars are written only at each warp's own
            row, so the merged plane is the diagonal of the per-warp
            copies.  All warps reach the same exit under the
            aligned-barrier assumption; warp 0's is taken (the block-peel
            analogue of "warp 0 lane 0 decides")."""
            plan = pr_plans[node.id]
            # under write-tracking (vmap/sharded grid backends) per-warp
            # deltas start from the block's carried deltas so LoadGlobal
            # still observes earlier PRs' atomic effects; under the
            # loop-carried scan outer they start at zero (earlier deltas
            # are already folded into g at each PR boundary)
            ad_in = ad if track_writes else (
                {k: jnp.zeros(g[k].shape, merge.num(g[k]).dtype)
                 for k in plan.atomics})
            log_names: List[str] = []

            def one_warp(wid):
                # dict copies: _Env mutates its dicts in place, and the
                # carried sh/g must stay pristine for the post-vmap
                # merge (aliasing would leak batched tracers into them).
                # Block-replicated vars are handed over as this warp's
                # own (W,) row — see _Env.block_rows.
                env = _Env(
                    ck, wid=wid, n_warps=n_warps, uniforms=uniforms,
                    warp_vars={},
                    block_vars={k: v[wid] for k, v in bv.items()},
                    shmem=dict(sh), globals_=dict(g),
                    simd=simd, track_writes=True, block_rows=True,
                    store_masks={k: jnp.zeros(g[k].shape, jnp.bool_)
                                 for k in plan.masked},
                    atomic_deltas=dict(ad_in),
                    shared_masks={k: jnp.zeros(sh[k].shape, jnp.bool_)
                                  for k in plan.shared},
                    log_arrays=set(plan.logged),
                    block_dim3=bdim3, grid_dim3=gdim3)
                ex = run_warp_graph(node, env, jit_mode=jit_mode)
                # the log structure is static (one trace): capture the
                # entry order once, ship only the lane tensors out
                log_names.clear()
                log_names.extend(n for n, _, _ in env.store_log)
                # return only what this PR can write — unbatched arrays
                # stay broadcast constants with no copy/stack cost
                return ({k: env.block_vars[k] for k in plan.block_vars},
                        {k: env.shmem[k] for k in plan.shared},
                        {k: env.shared_masks[k] for k in plan.shared},
                        {k: env.globals[k] for k in plan.masked},
                        {k: env.store_masks[k] for k in plan.masked},
                        {k: env.atomic_deltas[k] for k in plan.atomics},
                        [(i, v) for _, i, v in env.store_log],
                        ex)

            wids = jnp.arange(n_warps, dtype=jnp.int32)
            bvs, shs, shms, gs, gms, ads, logs, exs = jax.vmap(one_warp)(wids)
            # block-replicated vars: each warp ran on its own (W,) row,
            # so the stacked rows ARE the merged (n_warps, W) plane
            bv2 = {**bv, **bvs}
            shm_in = {k: sh[k] for k in plan.shared}
            sh_new, _, _ = merge.merge_chunk(shm_in, shs, shms, {},
                                             fold_deltas=True)
            sh2 = {**sh, **sh_new}
            g_in = {k: g[k] for k in plan.masked}
            if track_writes:
                new_d = {k: ads[k] - ad_in[k][None] for k in ads}
                g_new, wrote, dsum = merge.merge_chunk(
                    g_in, gs, gms, new_d, fold_deltas=False)
                sm = {**sm, **{k: sm[k] | wrote[k] for k in wrote}}
                if dsum:
                    ad = {**ad, **{k: merge.denum(
                        merge.num(ad[k]) + dsum[k], ad[k].dtype)
                        for k in dsum}}
            else:
                g_new, _, _ = merge.merge_chunk(g_in, gs, gms, ads,
                                                fold_deltas=True)
            g2 = {**g, **g_new}
            # store-log replay: one flat scatter per logged store — the
            # single-writer contract makes cross-warp lanes disjoint
            # (masked-off lanes carry the one-past-end index and drop).
            # Wave-logged arrays skip the block's copy: their entries
            # go out with the block for the vmap wave to apply.
            for name, (idx, val) in zip(log_names, logs):
                if name in wave_log:
                    wlog[name].append((idx, val))
                    continue
                g2[name] = g2[name].at[idx.reshape(-1)].set(
                    val.reshape(-1), mode="drop")
                if track_writes:
                    sm = {**sm, name: sm[name].at[idx.reshape(-1)].set(
                        True, mode="drop")}
            return bv2, sh2, g2, sm, ad, exs[0]

        def run_block_pr(node: BlockPR, bv, sh, g, sm, ad):
            """One block-level PR: the inter-warp loop (paper's Code 3
            outer loop), or the batched (n_warps, W) warp plane."""
            if batch_warps:
                bv, sh, g, sm, ad, ex = run_warp_plane(node, bv, sh, g,
                                                       sm, ad)
                return _block_succ(node, ex), bv, sh, g, sm, ad

            def one_warp(wid, carry):
                bv, sh, g, sm, ad, _ = carry
                env = _Env(ck, wid=wid, n_warps=n_warps, uniforms=uniforms,
                           warp_vars={}, block_vars=bv, shmem=sh, globals_=g,
                           simd=simd, track_writes=track_writes,
                           store_masks=sm, atomic_deltas=ad,
                           block_dim3=bdim3, grid_dim3=gdim3)
                ex = run_warp_graph(node, env, jit_mode=jit_mode)
                return (env.block_vars, env.shmem, env.globals,
                        env.store_masks, env.atomic_deltas, ex)

            init = (bv, sh, g, sm, ad, jnp.int32(0))
            if jit_mode:
                carry = init
                for wid in range(n_warps):
                    carry = one_warp(wid, carry)
            else:
                carry = lax.fori_loop(0, n_warps, one_warp, init)
            bv, sh, g, sm, ad, ex = carry
            return _block_succ(node, ex), bv, sh, g, sm, ad

        def _block_succ(node: BlockPR, ex):
            succ = jnp.asarray(
                [EXIT if s == EXIT else s for s in node.succ_ids] or [EXIT],
                jnp.int32)
            return succ[jnp.clip(ex, 0, len(node.succ_ids) - 1)] \
                if node.succ_ids else jnp.int32(EXIT)

        def outputs(g, sm, ad, bv, sh):
            if wave:
                return ({k: g[k] for k in copied},
                         {k: sm[k] for k in copied},
                         {k: ad[k] for k in atomic_targets}, wlog)
            if persist is None:
                return g, sm, ad
            return g, sm, ad, {"bv": {v: bv[v] for v in persist[0]},
                               "sh": {s: sh[s] for s in persist[1]}}

        nodes = ck.machine.nodes
        linear = _try_linear_block(ck.machine)
        if linear is not None:
            bv, sh, g, sm, ad = block_vars, shmem, globals_, store_masks, atomic_deltas
            for node in linear:
                _, bv, sh, g, sm, ad = run_block_pr(node, bv, sh, g, sm, ad)
            return outputs(g, sm, ad, bv, sh)

        # general PC machine at block level
        def mk_fn(node):
            def fn(st):
                bv, sh, g, sm, ad = (st["bv"], st["sh"], st["g"],
                                     st["sm"], st["ad"])
                if isinstance(node, BlockPR):
                    nxt, bv, sh, g, sm, ad = run_block_pr(node, bv, sh, g, sm, ad)
                else:  # BlockPeel — warp 0 lane 0 decides
                    flag = bv[node.cond][0, 0].astype(jnp.bool_)
                    nxt = jnp.where(flag, jnp.int32(node.t_id),
                                    jnp.int32(node.f_id))
                return {"pc": nxt, "bv": bv, "sh": sh, "g": g, "sm": sm,
                        "ad": ad}
            return fn

        fns = [mk_fn(n) for n in nodes]
        st0 = {"pc": jnp.int32(ck.machine.entry), "bv": block_vars,
               "sh": shmem, "g": globals_, "sm": store_masks,
               "ad": atomic_deltas}
        st = lax.while_loop(
            lambda s: s["pc"] != jnp.int32(EXIT),
            lambda s: lax.switch(jnp.clip(s["pc"], 0, len(fns) - 1), fns, s),
            st0)
        return outputs(st["g"], st["sm"], st["ad"], st["bv"], st["sh"])

    return block_fn


def _try_linear_block(machine: Machine) -> Optional[List[BlockPR]]:
    out: List[BlockPR] = []
    seen = set()
    cur = machine.entry
    while cur != EXIT:
        node = machine.nodes[cur]
        if not isinstance(node, BlockPR) or cur in seen:
            return None
        if len(set(node.succ_ids)) > 1:
            return None
        seen.add(cur)
        out.append(node)
        cur = node.succ_ids[0] if node.succ_ids else EXIT
    return out


def walk_instrs(ck: CompiledKernel):
    """Yield every instruction in the kernel, descending into If/While
    (and into every phase of a multi-phase compilation)."""
    return _all_instrs(ck)


def _all_instrs(ck: CompiledKernel):
    for sub in ck.phase_list():
        for blk in sub.cfg.blocks.values():
            stack = list(blk.instrs)
            while stack:
                s = stack.pop()
                yield s
                if isinstance(s, K.If):
                    stack.extend(s.then_body)
                    stack.extend(s.else_body)
                elif isinstance(s, K.While):
                    stack.extend(s.body)
