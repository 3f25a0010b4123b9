"""The coverage kernel suite — Table 1 of the paper, rebuilt.

31 kernels mirroring the CUDA SDK 10.1 rows the paper evaluates (same
feature classes: plain SPMD, block cooperative groups / __syncthreads
reductions, warp cooperative groups, warp shuffle, warp vote, grid sync,
dynamic cooperative groups).  Each entry carries the feature tag used in
the paper's table so the coverage comparison (flat vs hierarchical)
reproduces Table 1's structure.

Unsupported-on-purpose rows (grid sync, multi-grid sync, dynamic groups)
are represented by builders that raise CoxUnsupported at parse/compile
time — the same 3 rows COX itself cannot run (90% coverage).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

import numpy as np

from repro.core import cox


@dataclasses.dataclass
class SuiteKernel:
    name: str
    features: str                  # '' | block-cg | warp-cg | shuffle | vote | grid-sync | dynamic-cg
    kernel: Optional[object]       # KernelFn, or None for unsupported rows
    grid: object                   # int | (x, y[, z]) dim3
    block: object                  # int | (x, y[, z]) dim3
    make_args: Callable[[], tuple]
    check: Optional[Callable] = None
    unsupported_reason: str = ""


RNG = np.random.default_rng(7)
KERNELS: List[SuiteKernel] = []


def _reg(name, features, kernel, grid, block, make_args, check=None,
         unsupported_reason=""):
    KERNELS.append(SuiteKernel(name, features, kernel, grid, block,
                               make_args, check, unsupported_reason))


# ---------------------------------------------------------------------------
# plain SPMD kernels (the ✓✓✓ rows)
# ---------------------------------------------------------------------------

@cox.kernel
def initVectors(c, rhs: cox.Array(cox.f32), x: cox.Array(cox.f32),
                n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        rhs[i] = 1.0
        x[i] = 0.0


_reg("initVectors", "", initVectors, 2, 256,
     lambda: (np.zeros(512, np.float32), np.ones(512, np.float32), 500),
     lambda out: np.allclose(out["rhs"][:500], 1.0) and
     np.allclose(out["x"][:500], 0.0))


@cox.kernel
def vectorAdd(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32),
              b: cox.Array(cox.f32), n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        out[i] = a[i] + b[i]


def _va_args():
    a = RNG.normal(size=512).astype(np.float32)
    b = RNG.normal(size=512).astype(np.float32)
    return (np.zeros(512, np.float32), a, b, 512)


_reg("vectorAdd", "", vectorAdd, 2, 256, _va_args)


@cox.kernel
def gpuSpMV(c, y: cox.Array(cox.f32), vals: cox.Array(cox.f32),
            cols: cox.Array(cox.i32), rowptr: cox.Array(cox.i32),
            x: cox.Array(cox.f32), n_rows: cox.i32):
    row = c.block_idx() * c.block_dim() + c.thread_idx()
    if row < n_rows:
        acc = 0.0
        start = rowptr[row]
        end = rowptr[row + 1]
        j = start
        while j < end:
            acc = acc + vals[j] * x[cols[j]]
            j = j + 1
        y[row] = acc


def _spmv_args():
    n = 64
    rowptr = np.arange(n + 1, dtype=np.int32) * 4
    cols = RNG.integers(0, n, size=4 * n).astype(np.int32)
    vals = RNG.normal(size=4 * n).astype(np.float32)
    x = RNG.normal(size=n).astype(np.float32)
    return (np.zeros(n, np.float32), vals, cols, rowptr, x, n)


_reg("gpuSpMV", "", gpuSpMV, 1, 64, _spmv_args)


@cox.kernel
def r1_div_x(c, r1: cox.Array(cox.f32), r0: cox.Array(cox.f32),
             dot: cox.Array(cox.f32)):
    i = c.thread_idx()
    if i == 0:
        r1[0] = r0[0] / dot[0]


_reg("r1_div_x", "", r1_div_x, 1, 32,
     lambda: (np.zeros(1, np.float32), np.array([6.0], np.float32),
              np.array([2.0], np.float32)),
     lambda out: np.allclose(out["r1"], 3.0))


@cox.kernel
def a_minus(c, a: cox.Array(cox.f32), na: cox.Array(cox.f32)):
    i = c.thread_idx()
    if i == 0:
        na[0] = 0.0 - a[0]


_reg("a_minus", "", a_minus, 1, 32,
     lambda: (np.array([5.0], np.float32), np.zeros(1, np.float32)),
     lambda out: np.allclose(out["na"], -5.0))


@cox.kernel
def MatrixMulCUDA(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32),
                  b: cox.Array(cox.f32), n: cox.i32):
    # the CUDA SDK's natural 2-D form: tiled 16x16 matmul with shared
    # memory + block barriers, launched <<<dim3(n/16, n/16), dim3(16, 16)>>>
    tile_a = c.shared((16, 16), cox.f32)
    tile_b = c.shared((16, 16), cox.f32)
    ty = c.thread_idx('y')
    tx = c.thread_idx('x')
    row = c.block_idx('y') * 16 + ty
    col = c.block_idx('x') * 16 + tx
    acc = 0.0
    for t in range(0, 64, 16):
        tile_a[ty, tx] = a[row * n + t + tx]
        tile_b[ty, tx] = b[(t + ty) * n + col]
        c.syncthreads()
        for kk in range(16):
            acc = acc + tile_a[ty, kk] * tile_b[kk, tx]
        c.syncthreads()
    out[row * n + col] = acc


def _mm_args():
    n = 64
    a = RNG.normal(size=(n, n)).astype(np.float32)
    b = RNG.normal(size=(n, n)).astype(np.float32)
    return (np.zeros((n, n), np.float32), a, b, n)


def _mm_check(out):
    a, b = _MM_CACHE
    return np.allclose(out["out"], a @ b, atol=1e-3)


_MM_CACHE = None


def _mm_args_cached():
    global _MM_CACHE
    args = _mm_args()
    _MM_CACHE = (args[1], args[2])
    return args


_reg("MatrixMulCUDA", "", MatrixMulCUDA, (4, 4), (16, 16), _mm_args_cached,
     _mm_check)
_reg("matrixMul", "", MatrixMulCUDA, (4, 4), (16, 16), _mm_args_cached,
     _mm_check)
_reg("matrixMultiplyKernel", "", MatrixMulCUDA, (4, 4), (16, 16),
     _mm_args_cached, _mm_check)


@cox.kernel
def copyp2p(c, dst: cox.Array(cox.f32), src: cox.Array(cox.f32),
            n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        dst[i] = src[i]


_reg("copyp2p", "", copyp2p, 2, 128,
     lambda: (np.zeros(256, np.float32),
              RNG.normal(size=256).astype(np.float32), 256))


@cox.kernel
def simpleKernel(c, out: cox.Array(cox.f32), inp: cox.Array(cox.f32)):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    out[i] = inp[i] * 2.0 + 1.0


_reg("simpleKernel", "", simpleKernel, 2, 64,
     lambda: (np.zeros(128, np.float32),
              RNG.normal(size=128).astype(np.float32)))


@cox.kernel
def uniform_add(c, out: cox.Array(cox.f32), uni: cox.Array(cox.f32),
                n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        out[i] += uni[c.block_idx()]


_reg("uniform_add", "", uniform_add, 2, 128,
     lambda: (np.zeros(256, np.float32),
              np.array([1.0, 2.0], np.float32), 256))


@cox.kernel
def spinWhileLessThanOne(c, flag: cox.Array(cox.i32),
                         out: cox.Array(cox.i32)):
    i = c.thread_idx()
    spins = 0
    while flag[0] < 1 and spins < 4:
        spins = spins + 1
    out[i] = spins


_reg("spinWhileLessThanone", "", spinWhileLessThanOne, 1, 64,
     lambda: (np.zeros(1, np.int32), np.zeros(64, np.int32)))


# ---------------------------------------------------------------------------
# block cooperative groups (reduce0-3: __syncthreads tree reductions)
# ---------------------------------------------------------------------------


def _make_block_reduce(name):
    @cox.kernel(name=name)
    def reduce_block(c, out: cox.Array(cox.f32), val: cox.Array(cox.f32)):
        tile = c.shared((256,), cox.f32)
        tid = c.thread_idx()
        tile[tid] = val[c.block_idx() * c.block_dim() + tid]
        c.syncthreads()
        s = 128
        while s > 0:
            if tid < s:
                tile[tid] = tile[tid] + tile[tid + s]
            c.syncthreads()
            s = s // 2
        if tid == 0:
            out[c.block_idx()] = tile[0]
    return reduce_block


def _br_args():
    v = RNG.normal(size=512).astype(np.float32)
    return (np.zeros(2, np.float32), v)


def _br_check(out):
    return True  # validated against oracle in tests


for nm in ("reduce0", "reduce1", "reduce2", "reduce3"):
    _reg(nm, "block-cg", _make_block_reduce(nm), 2, 256, _br_args)


# ---------------------------------------------------------------------------
# warp cooperative groups / shuffle / vote (the rows flat collapsing fails)
# ---------------------------------------------------------------------------


def _make_warp_reduce(name):
    @cox.kernel(name=name)
    def reduce_warp(c, out: cox.Array(cox.f32), val: cox.Array(cox.f32)):
        tile = c.shared((8,), cox.f32)
        tid = c.thread_idx()
        v = val[c.block_idx() * c.block_dim() + tid]
        offset = 16
        while offset > 0:
            s = c.shfl_down(v, offset)
            v = v + s
            offset = offset // 2
        if c.lane_id() == 0:
            tile[c.warp_id()] = v
        c.syncthreads()
        if tid < 8:
            w = tile[tid]
            off2 = 4
            while off2 > 0:
                s2 = c.shfl_down(w, off2, width=8)
                w = w + s2
                off2 = off2 // 2
            if tid == 0:
                out[c.block_idx()] = w
    return reduce_warp


for nm in ("reduce4", "reduce5", "reduce6", "reduce", "reduceFinal",
           "gpuDotProduct"):
    _reg(nm, "warp-cg", _make_warp_reduce(nm), 2, 256, _br_args)


def _make_shfl_scan(name):
    @cox.kernel(name=name)
    def shfl_scan(c, out: cox.Array(cox.f32), val: cox.Array(cox.f32)):
        tid = c.thread_idx()
        v = val[c.block_idx() * c.block_dim() + tid]
        lane = c.lane_id()
        offset = 1
        while offset < 32:
            s = c.shfl_up(v, offset)
            if lane >= offset:
                v = v + s
            offset = offset * 2
        out[c.block_idx() * c.block_dim() + tid] = v
    return shfl_scan


for nm in ("shfl_intimage_rows", "shfl_vertical_shfl", "shfl_scan_test"):
    _reg(nm, "shuffle", _make_shfl_scan(nm), 2, 64,
         lambda: (np.zeros(128, np.float32),
                  RNG.normal(size=128).astype(np.float32)))


@cox.kernel
def VoteAnyKernel1(c, result: cox.Array(cox.i32), inp: cox.Array(cox.i32)):
    tx = c.thread_idx()
    r = c.vote_any(inp[tx] > 0)
    result[tx] = c.i32(r)


@cox.kernel
def VoteAllKernel2(c, result: cox.Array(cox.i32), inp: cox.Array(cox.i32)):
    tx = c.thread_idx()
    r = c.vote_all(inp[tx] > 0)
    result[tx] = c.i32(r)


@cox.kernel
def VoteAnyKernel3(c, result: cox.Array(cox.i32), inp: cox.Array(cox.i32)):
    tx = c.thread_idx()
    p = tx % 3 == 0
    r = c.vote_any(p)
    b = c.ballot(inp[tx] > 0)
    result[tx] = c.i32(r) + c.i32(b & 1)


def _vote_args():
    return (np.zeros(64, np.int32),
            RNG.integers(-2, 3, size=64).astype(np.int32))


_reg("VoteAnyKernel1", "vote", VoteAnyKernel1, 1, 64, _vote_args)
_reg("VoteAllKernel2", "vote", VoteAllKernel2, 1, 64, _vote_args)
_reg("VoteAnyKernel3", "vote", VoteAnyKernel3, 1, 64, _vote_args)


# ---------------------------------------------------------------------------
# extra kernels outside the paper's 31-row table: atomics + a memory-light
# many-block kernel, used by the backend-equivalence and grid-stride
# tests (Table-1 coverage counts stay on KERNELS; all_kernels() =
# KERNELS + EXTRA_KERNELS)
# ---------------------------------------------------------------------------

EXTRA_KERNELS: List[SuiteKernel] = []


def _reg_extra(name, features, kernel, grid, block, make_args, check=None):
    EXTRA_KERNELS.append(SuiteKernel(name, features, kernel, grid, block,
                                     make_args, check))


@cox.kernel
def histogram64(c, hist: cox.Array(cox.f32), data: cox.Array(cox.i32),
                n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        c.atomic_add(hist, data[i], 1.0)


def _hist_args():
    data = RNG.integers(0, 64, size=2000).astype(np.int32)
    return (np.zeros(64, np.float32), data, 2000)


_reg_extra("histogram64", "atomics", histogram64, 16, 128, _hist_args,
           lambda out: out["hist"].sum() == 2000)


@cox.kernel
def blockCounter(c, total: cox.Array(cox.f32), partial: cox.Array(cox.f32),
                 val: cox.Array(cox.f32), n: cox.i32):
    # atomics + plain stores on different arrays in one kernel: each
    # thread stores its element and block-atomically counts valid ones
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        partial[i] = val[i] * 0.5
        c.atomic_add(total, 0, 1.0)


def _bc_args():
    v = RNG.normal(size=1000).astype(np.float32)
    return (np.zeros(1, np.float32), np.zeros(1000, np.float32), v, 900)


_reg_extra("blockCounter", "atomics", blockCounter, 8, 128, _bc_args,
           lambda out: out["total"][0] == 900)


@cox.kernel
def saxpyHeavy(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32),
               b: cox.Array(cox.f32), n: cox.i32):
    # memory-light, many-block, compute-heavy (Hetero-mark style): the
    # backend sweep's flagship — block parallelism dominates here
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        acc = 0.0
        for t in range(64):
            acc = acc + a[i] * 1.0001 + b[i] * 0.9999
        out[i] = acc


def _saxpy_args():
    n = 64 * 256
    a = RNG.normal(size=n).astype(np.float32)
    b = RNG.normal(size=n).astype(np.float32)
    return (np.zeros(n, np.float32), a, b, n)


_reg_extra("saxpyHeavy", "", saxpyHeavy, 64, 256, _saxpy_args)


@cox.kernel
def warpPrefixStats(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32)):
    # collective-dense warp statistics (butterfly all-reduce pipelines +
    # segmented reductions) staged through shared memory: 8 warps of
    # peel-free chained collectives, a block barrier, cross-warp shared
    # reads — the flagship for warp-batched execution (the serial
    # inter-warp loop pays one collective-op chain per warp; the batched
    # (n_warps, W) plane pays one chain total)
    tile = c.shared((8,), cox.f32)
    tid = c.thread_idx()
    i = c.block_idx() * c.block_dim() + tid
    v = a[i]
    x = v
    s1 = c.shfl_xor(x, 1)
    x = x + s1
    s2 = c.shfl_xor(x, 2)
    x = x + s2
    s4 = c.shfl_xor(x, 4)
    x = x + s4
    s8 = c.shfl_xor(x, 8)
    x = x + s8
    s16 = c.shfl_xor(x, 16)
    x = x + s16
    y = v * v
    t1 = c.shfl_xor(y, 1)
    y = c.max(y, t1)
    t2 = c.shfl_xor(y, 2)
    y = c.max(y, t2)
    t4 = c.shfl_xor(y, 4)
    y = c.max(y, t4)
    t8 = c.shfl_xor(y, 8)
    y = c.max(y, t8)
    t16 = c.shfl_xor(y, 16)
    y = c.max(y, t16)
    z = v + 1.0
    u1 = c.shfl_down(z, 1)
    z = z + u1
    u2 = c.shfl_down(z, 2)
    z = z + u2
    u4 = c.shfl_down(z, 4)
    z = z + u4
    m = c.red_max(v)
    n = c.red_min(v)
    r = c.red_add(z)
    b = c.red_add(y, width=8)
    if c.lane_id() == 0:
        tile[c.warp_id()] = x + m
    c.syncthreads()
    t = tile[tid % 8]
    out[i] = x + y + z + m + n + r + b + t


def _wps_args():
    # small-integer values keep every float reduction exact in any
    # association order, so all executor flavors agree bitwise
    n = 32 * 256
    a = RNG.integers(-6, 7, size=n).astype(np.float32)
    return (np.zeros(n, np.float32), a)


_reg_extra("warpPrefixStats", "warp-cg", warpPrefixStats, 32, 256, _wps_args)


@cox.kernel
def gridReduce(c, total: cox.Array(cox.f32), partial: cox.Array(cox.f32),
               data: cox.Array(cox.f32), n: cox.i32):
    # cooperative two-pass grid-wide reduction (the SDK's
    # reduceSinglePassMultiBlockCG shape): every block tree-reduces its
    # tile into partial[bid], the grid synchronizes, block 0 totals the
    # partials — no host round-trip between the passes.  The paper's
    # Table 1 marks this feature class ✗ for COX; our phase-split
    # grid_sync (repro.core.phases) runs it.
    tile = c.shared((128,), cox.f32)
    tid = c.thread_idx()
    i = c.block_idx() * c.block_dim() + tid
    tile[tid] = data[i] if i < n else 0.0
    c.syncthreads()
    s = 64
    while s > 0:
        if tid < s:
            tile[tid] = tile[tid] + tile[tid + s]
        c.syncthreads()
        s = s // 2
    if tid == 0:
        partial[c.block_idx()] = tile[0]
    c.grid_sync()
    if c.block_idx() == 0:
        acc = 0.0
        j = tid
        while j < c.grid_dim():
            acc = acc + partial[j]
            j = j + c.block_dim()
        tile[tid] = acc
        c.syncthreads()
        s2 = 64
        while s2 > 0:
            if tid < s2:
                tile[tid] = tile[tid] + tile[tid + s2]
            c.syncthreads()
            s2 = s2 // 2
        if tid == 0:
            total[0] = tile[0]


def _gr_args():
    # small integers: every float add is exact in any association order,
    # so scan/vmap/sharded × serial/batched agree bitwise with the oracle
    n = 1000
    data = RNG.integers(-8, 9, size=n).astype(np.float32)
    return (np.zeros(1, np.float32), np.zeros(8, np.float32), data, n)


_reg_extra("gridReduce", "grid-sync", gridReduce, 8, 128, _gr_args,
           lambda out: out["total"][0] == out["partial"].sum())


# ---------------------------------------------------------------------------
# dim3 kernels: the 2-D geometry the SDK actually ships (matrixMul above
# runs <<<dim3(4,4), dim3(16,16)>>>), plus the hand-flattened 1-D matmul
# kept as the perf baseline for the natural-2-D-within-10% comparison
# ---------------------------------------------------------------------------


@cox.kernel
def matrixMul1D(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32),
                b: cox.Array(cox.f32), n: cox.i32):
    # the pre-dim3 port: same tiled matmul with the index arithmetic a
    # human flattened by hand (row/col recovered from linear ids)
    tile_a = c.shared((16, 16), cox.f32)
    tile_b = c.shared((16, 16), cox.f32)
    ty = c.thread_idx() // 16
    tx = c.thread_idx() % 16
    row = c.block_idx() // (n // 16) * 16 + ty
    col = c.block_idx() % (n // 16) * 16 + tx
    acc = 0.0
    for t in range(0, 64, 16):
        tile_a[ty, tx] = a[row * n + t + tx]
        tile_b[ty, tx] = b[(t + ty) * n + col]
        c.syncthreads()
        for kk in range(16):
            acc = acc + tile_a[ty, kk] * tile_b[kk, tx]
        c.syncthreads()
    out[row * n + col] = acc


_reg_extra("matrixMul1D", "", matrixMul1D, 16, 256, _mm_args_cached,
           _mm_check)


@cox.kernel
def transpose(c, odata: cox.Array(cox.f32), idata: cox.Array(cox.f32),
              n: cox.i32):
    # the SDK's shared-memory tiled transpose: coalesced reads into a
    # padded tile (TILE_DIM+1 kills bank conflicts on real hardware;
    # kept for fidelity), barrier, coalesced transposed writes
    tile = c.shared((16, 17), cox.f32)
    x = c.block_idx('x') * 16 + c.thread_idx('x')
    y = c.block_idx('y') * 16 + c.thread_idx('y')
    tile[c.thread_idx('y'), c.thread_idx('x')] = idata[y * n + x]
    c.syncthreads()
    xo = c.block_idx('y') * 16 + c.thread_idx('x')
    yo = c.block_idx('x') * 16 + c.thread_idx('y')
    odata[yo * n + xo] = tile[c.thread_idx('x'), c.thread_idx('y')]


_T_CACHE = None


def _tr_args():
    global _T_CACHE
    n = 64
    _T_CACHE = RNG.normal(size=(n, n)).astype(np.float32)
    return (np.zeros((n, n), np.float32), _T_CACHE, n)


_reg_extra("transpose", "block-cg", transpose, (4, 4), (16, 16), _tr_args,
           lambda out: np.array_equal(out["odata"], _T_CACHE.T))


@cox.kernel
def stencil2d(c, out: cox.Array(cox.f32), inp: cox.Array(cox.f32),
              n: cox.i32):
    # 5-point Jacobi step over the interior, natural 2-D indexing
    x = c.block_idx('x') * c.block_dim('x') + c.thread_idx('x')
    y = c.block_idx('y') * c.block_dim('y') + c.thread_idx('y')
    if x > 0 and x < n - 1 and y > 0 and y < n - 1:
        out[y * n + x] = 0.25 * (inp[(y - 1) * n + x] + inp[(y + 1) * n + x]
                                 + inp[y * n + x - 1] + inp[y * n + x + 1])


_ST_CACHE = None


def _st_args():
    global _ST_CACHE
    n = 64
    _ST_CACHE = RNG.normal(size=(n, n)).astype(np.float32)
    return (np.zeros((n, n), np.float32), _ST_CACHE, n)


def _st_check(out):
    i = _ST_CACHE
    want = np.zeros_like(i)
    want[1:-1, 1:-1] = 0.25 * (i[:-2, 1:-1] + i[2:, 1:-1]
                               + i[1:-1, :-2] + i[1:-1, 2:])
    return np.allclose(out["out"], want, atol=1e-6)


_reg_extra("stencil2d", "", stencil2d, (4, 4), (16, 16), _st_args, _st_check)


def all_kernels() -> List[SuiteKernel]:
    """Table-1 rows plus the extra (atomics / sweep) kernels."""
    return KERNELS + EXTRA_KERNELS


# ---------------------------------------------------------------------------
# unsupported rows (grid sync / dynamic groups — COX's own ✗ rows)
# ---------------------------------------------------------------------------


def _unsupported(name, features, reason):
    _reg(name, features, None, 1, 64, lambda: (),
         unsupported_reason=reason)


_unsupported("gpuConjugateGradient", "grid-sync",
             "grid sync inside the CG iteration loop: dynamic phase "
             "count (phase-split grid_sync covers top-level syncs "
             "only — see gridReduce; paper §5.1: fully unsupported "
             "in COX)")
_unsupported("multiGpuConjugateGradient", "multi-grid-sync",
             "multi-grid sync across devices (paper: unsupported)")
_unsupported("filter_arr", "dynamic-cg",
             "dynamic cooperative group of activated threads "
             "(paper §2.2.3: runtime-level feature)")
