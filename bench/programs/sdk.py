"""Frozen CUDA SDK-class programs the COX cells launch, with their numpy
references.

The kernel bodies are copies of ``benchmarks/kernels_suite.py`` as of the
benchmark's definition, so a later edit of the suite does not move the
yardstick.  Each program is a :class:`Program`: the kernel, its launch
geometry for ``n`` elements, a seeded input set made on the device, and
a numpy reference that computes every checked output exactly.

Every input is drawn so each float operation is exact: single correctly
rounded adds (vectorAdd), or small integers whose sums stay far below
2**24 (warpPrefixStats, histogram counts, gridReduce).  Any difference
from numpy is therefore a fault and not rounding, and the comparison
has the limit 0.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cox


@cox.kernel
def vectorAdd(
    c, out: cox.Array(cox.f32), a: cox.Array(cox.f32), b: cox.Array(cox.f32), n: cox.i32
):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        out[i] = a[i] + b[i]


@cox.kernel
def histogram64(c, hist: cox.Array(cox.f32), data: cox.Array(cox.i32), n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        c.atomic_add(hist, data[i], 1.0)


@cox.kernel
def warpPrefixStats(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32)):
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


@cox.kernel
def gridReduce(
    c,
    total: cox.Array(cox.f32),
    partial: cox.Array(cox.f32),
    data: cox.Array(cox.f32),
    n: cox.i32,
):
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


# --- numpy references --------------------------------------------------------


# Each reference computes in ``dtype``: float32 or wider gives the exact
# answer; the control computes in bfloat16, the next precision down, and
# has to fail the comparison.


def ref_vector_add(a, b, repeat: int = 1, dtype=np.float32) -> np.ndarray:
    """``repeat`` dependent adds x <- x + b, one rounding each."""
    x = a.astype(dtype)
    for _ in range(repeat):
        x = (x + b.astype(dtype)).astype(dtype)
    return x.astype(np.float32)


def ref_warp_prefix_stats(a: np.ndarray, dtype=np.float64) -> np.ndarray:
    """warpPrefixStats, one row per warp of 32 lanes, 8 warps a block."""
    v = a.reshape(-1, 32).astype(dtype)
    lane = np.arange(32)

    def shfl_down(z, off):  # lanes past the warp keep their own value
        src = np.minimum(lane + off, 31)
        return np.where(lane + off < 32, z[:, src], z)

    x = np.repeat(v.sum(1, keepdims=True), 32, 1)  # xor butterfly sum
    y = np.repeat((v * v).max(1, keepdims=True), 32, 1)  # butterfly max
    z = v + dtype(1.0)
    for off in (1, 2, 4):
        z = z + shfl_down(z, off)
    m = v.max(1, keepdims=True)
    n = v.min(1, keepdims=True)
    r = z.sum(1, keepdims=True)
    b = dtype(8.0) * y  # red_add(width=8) of a warp-uniform value
    lead = (x + m)[:, 0].reshape(-1, 8)  # tile[warp] per block
    tid = np.arange(256)
    t = lead[:, tid % 8].reshape(-1, 32)
    return (x + y + z + m + n + r + b + t).reshape(-1).astype(np.float32)


def ref_histogram64(data: np.ndarray, dtype=np.float64) -> np.ndarray:
    return np.bincount(data, minlength=64).astype(dtype).astype(np.float32)


def ref_grid_reduce(data, block: int, dtype=np.float64) -> Dict[str, np.ndarray]:
    partial = data.reshape(-1, block).astype(dtype).sum(1, dtype=dtype)
    return {
        "partial": partial.astype(np.float32),
        "total": np.array([partial.sum(dtype=dtype)], np.float32),
    }


# --- programs ----------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Program:
    """One CUDA program at ``n`` elements: how to make its inputs on the
    device from a key, how to bind a launch to them, and what numpy says
    its checked outputs are."""

    name: str
    kernel: object
    block: int
    make_inputs: Callable[[jax.Array, int], Dict[str, jax.Array]]
    args: Callable[[Dict[str, jax.Array], int], Tuple]
    reference: Callable[..., Dict[str, np.ndarray]]

    def grid(self, n: int) -> int:
        return -(-n // self.block)


def _vector_inputs(key, n):
    ka, kb = jax.random.split(key)
    return {
        "a": jax.random.normal(ka, (n,), jnp.float32),
        "b": jax.random.normal(kb, (n,), jnp.float32),
    }


def _small_ints(lo, hi, dtype):
    def make(key, n):
        return {"x": jax.random.randint(key, (n,), lo, hi).astype(dtype)}

    return make


PROGRAMS: Dict[str, Program] = {
    p.name: p
    for p in (
        Program(
            "vectorAdd",
            vectorAdd,
            256,
            _vector_inputs,
            lambda d, n: (jnp.zeros(n, jnp.float32), d["a"], d["b"], n),
            lambda d, n, repeat, dt=np.float32: {
                "out": ref_vector_add(d["a"], d["b"], repeat, dt)
            },
        ),
        Program(
            "warpPrefixStats",
            warpPrefixStats,
            256,
            _small_ints(-6, 7, jnp.float32),
            lambda d, n: (jnp.zeros(n, jnp.float32), d["x"]),
            lambda d, n, repeat, dt=np.float64: {
                "out": ref_warp_prefix_stats(d["x"], dt)
            },
        ),
        Program(
            "histogram64",
            histogram64,
            256,
            _small_ints(0, 64, jnp.int32),
            lambda d, n: (jnp.zeros(64, jnp.float32), d["x"], n),
            lambda d, n, repeat, dt=np.float64: {"hist": ref_histogram64(d["x"], dt)},
        ),
        Program(
            "gridReduce",
            gridReduce,
            128,
            _small_ints(-8, 9, jnp.float32),
            lambda d, n: (
                jnp.zeros(1, jnp.float32),
                jnp.zeros(-(-n // 128), jnp.float32),
                d["x"],
                n,
            ),
            lambda d, n, repeat, dt=np.float64: ref_grid_reduce(d["x"], 128, dt),
        ),
    )
}
