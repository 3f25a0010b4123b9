"""Row-wise reduction kernel — the COX warp-collective pattern on TPU.

Hierarchical-collapsing mapping (DESIGN.md §2): the Pallas grid iterates
row tiles (the *inter-warp loop*); within a tile the (sublane × lane)
VREG layout holds 8×128 elements and the reduction over the lane axis is
the *intra-warp collective* (`red_add`/`red_max` — the AVX role from the
paper's Table 2, performed by the VPU in one shot instead of 32 scalar
iterations).

Tiling: rows are processed ROWS_PER_TILE at a time; the full column
extent of a tile lives in VMEM (cols ≤ ~64K f32 per 8-row tile is far
under the 16 MiB/core budget).  Column-tiled accumulation is used above
COL_TILE to bound VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import cdiv

ROWS_PER_TILE = 8
COL_TILE = 2048


def _reduce_kernel(x_ref, o_ref, *, op: str, n_col_tiles: int, cols: int):
    acc = None
    for t in range(n_col_tiles):  # inter-warp loop over column tiles
        lo = t * COL_TILE
        width = min(COL_TILE, cols - lo)
        blk = x_ref[:, lo:lo + width].astype(jnp.float32)
        if op == "absmax":
            blk = jnp.abs(blk)
        # intra-warp collective: lane-axis reduction
        part = blk.sum(axis=1) if op == "sum" else blk.max(axis=1)
        if acc is None:
            acc = part
        else:
            acc = acc + part if op == "sum" else jnp.maximum(acc, part)
    o_ref[:, 0] = acc


def row_reduce(x: jnp.ndarray, op: str = "sum", *,
               interpret: bool = False) -> jnp.ndarray:
    """(rows, cols) -> (rows,). op in {sum, max, absmax}."""
    rows, cols = x.shape
    rt = min(ROWS_PER_TILE, rows)
    grid = (cdiv(rows, rt),)
    out = pl.pallas_call(
        functools.partial(_reduce_kernel, op=op,
                          n_col_tiles=cdiv(cols, COL_TILE), cols=cols),
        grid=grid,
        in_specs=[pl.BlockSpec((rt, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rt, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
    return out[:, 0].astype(x.dtype if op != "sum" else jnp.float32)
