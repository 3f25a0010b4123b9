"""Row softmax kernel (numerically stable, single VMEM pass).

COX mapping: row tile = warp batch; lane-axis max/sum are the warp
collectives (`red_max`, `red_add`) that the paper implements with AVX —
one VPU reduction here instead of a 32-step scalar loop (Table 2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import cdiv

ROWS_PER_TILE = 8


def _softmax_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    m = x.max(axis=1, keepdims=True)          # warp red_max
    e = jnp.exp(x - m)
    s = e.sum(axis=1, keepdims=True)          # warp red_add
    o_ref[...] = (e / s).astype(o_ref.dtype)


def softmax(x: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1])
    rows, cols = x2.shape
    rt = min(ROWS_PER_TILE, rows)
    out = pl.pallas_call(
        _softmax_kernel,
        grid=(cdiv(rows, rt),),
        in_specs=[pl.BlockSpec((rt, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rt, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2)
    return out.reshape(orig_shape)
