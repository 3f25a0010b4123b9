"""RMSNorm / LayerNorm kernels.

COX mapping: the mean/variance reductions are warp `red_add` collectives
on the lane axis; rows are the inter-warp loop (grid).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import cdiv

ROWS_PER_TILE = 8


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = (x * x).mean(axis=1, keepdims=True)      # warp red_add / n
    y = x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm(x, w, *, eps: float = 1e-6, interpret: bool = False):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    rows, cols = x2.shape
    rt = min(ROWS_PER_TILE, rows)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(cdiv(rows, rt),),
        in_specs=[pl.BlockSpec((rt, cols), lambda i: (i, 0)),
                  pl.BlockSpec((1, cols), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((rt, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, w.reshape(1, -1))
    return out.reshape(shape)


def _layernorm_kernel(x_ref, w_ref, b_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    mu = x.mean(axis=1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    y = y * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def layernorm(x, w, b, *, eps: float = 1e-6, interpret: bool = False):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    rows, cols = x2.shape
    rt = min(ROWS_PER_TILE, rows)
    out = pl.pallas_call(
        functools.partial(_layernorm_kernel, eps=eps),
        grid=(cdiv(rows, rt),),
        in_specs=[pl.BlockSpec((rt, cols), lambda i: (i, 0)),
                  pl.BlockSpec((1, cols), lambda i: (0, 0)),
                  pl.BlockSpec((1, cols), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((rt, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, w.reshape(1, -1), b.reshape(1, -1))
    return out.reshape(shape)
