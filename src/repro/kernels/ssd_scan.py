"""Mamba2 SSD (state-space duality) chunked scan kernel.

COX mapping: the chunk loop is the *inter-warp loop* (sequential grid
dimension carrying the (N, P) state in VMEM scratch — the role of the
paper's replicated cross-PR variables); intra-chunk work is the
*intra-warp* part, done as dense MXU matmuls via the SSD dual form:

    y_intra = ((C Bᵀ) ⊙ L) X          L[i,j] = exp(A_i − A_j)·[i ≥ j]
    y_inter = exp(A) ⊙ (C h_in)
    h_out   = exp(A_C) h_in + (B ⊙ exp(A_C − A))ᵀ X

with A the within-chunk cumulative log-decay (A_C its total).  a ≤ 0
(decay), so every exponent is ≤ 0 — numerically safe.

Heads lead every per-head operand, as in ``flash_attention``, so each
block's last two dims are (chunk, P) or (chunk, 1): the TPU compiler
requires them to be multiples of (8, 128) or the array's own dims.
The within-chunk cumulative sum is taken outside the kernel and passed
both as a column and as a row, so the kernel forms A_i − A_j by
broadcasting alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _ssd_kernel(x_ref, acol_ref, arow_ref, b_ref, c_ref, y_ref, h_scr, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)              # (C, P)
    A_col = acol_ref[0]                           # (C, 1) within-chunk
    A_row = arow_ref[0]                           # (1, C)   log decay
    b = b_ref[...].astype(jnp.float32)            # (C, N)
    c = c_ref[...].astype(jnp.float32)            # (C, N)

    # intra-chunk (dual/matmul form — MXU work)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    diff = A_col - A_row                          # A_i − A_j
    # mask the exponent before exp (overflow hygiene; see ref.py)
    L = jnp.exp(jnp.where(i >= j, diff, -jnp.inf))
    s = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ()))) * L   # (C, C)
    y = s @ x                                     # (C, P)

    # inter-chunk contribution from carried state
    h_in = h_scr[...]                             # (N, P)
    y = y + jnp.exp(A_col) * (c @ h_in)

    # state update for the next chunk.  A_C is read out by masked
    # reductions, as a column (C, 1) and as a row (1, P): Mosaic cannot
    # broadcast a (1, 1) value across sublanes and lanes at once
    to_end = -jnp.sum(jnp.where(j == chunk - 1, diff, 0.0), axis=1,
                      keepdims=True)              # A_C − A_i: (C, 1)
    ip = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    A_total = jnp.sum(jnp.where(ip == chunk - 1, A_col, 0.0), axis=0,
                      keepdims=True)              # (1, P)
    w = b * jnp.exp(to_end)                       # (C, N)
    h_scr[...] = jnp.exp(A_total) * h_in + jax.lax.dot_general(
        w, x, (((0,), (0,)), ((), ())))           # wᵀ x: (N, P)

    y_ref[0] = y.astype(y_ref.dtype)


def ssd_scan(x, a, b, c, *, chunk: int = DEFAULT_CHUNK,
             interpret: bool = False):
    """x: (S, H, P); a: (S, H); b, c: (S, N) -> y: (S, H, P)."""
    S, H, P = x.shape
    N = b.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, "pad sequence to chunk multiple"
    nc = S // chunk

    xt = x.transpose(1, 0, 2)                     # (H, S, P)
    A = jnp.cumsum(a.astype(jnp.float32).reshape(nc, chunk, H), axis=1)
    At = A.reshape(S, H).T                        # (H, S)

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda h, ci: (h, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda h, ci: (h, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda h, ci: (h, 0, ci)),
            pl.BlockSpec((chunk, N), lambda h, ci: (ci, 0)),
            pl.BlockSpec((chunk, N), lambda h, ci: (ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda h, ci: (h, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xt, At[:, :, None], At[:, None, :], b, c)
    return out.transpose(1, 0, 2)
