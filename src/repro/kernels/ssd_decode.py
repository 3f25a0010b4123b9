"""Mamba2 one-token state update: the recurrent form of SSD at decode.

For every head, with the head's decay exp(A·dt), the dt-scaled input
x·dt and the skip term D·x (Mamba2's D multiplies x before its dt
scaling),

    h' = h·exp(A·dt) + (x·dt) ⊗ B          (P, N) per head
    y  = h'·C + D·x                         (P,)  per head

B and C are shared by the heads (one group).  The state is laid out
(P, N) per head, so its tiles are lane-dense (N = 128 lanes; P = 64 would
fill half of them).  The kernel is handed the whole f32 state stack of a
run of layers, (L, B, H, P, N), and a layer index, scalar-prefetched: the
BlockSpecs pick that layer's state, which is read once from HBM and
written once, in place (the stack is aliased to the output and held in
HBM; a slice of the stack taken outside the kernel would be copied
first).  The stack must be an intermediate or donated, as a scan carry
is: on a jitted program's undonated parameter the TPU compiler's memory
space assignment aborts.  A sequence marked fresh starts from the zero
state.  The grid
walks the batch and blocks of heads, each block's state small enough
that its input and output blocks, double-buffered, stay inside v5e's
scoped VMEM.

Per-head values reach the kernel broadcast along P, (H, P), and B and C
as rows (1, N).  Everything is computed in f32, the contraction over N
as a sum of products, as the einsums of the XLA path do.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one head block's f32 state in VMEM
BLOCK_BYTES = 2 << 20


def head_block(H: int, P: int, N: int) -> int:
    """The most heads whose state block fits ``BLOCK_BYTES``, dividing H."""
    per_head = P * max(N, 128) * 4
    fits = [b for b in range(1, H + 1) if H % b == 0
            and b * per_head <= BLOCK_BYTES]
    return max(fits) if fits else 1


def _kernel(layer_ref, fresh_ref, h_ref, x_ref, dt_ref, a_ref, b_ref, c_ref,
            d_ref, y_ref, ho_ref):
    x, dt = x_ref[0], dt_ref[0]                     # (hb, P)
    decay = jnp.exp(a_ref[...] * dt)[:, :, None]    # (hb, P, 1)
    h = jnp.where(fresh_ref[pl.program_id(0)] == 0, h_ref[0, 0], 0.0)
    h = h * decay + (x * dt)[:, :, None] * b_ref[0]   # (hb, P, N)
    ho_ref[0, 0] = h
    y_ref[0] = jnp.sum(h * c_ref[0], axis=-1) + d_ref[...] * x


def ssd_decode(h, layer, fresh, x, dt, a, b, c, d, *,
               interpret: bool = False):
    """h: (L, B, H, P, N) f32 state stack; layer: (1,) int32, the layer
    updated; fresh: (B,) bool, the sequences that start from the zero
    state; x: (B, H, P); dt: (B, H) after the softplus; a: (H,) =
    -exp(A_log); b, c: (B, N); d: (H,) -> (y (B, H, P) f32, the stack
    with layer ``layer`` updated)."""
    _, Bt, H, P, N = h.shape
    hb = head_block(H, P, N)
    f32 = jnp.float32

    def along_p(v):                                 # (..., H) -> (..., H, P)
        v = v.astype(f32)[..., None]
        return jnp.broadcast_to(v, v.shape[:-1] + (P,))

    state_in = h.astype(f32)
    state_out = jax.ShapeDtypeStruct(h.shape, f32)
    if not interpret:
        # the stack stays in HBM, where the kernel reads and writes it
        state_in = pltpu.with_memory_space_constraint(state_in, pltpu.HBM)
        state_out = pltpu.HBM(h.shape, f32)
    per_head = pl.BlockSpec((hb, P), lambda i, j, *_: (j, 0))
    per_row = pl.BlockSpec((1, hb, P), lambda i, j, *_: (i, j, 0))
    row = pl.BlockSpec((1, 1, N), lambda i, j, *_: (i, 0, 0))
    state = pl.BlockSpec((1, 1, hb, P, N),
                         lambda i, j, layer, _: (layer[0], i, j, 0, 0))
    y, h_new = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Bt, H // hb),
            in_specs=[state, per_row, per_row, per_head, row, row, per_head],
            out_specs=[per_row, state]),
        out_shape=[jax.ShapeDtypeStruct((Bt, H, P), f32), state_out],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssd_decode",
    )(layer.astype(jnp.int32), fresh.astype(jnp.int32), state_in,
      x.astype(f32), along_p(dt), along_p(a),
      b.astype(f32)[:, None], c.astype(f32)[:, None], along_p(d))
    return y, h_new
