"""Decode MoE: the router's chosen experts only, read straight from the
whole stacked expert weights.

At decode a layer sees a few tokens, and top-k of E experts leaves most
experts with none.  The capacity path (models/layers.py) multiplies every
expert's weights by zero-padded slots; this kernel reads each chosen
expert's slabs once.  Its grid walks the T·k choices.  Each weight
BlockSpec's index map picks ``(layer, ids[j])`` from the scalar-prefetched
operands, so each step DMAs one expert's gate, up and down slabs out of
the (L, E, ...) stacks while the step before multiplies; an expert too
wide for VMEM is read in slices of its width f.  The stacks are passed
unsliced: a custom call cannot fuse a dynamic-slice into its operand, so a
per-layer slice would copy all E experts of the layer first.

A chip that holds a share of the experts (``held`` given) walks every
choice all the same; a choice held elsewhere is weighted 0, and its grid
steps repeat the block index of the step before (the last held choice's
last slice, or the first held choice's first slice where none came
before), so Pallas fetches nothing for it.

Rounding follows the capacity path: the gate and up products and
``silu(h) * u`` are rounded to the activation dtype, the down product
(summed over the slices in f32) is rounded to it too, then weighted and
summed in f32, token by token in choice order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import round_up

ROWS = 16   # token rows padded to one bf16 sublane tile
# one grid step's three weight blocks: double-buffered, they stay under
# v5e's 16 MiB of scoped VMEM
STEP_BYTES = 4 << 20


def f_tile(d: int, f: int, itemsize: int) -> int:
    """The widest slice of the expert width f whose gate, up and down
    blocks fit ``STEP_BYTES``: f itself where it fits, else a multiple of
    128 lanes that divides f."""
    fits = [t for t in range(128, f + 1, 128)
            if f % t == 0 and 3 * d * t * itemsize <= STEP_BYTES]
    return max(fits) if fits else f


def _kernel(*refs, k: int):
    x_ref, w_ref, wg_ref, wu_ref, wd_ref, y_ref, acc_ref = refs[-7:]
    j, c = pl.program_id(0), pl.program_id(1)

    @pl.when((j == 0) & (c == 0))
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(c == 0)
    def _start():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                        # (rows, d)
    dt = x.dtype
    h = jnp.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0, 0], preferred_element_type=jnp.float32)
    g = jax.nn.silu(h.astype(dt).astype(jnp.float32)).astype(dt)
    a = (g.astype(jnp.float32) * u.astype(dt).astype(jnp.float32)).astype(dt)
    acc_ref[...] += jnp.dot(a, wd_ref[0, 0], preferred_element_type=jnp.float32)

    @pl.when(c == pl.num_programs(1) - 1)
    def _combine():
        down = acc_ref[...].astype(dt).astype(jnp.float32)
        # choice j belongs to token j // k: only that row takes its product
        row = jax.lax.broadcasted_iota(jnp.int32, down.shape, 0)
        y_ref[...] += jnp.where(row == j // k, w_ref[j] * down, 0.0)


def _held_fetches(ids, held, slices: int):
    """For a share: the expert each choice's steps fetch, and the slice a
    choice held elsewhere keeps (-1 for a held choice, which walks every
    slice), so that its steps repeat the block index of the step before."""
    j = jnp.arange(ids.shape[0])
    last = jax.lax.cummax(jnp.where(held, j, -1))    # last held choice <= j
    first = jnp.argmax(held)
    fetch = ids[jnp.where(last >= 0, last, first)]
    keep = jnp.where(held, -1, jnp.where(last >= 0, slices - 1, 0))
    return fetch.astype(jnp.int32), keep.astype(jnp.int32)


def moe_decode_experts(layer, ids, x, weights, w_gate, w_up, w_down,
                       held=None, *, interpret: bool = False):
    """layer: (1,) int32; ids: (T·k,) int32, token-major; x: (T, d);
    weights: (T·k,) f32 combine weights; w_gate, w_up: (L, E, d, f);
    w_down: (L, E, f, d) -> y: (T, d) f32, the weighted sum of each
    token's chosen experts' SwiGLU outputs.  The grid walks the choices,
    and within each the expert width in slices of ``f_tile``.  ``held``
    (T·k,) bool, where this chip holds a share of the experts, marks the
    choices whose expert is among the stacks' E; the others add nothing
    and fetch nothing."""
    T, d = x.shape
    n = ids.shape[0]
    f = w_gate.shape[-1]
    dt = jnp.promote_types(x.dtype, w_gate.dtype)
    rows = round_up(T, ROWS)
    xp = jnp.zeros((rows, d), dt).at[:T].set(x.astype(dt))
    tf = f_tile(d, f, w_gate.dtype.itemsize)
    scalars = (layer, ids)
    if held is not None:
        weights = jnp.where(held, weights, 0.0)
        scalars = (layer, *_held_fetches(ids, held, f // tf))

    def part(j, c, keep):
        # a choice held elsewhere keeps the slice of the step before it
        return c if keep is None else jnp.where(keep[j] < 0, c, keep[j])

    def gate_up(j, c, layer_ref, ids_ref, keep=None):
        return (layer_ref[0], ids_ref[j], 0, part(j, c, keep))

    def down(j, c, layer_ref, ids_ref, keep=None):
        return (layer_ref[0], ids_ref[j], part(j, c, keep), 0)

    def whole(j, c, *refs):
        return (0, 0)

    y = pl.pallas_call(
        functools.partial(_kernel, k=n // T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n, f // tf),
            in_specs=[
                pl.BlockSpec((rows, d), whole),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, d, tf), gate_up),
                pl.BlockSpec((1, 1, d, tf), gate_up),
                pl.BlockSpec((1, 1, tf, d), down),
            ],
            out_specs=pl.BlockSpec((rows, d), whole),
            scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_decode_experts",
    )(*scalars, xp, weights, w_gate, w_up, w_down)
    return y[:T]
