"""Blocked (flash) attention kernels: causal/windowed prefill + decode.

COX mapping (DESIGN.md §2): the Pallas grid over KV blocks is the
*inter-warp loop*; the online-softmax running max / running sum are the
warp collectives (`red_max` / `red_add`) vectorized over lanes; loop
peeling appears as the `pl.when` causal-block skip — the whole-warp
uniform branch of the paper's §3.3.1.

GQA is expressed through BlockSpec index maps (a q-head group reads its
shared KV head), so no repeated KV is materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import NEG_INF

DEFAULT_BQ = 128
DEFAULT_BK = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  bq: int, bk: int, nk: int):
    h, iq, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    def _body():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)                  # (bk, d)
        s = q @ k.T                                       # MXU (bq, bk)
        if causal:
            msk = q_pos >= k_pos
            if window:
                msk = msk & (q_pos - k_pos < window)
            s = jnp.where(msk, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))        # warp red_max
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)   # warp red_add
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
        m_scr[...] = m_new

    if causal:
        # peeled uniform branch (paper §3.3.1): whole KV blocks above the
        # diagonal are skipped — all "lanes" take the same direction
        pl.when((ik * bk) <= (iq * bq + bq - 1))(_body)
    else:
        _body()

    @pl.when(ik == nk - 1)
    def _finish():
        lsum = l_scr[...]
        lsum = jnp.where(lsum == 0.0, 1.0, lsum)
        o_ref[0] = (acc_scr[...] / lsum[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale=None, bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = False):
    """q: (S, H, D); k/v: (S, Hkv, D) -> (S, H, D)."""
    S, H, D = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, "pad sequence to block multiple"
    nq, nk = S // bq, S // bk

    qt = q.transpose(1, 0, 2)   # (H, S, D)
    kt = k.transpose(1, 0, 2)   # (Hkv, S, D)
    vt = v.transpose(1, 0, 2)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, nk=nk),
        grid=(H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bk, D), lambda h, iq, ik: (h // g, ik, 0)),
            pl.BlockSpec((1, bk, D), lambda h, iq, ik: (h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(1, 0, 2)


# ---------------------------------------------------------------------------
# decode: one new token against a long KV cache
# ---------------------------------------------------------------------------


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, bk: int, nk: int):
    hkv, ik = pl.program_id(0), pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[0]
    g = q_ref.shape[1]

    @pl.when(ik * bk < kv_len)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale          # (g, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = q @ k.T                                       # (g, bk)
        pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
        s = jnp.where(pos < kv_len, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        lsum = l_scr[...]
        lsum = jnp.where(lsum == 0.0, 1.0, lsum)
        o_ref[0] = (acc_scr[...] / lsum[:, None]).astype(o_ref.dtype)


def flash_decode(q, k_cache, v_cache, kv_len, *, scale=None,
                 bk: int = 512, interpret: bool = False):
    """q: (H, D); caches: (S, Hkv, D); kv_len: () int32 -> (H, D)."""
    H, D = q.shape
    S, Hkv, _ = k_cache.shape
    g = H // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    bk = min(bk, S)
    assert S % bk == 0
    nk = S // bk

    qg = q.reshape(Hkv, g, D)
    kt = k_cache.transpose(1, 0, 2)
    vt = v_cache.transpose(1, 0, 2)
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(1)

    # kv_len rides in SMEM as a prefetched scalar: the skip predicate
    # below needs it as a scalar, and a (1,)-shaped VMEM block breaks
    # the TPU's (8, 128) block rule once the serve step vmaps the call
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bk=bk, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Hkv, nk),
            in_specs=[
                pl.BlockSpec((1, g, D), lambda h, ik, n: (h, 0, 0)),
                pl.BlockSpec((1, bk, D), lambda h, ik, n: (h, ik, 0)),
                pl.BlockSpec((1, bk, D), lambda h, ik, n: (h, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, g, D), lambda h, ik, n: (h, 0, 0)),
            scratch_shapes=[pltpu.VMEM((g,), jnp.float32),
                            pltpu.VMEM((g,), jnp.float32),
                            pltpu.VMEM((g, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((Hkv, g, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode",
    )(kv_len, qg, kt, vt)
    return out.reshape(H, D)


# ---------------------------------------------------------------------------
# decode on the cache stacks: the token written and attended in place
# ---------------------------------------------------------------------------

LANES = 128


def _cache_kernel(layer_ref, pos_ref, len_ref, q_ref, kn_ref, vn_ref, k_ref,
                  v_ref, o_ref, ko_ref, vo_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, bk: int, nk: int, minor: bool):
    b, ik = pl.program_id(0), pl.program_id(1)
    pos, kv_len = pos_ref[b], len_ref[b]
    ax = 2 if minor else 1          # the positions' axis, heads first
    f32 = jnp.float32

    def heads(blk):                 # (Hkv, D, positions), or (Hkv, ·, D)
        blk = blk.astype(f32)
        return blk if minor else jnp.swapaxes(blk, 0, 1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if not minor:               # the token's row is the block written
            ko_ref[0, 0] = kn_ref[0]
            vo_ref[0, 0] = vn_ref[0]

    if minor:
        @pl.when(ik == pos // bk)
        def _write():
            # the block written is the 128 positions around the token's,
            # as fetched, with the token's column in place
            off = pl.multiple_of(pos % bk // LANES * LANES, LANES)
            lane = jax.lax.broadcasted_iota(jnp.int32, ko_ref.shape[2:], 2)
            for new, blk, out in ((kn_ref, k_ref, ko_ref),
                                  (vn_ref, v_ref, vo_ref)):
                out[0, 0] = jnp.where(lane == pos % LANES, new[0],
                                      blk[0, 0, :, :, pl.ds(off, LANES)])

    @pl.when(ik * bk < kv_len)
    def _body():
        q = q_ref[0].astype(f32) * scale                  # (Hkv, g, D)
        k, v = heads(k_ref[0, 0]), heads(v_ref[0, 0])
        # the block as fetched holds the row at pos from before this
        # step: the token's takes its place
        at = ik * bk + jax.lax.broadcasted_iota(jnp.int32, k.shape, ax)
        k = jnp.where(at == pos, heads(kn_ref[0]), k)
        v = jnp.where(at == pos, heads(vn_ref[0]), v)
        # each q-head group against its kv head, batched over kv heads
        s = jax.lax.dot_general(q, k, (((2,), (3 - ax,)), ((0,), (0,))),
                                preferred_element_type=f32)  # (Hkv, g, bk)
        at = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(at < kv_len, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (ax,)), ((0,), (0,))), preferred_element_type=f32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        lsum = l_scr[...]
        lsum = jnp.where(lsum == 0.0, 1.0, lsum)
        o_ref[0] = (acc_scr[...] / lsum).astype(o_ref.dtype)


def decode_attn_cache(layer, pos, kv_len, q, k_new, v_new, k_stack, v_stack,
                      *, minor: bool = False, scale=None, bk: int = 512,
                      interpret: bool = False, name: str = "decode_attn_cache"):
    """One decode step's attention of every sequence on the whole K/V
    stacks of a run of layers, with the token's K and V written in place.

    layer: (1,) int32; pos, kv_len: (B,) int32, the row each sequence
    writes and how many rows it attends (pos < kv_len); q: (B, H, D);
    k_new, v_new: (B, Hkv, D); stacks: (n, B, S, Hkv, D) -> (out (B, H,
    D), k_stack, v_stack), the stacks aliased to their inputs and changed
    only at ``(layer, b, pos[b])``.  A sliding layer's ring of W rows is
    such a stack too: its caller passes the ring slot as ``pos`` and
    min(position + 1, W) as ``kv_len``, and ``name`` tells its calls
    apart in a trace (``decode_attn_ring``).

    ``minor`` says how the device stores the stacks: with the positions
    minor, (n, B, Hkv, D, S) in memory (the TPU's layout for D = 64, which
    would otherwise pad each row to 128 lanes), else as indexed.  The
    kernel walks them in that order, so no stack is transposed or copied.
    The grid walks the sequences and blocks of ``bk`` positions; a
    block's index is clamped to the sequence's last valid block, so
    blocks past ``kv_len`` are never fetched.  The stacks' output blocks
    are the token's row (with the positions minor, the 128 positions
    around it), so each sequence writes back two small blocks; the stacks
    stay in HBM and must be an intermediate or donated, as a scan carry
    is.  A q-head group reads its kv head's tiles in VMEM.  The math is
    ``flash_decode``'s: f32 scores, online softmax."""
    Bt, H, D = q.shape
    n, _, S, Hkv, _ = k_stack.shape
    g = H // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    bk = min(bk, S)
    assert S % bk == 0 and not (minor and bk % LANES)
    nk = S // bk
    dt = k_stack.dtype
    stacks = [k_stack, v_stack]
    new = [k_new.astype(dt), v_new.astype(dt)]
    if minor:
        stacks = [jnp.moveaxis(s, 2, 4) for s in stacks]   # (n, B, Hkv, D, S)
        new = [t[..., None] for t in new]                   # (B, Hkv, D, 1)
        token = pl.BlockSpec((1, Hkv, D, 1), lambda b, ik, *_: (b, 0, 0, 0))
        block = pl.BlockSpec(
            (1, 1, Hkv, D, bk), lambda b, ik, layer, pos, ln: (
                layer[0], b, 0, 0, jnp.minimum(ik, (ln[b] - 1) // bk)))
        row = pl.BlockSpec(
            (1, 1, Hkv, D, LANES), lambda b, ik, layer, pos, ln: (
                layer[0], b, 0, 0, pos[b] // LANES))
    else:
        new = [t[:, None] for t in new]                     # (B, 1, Hkv, D)
        token = pl.BlockSpec((1, 1, Hkv, D), lambda b, ik, *_: (b, 0, 0, 0))
        block = pl.BlockSpec(
            (1, 1, bk, Hkv, D), lambda b, ik, layer, pos, ln: (
                layer[0], b, jnp.minimum(ik, (ln[b] - 1) // bk), 0, 0))
        row = pl.BlockSpec(
            (1, 1, 1, Hkv, D), lambda b, ik, layer, pos, ln: (
                layer[0], b, pos[b], 0, 0))
    stack_out = [jax.ShapeDtypeStruct(stacks[0].shape, dt)] * 2
    if not interpret:
        # the stacks stay in HBM, where the kernel reads and writes them
        stacks = [pltpu.with_memory_space_constraint(s, pltpu.HBM)
                  for s in stacks]
        stack_out = [pltpu.HBM(stacks[0].shape, dt)] * 2
    per_seq = pl.BlockSpec((1, Hkv, g, D), lambda b, ik, *_: (b, 0, 0, 0))
    # a position past the stacks writes the last row, as
    # dynamic_update_slice does, and never names a block outside them
    pos = jnp.minimum(pos.astype(jnp.int32), S - 1)
    kv_len = jnp.minimum(kv_len.astype(jnp.int32), S)
    out, k_stack, v_stack = pl.pallas_call(
        functools.partial(_cache_kernel, scale=scale, bk=bk, nk=nk,
                          minor=minor),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Bt, nk),
            in_specs=[per_seq, token, token, block, block],
            out_specs=[per_seq, row, row],
            scratch_shapes=[pltpu.VMEM((Hkv, g, 1), jnp.float32),
                            pltpu.VMEM((Hkv, g, 1), jnp.float32),
                            pltpu.VMEM((Hkv, g, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((Bt, Hkv, g, D), q.dtype), *stack_out],
        input_output_aliases={6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(layer.astype(jnp.int32), pos, kv_len, q.reshape(Bt, Hkv, g, D), *new,
      *stacks)
    if minor:
        k_stack, v_stack = (jnp.moveaxis(s, 4, 2) for s in (k_stack, v_stack))
    return out.reshape(Bt, H, D), k_stack, v_stack
