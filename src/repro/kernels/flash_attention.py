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
