"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels (interpret=True on
CPU, compiled on TPU) are tested against with shape/dtype sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def row_reduce(x: jnp.ndarray, op: str = "sum") -> jnp.ndarray:
    """(rows, cols) -> (rows,) reduction."""
    if op == "sum":
        return x.sum(axis=-1)
    if op == "max":
        return x.max(axis=-1)
    if op == "absmax":
        return jnp.abs(x).max(axis=-1)
    raise ValueError(op)


def softmax(x: jnp.ndarray) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    m = x32.max(axis=-1, keepdims=True)
    e = jnp.exp(x32 - m)
    return (e / e.sum(axis=-1, keepdims=True)).astype(x.dtype)


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = (x32 * x32).mean(axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, w, b, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(axis=-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


ATTN_Q_CHUNK = 1024  # q-chunking bound on the S² logits working set


def attention(q, k, v, *, causal: bool = True, scale=None,
              window: int = 0, q_chunk: int = ATTN_Q_CHUNK) -> jnp.ndarray:
    """q: (S, H, D); k/v: (S, Hkv, D) — GQA by head-group broadcast.

    Queries are processed in chunks (lax.map + remat) so the logits
    working set is (H, q_chunk, S) rather than (H, S, S): the XLA-path
    analogue of the Pallas flash kernel's blocking."""
    S, H, D = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    k32 = jnp.repeat(k.astype(jnp.float32), g, axis=1)
    v32 = jnp.repeat(v.astype(jnp.float32), g, axis=1)

    def chunk(args):
        qc, q0 = args                                   # (Cq, H, D), ()
        q32 = qc.astype(jnp.float32) * scale
        logits = jnp.einsum("qhd,khd->hqk", q32, k32)   # (H, Cq, S)
        if causal:
            qi = q0 + jnp.arange(qc.shape[0])[:, None]
            kj = jnp.arange(S)[None, :]
            msk = qi >= kj
            if window:
                msk = msk & (qi - kj < window)
            logits = jnp.where(msk[None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v32)

    if S <= q_chunk:
        out = chunk((q, jnp.int32(0)))
        return out.astype(q.dtype)
    assert S % q_chunk == 0
    nq = S // q_chunk
    qs = q.reshape(nq, q_chunk, H, D)
    starts = (jnp.arange(nq) * q_chunk).astype(jnp.int32)
    out = jax.lax.map(jax.checkpoint(chunk), (qs, starts))
    return out.reshape(S, H, D).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, kv_len=None, scale=None):
    """Single-token decode: q (H, D); caches (S, Hkv, D)."""
    H, D = q.shape
    S, Hkv, _ = k_cache.shape
    g = H // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    q32 = q.astype(jnp.float32) * scale
    k32 = jnp.repeat(k_cache.astype(jnp.float32), g, axis=1)
    v32 = jnp.repeat(v_cache.astype(jnp.float32), g, axis=1)
    logits = jnp.einsum("hd,shd->hs", q32, k32)
    if kv_len is not None:
        logits = jnp.where(jnp.arange(S)[None, :] < kv_len, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hs,shd->hd", p, v32).astype(q.dtype)


def decode_attn_cache(layer, pos, kv_len, q, k_new, v_new, k_stack, v_stack):
    """One decode step of every sequence on layer ``layer`` of the K/V
    stacks (n, B, S, Hkv, D): the token's K and V (B, Hkv, D) written at
    ``pos`` (B,), then ``decode_attention`` of q (B, H, D) over the first
    ``kv_len`` (B,) rows -> (out (B, H, D), the stacks so written)."""
    i, b = layer.reshape(()), jnp.arange(q.shape[0])
    k_stack = k_stack.at[i, b, pos].set(k_new.astype(k_stack.dtype))
    v_stack = v_stack.at[i, b, pos].set(v_new.astype(v_stack.dtype))
    out = jax.vmap(decode_attention)(q, k_stack[i], v_stack[i], kv_len)
    return out, k_stack, v_stack


def ssd_scan(x, a, b, c, *, chunk: int = 0):
    """Mamba2 SSD (state-space dual) sequential reference.

    x: (S, H, P)  input per head
    a: (S, H)     log-decay (a = -softplus(...)); decay factor exp(a)
    b: (S, N)     input projection (shared across heads)
    c: (S, N)     output projection
    Returns y: (S, H, P); state update  h_t = exp(a_t) h_{t-1} + b_t x_tᵀ.
    """
    S, H, P = x.shape
    N = b.shape[-1]

    def step(h, inp):
        xt, at, bt, ct = inp
        h = jnp.exp(at)[:, None, None] * h + \
            jnp.einsum("n,hp->hnp", bt, xt)
        y = jnp.einsum("n,hnp->hp", ct, h)
        return h, y

    h0 = jnp.zeros((H, N, P), jnp.float32)
    _, y = jax.lax.scan(step, h0, (x.astype(jnp.float32),
                                   a.astype(jnp.float32),
                                   b.astype(jnp.float32),
                                   c.astype(jnp.float32)))
    return y.astype(x.dtype)


def ssd_scan_chunked(x, a, b, c, *, chunk: int = 128):
    """Chunked SSD — the dual (matmul) form, same math as the Pallas
    kernel but in pure jnp.  O(S·C) work and O(S/C) scan steps instead of
    O(S) steps: this is the production XLA path (sequential `ssd_scan`
    stays as the oracle).

    x: (S,H,P); a: (S,H); b,c: (S,N) -> (S,H,P)
    """
    S, H, P = x.shape
    N = b.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    xc = x.reshape(nc, chunk, H, P).astype(jnp.float32)
    ac = a.reshape(nc, chunk, H).astype(jnp.float32)
    bc = b.reshape(nc, chunk, N).astype(jnp.float32)
    cc = c.reshape(nc, chunk, N).astype(jnp.float32)

    A = jnp.cumsum(ac, axis=1)                       # (nc, C, H)
    A_tot = A[:, -1]                                 # (nc, H)
    i = jnp.arange(chunk)[:, None]
    j = jnp.arange(chunk)[None, :]
    causal = i >= j
    # L: (nc, H, C, C).  Mask the exponent BEFORE exp: the non-causal side
    # has positive exponents that overflow, and inf-in-the-dead-branch
    # poisons the backward (0·inf = NaN through jnp.where).
    diff = (A.transpose(0, 2, 1)[:, :, :, None]
            - A.transpose(0, 2, 1)[:, :, None, :])
    L = jnp.exp(jnp.where(causal[None, None], diff, -jnp.inf))
    cb = jnp.einsum("gin,gjn->gij", cc, bc)          # (nc, C, C)
    y_intra = jnp.einsum("ghij,gij,gjhp->gihp", L, cb, xc)

    # inter-chunk: scan over chunks carrying h (H, N, P)
    w = jnp.einsum("gjn,gjh->gjhn", bc, jnp.exp(A_tot[:, None] - A))
    h_add = jnp.einsum("gjhn,gjhp->ghnp", w, xc)     # (nc, H, N, P)

    def step(h, inp):
        atot, hadd = inp
        y_state_in = h                                # state entering chunk
        h = jnp.exp(atot)[:, None, None] * h + hadd
        return h, y_state_in

    h0 = jnp.zeros((H, N, P), jnp.float32)
    _, h_in = jax.lax.scan(step, h0, (A_tot, h_add))  # (nc, H, N, P)
    y_inter = jnp.einsum("gin,ghnp,gih->gihp", cc, h_in, jnp.exp(A))
    y = (y_intra + y_inter).reshape(S, H, P)
    return y.astype(x.dtype)


def topk_gate(logits, k: int):
    """MoE router: top-k over experts, softmax over the selected subset.
    logits: (T, E) -> (weights (T, k), indices (T, k))."""
    vals, idx = jax.lax.top_k(logits.astype(jnp.float32), k)
    w = jax.nn.softmax(vals, axis=-1)
    return w, idx


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def moe_decode_experts(layer, ids, x, weights, w_gate, w_up, w_down,
                       held=None):
    """The chosen experts' SwiGLU, weighted and summed per token.
    layer: (1,); ids, weights: (T·k,), token-major; x: (T, d); stacks
    (L, E, d, f) and (L, E, f, d) -> (T, d) f32.  Only the chosen slabs
    are gathered; the products round as the capacity path's do.
    ``held`` (T·k,) bool weights the choices held elsewhere 0."""
    if held is not None:
        weights = jnp.where(held, weights, 0.0)
    T, d = x.shape
    k = ids.shape[0] // T
    sel = (layer.reshape(()), ids)
    xs = jnp.repeat(x, k, axis=0)                          # (T·k, d)
    h = jnp.einsum("nd,ndf->nf", xs, w_gate[sel])
    u = jnp.einsum("nd,ndf->nf", xs, w_up[sel])
    down = jnp.einsum("nf,nfd->nd", jax.nn.silu(h) * u,
                      w_down[sel]).astype(jnp.float32)
    contrib = (down * weights[:, None]).reshape(T, k, d)
    y = jnp.zeros((T, d), jnp.float32)
    for j in range(k):                  # choice order, as the kernel sums
        y = y + contrib[:, j]
    return y


def ssd_decode(h, layer, fresh, x, dt, a, b, c, d):
    """Mamba2's one-token state update of one layer, every head: h
    (L, B, H, P, N) f32 state stack; layer (1,); fresh (B,) bool, the
    sequences that start from the zero state; x (B, H, P); dt (B, H);
    a, d (H,); b, c (B, N) -> (y (B, H, P) f32, the stack with layer
    ``layer`` replaced by h' = h·exp(a·dt) + (x·dt) ⊗ b), where
    y = h'·c + d·x."""
    i = layer.reshape(())
    state = jnp.where(fresh[:, None, None, None], 0.0, h[i])
    dt = dt.astype(jnp.float32)
    decay = jnp.exp(a * dt)                                   # (B, H)
    x = x.astype(jnp.float32)
    b, c = b.astype(jnp.float32), c.astype(jnp.float32)
    state = state * decay[..., None, None] + \
        jnp.einsum("bhp,bn->bhpn", x * dt[..., None], b)
    y = jnp.einsum("bhpn,bn->bhp", state, c) + x * d[None, :, None]
    return y, h.at[i].set(state)
