"""The published granitemoehybrid forward pass (granite-4.0-h), written
out plainly in float32 ``jax.numpy`` for the tests to compare the model
stack with: no kernel, cache, capacity or batching, every matmul at the
highest precision.

Each layer, as the published decoder layer computes it:

    x = x + m_res · mixer(rmsnorm(x))           mixer: Mamba2 or attention
    x = x + m_res · (moe(h) + shared(h)),        h = rmsnorm(x)

with the embedding times ``m_emb``, the attention scores times
``m_attn`` and the logits divided by ``m_logits``.  The Mamba2 mixer is
the sequential recurrence (one group):

    xBC = silu(conv1d(xBC) + conv bias);  dt = softplus(dt + dt_bias)
    h_t = h_{t-1}·exp(A·dt_t) + B_t ⊗ (x_t·dt_t),   A = -exp(A_log)
    y_t = C_t·h_t + D·x_t;   out = rmsnorm(y · silu(z)) @ W_out

Attention is GQA with no position embedding (NoPE).  The MoE routes
top-k over all the router's experts with a softmax over the k chosen
logits; a SwiGLU expert's output is weighted by its gate.

Departures, each shared with the program:

* weights come in the program's parameter tree (``models/lm.py``); the
  multipliers default to the ones the program's layers imply (embedding
  1, scores 1/sqrt(head_dim), residual 1, logits 1), and a caller that
  folds published multipliers into the weights passes them here;
* a chip holding a share of the experts (``cfg.held_experts``) adds only
  its own experts' outputs: what the absent experts would add is left
  out, in the program and here alike.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _mamba(cfg, p, x):
    """x (B, S, d) -> (B, S, d), the recurrence taken token by token."""
    Bt, S, _ = x.shape
    di, N, H, P = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    proj = x @ p["w_in"]
    z, xBC, dt = proj[..., :di], proj[..., di:2 * di + 2 * N], \
        proj[..., 2 * di + 2 * N:]
    K = p["conv"].shape[0]
    xp = jnp.concatenate([jnp.zeros((Bt, K - 1, xBC.shape[-1])), xBC], 1)
    conv = sum(xp[:, i:i + S] * p["conv"][i] for i in range(K))
    xBC = jax.nn.silu(conv + p.get("conv_b", 0.0))
    xs = xBC[..., :di].reshape(Bt, S, H, P)
    b, c = xBC[..., di:di + N], xBC[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # (B, S, H)
    A = -jnp.exp(p["A_log"])

    def step(h, t):
        xt, bt, ct, dtt = t
        h = h * jnp.exp(A * dtt)[..., None, None] + \
            bt[:, None, :, None] * (xt * dtt[..., None])[:, :, None, :]
        y = jnp.sum(ct[:, None, :, None] * h, axis=2) + p["D"][:, None] * xt
        return h, y

    h0 = jnp.zeros((Bt, H, N, P))
    seq = (xs.swapaxes(0, 1), b.swapaxes(0, 1), c.swapaxes(0, 1),
           dt.swapaxes(0, 1))
    _, y = jax.lax.scan(step, h0, seq)
    y = y.swapaxes(0, 1).reshape(Bt, S, di) * jax.nn.silu(z)
    return _rmsnorm(y, p["norm"], cfg.norm_eps) @ p["w_out"]


def _attention(cfg, p, x, scale):
    S = x.shape[1]
    g = cfg.n_heads // cfg.n_kv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.repeat(jnp.einsum("bsd,dhk->bshk", x, p["wk"]), g, axis=2)
    v = jnp.repeat(jnp.einsum("bsd,dhk->bshk", x, p["wv"]), g, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v)
    return jnp.einsum("bqhk,hkd->bqd", o, p["wo"])


def _moe(cfg, p, x):
    lo, n_held = cfg.held_experts()
    top, idx = jax.lax.top_k(x @ p["router"], cfg.top_k)
    gate = jnp.sum(jax.nn.softmax(top, -1)[..., None]
                   * jax.nn.one_hot(idx, cfg.n_experts), -2)  # (B, S, E)
    up = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["w_gate"]))
    up = up * jnp.einsum("bsd,edf->bsef", x, p["w_up"])
    y = jnp.einsum("bsef,efd->bsed", up, p["w_down"])
    out = jnp.einsum("bsed,bse->bsd", y, gate[..., lo:lo + n_held])
    if cfg.n_shared:
        out = out + _swiglu(x, p["s_gate"], p["s_up"], p["s_down"])
    return out


def forward(cfg, params, tokens, mult: Optional[Dict[str, float]] = None):
    """Logits (B, S, padded vocab), f32, of ``tokens`` (B, S) from
    position 0.  ``mult``: ``embedding``, ``attention``, ``residual``,
    ``logits``."""
    m = {"embedding": 1.0, "attention": cfg.d_head ** -0.5,
         "residual": 1.0, "logits": 1.0, **(mult or {})}
    eps = cfg.norm_eps
    f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                 params)
    layers = f32["layers"]
    runs = layers if isinstance(layers, (list, tuple)) else [layers]
    with jax.default_matmul_precision("highest"):
        table = f32["embed"]["tok"]
        x = table[tokens] * m["embedding"]
        for (kind, _, n), run in zip(cfg.layer_runs(), runs):
            for i in range(n):
                lp = jax.tree_util.tree_map(lambda a: a[i], run)
                h = _rmsnorm(x, lp["ln1"], eps)
                if kind == "mamba":
                    h = _mamba(cfg, lp["mamba"], h)
                else:
                    h = _attention(cfg, lp["attn"], h, m["attention"])
                x = x + m["residual"] * h
                h = _rmsnorm(x, lp["ln2"], eps)
                x = x + m["residual"] * _moe(cfg, lp["moe"], h)
        x = _rmsnorm(x, f32["final_norm"], eps)
        return x @ table.T / m["logits"]
