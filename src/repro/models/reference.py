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

Mellum2 (``mellum`` model type) has the same layer with the multipliers
at 1 and no Mamba2: its mixers are full and sliding-window GQA
attention, with rotate-half RoPE of base ``cfg.rope_theta`` on the
queries and keys, YaRN-scaled on the full layers (the frequencies and
the cos/sin scale of transformers' ``_compute_yarn_parameters``), scores
``q·k/sqrt(head_dim)``, causal, and on a sliding layer only the keys
``i - window < j <= i``; the head is untied.

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

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


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


def yarn_inv_freq(dim, base, factor, original_max, beta_fast, beta_slow,
                  attention_factor):
    """transformers' ``_compute_yarn_parameters``, line by line: (inverse
    frequencies (dim/2,), cos/sin scale)."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original_max / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)
    extrapolation_factor = 1 - ramp
    inv_freq = interpolation * (1 - extrapolation_factor) + \
        extrapolation * extrapolation_factor
    if not attention_factor:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv_freq.astype(np.float32), attention_factor


def _rope(cfg, kind, x, positions):
    """Rotate-half RoPE of x (B, S, H, D) at ``positions`` (S,)."""
    D = x.shape[-1]
    y = cfg.yarn if kind == "attention" else None
    if y is None:
        inv = 1.0 / cfg.rope_theta ** (np.arange(0, D, 2, dtype=np.float32) / D)
        scale = 1.0
    else:
        inv, scale = yarn_inv_freq(D, cfg.rope_theta, y.factor, y.original_max,
                                   y.beta_fast, y.beta_slow,
                                   y.attention_factor)
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(inv)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None] * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None] * scale
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + half * sin


def _attention(cfg, p, x, scale, kind="attention"):
    S = x.shape[1]
    g = cfg.n_heads // cfg.n_kv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    if cfg.rope:
        q = _rope(cfg, kind, q, jnp.arange(S))
        k = _rope(cfg, kind, k, jnp.arange(S))
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(jnp.einsum("bsd,dhk->bshk", x, p["wv"]), g, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k) * scale
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None]
    seen = j <= i
    if kind == "sliding_attention":
        seen = seen & (j > i - cfg.window)
    s = jnp.where(seen, s, -jnp.inf)
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
                    h = _attention(cfg, lp["attn"], h, m["attention"], kind)
                x = x + m["residual"] * h
                h = _rmsnorm(x, lp["ln2"], eps)
                x = x + m["residual"] * _moe(cfg, lp["moe"], h)
        x = _rmsnorm(x, f32["final_norm"], eps)
        head = f32["embed"].get("unembed", table.T)
        return x @ head / m["logits"]
