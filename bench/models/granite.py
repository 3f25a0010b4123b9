"""granite-3.0-1b-a400m: seeded weights and the plain reference.

Weights are drawn from the seed on the device in one jitted call, as
the published ``initializer_range`` says (normal, std 0.02; norms 1),
and handed to the program in its own parameter layout, in bfloat16.

The program's decoder block has no scalar multipliers, where the
published model has four (``embedding_multiplier``,
``attention_multiplier``, ``residual_multiplier``, ``logits_scaling``).
The first three are folded into the weights the program is given, so it
computes the published function:

* the tied embedding table is stored times ``embedding_multiplier``;
* ``wq`` is stored times ``attention_multiplier * sqrt(head_dim)``,
  since the program scales its scores by ``1/sqrt(head_dim)``;
* ``wo`` and every expert's ``w_down`` are stored times
  ``residual_multiplier``.

The program's logits are then the published logits times
``embedding_multiplier * logits_scaling``, a positive constant that
moves no greedy choice.  Rows of the table past the vocabulary (the
program pads it to a multiple of 256) are zero.

:func:`reference` is the published forward pass written out in
float32 ``jax.numpy`` at the highest matmul precision, with no kernel,
cache or capacity: it unfolds the multipliers from the stored weights
and applies them where the published model does.  It imports nothing
of the program.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import seeds

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _dims(cfg: Dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(
        d=d,
        h=h,
        kv=cfg["num_key_value_heads"],
        dh=d // h,
        f=cfg["intermediate_size"],
        e=cfg["num_local_experts"],
        k=cfg["num_experts_per_tok"],
        layers=cfg["num_hidden_layers"],
        vocab=cfg["vocab_size"],
        vpad=-(-cfg["vocab_size"] // 256) * 256,
    )


def layout(cfg: Dict) -> Dict:
    """The program's parameter tree for this configuration:
    ``(shape, dtype)`` per leaf, layers stacked on a leading axis."""
    m = _dims(cfg)
    d, h, kv, dh, f, e = m["d"], m["h"], m["kv"], m["dh"], m["f"], m["e"]
    n = m["layers"]
    bf, f32 = jnp.bfloat16, jnp.float32
    return {
        "embed": {"tok": ((m["vpad"], d), bf)},
        "final_norm": ((d,), f32),
        "layers": {
            "ln1": ((n, d), f32),
            "attn": {
                "wq": ((n, d, h, dh), bf),
                "wk": ((n, d, kv, dh), bf),
                "wv": ((n, d, kv, dh), bf),
                "wo": ((n, h, dh, d), bf),
            },
            "ln2": ((n, d), f32),
            "moe": {
                "router": ((n, d, e), f32),
                "w_gate": ((n, e, d, f), bf),
                "w_up": ((n, e, d, f), bf),
                "w_down": ((n, e, f, d), bf),
            },
        },
    }


def _folds(cfg: Dict) -> Dict[str, float]:
    dh = cfg["hidden_size"] // cfg["num_attention_heads"]
    return {
        "tok": cfg["embedding_multiplier"],
        "wq": cfg["attention_multiplier"] * math.sqrt(dh),
        "wo": cfg["residual_multiplier"],
        "w_down": cfg["residual_multiplier"],
    }


def make_weights(cfg: Dict, seed: int):
    """The served weights, on the device, in one jitted call."""
    tree = layout(cfg)
    folds = _folds(cfg)
    std = cfg["initializer_range"]
    vocab = cfg["vocab_size"]
    paths = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    )[0]

    def build(key):
        out = {}
        for i, (path, (shape, dtype)) in enumerate(paths):
            name = path[-1].key
            if name in ("ln1", "ln2", "final_norm"):
                leaf = jnp.ones(shape, dtype)
            else:
                leaf = std * jax.random.normal(jax.random.fold_in(key, i), shape)
                leaf = leaf * folds.get(name, 1.0)
                if name == "tok":
                    leaf = jnp.where(jnp.arange(shape[0])[:, None] < vocab, leaf, 0.0)
                leaf = leaf.astype(dtype)
            out[jax.tree_util.keystr(path)] = leaf
        return out

    flat = jax.jit(build)(seeds.key(seed))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(
            tree, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
        ),
        [flat[jax.tree_util.keystr(p)] for p, _ in paths],
    )


# --- the reference -------------------------------------------------------------


def _quantize(x):
    """Per-tensor scaled float8 (e4m3) rounding, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """Rotate-half rotary embedding; x (B, S, H, D)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None] * freqs  # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def published_forward(cfg: Dict, weights, tokens, quantize: bool = False):
    """Logits (B, S, vocab) of the published model over ``tokens`` (B, S),
    causal, from position 0, and every layer's keys (after the rotary
    embedding) and values, each (layers, B, S, kv_heads, head_dim).
    ``quantize`` rounds both operands of every product to float8: the
    control that must fail the comparison."""
    m = _dims(cfg)
    folds = _folds(cfg)
    eps = cfg["rms_norm_eps"]
    res = cfg["residual_multiplier"]
    q8 = _quantize if quantize else (lambda x: x)

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b))

    table = weights["embed"]["tok"][: m["vocab"]].astype(jnp.float32) / folds["tok"]
    x = table[tokens] * cfg["embedding_multiplier"]
    seq = tokens.shape[1]
    pos = jnp.arange(seq, dtype=jnp.float32)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    group = m["h"] // m["kv"]

    def layer(x, lw):
        lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lw)
        a = lw["attn"]
        hn = _rmsnorm(x, lw["ln1"], eps)
        theta = cfg["rope_theta"]
        q = _rope(mm("bsd,dhk->bshk", hn, a["wq"] / folds["wq"]), pos, theta)
        k = _rope(mm("bsd,dhk->bshk", hn, a["wk"]), pos, theta)
        v = mm("bsd,dhk->bshk", hn, a["wv"])
        s = mm("bqhk,bshk->bhqs", q, jnp.repeat(k, group, axis=2))
        s = jnp.where(causal[None, None], s * cfg["attention_multiplier"], -jnp.inf)
        o = mm("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), jnp.repeat(v, group, axis=2))
        x = x + res * mm("bqhk,hkd->bqd", o, a["wo"] / folds["wo"])

        e = lw["moe"]
        hn = _rmsnorm(x, lw["ln2"], eps)
        top, idx = jax.lax.top_k(mm("bsd,de->bse", hn, e["router"]), m["k"])
        gate = jnp.sum(
            jax.nn.softmax(top, -1)[..., None] * jax.nn.one_hot(idx, m["e"]), -2
        )  # (B, S, E): each expert's weight, 0 off the top k
        up = jax.nn.silu(mm("bsd,edf->bsef", hn, e["w_gate"]))
        up = up * mm("bsd,edf->bsef", hn, e["w_up"])
        y = mm("bsef,efd->bsed", up, e["w_down"] / folds["w_down"])
        x = x + res * jnp.einsum("bsed,bse->bsd", y, gate)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(layer, x, weights["layers"])
    hn = _rmsnorm(x, weights["final_norm"].astype(jnp.float32), eps)
    return mm("bsd,vd->bsv", hn, table) / cfg["logits_scaling"], ks, vs


@functools.partial(jax.jit, static_argnums=(0, 4))
def _compare(cfg_items, weights, tokens, served, quantize):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        ref, ks, vs = published_forward(cfg, weights, tokens)
        best = ref.max(-1)
        if quantize:
            low, ks, vs = published_forward(cfg, weights, tokens, quantize=True)
            served = low.argmax(-1)
        index = jnp.clip(served, 0, ref.shape[-1] - 1)[..., None]
        got = jnp.take_along_axis(ref, index, -1)
    return best - got[..., 0], ks[:, 0], vs[:, 0]


def reference(
    cfg: Dict, weights, tokens: np.ndarray, served: np.ndarray, quantize: bool = False
):
    """Per position, how far the reference's logit of the served token
    lies below the reference's best, and the keys and values of the first
    sequence, (layers, S, kv_heads, head_dim) each.  ``tokens`` (B, S) is
    the context fed at each position and ``served`` (B, S) the token
    emitted there.  With ``quantize`` the gaps are those of the tokens the
    float8 control puts first, and the keys and values the control's.  A
    served id outside the vocabulary reads as an infinite gap."""
    items = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float))))
    gaps, k, v = _compare(
        items, weights, jnp.asarray(tokens), jnp.asarray(served), quantize
    )
    gaps = np.asarray(gaps)
    if not quantize:
        gaps = np.where((served < 0) | (served >= cfg["vocab_size"]), np.inf, gaps)
    return gaps, np.asarray(k), np.asarray(v)


def cache_error(k, v, k_ref, v_ref, length: int) -> float:
    """The worst layer's relative L2 error of the keys or the values over
    the first ``length`` positions."""
    worst = 0.0
    for got, want in ((k, k_ref), (v, v_ref)):
        got = np.asarray(got[:, :length], np.float64)
        want = np.asarray(want[:, :length], np.float64)
        err = np.linalg.norm((got - want).reshape(len(want), -1), axis=1)
        scale = np.linalg.norm(want.reshape(len(want), -1), axis=1)
        worst = max(worst, float((err / scale).max()))
    return worst
