"""Mellum2-12B-A2.5B: seeded weights and the plain reference.

Weights are drawn from the seed on the device in one jitted call, slab
by slab (``granite_hybrid``'s generator), and handed to the program in
its own parameter layout, in bfloat16: the layers in runs of one
attention kind (``layer_types``), each run's leaves stacked on a leading
axis, a list of runs; the embedding and the untied head.  Each chip of
the stated deployment holds ``num_experts`` of the ``num_router_experts``
a layer, from ``first_held_expert`` on; the router is whole.  Matrices
and the router are normal with std ``initializer_range``; norms are 1.
The published layer has no scalar multiplier, so nothing is folded.

:func:`reference` is the published ``mellum`` forward written out in
float32 ``jax.numpy`` at the highest matmul precision, with no kernel,
cache, capacity or batching: RMSNorm, GQA attention with rotate-half
RoPE (YaRN on the full layers, from ``rope_parameters``), causal, a
sliding layer seeing the keys ``i - sliding_window < j <= i``; a softmax
router over every expert, top-k, the k weights renormalised to sum 1
(``norm_topk_prob``), SwiGLU experts; the untied head.  The experts held
elsewhere add nothing, as in the program.  It computes one layer at a
time over the whole sequence, attention and experts in blocks of
``BLOCK`` positions, so that a context of 8.6k positions fits beside the
weights.  It imports nothing of the program.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import seeds
from bench.models.granite_hybrid import _is_leaf, _leaf, _quantize, _rmsnorm

BLOCK = 128  # positions a block of the reference computes at once
RING_KIND = "sliding_attention"


def dims(cfg: Dict) -> Dict:
    return dict(
        d=cfg["hidden_size"],
        h=cfg["num_attention_heads"],
        kv=cfg["num_key_value_heads"],
        dh=cfg["head_dim"],
        f=cfg["moe_intermediate_size"],
        e=cfg["num_router_experts"],
        held=cfg["num_experts"],
        lo=cfg["first_held_expert"],
        k=cfg["num_experts_per_tok"],
        vocab=cfg["vocab_size"],
        window=cfg["sliding_window"],
    )


def runs(cfg: Dict) -> List[Tuple[str, int]]:
    """(attention kind, layers) of each run of one kind over the layers."""
    out: List[List] = []
    for kind in cfg["layer_types"][: cfg["num_hidden_layers"]]:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [(k, n) for k, n in out]


def layout(cfg: Dict):
    """The program's parameter tree: ``(shape, dtype)`` per leaf."""
    m = dims(cfg)
    d, h, kv, dh, f, held = m["d"], m["h"], m["kv"], m["dh"], m["f"], m["held"]
    bf, f32 = jnp.bfloat16, jnp.float32

    def run(n: int) -> Dict:
        return {
            "ln1": ((n, d), f32),
            "ln2": ((n, d), f32),
            "attn": {
                "wq": ((n, d, h, dh), bf),
                "wk": ((n, d, kv, dh), bf),
                "wv": ((n, d, kv, dh), bf),
                "wo": ((n, h, dh, d), bf),
            },
            "moe": {
                "router": ((n, d, m["e"]), f32),
                "w_gate": ((n, held, d, f), bf),
                "w_up": ((n, held, d, f), bf),
                "w_down": ((n, held, f, d), bf),
            },
        }

    vpad = -(-m["vocab"] // 256) * 256
    return {
        "embed": {"tok": ((vpad, d), bf), "unembed": ((d, vpad), bf)},
        "final_norm": ((d,), f32),
        "layers": [run(n) for _, n in runs(cfg)],
    }


def make_weights(cfg: Dict, seed: int):
    """The served weights, on the device, in one jitted call."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(layout(cfg), is_leaf=_is_leaf)
    std = float(cfg["initializer_range"])

    def build(key):
        out = []
        for i, (path, (shape, dtype)) in enumerate(paths):
            name = path[-1].key
            kind = "ones" if name in ("ln1", "ln2", "final_norm") else "normal"
            rows = cfg["vocab_size"] if name == "tok" else 0
            out.append(_leaf(jax.random.fold_in(key, i), shape, dtype, kind, std, rows))
        return out

    return jax.tree_util.tree_unflatten(treedef, jax.jit(build)(seeds.key(seed)))


# --- the reference -------------------------------------------------------------


def inv_freq(cfg: Dict, kind: str) -> Tuple[np.ndarray, float]:
    """RoPE's inverse frequencies and cos/sin scale for a layer of
    ``kind``: plain on the sliding layers, YaRN (transformers'
    ``_compute_yarn_parameters``) on the full ones."""
    p = cfg["rope_parameters"][kind]
    dim, base = cfg["head_dim"], float(p["rope_theta"])
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    if p["rope_type"] == "default":
        return plain, 1.0
    assert p["rope_type"] == "yarn", p
    factor, orig = p["factor"], p["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(p["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(p["beta_slow"])), dim - 1)
    span = max(high - low, 1e-3)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / span, 0, 1)
    extrapolation = 1 - ramp
    freq = plain / factor * (1 - extrapolation) + plain * extrapolation
    return freq.astype(np.float32), float(p["attention_factor"])


def _rope(cfg: Dict, kind: str, x):
    """Rotate-half RoPE of x (S, heads, head_dim) at positions 0..S-1."""
    freq, scale = inv_freq(cfg, kind)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(freq)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None] * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None] * scale
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], -1) * sin


def _layer(cfg: Dict, kind: str, quantize: bool, lw, x):
    """One published decoder layer over x (S, d): (x, its keys and values
    (S, kv_heads, head_dim))."""
    m = dims(cfg)
    eps = cfg["rms_norm_eps"]
    q8 = _quantize if quantize else (lambda a: a)

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b))

    lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lw)
    a, e = lw["attn"], lw["moe"]
    seq = x.shape[0]
    hn = _rmsnorm(x, lw["ln1"], eps)
    q = _rope(cfg, kind, mm("sd,dhk->shk", hn, a["wq"]))
    k = _rope(cfg, kind, mm("sd,dhk->shk", hn, a["wk"]))
    v = mm("sd,dhk->shk", hn, a["wv"])
    group = m["h"] // m["kv"]
    k_all, v_all = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)

    def block(i):
        first = i * BLOCK
        qb = jax.lax.dynamic_slice_in_dim(q, first, BLOCK)
        s = mm("qhk,shk->hqs", qb, k_all) / math.sqrt(m["dh"])
        at = first + jnp.arange(BLOCK)[:, None]
        key = jnp.arange(seq)[None]
        seen = key <= at
        if kind == RING_KIND:
            seen = seen & (key > at - m["window"])
        o = mm("hqs,shk->qhk", jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v_all)
        xb = jax.lax.dynamic_slice_in_dim(x, first, BLOCK)
        xb = xb + mm("qhk,hkd->qd", o, a["wo"])
        hb = _rmsnorm(xb, lw["ln2"], eps)
        probs = jax.nn.softmax(mm("qd,de->qe", hb, e["router"]), -1)
        top, idx = jax.lax.top_k(probs, m["k"])
        top = top / top.sum(-1, keepdims=True)
        gate = jnp.sum(top[..., None] * jax.nn.one_hot(idx, m["e"]), -2)
        gate = gate[:, m["lo"] : m["lo"] + m["held"]]  # the held experts' weights
        up = jax.nn.silu(mm("qd,edf->qef", hb, e["w_gate"]))
        up = up * mm("qd,edf->qef", hb, e["w_up"])
        y = jnp.einsum("qed,qe->qd", mm("qef,efd->qed", up, e["w_down"]), gate)
        return xb + y

    x = jax.lax.map(block, jnp.arange(seq // BLOCK)).reshape(seq, m["d"])
    return x, k, v


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer_jit(cfg_items, kind, quantize, lw, x):
    with jax.default_matmul_precision("highest"):
        return _layer(_unstatic(cfg_items), kind, quantize, lw, x)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head_jit(cfg_items, quantize, norm, head, x, low, served):
    """Per position: the reference's best logit less its logit of the token
    served there (with ``quantize``: of the token the float8 control puts
    first, from its own final hidden state ``low``)."""
    cfg = _unstatic(cfg_items)
    q8 = _quantize if quantize else (lambda a: a)
    vocab = cfg["vocab_size"]
    head = head.astype(jnp.float32)[:, :vocab]
    norm = norm.astype(jnp.float32)

    def block(i):
        first = i * BLOCK
        with jax.default_matmul_precision("highest"):
            xb = jax.lax.dynamic_slice_in_dim(x, first, BLOCK)
            hn = _rmsnorm(xb, norm, cfg["rms_norm_eps"])
            ref = hn @ head
            pick = jax.lax.dynamic_slice_in_dim(served, first, BLOCK)
            if quantize:
                hl = _rmsnorm(jax.lax.dynamic_slice_in_dim(low, first, BLOCK), norm,
                              cfg["rms_norm_eps"])
                pick = jnp.argmax(q8(hl) @ q8(head), -1)
        got = jnp.take_along_axis(ref, jnp.clip(pick, 0, vocab - 1)[:, None], -1)[:, 0]
        return ref.max(-1) - got

    return jax.lax.map(block, jnp.arange(x.shape[0] // BLOCK)).reshape(-1)


def _static(cfg: Dict):
    def value(v):
        if isinstance(v, dict):
            return tuple(sorted((k, value(w)) for k, w in v.items()))
        return tuple(v) if isinstance(v, list) else v

    keep = ("layer_types", "rope_parameters")
    return tuple(sorted((k, value(v)) for k, v in cfg.items()
                        if isinstance(v, (int, float)) or k in keep))


def _unstatic(items) -> Dict:
    """The configuration ``_static`` made hashable, as a dict again."""
    cfg = dict(items)
    cfg["rope_parameters"] = {k: dict(v) for k, v in cfg["rope_parameters"]}
    return cfg


def _forward(cfg: Dict, weights, tokens, quantize: bool, length: int):
    """Final hidden states (S, d) of one sequence, and, where ``length``,
    the keys and values of its first ``length`` positions: each full
    layer's, (layers, length, kv_heads, head_dim), and each sliding
    layer's last ``sliding_window`` of them."""
    items = _static(cfg)
    w = dims(cfg)["window"]
    x = weights["embed"]["tok"][jnp.asarray(tokens)].astype(jnp.float32)
    full, ring = ([], []), ([], [])
    for (kind, n), run in zip(runs(cfg), weights["layers"]):
        for i in range(n):
            lw = jax.tree_util.tree_map(lambda a: a[i], run)
            x, k, v = _layer_jit(items, kind, quantize, lw, x)
            if not length:
                continue
            keep, span = (ring, slice(length - w, length)) if kind == RING_KIND \
                else (full, slice(0, length))
            keep[0].append(np.asarray(k[span]))
            keep[1].append(np.asarray(v[span]))
    if not length:
        return x, None, None
    return x, tuple(np.stack(a) for a in full), tuple(np.stack(a) for a in ring)


def reference(cfg: Dict, weights, tokens: np.ndarray, served: np.ndarray, length: int,
              quantize: bool = False):
    """Per position of each sequence of ``tokens`` (B, S), the context fed
    at each position (S a multiple of ``BLOCK``), how far the reference's
    logit of the token served there (``served`` (B, S), -1 where none
    was) lies below the reference's best; the first sequence's full
    layers' keys and values over its first ``length`` positions, (layers,
    length, kv_heads, head_dim) each; and its sliding layers' keys and
    values of positions ``length - sliding_window .. length - 1``, in that
    order.  With ``quantize`` every matrix product's operands are rounded
    to float8 (e4m3, per-tensor scale): the gaps are those of the tokens
    the control puts first, and the keys and values the control's.  A
    served id outside the vocabulary reads as an infinite gap."""
    items = _static(cfg)
    gaps, full, ring = [], None, None
    for b in range(len(tokens)):
        first = length if b == 0 else 0  # the cache checks read the first's
        x, f, r = _forward(cfg, weights, tokens[b], False, first)
        low = x
        if quantize:
            low, f, r = _forward(cfg, weights, tokens[b], True, first)
        if b == 0:
            full, ring = f, r
        gaps.append(np.asarray(_head_jit(items, quantize, weights["final_norm"],
                                         weights["embed"]["unembed"], x, low,
                                         jnp.asarray(served[b]))))
    gaps = np.stack(gaps)
    if not quantize:
        gaps = np.where((served < 0) | (served >= cfg["vocab_size"]), np.inf, gaps)
    return gaps, full, ring


def relative_error(got, want) -> float:
    """The worst layer's relative L2 error: arrays (layers, ...)."""
    got = np.asarray(got, np.float64).reshape(len(want), -1)
    want = np.asarray(want, np.float64).reshape(len(want), -1)
    err = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
    return float(err.max())


def ring_positions(cfg: Dict, length: int) -> np.ndarray:
    """The ring rows holding positions ``length - window .. length - 1``,
    in that order: position p is in row p mod window."""
    w = cfg["sliding_window"]
    return np.arange(length - w, length) % w
