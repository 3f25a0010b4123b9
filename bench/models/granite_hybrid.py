"""granite-4.0-h-small: seeded weights and the plain reference.

Weights are drawn from the seed on the device in one jitted call, slab
by slab, and handed to the program in its own parameter layout, in bfloat16: the
layers in runs of one mixer (``layer_types`` over the served layers),
each run's leaves stacked on a leading axis, a list of runs.  Each chip
of the stated deployment holds ``num_local_experts`` of the
``num_router_experts`` a layer, from ``first_held_expert`` on; the router
is whole.  Matrices, the router and the conv weight and bias are normal
with std ``initializer_range``; norms are 1; ``A_log``, ``D`` and
``dt_bias`` follow Mamba2's published initialisation (the configuration
names them under ``assumed``).

The program's decoder block has no scalar multipliers, where the
published model has four.  The first three are folded into the weights
the program is given, as ``granite.py`` does for granite-3.0:

* the tied embedding table is stored times ``embedding_multiplier``;
* ``wq`` is stored times ``attention_multiplier * sqrt(head_dim)``,
  since the program scales its scores by ``1/sqrt(head_dim)``;
* every output of a residual branch, the Mamba2 ``w_out``, the
  attention ``wo``, every expert's ``w_down`` and the shared expert's
  ``s_down``, is stored times ``residual_multiplier``.

The program's logits are then the published logits times
``embedding_multiplier * logits_scaling``, a positive constant that
moves no greedy choice.

:func:`reference` is the published granitemoehybrid forward written out
in float32 ``jax.numpy`` at the highest matmul precision, with no
kernel, cache, capacity or batching: the Mamba2 recurrence token by
token, NoPE GQA attention, top-k softmax gating over every router
output, the shared expert and the four multipliers, unfolded from the
stored weights.  The experts held elsewhere add nothing, as in the
program.  It imports nothing of the program.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import seeds

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
DT_MIN, DT_MAX, DT_FLOOR = 0.001, 0.1, 1e-4
SLAB = 1 << 20  # elements of one generated slab


def dims(cfg: Dict) -> Dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    heads = cfg["mamba_n_heads"]
    return dict(
        d=d,
        h=h,
        kv=cfg["num_key_value_heads"],
        dh=d // h,
        f=cfg["intermediate_size"],
        fs=cfg["shared_intermediate_size"],
        e=cfg["num_router_experts"],
        held=cfg["num_local_experts"],
        lo=cfg["first_held_expert"],
        k=cfg["num_experts_per_tok"],
        vocab=cfg["vocab_size"],
        vpad=-(-cfg["vocab_size"] // 256) * 256,
        di=cfg["mamba_expand"] * d,
        n=cfg["mamba_d_state"],
        ssm_h=heads,
        p=cfg["mamba_d_head"],
        conv=cfg["mamba_d_conv"],
    )


def runs(cfg: Dict) -> List[Tuple[str, int]]:
    """(mixer, layers) of each run of one mixer over the served layers."""
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
    d, di, n_, hs = m["d"], m["di"], m["n"], m["ssm_h"]
    bf, f32 = jnp.bfloat16, jnp.float32
    conv_c = di + 2 * n_

    def run(kind: str, n: int) -> Dict:
        layer = {"ln1": ((n, d), f32), "ln2": ((n, d), f32)}
        if kind == "mamba":
            layer["mamba"] = {
                "w_in": ((n, d, 2 * di + 2 * n_ + hs), bf),
                "conv": ((n, m["conv"], conv_c), bf),
                "conv_b": ((n, conv_c), bf),
                "A_log": ((n, hs), f32),
                "D": ((n, hs), f32),
                "dt_bias": ((n, hs), f32),
                "norm": ((n, di), f32),
                "w_out": ((n, di, d), bf),
            }
        else:
            h, kv, dh = m["h"], m["kv"], m["dh"]
            layer["attn"] = {
                "wq": ((n, d, h, dh), bf),
                "wk": ((n, d, kv, dh), bf),
                "wv": ((n, d, kv, dh), bf),
                "wo": ((n, h, dh, d), bf),
            }
        f, fs, held = m["f"], m["fs"], m["held"]
        layer["moe"] = {
            "router": ((n, d, m["e"]), f32),
            "w_gate": ((n, held, d, f), bf),
            "w_up": ((n, held, d, f), bf),
            "w_down": ((n, held, f, d), bf),
            "s_gate": ((n, d, fs), bf),
            "s_up": ((n, d, fs), bf),
            "s_down": ((n, fs, d), bf),
        }
        return layer

    return {
        "embed": {"tok": ((m["vpad"], d), bf)},
        "final_norm": ((d,), f32),
        "layers": [run(k, n) for k, n in runs(cfg)],
    }


def _folds(cfg: Dict) -> Dict[str, float]:
    dh = cfg["hidden_size"] // cfg["num_attention_heads"]
    res = cfg["residual_multiplier"]
    return {
        "tok": cfg["embedding_multiplier"],
        "wq": cfg["attention_multiplier"] * math.sqrt(dh),
        "wo": res,
        "w_out": res,
        "w_down": res,
        "s_down": res,
    }


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and isinstance(x[0], tuple)


def _slabs(shape) -> Tuple[int, Tuple[int, ...]]:
    """(count, slab) with count slabs of shape ``slab`` making ``shape``:
    the leading dims, and the rows of a matrix in blocks a multiple of
    16, so that a slab holds at most ``SLAB`` elements."""
    if len(shape) < 2:
        return 1, tuple(shape)
    rows, cols = shape[-2:]
    r = rows
    while r * cols > SLAB and r % 32 == 0:
        r //= 2
    return int(np.prod(shape[:-2], dtype=np.int64)) * (rows // r), (r, cols)


def _leaf(key, shape, dtype, kind: str, std: float, rows: int):
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "A_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1.0)), shape)
    if kind == "dt_bias":
        u = jax.random.uniform(key, shape)
        dt = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
        dt = jnp.maximum(dt, DT_FLOOR)
        return dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus
    count, slab = _slabs(shape)
    # slab by slab: the compiled program stays small at any leaf size
    leaf = jax.lax.map(
        lambda k: (std * jax.random.normal(k, slab)).astype(dtype),
        jax.random.split(key, count),
    ).reshape(shape)
    if rows:  # the table's rows past the vocabulary are zero
        leaf = jnp.where(jnp.arange(shape[0])[:, None] < rows, leaf, 0)
    return leaf


def make_weights(cfg: Dict, seed: int):
    """The served weights, on the device, in one jitted call."""
    tree = layout(cfg)
    folds = _folds(cfg)
    std = float(cfg["initializer_range"])
    paths, treedef = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_leaf)
    kinds = {"ln1": "ones", "ln2": "ones", "final_norm": "ones", "norm": "ones",
             "D": "ones", "A_log": "A_log", "dt_bias": "dt_bias"}

    def build(key):
        out = []
        for i, (path, (shape, dtype)) in enumerate(paths):
            name = path[-1].key
            out.append(
                _leaf(jax.random.fold_in(key, i), shape, dtype, kinds.get(name, "normal"),
                      std * folds.get(name, 1.0),
                      cfg["vocab_size"] if name == "tok" else 0)
            )
        return out

    return jax.tree_util.tree_unflatten(treedef, jax.jit(build)(seeds.key(seed)))


# --- the reference -------------------------------------------------------------


def _quantize(x):
    """Per-tensor scaled float8 (e4m3) rounding, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _layer(cfg: Dict, kind: str, lw, x, last, quantize: bool):
    """One published decoder layer over x (B, S, d); with the mixer's
    keys and values (attention) or its SSM state after position ``last``
    (Mamba2)."""
    m = dims(cfg)
    folds = _folds(cfg)
    eps = cfg["rms_norm_eps"]
    res = cfg["residual_multiplier"]
    q8 = _quantize if quantize else (lambda a: a)

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b))

    lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lw)
    bt, seq, _ = x.shape
    hn = _rmsnorm(x, lw["ln1"], eps)
    if kind == "mamba":
        p = lw["mamba"]
        di, n_, hs, ph = m["di"], m["n"], m["ssm_h"], m["p"]
        proj = mm("bsd,de->bse", hn, p["w_in"])
        z, xbc, dt = proj[..., :di], proj[..., di : 2 * di + 2 * n_], proj[..., 2 * di + 2 * n_ :]
        k = m["conv"]
        xp = jnp.concatenate([jnp.zeros((bt, k - 1, xbc.shape[-1])), xbc], 1)
        xbc = sum(xp[:, i : i + seq] * p["conv"][i] for i in range(k)) + p["conv_b"]
        xbc = jax.nn.silu(xbc)
        xs = xbc[..., :di].reshape(bt, seq, hs, ph)
        b, c = xbc[..., di : di + n_], xbc[..., di + n_ :]
        dt = jax.nn.softplus(dt + p["dt_bias"])
        a = -jnp.exp(p["A_log"])

        def step(carry, t):
            h, kept = carry
            xt, bt_, ct, dtt, i = t
            h = h * jnp.exp(a * dtt)[..., None, None] + (
                bt_[:, None, :, None] * (xt * dtt[..., None])[:, :, None, :]
            )
            y = jnp.sum(ct[:, None, :, None] * h, axis=2) + p["D"][:, None] * xt
            kept = jnp.where(i == last, h, kept)
            return (h, kept), y

        h0 = jnp.zeros((bt, hs, n_, ph))
        seq_in = tuple(v.swapaxes(0, 1) for v in (xs, b, c, dt)) + (jnp.arange(seq),)
        (_, state), y = jax.lax.scan(step, (h0, h0), seq_in)
        y = y.swapaxes(0, 1).reshape(bt, seq, di) * jax.nn.silu(z)
        out = mm("bse,ed->bsd", _rmsnorm(y, p["norm"], eps), p["w_out"] / folds["w_out"])
        mixer_state = state
    else:
        a = lw["attn"]
        group = m["h"] // m["kv"]
        q = mm("bsd,dhk->bshk", hn, a["wq"] / folds["wq"])
        k = mm("bsd,dhk->bshk", hn, a["wk"])
        v = mm("bsd,dhk->bshk", hn, a["wv"])
        s = mm("bqhk,bshk->bhqs", q, jnp.repeat(k, group, axis=2))
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        s = jnp.where(causal[None, None], s * cfg["attention_multiplier"], -jnp.inf)
        o = mm("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), jnp.repeat(v, group, axis=2))
        out = mm("bqhk,hkd->bqd", o, a["wo"] / folds["wo"])
        mixer_state = (k, v)
    x = x + res * out

    e = lw["moe"]
    hn = _rmsnorm(x, lw["ln2"], eps)
    top, idx = jax.lax.top_k(mm("bsd,de->bse", hn, e["router"]), m["k"])
    gate = jnp.sum(jax.nn.softmax(top, -1)[..., None] * jax.nn.one_hot(idx, m["e"]), -2)
    gate = gate[..., m["lo"] : m["lo"] + m["held"]]  # the held experts' weights
    up = jax.nn.silu(mm("bsd,edf->bsef", hn, e["w_gate"]))
    up = up * mm("bsd,edf->bsef", hn, e["w_up"])
    y = jnp.einsum("bsed,bse->bsd", mm("bsef,efd->bsed", up, e["w_down"] / folds["w_down"]), gate)
    sh = jax.nn.silu(mm("bsd,df->bsf", hn, e["s_gate"])) * mm("bsd,df->bsf", hn, e["s_up"])
    y = y + mm("bsf,fd->bsd", sh, e["s_down"] / folds["s_down"])
    return x + res * y, mixer_state


def published_forward(cfg: Dict, weights, tokens, last, quantize: bool = False):
    """Logits (B, S, vocab) of the published model over ``tokens`` (B, S),
    causal, from position 0; the attention layers' keys and values, each
    (layers, B, S, kv_heads, head_dim); and each Mamba2 layer's state
    after position ``last``, (layers, B, heads, d_state, d_head).
    ``quantize`` rounds both operands of every matrix product to float8:
    the control that must fail the comparison."""
    m = dims(cfg)
    folds = _folds(cfg)
    eps = cfg["rms_norm_eps"]
    q8 = _quantize if quantize else (lambda a: a)
    table = weights["embed"]["tok"][: m["vocab"]].astype(jnp.float32) / folds["tok"]
    x = table[tokens] * cfg["embedding_multiplier"]
    ks, vs, states = [], [], []
    for (kind, _), run in zip(runs(cfg), weights["layers"]):
        # a run's layers share one scanned body: one compile a run

        def body(x, lw, kind=kind):
            return _layer(cfg, kind, lw, x, last, quantize)

        x, st = jax.lax.scan(body, x, run)
        if kind == "mamba":
            states.append(st)
        else:
            ks.append(st[0])
            vs.append(st[1])
    hn = _rmsnorm(x, weights["final_norm"].astype(jnp.float32), eps)
    logits = jnp.einsum("bsd,vd->bsv", q8(hn), q8(table)) / cfg["logits_scaling"]
    return logits, jnp.concatenate(ks), jnp.concatenate(vs), jnp.concatenate(states)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _compare(cfg_items, weights, tokens, served, last, quantize):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        ref, ks, vs, states = published_forward(cfg, weights, tokens, last)
        best = ref.max(-1)
        if quantize:
            low, ks, vs, states = published_forward(
                cfg, weights, tokens, last, quantize=True
            )
            served = low.argmax(-1)
        index = jnp.clip(served, 0, ref.shape[-1] - 1)[..., None]
        got = jnp.take_along_axis(ref, index, -1)
    return best - got[..., 0], ks[:, 0], vs[:, 0], states[:, 0]


def _static(cfg: Dict):
    def value(v):
        return tuple(v) if isinstance(v, list) else v

    return tuple(
        sorted(
            (k, value(v))
            for k, v in cfg.items()
            if isinstance(v, (int, float)) or k == "layer_types"
        )
    )


def reference(
    cfg: Dict,
    weights,
    tokens: np.ndarray,
    served: np.ndarray,
    length: int,
    quantize: bool = False,
):
    """Per position, how far the reference's logit of the served token
    lies below the reference's best; the attention layers' keys and
    values of the first sequence, (layers, S, kv_heads, head_dim) each;
    and its Mamba2 states after its first ``length`` positions,
    (layers, heads, d_state, d_head).  ``tokens`` (B, S) is the context
    fed at each position and ``served`` (B, S) the token emitted there.
    With ``quantize`` the gaps are those of the tokens the float8 control
    puts first, and the keys, values and states the control's.  A served
    id outside the vocabulary reads as an infinite gap."""
    gaps, k, v, states = _compare(
        _static(cfg),
        weights,
        jnp.asarray(tokens),
        jnp.asarray(served),
        jnp.int32(length - 1),
        quantize,
    )
    gaps = np.asarray(gaps)
    if not quantize:
        gaps = np.where((served < 0) | (served >= cfg["vocab_size"]), np.inf, gaps)
    return gaps, np.asarray(k), np.asarray(v), np.asarray(states)


def relative_error(got, want) -> float:
    """The worst layer's relative L2 error: arrays (layers, ...)."""
    got = np.asarray(got, np.float64).reshape(len(want), -1)
    want = np.asarray(want, np.float64).reshape(len(want), -1)
    err = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
    return float(err.max())


def cache_error(k, v, k_ref, v_ref, length: int) -> float:
    """The worst layer's relative L2 error of the keys or the values over
    the first ``length`` positions."""
    return max(
        relative_error(got[:, :length], want[:, :length])
        for got, want in ((k, k_ref), (v, v_ref))
    )
