"""Model building blocks (functional: spec builders + apply functions).

All heavy math calls the kernel dispatch layer (repro.kernels.ops), so
the same model runs Pallas kernels on TPU and compact XLA math on the
CPU dry-run.  Activation shardings are expressed with
``with_sharding_constraint`` through the AxisRules table.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.layout import Layout

from ..kernels import ops
from .params import AxisRules, ParamSpec

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def constrain(x, rules: Optional[AxisRules], axes):
    if rules is None:
        return x
    return lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(
            rules.mesh, rules.pspec_for(x.shape, axes, what="act")))


def round_up(a: int, b: int) -> int:
    return -(-a // b) * b


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def yarn_frequencies(dim: int, base: float, yarn):
    """(inverse frequencies (dim/2,) f32, cos/sin scale) of YaRN, as
    transformers' ``_compute_yarn_parameters`` computes them: frequencies
    below the ``beta_slow`` rotations over ``original_max`` positions are
    interpolated (÷ factor), those above ``beta_fast`` kept, with a linear
    ramp between (bounds floored and ceiled), and the scale
    ``attention_factor`` (0 → 0.1·ln(factor) + 1)."""
    half = dim // 2
    kept = base ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)

    def bound(rotations):
        return dim * math.log(yarn.original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(bound(yarn.beta_fast)), 0)
    high = min(math.ceil(bound(yarn.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half) - low) / (high - low or 0.001), 0, 1)
    inv = kept / yarn.factor * ramp + kept * (1 - ramp)
    scale = yarn.attention_factor or 0.1 * math.log(yarn.factor) + 1
    return inv.astype(np.float32), float(scale)


def rope(x, positions, *, base: float = 10000.0, yarn=None):
    """x: (..., S, H, D) or (..., H, D) with positions broadcastable;
    rotate-half RoPE of base ``base``, YaRN-scaled where ``yarn`` is
    given."""
    D = x.shape[-1]
    half = D // 2
    if yarn is None:
        freqs = jnp.exp(-jnp.log(base) * jnp.arange(half, dtype=jnp.float32)
                        / half)
    else:
        inv, scale = yarn_frequencies(D, base, yarn)
        freqs = jnp.asarray(inv)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        cos, sin = cos * scale, sin * scale
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def rotate(cfg, kind: str, x, positions):
    """The position embedding of an attention layer of ``kind``: none
    (NoPE), or RoPE of base ``cfg.rope_theta``, YaRN-scaled on full
    attention where ``cfg.yarn`` is set (sliding layers keep plain
    RoPE)."""
    if not cfg.rope:
        return x
    yarn = cfg.yarn if kind == "attention" else None
    return rope(x, positions, base=cfg.rope_theta, yarn=yarn)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def norm_spec(cfg) -> ParamSpec:
    return ParamSpec((cfg.d_model,), jnp.float32, ("embed",), init="ones")


def apply_norm(w, x, kind: str = "rms", b=None, backend: str = "auto",
               eps: float = 1e-6):
    if kind == "rms":
        return ops.rmsnorm(x, w, eps=eps, backend=backend)
    return ops.layernorm(x, w, b if b is not None else jnp.zeros_like(w),
                         eps=eps, backend=backend)


# ---------------------------------------------------------------------------
# attention (GQA, rope or none, optional window) — train/prefill and decode
# ---------------------------------------------------------------------------


def attention_specs(cfg, d_model: Optional[int] = None) -> Dict[str, ParamSpec]:
    d = d_model or cfg.d_model
    Hp, gp, g = cfg.head_padding()
    Hkv, Dh = cfg.n_kv, cfg.d_head
    dt = cfg.param_dtype
    # KV projections: column-parallel over kv heads when divisible by TP,
    # else row-parallel over d_model ("kv_embed" → model): the weights
    # stay sharded and XLA inserts a small all-reduce on the kv
    # activations instead of replicating the parameters.
    kv_col = (not cfg.tp_pad) or (Hkv % cfg.tp_pad == 0)
    kv_axes = ("embed", "kv_heads", None) if kv_col \
        else ("kv_embed", None, None)
    sp = {
        "wq": ParamSpec((d, Hp, Dh), dt, ("embed", "heads", None)),
        "wk": ParamSpec((d, Hkv, Dh), dt, kv_axes),
        "wv": ParamSpec((d, Hkv, Dh), dt, kv_axes),
        "wo": ParamSpec((Hp, Dh, d), dt, ("heads", None, "embed")),
    }
    if cfg.qkv_bias:
        sp["bq"] = ParamSpec((Hp, Dh), dt, ("heads", None), init="zeros")
        sp["bk"] = ParamSpec((Hkv, Dh), dt, ("kv_heads", None), init="zeros")
        sp["bv"] = ParamSpec((Hkv, Dh), dt, ("kv_heads", None), init="zeros")
    return sp


def _head_mask(cfg):
    """(Hp,) validity mask: padded q-head slots contribute zero to the
    output projection, making padded execution exactly equal to the
    true architecture."""
    Hp, gp, g = cfg.head_padding()
    if Hp == cfg.n_heads:
        return None
    slot = jnp.arange(Hp) % gp
    return (slot < g)


def attention_apply(p, x, positions, *, cfg, rules=None, causal=True,
                    window: int = 0, backend="auto", kind="attention"):
    """x: (B, S, d) -> (B, S, d); ``kind`` (``"attention"`` or
    ``"sliding_attention"``) picks the position embedding
    (``rotate``)."""
    B, S, d = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = rotate(cfg, kind, q, positions)
    k = rotate(cfg, kind, k, positions)
    q = constrain(q, rules, ("batch", None, "heads", None))
    k = constrain(k, rules, ("batch", None, "kv_heads", None))
    att = jax.vmap(lambda qq, kk, vv: ops.attention(
        qq, kk, vv, causal=causal, window=window, backend=backend))(q, k, v)
    att = constrain(att, rules, ("batch", None, "heads", None))
    hm = _head_mask(cfg)
    if hm is not None:
        att = att * hm[None, None, :, None].astype(att.dtype)
    out = jnp.einsum("bshk,hkd->bsd", att, p["wo"])
    return constrain(out, rules, ("batch", None, "embed"))


def decode_attn_in_place(cfg, rules, backend: str) -> bool:
    """Whether a decode step's attention writes and reads a run's K/V
    stacks in place (``attention_decode`` with ``layer``, on
    ``ops.decode_attn_cache``): the stacks are whole on the one device
    the step runs on (a Pallas call is not partitioned over ``seq_kv``)
    and the backend runs Pallas kernels.  Nothing in ``cfg`` enters:
    every attention run of a dense, MoE or VLM stack can take the path
    (the hybrid family's rolling window and the encoder-decoder keep
    their own decode)."""
    del cfg
    return ((rules is None or rules.mesh.size == 1)
            and ops.resolve(backend) in ("pallas", "interpret"))


def cache_positions_minor(rules, shape, dtype) -> bool:
    """Whether the device the step runs on keeps a K/V stack of ``shape``
    (n, B, S, Hkv, Dh) with its positions minor, as the TPU does where
    Dh = 64 would pad each row to 128 lanes: the device's own default
    layout, asked of its client."""
    device = rules.mesh.devices.flat[0] if rules is not None \
        else jax.devices()[0]
    try:
        layout = device.client.get_default_layout(jnp.dtype(dtype),
                                                  tuple(shape), device)
    except jax.errors.JaxRuntimeError:       # a backend without layouts
        return False
    return Layout.from_pjrt_layout(layout).major_to_minor[-1] == 2


def attention_decode(p, x, cache, pos, *, cfg, rules=None, backend="auto",
                     slot=None, kv_len=None, layer=None, kind="attention"):
    """One-token decode.  x: (B, d); cache: {k: (B, S, Hkv, Dh), v: ...};
    pos: (B,) absolute positions (for RoPE); slot: (B,) cache write slots
    (rolling-buffer windows; defaults to pos); kv_len: (B,) valid cache
    length (defaults to pos+1).  With ``layer`` (where
    ``decode_attn_in_place`` holds), the cache holds the (L, B, S, Hkv,
    Dh) stacks of a run of layers, and layer ``layer``'s rows are written
    and read in place: ``decode_attn_cache``, or ``decode_attn_ring`` on
    a sliding layer's ring.  ``kind`` as ``attention_apply``'s."""
    B, d = x.shape
    slot = pos if slot is None else slot
    kv_len = pos + 1 if kv_len is None else kv_len
    q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
    k = jnp.einsum("bd,dhk->bhk", x, p["wk"])
    v = jnp.einsum("bd,dhk->bhk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.rope:
        # rope wants (..., S, H, D): add a singleton S axis
        q = rotate(cfg, kind, q[:, None], pos[:, None])[:, 0]
        k = rotate(cfg, kind, k[:, None], pos[:, None])[:, 0]
    if layer is None:
        kc = _scatter_token(cache["k"], k, slot)
        vc = _scatter_token(cache["v"], v, slot)
        out = jax.vmap(lambda qq, kk, vv, ln: ops.decode_attention(
            qq, kk, vv, ln, backend=backend))(q, kc, vc, kv_len)
    else:
        kc, vc = cache["k"], cache["v"]
        out, kc, vc = ops.decode_attn_cache(
            jnp.asarray(layer, jnp.int32).reshape(1), slot, kv_len, q, k, v,
            kc, vc, minor=cache_positions_minor(rules, kc.shape, kc.dtype),
            backend=backend, name="decode_attn_ring"
            if kind == "sliding_attention" else "decode_attn_cache")
    hm = _head_mask(cfg)
    if hm is not None:
        out = out * hm[None, :, None].astype(out.dtype)
    y = jnp.einsum("bhk,hkd->bd", out, p["wo"])
    return y, {"k": kc, "v": vc}


def _scatter_token(cache, token, pos):
    """cache: (B, S, H, D); token: (B, H, D); pos: (B,)."""
    def one(c, t, i):
        return lax.dynamic_update_slice_in_dim(
            c, t[None].astype(c.dtype), i, axis=0)
    return jax.vmap(one)(cache, token, pos)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_specs(cfg) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, cfg.d_ff
    dt = cfg.param_dtype
    if cfg.act == "swiglu":
        return {"w_gate": ParamSpec((d, f), dt, ("embed", "mlp")),
                "w_up": ParamSpec((d, f), dt, ("embed", "mlp")),
                "w_down": ParamSpec((f, d), dt, ("mlp", "embed"))}
    return {"w_in": ParamSpec((d, f), dt, ("embed", "mlp")),
            "w_out": ParamSpec((f, d), dt, ("mlp", "embed"))}


def mlp_apply(p, x, *, cfg, rules=None):
    if cfg.act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
        h = constrain(h, rules, ("batch", None, "mlp"))
        out = h @ p["w_down"]
    else:
        h = jax.nn.gelu(x @ p["w_in"])
        h = constrain(h, rules, ("batch", None, "mlp"))
        out = h @ p["w_out"]
    return constrain(out, rules, ("batch", None, "embed"))


# ---------------------------------------------------------------------------
# Mixture of Experts (token-choice top-k, einsum dispatch; EP over 'experts')
# ---------------------------------------------------------------------------


def moe_specs(cfg) -> Dict[str, ParamSpec]:
    """The router over all n_experts, and the held experts' weights."""
    d, fe = cfg.d_model, cfg.d_expert or cfg.d_ff
    E = cfg.n_experts
    _, held = cfg.held_experts()
    dt = cfg.param_dtype
    sp = {
        "router": ParamSpec((d, E), jnp.float32, ("embed", None)),
        "w_gate": ParamSpec((held, d, fe), dt, ("experts", "embed", None)),
        "w_up": ParamSpec((held, d, fe), dt, ("experts", "embed", None)),
        "w_down": ParamSpec((held, fe, d), dt, ("experts", None, "embed")),
    }
    if cfg.n_shared:
        fs = cfg.shared_width()
        sp.update({
            "s_gate": ParamSpec((d, fs), dt, ("embed", "mlp")),
            "s_up": ParamSpec((d, fs), dt, ("embed", "mlp")),
            "s_down": ParamSpec((fs, d), dt, ("mlp", "embed"))})
    return sp


def moe_capacity(cfg, n_tokens: int) -> int:
    c = int(-(-cfg.top_k * n_tokens * cfg.capacity_factor // cfg.n_experts))
    return max(8, -(-c // 8) * 8)


def _moe_local(p, xt, *, cfg, C: int, e_lo, E_loc: int):
    """Token-choice top-k over a LOCAL token slab, computing only the
    expert slice [e_lo, e_lo+E_loc) whose weights this rank holds.
    Returns the (partial) combined output — summed over ranks outside,
    or, for a chip's share of a deployment, passed on as it is.
    GShard positions: per-choice running cumsum, no sort."""
    T, d = xt.shape
    E, k = cfg.n_experts, cfg.top_k
    logits = xt.astype(jnp.float32) @ p["router"]            # (T,E)
    w, idx = ops.topk_gate(logits, k)                        # (T,k)

    base_count = jnp.zeros((E,), jnp.int32)
    slots, keeps = [], []
    for j in range(k):                                       # k small: unroll
        mask_j = jax.nn.one_hot(idx[:, j], E, dtype=jnp.int32)
        pos_in_e = jnp.cumsum(mask_j, axis=0) - mask_j
        pos_j = (pos_in_e * mask_j).sum(-1) + base_count[idx[:, j]]
        base_count = base_count + mask_j.sum(0)
        keep_j = pos_j < C
        # slot relative to this rank's expert slice; OOB -> trash row
        rel_e = idx[:, j] - e_lo
        mine = keep_j & (rel_e >= 0) & (rel_e < E_loc)
        slots.append(jnp.where(mine, rel_e * C + pos_j, E_loc * C))
        keeps.append(mine)

    xe = jnp.zeros((E_loc * C + 1, d), xt.dtype)
    for j in range(k):
        xe = xe.at[slots[j]].set(xt, mode="drop")
    xe = xe[: E_loc * C].reshape(E_loc, C, d)

    h = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u,
                    p["w_down"]).astype(jnp.float32)
    ye_flat = jnp.concatenate(
        [ye.reshape(E_loc * C, d), jnp.zeros((1, d), jnp.float32)], 0)

    y = jnp.zeros((T, d), jnp.float32)
    for j in range(k):
        contrib = ye_flat[slots[j]] * (w[:, j] * keeps[j])[:, None]
        y = y + contrib
    return y


EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def moe_reads_chosen(cfg, rules, n_tokens: int, backend: str) -> bool:
    """Whether ``moe_apply`` reads only the chosen experts' weights
    (``ops.moe_decode_experts``) for ``n_tokens`` tokens: the experts the
    chip holds are on the one device the step runs on (a Pallas call is
    not partitioned over a mesh), the T·k choices leave some expert with
    none (for a share, the T·k·held/E choices expected on the held
    experts leave some held expert with none: the same test), the
    capacity path could drop no token (a token takes at most one slot of
    an expert, so T <= capacity), so both paths compute the same
    function, and the backend runs Pallas kernels."""
    return ((rules is None or rules.mesh.size == 1)
            and n_tokens * cfg.top_k < cfg.n_experts
            and n_tokens <= moe_capacity(cfg, n_tokens)
            and ops.resolve(backend) in ("pallas", "interpret"))


def _moe_chosen(p, xt, *, cfg, layer, backend):
    """Token-choice top-k that reads each chosen expert's slabs once.
    With ``layer``, p's expert weights are the whole (L, E, ...) stacks
    and ``layer`` picks the slabs; without, p holds one layer's.  A chip
    holding a share of the experts routes over all of them and adds what
    its own give."""
    logits = xt.astype(jnp.float32) @ p["router"]            # (T,E)
    w, idx = ops.topk_gate(logits, cfg.top_k)                # (T,k)
    stacks = [p[n] for n in EXPERT_STACKS]
    if layer is None:
        stacks, layer = [s[None] for s in stacks], 0
    ids, held = idx.reshape(-1), None
    lo, n_held = cfg.held_experts()
    if n_held < cfg.n_experts:
        ids = ids - lo
        held = (ids >= 0) & (ids < n_held)
        ids = jnp.clip(ids, 0, n_held - 1)
    return ops.moe_decode_experts(
        jnp.asarray(layer, jnp.int32).reshape(1), ids, xt,
        w.reshape(-1), *stacks, held=held, backend=backend)


def moe_apply(p, x, *, cfg, rules=None, backend="auto", layer=None):
    """Capacity-based token-choice top-k MoE with Megatron-style expert
    parallelism: tokens stay sharded on the data axis (activations are
    replicated over 'model'), each model rank runs only its expert slice
    on its data slab, and one psum over 'model' combines partial outputs.
    A chip that holds a share of the experts (``cfg.held_experts``)
    routes over all of them and computes its own experts' part, with no
    exchange where it has no mesh.
    No global scatter, no token all-to-all.  Tokens beyond per-slab
    expert capacity are dropped (capacity_factor controls head-room;
    smoke configs use a no-drop factor, tested against the dense-dispatch
    oracle).

    Where ``moe_reads_chosen`` holds (decode: a few tokens, experts all
    local), the same function reads only the chosen experts' weights.
    A caller that passes ``layer`` has checked that and passes the whole
    expert stacks in p (see ``lm.decode_step``)."""
    B, S, d = x.shape
    E = cfg.n_experts
    xt = x.reshape(B * S, d)

    if layer is not None or moe_reads_chosen(cfg, rules, B * S, backend):
        y = _moe_chosen(p, xt, cfg=cfg, layer=layer, backend=backend)
    elif rules is None or "model" not in rules.mesh.shape:
        # single-device / test path: the held experts are all local
        C = moe_capacity(cfg, B * S)
        lo, n_held = cfg.held_experts()
        y = _moe_local(p, xt, cfg=cfg, C=C, e_lo=jnp.int32(lo), E_loc=n_held)
    else:
        y = _moe_expert_parallel(p, x, cfg=cfg, rules=rules)
    out = y.astype(x.dtype).reshape(B, S, d)
    if cfg.n_shared:
        sh = (jax.nn.silu(xt @ p["s_gate"]) * (xt @ p["s_up"])) @ p["s_down"]
        out = out + sh.reshape(B, S, d)
    return constrain(out, rules, ("batch", None, "embed"))


def _moe_expert_parallel(p, x, *, cfg, rules):
    """Each model rank's expert slice on its data slab, psum over
    'model': (B, S, d) in x's dtype."""
    B, S, d = x.shape
    lo, n_held = cfg.held_experts()
    mesh = rules.mesh
    tp = mesh.shape["model"]
    assert n_held % tp == 0, "experts must divide the model axis"
    E_loc = n_held // tp
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    b_spec = batch_axes if B % dp == 0 else None
    from jax.sharding import PartitionSpec as P
    x_spec = P(b_spec, None, None)
    router_spec = P(None, None)
    ew_spec = P("model", None, None)

    def local(xb, router, wg, wu, wd):
        Bl, Sl, _ = xb.shape
        xt = xb.reshape(Bl * Sl, d)
        C = moe_capacity(cfg, Bl * Sl)
        rank = jax.lax.axis_index("model")
        pl = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        y = _moe_local(pl, xt, cfg=cfg, C=C, e_lo=lo + rank * E_loc,
                       E_loc=E_loc)
        y = jax.lax.psum(y, "model")
        return y.reshape(Bl, Sl, d).astype(xb.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, router_spec, ew_spec, ew_spec, ew_spec),
        out_specs=x_spec, check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def moe_apply_dense(p, x, *, cfg, rules=None):
    """Dense-dispatch oracle (exact, no capacity): used by tests to
    validate the capacity path on small shapes."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    lo, n_held = cfg.held_experts()
    logits = (x.astype(jnp.float32) @ p["router"])
    w, idx = ops.topk_gate(logits.reshape(-1, E), k)
    T = B * S
    xt = x.reshape(T, d)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
    combine = (w[..., None] * onehot).sum(1)[:, lo:lo + n_held]  # (T,held)
    h = jnp.einsum("td,edf->tef", xt, p["w_gate"])
    u = jnp.einsum("td,edf->tef", xt, p["w_up"])
    h = jax.nn.silu(h) * u
    yv = jnp.einsum("tef,efd->ted", h, p["w_down"]).astype(jnp.float32)
    out = jnp.einsum("ted,te->td", yv, combine).astype(x.dtype)
    if cfg.n_shared:
        out = out + (jax.nn.silu(xt @ p["s_gate"]) * (xt @ p["s_up"])) @ p["s_down"]
    return constrain(out.reshape(B, S, d), rules, ("batch", None, "embed"))


# ---------------------------------------------------------------------------
# Mamba2 (SSD) block
# ---------------------------------------------------------------------------


def mamba2_specs(cfg) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    di = cfg.ssm_inner            # 2*d typically
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    dt = cfg.param_dtype
    sp = {
        # in_proj → [z (gate), x, B, C, dt]
        "w_in": ParamSpec((d, 2 * di + 2 * N + H), dt,
                          ("embed", "ssm_inner")),
        "conv": ParamSpec((cfg.conv_k, di + 2 * N), dt, (None, "ssm_inner")),
        "A_log": ParamSpec((H,), jnp.float32, (None,), init="zeros"),
        "D": ParamSpec((H,), jnp.float32, (None,), init="ones"),
        "dt_bias": ParamSpec((H,), jnp.float32, (None,), init="zeros"),
        "norm": ParamSpec((di,), jnp.float32, ("ssm_inner",), init="ones"),
        "w_out": ParamSpec((di, d), dt, ("ssm_inner", "embed")),
    }
    if cfg.conv_bias:
        sp["conv_b"] = ParamSpec((di + 2 * N,), dt, ("ssm_inner",),
                                 init="zeros")
    return sp


def _mamba_split(cfg, proj):
    di, N, H = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads
    z = proj[..., :di]
    xBC = proj[..., di:di + di + 2 * N]
    dt = proj[..., di + di + 2 * N:]
    return z, xBC, dt


def _causal_conv(xBC, conv, state=None, bias=None):
    """Depthwise causal conv along S, then SiLU. xBC: (B,S,C); conv:
    (K,C); bias (C,) or None.  If state (B,K-1,C) given, runs in
    streaming mode, returns new state."""
    K = conv.shape[0]
    if state is None:
        pad = jnp.zeros_like(xBC[:, :K - 1])
        xp = jnp.concatenate([pad, xBC], axis=1)
    else:
        xp = jnp.concatenate([state.astype(xBC.dtype), xBC], axis=1)
    out = sum(xp[:, i:i + xBC.shape[1]] * conv[i] for i in range(K))
    if bias is not None:
        out = out + bias
    new_state = xp[:, -(K - 1):] if K > 1 else None
    return jax.nn.silu(out), new_state


def mamba2_apply(p, x, *, cfg, rules=None, backend="auto"):
    """x: (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    di, N, H, P = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    proj = x @ p["w_in"]
    z, xBC, dtp = _mamba_split(cfg, proj)
    xBC, _ = _causal_conv(xBC, p["conv"], bias=p.get("conv_b"))
    xs = xBC[..., :di]
    Bm = xBC[..., di:di + N].astype(jnp.float32)
    Cm = xBC[..., di + N:].astype(jnp.float32)
    dt = jax.nn.softplus(dtp.astype(jnp.float32) + p["dt_bias"])      # (B,S,H)
    A = -jnp.exp(p["A_log"])                                          # (H,)
    a = (A * dt)                                                      # (B,S,H) ≤0
    xh = (xs.reshape(B, S, H, P).astype(jnp.float32)
          * dt[..., None])                                            # dt-scaled
    chunk = min(cfg.ssd_chunk, S)
    y = jax.vmap(lambda xx, aa, bb, cc: ops.ssd_scan(
        xx, aa, bb, cc, chunk=chunk, backend=backend))(xh, a, Bm, Cm)
    # the skip term multiplies the input before its dt scaling
    y = y + xs.reshape(B, S, H, P).astype(jnp.float32) * \
        p["D"][None, None, :, None]
    y = y.reshape(B, S, di).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = ops.rmsnorm(y, p["norm"], eps=cfg.norm_eps, backend=backend)
    out = y @ p["w_out"]
    return constrain(out, rules, ("batch", None, "embed"))


def mamba2_decode(p, x, state, *, cfg, fresh, backend="auto", layer=None):
    """One-token recurrent step.  x: (B, d); state: {"h": (B,H,P,N) f32,
    "conv": (B,K-1,C)}, or with ``layer``, "h" the (L,B,H,P,N) stack of a
    run of layers, updated in place at ``layer``; fresh: (B,) bool, the
    sequences that start here, from the zero state."""
    B, d = x.shape
    di, N, H, P = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    hs, conv0 = state["h"], state["conv"]
    one = layer is None
    if one:
        hs, layer = hs[None], 0
    conv0 = jnp.where(fresh[:, None, None], 0, conv0).astype(conv0.dtype)
    proj = x @ p["w_in"]
    z, xBC, dtp = _mamba_split(cfg, proj[:, None])
    xBC, conv_state = _causal_conv(xBC, p["conv"], conv0, p.get("conv_b"))
    z, xBC, dtp = z[:, 0], xBC[:, 0], dtp[:, 0]
    xs = xBC[..., :di]
    Bm = xBC[..., di:di + N]
    Cm = xBC[..., di + N:]
    dt = jax.nn.softplus(dtp.astype(jnp.float32) + p["dt_bias"])  # (B,H)
    y, hs = ops.ssd_decode(hs, jnp.asarray(layer, jnp.int32).reshape(1),
                           fresh, xs.reshape(B, H, P), dt,
                           -jnp.exp(p["A_log"]), Bm, Cm, p["D"],
                           backend=backend)
    y = y.reshape(B, di).astype(x.dtype) * jax.nn.silu(z)
    y = ops.rmsnorm(y, p["norm"], eps=cfg.norm_eps, backend=backend)
    return y @ p["w_out"], {"h": hs[0] if one else hs,
                            "conv": conv_state.astype(state["conv"].dtype)}


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------


def embed_specs(cfg) -> Dict[str, ParamSpec]:
    vpad = round_up(cfg.vocab, 256)
    sp = {"tok": ParamSpec((vpad, cfg.d_model), cfg.param_dtype,
                           ("vocab", "embed"), scale=1.0)}
    if not cfg.tie_embeddings:
        sp["unembed"] = ParamSpec((cfg.d_model, vpad), cfg.param_dtype,
                                  ("embed", "vocab"))
    return sp


def embed_apply(p, tokens):
    return jnp.take(p["tok"], tokens, axis=0)


def unembed_apply(p, x, cfg):
    w = p.get("unembed")
    if w is None:
        w = p["tok"].T
    return (x @ w).astype(jnp.float32)


def cross_entropy(logits, labels, vocab: int):
    """logits: (B,S,Vpad) f32; labels: (B,S) int32; mean over valid."""
    vpad = logits.shape[-1]
    mask = jnp.arange(vpad) < vocab
    logits = jnp.where(mask, logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    valid = (labels >= 0) & (labels < vocab)
    nll = jnp.where(valid, lse - ll, 0.0)
    return nll.sum() / jnp.maximum(valid.sum(), 1)
