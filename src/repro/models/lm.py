"""Decoder-only LM assembly: dense / MoE / SSM / hybrid / VLM families.

Layers are scanned (stacked params) to keep HLO size and compile time
independent of depth — essential for the 512-device dry-runs.

A dense/MoE stack may change its mixer by layer (``cfg.layer_types``:
full attention, sliding-window attention or Mamba2, each followed by the
layer's MLP or MoE).  Each run of same-mixer layers (``cfg.layer_runs``)
is one scanned stack, and its cache holds K/V for a full-attention run
(every position), a ring of ``cfg.window`` K/V rows for a sliding run
(position p in row p mod W), the f32 SSM state and the conv tail for a
Mamba run.  A stack of one run keeps ``params["layers"]`` and the cache
as that run's; several runs make both lists, one entry a run.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from . import layers as L
from .params import ParamSpec, is_spec

# ---------------------------------------------------------------------------
# spec assembly
# ---------------------------------------------------------------------------


def _stack(spec_tree, n: int):
    def f(s: ParamSpec) -> ParamSpec:
        return dataclasses.replace(s, shape=(n,) + s.shape,
                                   axes=(None,) + tuple(s.axes or
                                                        (None,) * len(s.shape)))
    return jax.tree_util.tree_map(f, spec_tree, is_leaf=is_spec)


def _norm_pair(cfg, name: str) -> Dict[str, ParamSpec]:
    sp = {name: L.norm_spec(cfg)}
    if cfg.norm == "ln":
        sp[name + "_b"] = dataclasses.replace(L.norm_spec(cfg), init="zeros")
    return sp


def _norm(cfg, p, name: str, x, backend):
    return L.apply_norm(p[name], x, cfg.norm, p.get(name + "_b"), backend,
                        eps=cfg.norm_eps)


def _runs(cfg, tree):
    """(mixer, that run's subtree) for each run of ``cfg.layer_runs``."""
    trees = tree if isinstance(tree, (list, tuple)) else [tree]
    return [(kind, t) for (kind, _, _), t in zip(cfg.layer_runs(), trees)]


def _one_or_list(per_run):
    return per_run[0] if len(per_run) == 1 else list(per_run)


def _dense_layer_specs(cfg, mixer: str = "attention") -> Dict[str, Any]:
    sp: Dict[str, Any] = {}
    sp.update(_norm_pair(cfg, "ln1"))
    if mixer == "mamba":
        sp["mamba"] = L.mamba2_specs(cfg)
    else:
        sp["attn"] = L.attention_specs(cfg)
    sp.update(_norm_pair(cfg, "ln2"))
    if cfg.family == "moe":
        sp["moe"] = L.moe_specs(cfg)
    else:
        sp["mlp"] = L.mlp_specs(cfg)
    return sp


def _ssm_layer_specs(cfg) -> Dict[str, Any]:
    sp: Dict[str, Any] = {}
    sp.update(_norm_pair(cfg, "ln1"))
    sp["mamba"] = L.mamba2_specs(cfg)
    return sp


def lm_specs(cfg) -> Dict[str, Any]:
    specs: Dict[str, Any] = {"embed": L.embed_specs(cfg)}
    specs.update(_norm_pair(cfg, "final_norm"))
    if cfg.family in ("dense", "moe", "vlm"):
        specs["layers"] = _one_or_list(
            [_stack(_dense_layer_specs(cfg, kind), n)
             for kind, _, n in cfg.layer_runs()])
    elif cfg.family == "ssm":
        specs["layers"] = _stack(_ssm_layer_specs(cfg), cfg.n_layers)
    elif cfg.family == "hybrid":
        specs["layers"] = _stack(_ssm_layer_specs(cfg), cfg.n_layers)
        shared = {}
        shared.update(_norm_pair(cfg, "ln1"))
        shared["attn"] = L.attention_specs(cfg)
        shared.update(_norm_pair(cfg, "ln2"))
        shared["mlp"] = L.mlp_specs(cfg)
        specs["shared_attn"] = shared
    else:
        raise ValueError(cfg.family)
    return specs


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _dense_layer_apply(cfg, rules, window, backend, mixer, lp, x, positions):
    h = _norm(cfg, lp, "ln1", x, backend)
    if mixer == "mamba":
        h = L.mamba2_apply(lp["mamba"], h, cfg=cfg, rules=rules,
                           backend=backend)
    else:
        h = L.attention_apply(lp["attn"], h, positions, cfg=cfg, rules=rules,
                              causal=True, window=window, backend=backend,
                              kind=mixer)
    x = x + h
    h = _norm(cfg, lp, "ln2", x, backend)
    if cfg.family == "moe":
        h = L.moe_apply(lp["moe"], h, cfg=cfg, rules=rules, backend=backend)
    else:
        h = L.mlp_apply(lp["mlp"], h, cfg=cfg, rules=rules)
    return x + h


def _ssm_layer_apply(cfg, rules, backend, lp, x):
    h = _norm(cfg, lp, "ln1", x, backend)
    h = L.mamba2_apply(lp["mamba"], h, cfg=cfg, rules=rules, backend=backend)
    return x + h


def _shared_attn_apply(cfg, rules, backend, sp, x, positions):
    h = _norm(cfg, sp, "ln1", x, backend)
    h = L.attention_apply(sp["attn"], h, positions, cfg=cfg, rules=rules,
                          causal=True, window=cfg.window, backend=backend)
    x = x + h
    h = _norm(cfg, sp, "ln2", x, backend)
    return x + L.mlp_apply(sp["mlp"], h, cfg=cfg, rules=rules)


def _scan_layers(layer_fn, stacked_params, x, remat: bool, rules=None):
    """Scan the layer stack.  The carry (residual stream) — which is what
    full remat saves per layer — is constrained to sequence-parallel
    sharding ('seq_act' → model) so 34B-class × 4k × 256-batch activation
    checkpoints fit per-device HBM; XLA inserts the all-gather /
    reduce-scatter pair around the head/mlp-sharded interior."""
    def seq_shard(h):
        return L.constrain(h, rules, ("batch", "seq_act", "embed"))

    fn = layer_fn
    if remat:
        fn = jax.checkpoint(fn)

    def body(carry, lp):
        return seq_shard(fn(lp, carry)), None

    x, _ = lax.scan(body, seq_shard(x), stacked_params)
    return x


def hidden_states(cfg, params, x, positions, *, rules=None, backend="auto"):
    """Run the layer stack on embedded inputs x: (B, S, d)."""
    remat = cfg.remat == "full"
    if cfg.family in ("dense", "moe", "vlm"):
        for kind, stack in _runs(cfg, params["layers"]):
            # only a sliding layer's keys are bounded by the window
            window = cfg.window if kind == "sliding_attention" else 0
            fn = functools.partial(_dense_layer_apply, cfg, rules,
                                   window, backend, kind)
            x = _scan_layers(lambda lp, h, fn=fn: fn(lp, h, positions),
                             stack, x, remat, rules=rules)
    elif cfg.family == "ssm":
        fn = functools.partial(_ssm_layer_apply, cfg, rules, backend)
        x = _scan_layers(fn, params["layers"], x, remat, rules=rules)
    elif cfg.family == "hybrid":
        ae = cfg.attn_every or cfg.n_layers
        n = cfg.n_layers
        fn = functools.partial(_ssm_layer_apply, cfg, rules, backend)
        start = 0
        while start < n:
            width = min(ae, n - start)
            group = jax.tree_util.tree_map(
                lambda a: lax.slice_in_dim(a, start, start + width, axis=0),
                params["layers"])
            x = _scan_layers(fn, group, x, remat, rules=rules)
            x = _shared_attn_apply(cfg, rules, backend,
                                   params["shared_attn"], x, positions)
            start += width
    else:
        raise ValueError(cfg.family)
    return _norm(cfg, params, "final_norm", x, backend)


def forward(cfg, params, batch, *, rules=None, backend="auto"):
    """Training forward.  batch: tokens (B,S_text), labels (B,S_text),
    optional frontend (B,Nf,d) for vlm/audio.  Returns (loss, logits)."""
    tokens = batch["tokens"]
    x = L.embed_apply(params["embed"], tokens)
    if cfg.n_frontend_tokens:
        fe = batch["frontend"].astype(x.dtype)
        x = jnp.concatenate([fe, x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = L.constrain(x, rules, ("batch", None, "embed"))
    h = hidden_states(cfg, params, x, positions, rules=rules, backend=backend)
    if cfg.n_frontend_tokens:
        h = h[:, cfg.n_frontend_tokens:]
    logits = L.unembed_apply(params["embed"], h, cfg)
    logits = L.constrain(logits, rules, ("batch", None, "vocab"))
    loss = L.cross_entropy(logits, batch["labels"], cfg.vocab)
    return loss, logits


# ---------------------------------------------------------------------------
# decode (serve step)
# ---------------------------------------------------------------------------


def _ssm_cache_specs(cfg, n: int, B: int) -> Dict[str, ParamSpec]:
    """f32 SSM state (n, B, H, P, N) and conv tail of n Mamba layers."""
    H, N, P = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim
    conv_c = cfg.ssm_inner + 2 * N
    return {
        "h": ParamSpec((n, B, H, P, N), jnp.float32,
                       (None, "batch", "ssm_inner", None, None), init="zeros"),
        "conv": ParamSpec((n, B, cfg.conv_k - 1, conv_c), cfg.param_dtype,
                          (None, "batch", None, "ssm_inner"), init="zeros"),
    }


def cache_specs(cfg, batch: int, seq_len: int) -> Dict[str, Any]:
    """Abstract KV/state cache layout for one-token decode.

    Dense/MoE/VLM: per-layer KV (L, B, S, Hkv, Dh) — S sharded over
    'model' (seq_kv) so 32k×128 caches fit HBM; with a layer pattern, one
    entry per run: KV for an attention run, a KV ring of min(S, window)
    rows for a sliding run, SSM state and conv tail for a Mamba run.
    SSM: recurrent state (L, B, H, P, N) + conv tail.
    Hybrid: SSM states + one KV cache per shared-attention application
    (window-bounded for long contexts)."""
    Lc, B, S = cfg.n_layers, batch, seq_len
    Hkv, Dh = cfg.n_kv, cfg.d_head
    dt = cfg.param_dtype
    if cfg.family in ("dense", "moe", "vlm"):
        runs = []
        for kind, _, n in cfg.layer_runs():
            if kind == "mamba":
                runs.append(_ssm_cache_specs(cfg, n, B))
                continue
            rows = min(S, cfg.window) if kind == "sliding_attention" else S
            kv = ParamSpec((n, B, rows, Hkv, Dh), dt,
                           (None, "batch", "seq_kv", "kv_heads", None),
                           init="zeros")
            runs.append({"k": kv, "v": kv})
        return _one_or_list(runs)
    ssm = _ssm_cache_specs(cfg, Lc, B)
    if cfg.family == "ssm":
        return ssm
    # hybrid: shared-attention KV per application, window-bounded
    ae = cfg.attn_every or cfg.n_layers
    n_app = -(-cfg.n_layers // ae)
    Sw = min(S, cfg.window) if cfg.window else S
    kv = ParamSpec((n_app, B, Sw, Hkv, Dh), dt,
                   (None, "batch", "seq_kv", "kv_heads", None), init="zeros")
    ssm.update({"k": kv, "v": kv})
    return ssm


def rewritten(cfg, cache):
    """Per leaf of ``cache`` (``tree_leaves`` order), whether a token at a
    later position overwrites what the leaf holds of earlier ones: a
    sliding run's ring, a Mamba run's state and conv tail.  A full
    attention run's rows below a position are never written again, so
    the position alone restores them.  Other families: every leaf."""
    if cfg.family not in ("dense", "moe", "vlm"):
        return [True] * len(jax.tree_util.tree_leaves(cache))
    return [kind != "attention" for kind, run in _runs(cfg, cache)
            for _ in jax.tree_util.tree_leaves(run)]


def decode_step(cfg, params, cache, tokens, pos, *, rules=None,
                backend="auto"):
    """One token for every sequence.  tokens: (B,), pos: (B,) current
    lengths.  Returns (logits (B, Vpad), new cache)."""
    x = L.embed_apply(params["embed"], tokens)          # (B, d)
    x = L.constrain(x, rules, ("batch", "embed"))
    # a sequence at position 0 starts from the zero recurrent state
    fresh = pos == 0

    if cfg.family in ("dense", "moe", "vlm"):
        h, runs = x, []
        for (kind, layers), (_, run_cache) in zip(
                _runs(cfg, params["layers"]), _runs(cfg, cache)):
            h, new = _decode_run(cfg, kind, layers, run_cache, h, pos, fresh,
                                 rules=rules, backend=backend)
            runs.append(new)
        new_cache = _one_or_list(runs)

    elif cfg.family == "ssm":
        def body(h, inp):
            lp, hs, cs = inp
            hn = _norm(cfg, lp, "ln1", h, backend)
            y, st = L.mamba2_decode(lp["mamba"], hn, {"h": hs, "conv": cs},
                                    cfg=cfg, backend=backend, fresh=fresh)
            return h + y, (st["h"], st["conv"])

        h, (hs, cs) = lax.scan(body, x, (params["layers"], cache["h"],
                                         cache["conv"]))
        new_cache = {"h": hs, "conv": cs}

    elif cfg.family == "hybrid":
        ae = cfg.attn_every or cfg.n_layers
        n = cfg.n_layers
        W = cache["k"].shape[2]
        slot = pos % W
        kv_len = jnp.minimum(pos + 1, W)
        h = x
        hs_out, cs_out, k_out, v_out = [], [], [], []
        start, app = 0, 0
        while start < n:
            width = min(ae, n - start)
            group = jax.tree_util.tree_map(
                lambda a: lax.slice_in_dim(a, start, start + width, axis=0),
                params["layers"])

            def body(hh, inp):
                lp, hstate, cstate = inp
                hn = _norm(cfg, lp, "ln1", hh, backend)
                y, st = L.mamba2_decode(lp["mamba"], hn,
                                        {"h": hstate, "conv": cstate},
                                        cfg=cfg, backend=backend,
                                        fresh=fresh)
                return hh + y, (st["h"], st["conv"])

            h, (hs, cs) = lax.scan(
                body, h, (group,
                          lax.slice_in_dim(cache["h"], start,
                                           start + width, axis=0),
                          lax.slice_in_dim(cache["conv"], start,
                                           start + width, axis=0)))
            hs_out.append(hs)
            cs_out.append(cs)
            sp = params["shared_attn"]
            hn = _norm(cfg, sp, "ln1", h, backend)
            y, newkv = L.attention_decode(
                sp["attn"], hn, {"k": cache["k"][app], "v": cache["v"][app]},
                pos, cfg=cfg, rules=rules, backend=backend,
                slot=slot, kv_len=kv_len)
            h = h + y
            hn = _norm(cfg, sp, "ln2", h, backend)
            h = h + L.mlp_apply(sp["mlp"], hn[:, None], cfg=cfg,
                                rules=rules)[:, 0]
            k_out.append(newkv["k"])
            v_out.append(newkv["v"])
            start += width
            app += 1
        new_cache = {"h": jnp.concatenate(hs_out, 0),
                     "conv": jnp.concatenate(cs_out, 0),
                     "k": jnp.stack(k_out, 0), "v": jnp.stack(v_out, 0)}
    else:
        raise ValueError(cfg.family)

    h = _norm(cfg, params, "final_norm", h, backend)
    logits = L.unembed_apply(params["embed"], h, cfg)
    logits = L.constrain(logits, rules, ("batch", "vocab"))
    return logits, new_cache


def _decode_run(cfg, mixer, layers, cache, h, pos, fresh, *, rules, backend):
    """One token through a run of same-mixer layers (stacked params,
    scanned), each with its MLP or MoE: (h, the run's new cache).  A
    Mamba run carries its whole SSM state stack through the scan, each
    layer's state updated in place (``ssd_decode``); so does an attention
    run its K/V stacks where ``decode_attn_in_place`` holds
    (``decode_attn_cache``).  The rest of the cache goes in and out as
    the scan's per-layer slices.  A sliding run writes ring row pos mod W
    and attends the min(pos + 1, W) rows written."""
    stacks = None
    if cfg.family == "moe" and L.moe_reads_chosen(cfg, rules, h.shape[0],
                                                  backend):
        # the expert stacks stay out of the scanned layers, whole: the
        # kernel reads layer i's chosen slabs straight from them
        moe = dict(layers["moe"])
        stacks = {n: moe.pop(n) for n in L.EXPERT_STACKS}
        layers = dict(layers, moe=moe)
    if mixer == "mamba":
        carried, names = ("h",), ("conv",)
    elif L.decode_attn_in_place(cfg, rules, backend):
        carried, names = ("k", "v"), ()
    else:
        carried, names = (), ("k", "v")
    ring = {}
    if mixer == "sliding_attention":
        W = cache["k"].shape[2]
        ring = {"slot": pos % W, "kv_len": jnp.minimum(pos + 1, W)}

    def body(carry, inp):
        x, i, *whole = carry
        lp, *sliced = inp
        state = dict(zip(carried, whole), **dict(zip(names, sliced)))
        hn = _norm(cfg, lp, "ln1", x, backend)
        if mixer == "mamba":
            y, new = L.mamba2_decode(lp["mamba"], hn, state, cfg=cfg,
                                     backend=backend, fresh=fresh, layer=i)
        else:
            y, new = L.attention_decode(lp["attn"], hn, state, pos, cfg=cfg,
                                        rules=rules, backend=backend,
                                        layer=i if carried else None,
                                        kind=mixer, **ring)
        x = x + y
        hn = _norm(cfg, lp, "ln2", x, backend)
        if cfg.family == "moe":
            y = L.moe_apply(dict(lp["moe"], **(stacks or {})), hn[:, None],
                            cfg=cfg, rules=rules, backend=backend,
                            layer=None if stacks is None else i)[:, 0]
        else:
            y = L.mlp_apply(lp["mlp"], hn[:, None], cfg=cfg,
                            rules=rules)[:, 0]
        return ((x + y, i + 1, *(new[n] for n in carried)),
                tuple(new[n] for n in names))

    (h, _, *whole), sliced = lax.scan(
        body, (h, jnp.int32(0), *(cache[n] for n in carried)),
        (layers, *(cache[n] for n in names)))
    return h, dict(zip(carried, whole), **dict(zip(names, sliced)))
