"""pjit step builders: training and serving, with full sharding tables.

``make_train_step``/``make_serve_step`` return (jitted fn, in/out
shardings, abstract inputs) so the same builder serves the dry-run
(lower+compile only), the real trainer, and the benchmarks.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig
from ..launch import specs as S
from ..models import encdec, lm
from ..models.params import (AxisRules, ParamSpec, default_rules, is_spec,
                             tree_abstract, zero1_pspec)
from ..optim import adamw


def model_specs(cfg: ModelConfig):
    return encdec.encdec_specs(cfg) if cfg.family == "encdec" \
        else lm.lm_specs(cfg)


def param_shardings(rules: AxisRules, spec_tree):
    return rules.tree_shardings(spec_tree)


def opt_shardings(rules: AxisRules, spec_tree, opt_cfg: adamw.AdamWConfig):
    """ZeRO-1: moments take the param sharding + 'data' on a free axis."""
    def sh(spec: ParamSpec):
        return NamedSharding(rules.mesh, zero1_pspec(rules, spec))
    moments = jax.tree_util.tree_map(sh, spec_tree, is_leaf=is_spec)
    out = {"m": moments, "v": moments,
           "step": NamedSharding(rules.mesh, P())}
    if opt_cfg.grad_compress:
        out["err"] = moments
    return out


def batch_shardings(rules: AxisRules, cfg, shape):
    axes = S.batch_pspec_axes(cfg, shape)
    bspecs = S.batch_specs(cfg, shape)
    return {k: NamedSharding(rules.mesh,
                             rules.pspec_for(bspecs[k].shape, axes[k],
                                             what=f"batch.{k}"))
            for k in bspecs}


# ---------------------------------------------------------------------------


def _with_tp_pad(cfg: ModelConfig, mesh: Mesh) -> ModelConfig:
    """Record the mesh's TP degree on the config: enables group-aligned
    head padding (exact math; see ModelConfig.head_padding) and the
    row-parallel KV fallback in attention_specs."""
    tp = mesh.shape.get("model", 1)
    if tp > 1 and cfg.n_heads:
        return dataclasses.replace(cfg, tp_pad=tp)
    return cfg


def make_train_step(cfg: ModelConfig, mesh: Mesh,
                    opt_cfg: Optional[adamw.AdamWConfig] = None,
                    rules: Optional[AxisRules] = None,
                    backend: str = "auto", strategy: str = "tp"):
    """Returns (step_fn, bundle) where step_fn(params, opt_state, batch)
    -> (params, opt_state, metrics), fully sharded and donated."""
    cfg = _with_tp_pad(cfg, mesh)
    opt_cfg = opt_cfg or adamw.AdamWConfig()
    rules = rules or default_rules(mesh, strategy)
    spec_tree = model_specs(cfg)
    fwd = encdec.forward if cfg.family == "encdec" else lm.forward

    # ZeRO-2: gradients are reduce-scattered onto the data axis right out
    # of backward (same placement as the ZeRO-1 moments), so no device
    # ever holds a full gradient replica.
    z1_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, zero1_pspec(rules, s)),
        spec_tree, is_leaf=is_spec)

    def step(params, opt_state, batch):
        def loss_fn(p):
            loss, _ = fwd(cfg, p, batch, rules=rules, backend=backend)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree_util.tree_map(
            lambda g, sh: jax.lax.with_sharding_constraint(g, sh),
            grads, z1_sh)
        params, opt_state, metrics = adamw.update(grads, opt_state, params,
                                                  opt_cfg)
        metrics = dict(metrics, loss=loss)
        return params, opt_state, metrics

    p_sh = param_shardings(rules, spec_tree)
    o_sh = opt_shardings(rules, spec_tree, opt_cfg)
    # batch shardings are supplied by the caller per shape
    return step, {"rules": rules, "specs": spec_tree, "param_sh": p_sh,
                  "opt_sh": o_sh, "opt_cfg": opt_cfg}


def jit_train_step(cfg, mesh, shape: ShapeConfig,
                   opt_cfg: Optional[adamw.AdamWConfig] = None,
                   backend: str = "auto", rules=None, strategy: str = "tp"):
    step, bundle = make_train_step(cfg, mesh, opt_cfg, rules=rules,
                                   backend=backend, strategy=strategy)
    rules = bundle["rules"]
    b_sh = batch_shardings(rules, cfg, shape)
    metrics_sh = {"grad_norm": NamedSharding(mesh, P()),
                  "lr": NamedSharding(mesh, P()),
                  "loss": NamedSharding(mesh, P())}
    jitted = jax.jit(
        step,
        in_shardings=(bundle["param_sh"], bundle["opt_sh"], b_sh),
        out_shardings=(bundle["param_sh"], bundle["opt_sh"], metrics_sh),
        donate_argnums=(0, 1),
    )
    abstract = (tree_abstract(bundle["specs"]),
                _opt_abstract(bundle["specs"], bundle["opt_cfg"]),
                S.batch_specs(cfg, shape))
    return jitted, bundle, abstract


def _opt_abstract(spec_tree, opt_cfg):
    mom = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), spec_tree,
        is_leaf=is_spec)
    out = {"m": mom, "v": mom, "step": jax.ShapeDtypeStruct((), jnp.int32)}
    if opt_cfg.grad_compress:
        out["err"] = mom
    return out


# ---------------------------------------------------------------------------


def make_serve_step(cfg: ModelConfig, mesh: Mesh,
                    rules: Optional[AxisRules] = None,
                    backend: str = "auto", strategy: str = "tp"):
    cfg = _with_tp_pad(cfg, mesh)
    rules = rules or default_rules(mesh, strategy)
    spec_tree = model_specs(cfg)
    dec = encdec.decode_step if cfg.family == "encdec" else lm.decode_step

    def step(params, cache, tokens, pos):
        logits, new_cache = dec(cfg, params, cache, tokens, pos,
                                rules=rules, backend=backend)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, new_cache

    # raw_step: the un-jitted body, re-traceable inside larger programs
    # (the serving driver scans it over a whole prompt for one-call
    # batched prefill instead of one jitted dispatch per token)
    return step, {"rules": rules, "specs": spec_tree, "raw_step": step,
                  "param_sh": param_shardings(rules, spec_tree)}


def jit_serve_step(cfg, mesh, shape: ShapeConfig, backend: str = "auto",
                   rules=None, strategy: str = "tp"):
    step, bundle = make_serve_step(cfg, mesh, rules=rules, backend=backend,
                                   strategy=strategy)
    rules = bundle["rules"]
    cache_tree = S.cache_spec_tree(cfg, shape)
    cache_sh = rules.tree_shardings(cache_tree)
    b_sh = batch_shardings(rules, cfg, shape)
    tok_sh = NamedSharding(mesh, rules.pspec_for(
        (shape.global_batch,), ("batch",), what="tokens_out"))
    jitted = jax.jit(
        step,
        in_shardings=(bundle["param_sh"], cache_sh, b_sh["tokens"],
                      b_sh["pos"]),
        out_shardings=(tok_sh, cache_sh),
        donate_argnums=(1,),
    )
    abstract = (tree_abstract(bundle["specs"]),
                tree_abstract(cache_tree),
                S.batch_specs(cfg, shape)["tokens"],
                S.batch_specs(cfg, shape)["pos"])
    return jitted, bundle, abstract
