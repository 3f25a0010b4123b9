"""The decode MoE that reads only the chosen experts' weights
(``ops.moe_decode_experts``): the kernel in interpret mode and its XLA
reference against the dense-dispatch oracle and the capacity path, the
shapes that choose between the paths, and a whole decode step."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels import moe_decode, ops
from repro.launch.mesh import make_mesh
from repro.models import layers as L
from repro.models import lm
from repro.models.params import default_rules, init_params

# granite-moe's routing (32 experts, top-8) at small widths
CFG = dataclasses.replace(registry.get("granite-moe-1b-a400m", smoke=True),
                          n_experts=32, top_k=8, d_model=128, d_expert=128)
LAYERS = 3


def _cfg(dtype):
    return dataclasses.replace(CFG, param_dtype=dtype)


def _stacked(cfg, seed=0):
    """Three layers of MoE weights, stacked as the model scans them."""
    keys = jax.random.split(jax.random.PRNGKey(seed), LAYERS)
    layers = [init_params(L.moe_specs(cfg), k) for k in keys]
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers)


def _tokens(cfg, T, seed=1):
    x = jax.random.normal(jax.random.PRNGKey(seed), (T, cfg.d_model))
    return x.astype(cfg.param_dtype)


def _layer(stack, i):
    return jax.tree_util.tree_map(lambda a: a[i], stack)


def _kernel_path(stack, layer, xt, cfg, backend):
    p = _layer(stack, layer)
    w, idx = ops.topk_gate(xt.astype(jnp.float32) @ p["router"], cfg.top_k)
    return ops.moe_decode_experts(
        jnp.array([layer], jnp.int32), idx.reshape(-1), xt, w.reshape(-1),
        stack["w_gate"], stack["w_up"], stack["w_down"], backend=backend)


def _f32(a):
    return np.asarray(a, np.float32)


# f32 weights: every path is the same sum, to rounding of f32; bf16: the
# paths round the same products, so they differ by a bf16 ulp of an
# expert's output at most
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("backend", ["interpret", "xla"])
@pytest.mark.parametrize("T", [1, 2, 3])
def test_chosen_experts_match_dense_and_capacity(T, backend, dtype):
    cfg = _cfg(dtype)
    stack = _stacked(cfg)
    xt = _tokens(cfg, T)
    layer = 2                                   # a nonzero layer of the stack
    got = _kernel_path(stack, layer, xt, cfg, backend).astype(dtype)
    p = _layer(stack, layer)
    dense = L.moe_apply_dense(p, xt[None], cfg=cfg)[0]
    capacity = L.moe_apply(p, xt[None], cfg=cfg, backend="xla")[0]
    scale = np.abs(_f32(dense)).max()
    for want in (dense, capacity):
        np.testing.assert_allclose(_f32(got), _f32(want), rtol=0,
                                   atol=TOL[dtype] * scale)


@pytest.mark.parametrize("T", [1, 3])
def test_interpret_equals_reference_in_f32(T):
    cfg = _cfg(jnp.float32)
    stack = _stacked(cfg, seed=T)
    xt = _tokens(cfg, T, seed=T + 1)
    got = _kernel_path(stack, 1, xt, cfg, "interpret")
    want = _kernel_path(stack, 1, xt, cfg, "xla")
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_expert_width_in_slices(monkeypatch, dtype):
    """Experts too wide for one grid step's VMEM are read in slices of f,
    and the down product is summed over the slices before it rounds."""
    cfg = dataclasses.replace(_cfg(dtype), d_expert=384)
    itemsize = jnp.dtype(dtype).itemsize
    monkeypatch.setattr(moe_decode, "STEP_BYTES", 3 * 128 * 128 * itemsize)
    assert moe_decode.f_tile(128, 384, itemsize) == 128
    stack = _stacked(cfg)
    xt = _tokens(cfg, 2)
    got = _kernel_path(stack, 1, xt, cfg, "interpret").astype(dtype)
    want = _kernel_path(stack, 1, xt, cfg, "xla").astype(dtype)
    scale = np.abs(_f32(want)).max()
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=0,
                               atol=TOL[dtype] * scale)


def _takes_kernel(fn, *args) -> bool:
    return "moe_decode_experts" in str(jax.make_jaxpr(fn)(*args))


def _apply(cfg, rules, backend):
    return jax.jit(lambda p, x: L.moe_apply(p, x, cfg=cfg, rules=rules,
                                            backend=backend))


def test_path_follows_the_shapes():
    cfg = _cfg(jnp.float32)
    p = _layer(_stacked(cfg), 0)
    few, many = _tokens(cfg, 3)[None], _tokens(cfg, 4)[None]
    assert _takes_kernel(_apply(cfg, None, "interpret"), p, few)
    # T·k = 32 >= E: every expert may be busy, the capacity path stays
    assert not _takes_kernel(_apply(cfg, None, "interpret"), p, many)
    # the XLA backend keeps the capacity path at any shape
    assert not _takes_kernel(_apply(cfg, None, "xla"), p, few)


def test_tokens_beyond_capacity_keep_the_capacity_path():
    cfg = dataclasses.replace(_cfg(jnp.float32), top_k=1)
    p = _layer(_stacked(cfg), 0)
    x = _tokens(cfg, 9)[None]                   # T·k = 9 < 32, capacity 8
    assert L.moe_capacity(cfg, 9) < 9
    assert not L.moe_reads_chosen(cfg, None, 9, "interpret")
    assert not _takes_kernel(_apply(cfg, None, "interpret"), p, x)
    assert L.moe_reads_chosen(cfg, None, 8, "interpret")


def test_model_axis_keeps_expert_parallel_path():
    cfg = _cfg(jnp.float32)
    mesh = types.SimpleNamespace(size=2, shape={"data": 1, "model": 2})
    assert not L.moe_reads_chosen(cfg, types.SimpleNamespace(mesh=mesh), 1,
                                  "interpret")


def test_model_axis_of_one_takes_the_kernel():
    cfg = _cfg(jnp.float32)
    p = _layer(_stacked(cfg), 0)
    x = _tokens(cfg, 2)[None]
    rules = default_rules(make_mesh((1, 1), ("data", "model")))
    assert _takes_kernel(_apply(cfg, rules, "interpret"), p, x)
    got = _apply(cfg, rules, "interpret")(p, x)
    want = _apply(cfg, rules, "xla")(p, x)      # the EP path, tp = 1
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("batch", [1, 2])
def test_decode_step_interpret_matches_xla(batch):
    """Reduced granite (32 experts top-8): batch 1 takes the kernel in
    every layer, batch 2 (T·k = 16) too; logits and cache agree with the
    XLA step."""
    cfg = dataclasses.replace(CFG, d_model=64, d_expert=32)
    params = init_params(lm.lm_specs(cfg), jax.random.PRNGKey(0))
    cache = init_params(lm.cache_specs(cfg, batch, 32), jax.random.PRNGKey(1))
    tokens = jnp.arange(1, batch + 1, dtype=jnp.int32)
    pos = jnp.full((batch,), 5, jnp.int32)

    def step(backend):
        return jax.jit(lambda p, c, t, q: lm.decode_step(
            cfg, p, c, t, q, backend=backend))

    assert _takes_kernel(step("interpret"), params, cache, tokens, pos)
    assert not _takes_kernel(step("xla"), params, cache, tokens, pos)
    got, got_cache = step("interpret")(params, cache, tokens, pos)
    want, want_cache = step("xla")(params, cache, tokens, pos)
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=1e-4, atol=1e-4)
    for n in ("k", "v"):
        np.testing.assert_allclose(_f32(got_cache[n]), _f32(want_cache[n]),
                                   rtol=1e-4, atol=1e-4)
