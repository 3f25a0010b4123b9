"""Decode attention on the whole K/V stacks (``decode_attn_cache``): the
kernel in interpret mode against ``ref.decode_attention`` on the layer's
slice with the token written in, in both of the orders a device may store
the stacks in; and the serve step and the prefill scan with the in-place
path engaged, against the per-layer slices of the path they replace."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref
from repro.launch.mesh import make_mesh
from repro.launch.serve import prefill_program
from repro.models import layers as L
from repro.models import lm
from repro.models.params import init_params
from repro.parallel import steps

N_LAYERS, S, BK = 2, 512, 128
# (q heads, kv heads, head dim): granite-3.0's GQA 16/8 of 64,
# granite-4.0-h's 32/8 of 128
GQA = [(16, 8, 64), (32, 8, 128)]


def _stacks(Hkv, D, B, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return [jax.random.normal(k, (N_LAYERS, B, S, Hkv, D)).astype(jnp.bfloat16)
            for k in ks]


def _f32(a):
    return np.asarray(a, np.float32)


@pytest.mark.parametrize("minor", [False, True])
@pytest.mark.parametrize("H,Hkv,D", GQA)
@pytest.mark.parametrize("kv_len", [1, BK - 1, BK, BK + 1, S])
def test_kernel_matches_reference_and_writes_one_row(kv_len, H, Hkv, D, minor):
    """Three sequences at different positions, the first attending
    ``kv_len`` rows: the output is the reference's on layer 1's slice with
    the token written, and the stacks are bitwise unchanged outside
    ``(layer, b, pos[b])``."""
    B, layer = 3, 1
    k_stack, v_stack = _stacks(Hkv, D, B)
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (B, H, D)).astype(jnp.bfloat16)
    k_new, v_new = (jax.random.normal(k, (B, Hkv, D)).astype(jnp.bfloat16)
                    for k in keys[1:])
    pos = jnp.asarray([kv_len - 1, S - kv_len, (kv_len - 1) // 2], jnp.int32)
    run = jax.jit(lambda *a: fa.decode_attn_cache(
        *a, minor=minor, bk=BK, interpret=True))
    out, k_got, v_got = run(jnp.asarray([layer], jnp.int32), pos, pos + 1, q,
                            k_new, v_new, k_stack, v_stack)
    b = jnp.arange(B)
    k_want = k_stack.at[layer, b, pos].set(k_new)
    v_want = v_stack.at[layer, b, pos].set(v_new)
    assert np.array_equal(_f32(k_got), _f32(k_want))
    assert np.array_equal(_f32(v_got), _f32(v_want))
    want = jax.vmap(ref.decode_attention)(q, k_want[layer], v_want[layer],
                                          pos + 1)
    # f32 math in both, rounded to bf16 once: a bf16 ulp at most
    np.testing.assert_allclose(_f32(out), _f32(want), rtol=2 ** -7,
                               atol=2 ** -7)


def test_xla_path_is_the_reference():
    Hkv, D, B = 8, 64, 2
    k_stack, v_stack = _stacks(Hkv, D, B, seed=2)
    q = jnp.ones((B, 16, D), jnp.bfloat16)
    new = jnp.full((B, Hkv, D), 0.5, jnp.bfloat16)
    pos = jnp.asarray([3, 200], jnp.int32)
    args = (jnp.asarray([0], jnp.int32), pos, pos + 1, q, new, new, k_stack,
            v_stack)
    got = ops.decode_attn_cache(*args, backend="xla")
    want = ref.decode_attn_cache(*args)
    for g, w in zip(got, want):
        assert np.array_equal(_f32(g), _f32(w))


# ---------------------------------------------------------------------------
# the serve step and the prefill scan
# ---------------------------------------------------------------------------

GRANITE = dataclasses.replace(
    registry.get("granite-moe-1b-a400m", smoke=True), n_experts=32, top_k=8,
    d_model=64, d_expert=32)
HYBRID = dataclasses.replace(
    registry.get("granite-4.0-h-small", smoke=True), n_layers=3,
    layer_types=("mamba", "attention", "mamba"), n_experts=8, top_k=2,
    experts_held=4, expert_lo=2)
CTX, BATCH = 256, 2


def _takes_kernel(fn, *args):
    return "decode_attn_cache" in str(jax.make_jaxpr(fn)(*args))


def _step(cfg, backend):
    """The serve step's raw body on one device."""
    mesh = make_mesh((1, 1), ("data", "model"))
    return steps.make_serve_step(cfg, mesh, backend=backend)[0]


def _serve(raw, params, cache, prompt):
    """A prefill scan over ``prompt`` (T, B), then three decode steps fed
    their own tokens: the logits' argmax tokens and the final cache."""
    prefill = jax.jit(prefill_program(raw))
    pos0 = jnp.zeros((BATCH,), jnp.int32)
    cache, pos = prefill(params, cache, prompt, pos0,
                         jnp.ones((BATCH,), jnp.int32))
    step = jax.jit(raw)
    toks, out = prompt[-1], []
    for _ in range(3):
        toks, cache = step(params, cache, toks, pos)
        out.append(toks)
        pos = pos + 1
    return jnp.stack(out), cache


@pytest.mark.parametrize("cfg", [GRANITE, HYBRID], ids=["granite-moe",
                                                        "granite-hybrid"])
def test_serving_in_place_matches_the_sliced_path(cfg, monkeypatch):
    """Engaged (Pallas interpret) against the xs/ys path on the same
    backend: the same tokens and bitwise the same caches; against the XLA
    backend's path, logits and caches to the decode tests' tolerance."""
    params = init_params(lm.lm_specs(cfg), jax.random.PRNGKey(0))
    cache = init_params(lm.cache_specs(cfg, BATCH, CTX), jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (5, BATCH)), jnp.int32)

    raw = _step(cfg, "interpret")
    tokens = jnp.zeros((BATCH,), jnp.int32)
    pos = jnp.asarray([4, 9], jnp.int32)
    assert _takes_kernel(raw, params, cache, tokens, pos)
    got_tok, got_cache = _serve(raw, params, cache, prompt)

    # the same backend with the path held off: the per-layer slices
    monkeypatch.setattr(L, "decode_attn_in_place", lambda *a: False)
    sliced = _step(cfg, "interpret")
    assert not _takes_kernel(sliced, params, cache, tokens, pos)
    want_tok, want_cache = _serve(sliced, params, cache, prompt)
    assert np.array_equal(np.asarray(got_tok), np.asarray(want_tok))
    for g, w in zip(jax.tree_util.tree_leaves(got_cache),
                    jax.tree_util.tree_leaves(want_cache)):
        assert np.array_equal(_f32(g), _f32(w))

    monkeypatch.undo()
    xla = _step(cfg, "xla")
    assert not _takes_kernel(xla, params, cache, tokens, pos)
    want = jax.jit(lambda p, c, t, q: lm.decode_step(
        cfg, p, c, t, q, backend="xla"))(params, got_cache, got_tok[-1], pos)
    got = jax.jit(lambda p, c, t, q: lm.decode_step(
        cfg, p, c, t, q, backend="interpret"))(params, got_cache, got_tok[-1],
                                               pos)
    np.testing.assert_allclose(_f32(got[0]), _f32(want[0]), rtol=1e-4,
                               atol=1e-4)
    for g, w in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(_f32(g), _f32(w), rtol=1e-4, atol=1e-4)


def test_predicate_keeps_the_sliced_path_on_a_mesh_or_xla():
    cfg = GRANITE
    assert L.decode_attn_in_place(cfg, None, "interpret")
    assert L.decode_attn_in_place(cfg, None, "pallas")
    assert not L.decode_attn_in_place(cfg, None, "xla")
    model_axis = types.SimpleNamespace(
        mesh=types.SimpleNamespace(size=2, shape={"data": 1, "model": 2}))
    assert not L.decode_attn_in_place(cfg, model_axis, "pallas")
