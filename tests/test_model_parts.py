"""Model-component equivalence tests: capacity MoE vs dense-dispatch
oracle, chunked SSD vs sequential scan, head padding exactness, encdec
decode vs teacher forcing, zero1 sharding specs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels import ref
from repro.launch.mesh import make_mesh
from repro.models import layers as L
from repro.models.params import (ParamSpec, default_rules, init_params,
                                 zero1_pspec)

RNG = np.random.default_rng(11)


def rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale).astype(dtype)


def test_moe_capacity_matches_dense_oracle():
    cfg = registry.get("deepseek-moe-16b", smoke=True)  # cf=8: no drops
    p = init_params(L.moe_specs(cfg), jax.random.PRNGKey(0))
    x = rand((2, 16, cfg.d_model))
    got = L.moe_apply(p, x, cfg=cfg, rules=None)
    want = L.moe_apply_dense(p, x, cfg=cfg, rules=None)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-4, atol=1e-4)


def test_moe_capacity_drops_when_tight():
    cfg = dataclasses.replace(registry.get("deepseek-moe-16b", smoke=True),
                              capacity_factor=0.5)
    p = init_params(L.moe_specs(cfg), jax.random.PRNGKey(0))
    x = rand((2, 16, cfg.d_model))
    got = L.moe_apply(p, x, cfg=cfg, rules=None)  # must not crash
    assert np.isfinite(np.asarray(got, np.float32)).all()


def test_moe_shard_map_path_matches_local():
    """The EP shard_map path on a 1x1 mesh equals the local path."""
    cfg = registry.get("granite-moe-1b-a400m", smoke=True)
    p = init_params(L.moe_specs(cfg), jax.random.PRNGKey(1))
    x = rand((2, 8, cfg.d_model))
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = default_rules(mesh)
    got = L.moe_apply(p, x, cfg=cfg, rules=rules)
    want = L.moe_apply(p, x, cfg=cfg, rules=None)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("S,H,P,N,chunk", [(128, 2, 16, 8, 32),
                                           (256, 1, 32, 16, 64)])
def test_ssd_chunked_matches_sequential(S, H, P, N, chunk):
    x = rand((S, H, P), scale=0.5)
    a = -jnp.abs(rand((S, H), scale=0.3)) - 0.05
    b = rand((S, N), scale=0.3)
    c = rand((S, N), scale=0.3)
    got = ref.ssd_scan_chunked(x, a, b, c, chunk=chunk)
    want = ref.ssd_scan(x, a, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_ssd_chunked_grads_finite():
    x = rand((128, 2, 16), scale=0.5)
    a = -jnp.abs(rand((128, 2), scale=0.5)) - 0.05
    b = rand((128, 8), scale=0.3)
    c = rand((128, 8), scale=0.3)

    def loss(x, a, b, c):
        return (ref.ssd_scan_chunked(x, a, b, c, chunk=32) ** 2).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2, 3))(x, a, b, c)
    for g in grads:
        assert np.isfinite(np.asarray(g, np.float32)).all()


def test_head_padding_exactness():
    """Padded execution (tp_pad) must equal unpadded outputs exactly —
    the group-aligned masked padding from DESIGN.md."""
    base = registry.get("yi-34b", smoke=True)       # 4 heads, kv=2, g=2
    base = dataclasses.replace(base, n_heads=6, n_kv=2, d_head=16)  # g=3
    padded = dataclasses.replace(base, tp_pad=4)    # Hp: g 3->4 => 8 heads
    Hp, gp, g = padded.head_padding()
    assert (Hp, gp, g) == (8, 4, 3)

    x = rand((2, 16, base.d_model))
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    p_base = init_params(L.attention_specs(base), jax.random.PRNGKey(2))
    p_pad = init_params(L.attention_specs(padded), jax.random.PRNGKey(3))
    # copy true-head weights into the padded layout (kv-major groups)
    wq = np.array(p_pad["wq"], np.float32)
    wo = np.array(p_pad["wo"], np.float32)
    wqb = np.asarray(p_base["wq"], np.float32).reshape(
        base.d_model, base.n_kv, g, base.d_head)
    wob = np.asarray(p_base["wo"], np.float32).reshape(
        base.n_kv, g, base.d_head, base.d_model)
    wq = wq.reshape(base.d_model, base.n_kv, gp, base.d_head)
    wo = wo.reshape(base.n_kv, gp, base.d_head, base.d_model)
    wq[:, :, :g] = wqb
    wo[:, :g] = wob  # padded slots' wo irrelevant (masked)
    p_pad = dict(p_pad,
                 wq=jnp.asarray(wq.reshape(base.d_model, Hp, base.d_head),
                                p_pad["wq"].dtype),
                 wo=jnp.asarray(wo.reshape(Hp, base.d_head, base.d_model),
                                p_pad["wo"].dtype),
                 wk=p_base["wk"], wv=p_base["wv"])
    got = L.attention_apply(p_pad, x, pos, cfg=padded, backend="xla")
    want = L.attention_apply(p_base, x, pos, cfg=base, backend="xla")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-4, atol=1e-4)


def test_zero1_adds_data_axis():
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = default_rules(mesh)
    spec = ParamSpec((4, 8), jnp.float32, (None, "mlp"))
    ps = zero1_pspec(rules, spec)
    # with data=1 nothing changes; structure is a valid PartitionSpec
    assert len(ps) <= 2


def test_attention_q_chunking_equivalence():
    q = rand((256, 4, 32), scale=0.5)
    k = rand((256, 2, 32), scale=0.5)
    v = rand((256, 2, 32), scale=0.5)
    a1 = ref.attention(q, k, v, causal=True, q_chunk=64)
    a2 = ref.attention(q, k, v, causal=True, q_chunk=256)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2),
                               rtol=1e-5, atol=1e-5)
