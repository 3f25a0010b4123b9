"""Mellum2-12B-A2.5B's model stack at its smoke size on the CPU: one
period of [sliding, sliding, sliding, full] attention at window 16 with
RoPE (YaRN on the full layer), an expert share, seeded random weights
and the Pallas kernels in interpret mode, against the plain reference
(``models/reference.py``); and the server's prefix snapshots."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels import ops
from repro.launch.serve import BatchedServer
from repro.models import layers as L
from repro.models import lm, reference
from repro.models.params import init_params

ARCH = "mellum2-12b-a2.5b"
SMOKE = registry.get(ARCH, smoke=True)
# the granite-4.0-h-small twin of tests/test_granite_hybrid.py: a Mamba2
# run, an attention run and a Mamba2 run, 4 of 8 experts held
HYBRID = dataclasses.replace(
    registry.get("granite-4.0-h-small", smoke=True), n_layers=3,
    layer_types=("mamba", "attention", "mamba"), n_experts=8, top_k=2,
    experts_held=4, expert_lo=2)
# bfloat16-free smoke widths: the program and the reference differ by f32
# rounding and summation order only; 1e-4 of the largest logit holds that
# with room, and a wrong window, frequency or position moves logits by
# far more (test_a_wrong_window_or_rope_fails_the_tolerance)
TOL = 1e-4


def _params(cfg, seed=0):
    return init_params(lm.lm_specs(cfg), jax.random.PRNGKey(seed))


def _tokens(cfg, B, S, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)


def _f32(a):
    return np.asarray(a, np.float32)


@pytest.fixture
def interpret(monkeypatch):
    resolve = ops.resolve
    monkeypatch.setattr(
        ops, "resolve", lambda b="auto": "interpret" if b == "auto" else resolve(b))


def test_smoke_twin_keeps_the_period_the_window_and_the_share():
    cfg = registry.get(ARCH)
    assert cfg.layer_runs()[:2] == [("sliding_attention", 0, 3),
                                    ("attention", 3, 1)]
    assert len(cfg.layer_runs()) == 14
    assert SMOKE.layer_runs() == [("sliding_attention", 0, 3),
                                  ("attention", 3, 1)]
    assert SMOKE.window == 16 and SMOKE.yarn == cfg.yarn
    assert SMOKE.n_experts // SMOKE.experts_held == \
        cfg.n_experts // cfg.experts_held == 4
    ring, full = lm.cache_specs(SMOKE, 2, 64)
    assert ring["k"].shape == (3, 2, 16, SMOKE.n_kv, SMOKE.d_head)
    assert full["k"].shape == (1, 2, 64, SMOKE.n_kv, SMOKE.d_head)
    ring, full = lm.cache_specs(cfg, 1, 16384)[:2]
    assert ring["v"].shape == (3, 1, 1024, 4, 128)
    assert full["v"].shape == (1, 1, 16384, 4, 128)


def test_param_counts_of_the_published_model():
    """28 layers, 64 experts of 896 top-8, untied 98,304-row head: 12.15B
    parameters (12B), 2.21B multiplied a token (A2.5B counts the input
    embedding too: 2.44B)."""
    full = dataclasses.replace(registry.get(ARCH), experts_held=0)
    assert abs(full.param_count() - 12.15e9) < 0.01e9
    assert abs(full.active_param_count() - 2.21e9) < 0.01e9


@pytest.mark.parametrize("dim", [16, 128])
def test_yarn_frequencies_match_the_formula(dim):
    """The program's YaRN frequencies and scale against transformers'
    ``_compute_yarn_parameters`` as the reference writes it, and the
    closed form at the ends: the fast dimensions keep base^(-2i/d), the
    slow ones are that ÷ 16, and cos and sin are scaled by
    0.1·ln(16) + 1."""
    y = registry.get(ARCH).yarn
    inv, scale = L.yarn_frequencies(dim, 500000.0, y)
    want, want_scale = reference.yarn_inv_freq(
        dim, 500000.0, y.factor, y.original_max, y.beta_fast, y.beta_slow,
        y.attention_factor)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert scale == pytest.approx(want_scale) == pytest.approx(
        0.1 * math.log(16) + 1)
    plain = 500000.0 ** -(np.arange(0, dim, 2) / dim)
    low = math.floor(dim * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(500000.0)))
    high = math.ceil(dim * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(500000.0)))
    np.testing.assert_allclose(inv[:low + 1], plain[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(inv[high:], plain[high:] / 16, rtol=1e-6)
    assert np.all(np.diff(inv) < 0)
    # a rotation by 0 is the scale times the identity
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 2, dim))
    got = L.rope(x, jnp.zeros((1, 1), jnp.int32), base=500000.0, yarn=y)
    np.testing.assert_allclose(_f32(got), _f32(x) * scale, rtol=1e-6)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_forward_matches_reference(backend):
    """40 positions, more than twice the window: prefill's attention
    bounds the sliding layers' keys and not the full layer's."""
    params = _params(SMOKE)
    tokens = _tokens(SMOKE, 2, 40)
    _, logits = lm.forward(SMOKE, params, {"tokens": tokens, "labels": tokens},
                           backend=backend)
    want = _f32(reference.forward(SMOKE, params, tokens))
    np.testing.assert_allclose(_f32(logits), want, rtol=0,
                               atol=TOL * np.abs(want).max())


@pytest.mark.parametrize("change", [
    dict(window=17), dict(window=15), dict(yarn=None), dict(rope_theta=1e4),
    dict(layer_types=("attention",) * 4)])
def test_a_wrong_window_or_rope_fails_the_tolerance(change):
    """What the tolerance must catch: the reference with a window one
    longer or shorter, RoPE without YaRN or of another base, or every
    layer full, lies far outside it."""
    params = _params(SMOKE, seed=4)
    tokens = _tokens(SMOKE, 1, 40, seed=4)
    want = _f32(reference.forward(SMOKE, params, tokens))
    wrong = _f32(reference.forward(dataclasses.replace(SMOKE, **change),
                                   params, tokens))
    assert np.abs(wrong - want).max() > 20 * TOL * np.abs(want).max()


def test_served_requests_match_the_reference(monkeypatch, interpret):
    """BatchedServer: prefill 21 tokens, past the window, then decode 12
    through the rings (``decode_attn_ring``) and the full layer's stack
    (``decode_attn_cache``); two requests, one after the other on one
    slot.  Each served token is the reference's best at its position, and
    the logits of ``decode_step``, stepped over the same context, are the
    reference's full forward at every position."""
    monkeypatch.setitem(registry.ARCHS, "mellum2-test", SMOKE)
    params = _params(SMOKE, seed=1)
    server = BatchedServer("mellum2-test", batch=1, ctx=48, params=params)
    rng = np.random.default_rng(5)
    step = jax.jit(lambda c, t, p: lm.decode_step(SMOKE, params, c, t, p))
    text = str(jax.make_jaxpr(step)(
        init_params(lm.cache_specs(SMOKE, 1, 48), jax.random.PRNGKey(2)),
        jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32)))
    assert "decode_attn_ring" in text and "decode_attn_cache" in text
    for length in (21, 9):
        prompt = rng.integers(0, SMOKE.vocab, length).tolist()
        server.prefill_prompt(0, prompt)
        served = server.decode(12)[0]
        context = prompt + prompt[-1:] + served[:-1]
        want = _f32(reference.forward(SMOKE, params,
                                      jnp.asarray([context], jnp.int32))[0])
        scale = np.abs(want).max()
        for i, tok in enumerate(served):
            row = want[length + i]
            assert row[tok] >= row.max() - TOL * scale, (length, i)
        cache = init_params(lm.cache_specs(SMOKE, 1, 48), jax.random.PRNGKey(2))
        got = []
        for pos, tok in enumerate(context):
            logits, cache = step(cache, jnp.asarray([tok], jnp.int32),
                                 jnp.asarray([pos], jnp.int32))
            got.append(_f32(logits)[0, :want.shape[1]])
        np.testing.assert_allclose(np.stack(got), want, rtol=0,
                                   atol=TOL * scale)


def _serve(arch, params, script):
    server = BatchedServer(arch, batch=1, ctx=64, params=params)
    out = script(server)
    return out, server.pos.copy(), server


@pytest.mark.parametrize("arch", ["mellum2-test", "granite-hybrid-test"])
def test_resume_then_prefill_is_a_fresh_prefill(monkeypatch, interpret,
                                                 arch):
    """Prefill a 23-token prefix, snapshot, decode 14 tokens (past the
    window: every ring row and the Mamba2 state are overwritten), resume,
    prefill a prompt and decode: the same tokens, position and cache as
    prefilling prefix + prompt from position 0 and decoding.  The rings
    and the Mamba2 state and conv tail come back from the snapshot; the
    full K/V by position alone.  Without the resume's copy the tokens
    differ."""
    cfg = SMOKE if arch == "mellum2-test" else HYBRID
    monkeypatch.setitem(registry.ARCHS, arch, cfg)
    params = _params(cfg, seed=2)
    rng = np.random.default_rng(6)
    prefix = rng.integers(0, cfg.vocab, 23).tolist()
    prompt = rng.integers(0, cfg.vocab, 5).tolist()

    def resumed(server, copy=True):
        server.prefill_prompt(0, prefix)
        snap = server.snapshot(0)
        server.decode(14)
        if not copy:
            snap = dict(snap, rows=[None] * len(snap["rows"]))
        server.resume(0, snap)
        server.prefill_prompt(0, prompt)
        return server.decode(10)[0]

    def fresh(server):
        server.prefill_prompt(0, prefix + prompt)
        return server.decode(10)[0]

    got, pos, a = _serve(arch, params, resumed)
    want, want_pos, b = _serve(arch, params, fresh)
    assert got == want and list(pos) == list(want_pos) == [23 + 5 + 10]
    kept = lm.rewritten(cfg, a.cache)
    assert any(kept) and not all(kept)
    for k, x, y in zip(kept, jax.tree_util.tree_leaves(a.cache),
                       jax.tree_util.tree_leaves(b.cache)):
        if not k:          # a full stack: rows past the position are stale
            x, y = x[:, :, :pos[0]], y[:, :, :pos[0]]
        np.testing.assert_allclose(_f32(x), _f32(y), rtol=1e-5, atol=1e-6)
    stale, _, _ = _serve(arch, params, lambda s: resumed(s, copy=False))
    assert stale != want


@pytest.mark.parametrize("path", ["capacity", "chosen"])
def test_four_shares_add_up_to_the_uncut_layer(path):
    """64 experts, top-8, in four shares of 16 as the deployment splits
    them: the parts the four chips compute add up to the uncut reference
    MoE layer (no shared expert)."""
    cfg = dataclasses.replace(SMOKE, n_experts=64, top_k=8, experts_held=0)
    p = init_params(L.moe_specs(cfg), jax.random.PRNGKey(4))
    T = 2 if path == "chosen" else 16        # chosen: T·k = 16 < 64 experts
    x = jax.random.normal(jax.random.PRNGKey(5), (1, T, cfg.d_model))
    backend = "interpret" if path == "chosen" else "xla"
    parts = 0
    for lo in range(0, 64, 16):
        share = dataclasses.replace(cfg, experts_held=16, expert_lo=lo)
        q = dict(p, **{n: p[n][lo:lo + 16] for n in L.EXPERT_STACKS})
        fn = jax.jit(lambda q, x, c=share: L.moe_apply(q, x, cfg=c,
                                                       backend=backend))
        takes = "moe_decode_experts" in str(jax.make_jaxpr(fn)(q, x))
        assert takes == (path == "chosen")
        parts = parts + _f32(fn(q, x))
    with jax.default_matmul_precision("highest"):
        whole = _f32(reference._moe(cfg, p, x))
    np.testing.assert_allclose(parts, whole, rtol=0,
                               atol=1e-5 * np.abs(whole).max())
