"""granite-4.0-h-small's model stack at a smoke size on the CPU: one whole
period of a Mamba2/attention pattern (Mamba2, attention, Mamba2), an
expert share, seeded random weights and the Pallas kernels in interpret
mode, against the plain reference (``models/reference.py``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import pattern_period
from repro.kernels import moe_decode, ops, ref, ssd_decode
from repro.launch.serve import BatchedServer
from repro.models import layers as L
from repro.models import lm, reference
from repro.models.params import init_params

# 8 experts, top-2, experts 2..5 held here; a whole pattern period
CFG = dataclasses.replace(
    registry.get("granite-4.0-h-small", smoke=True), n_layers=3,
    layer_types=("mamba", "attention", "mamba"), n_experts=8, top_k=2,
    experts_held=4, expert_lo=2)


def _params(cfg, seed=0):
    """Seeded weights, with the Mamba2 scalars and the conv bias drawn too
    (their inits are constants)."""
    params = init_params(lm.lm_specs(cfg), jax.random.PRNGKey(seed))
    draws = {"conv_b": (-0.3, 0.3), "A_log": (-1.0, 1.5),
             "dt_bias": (-3.0, 0.0), "D": (-1.0, 1.0)}

    def draw(path, a):
        name = path[-1].key
        if name not in draws:
            return a
        k = jax.random.fold_in(jax.random.PRNGKey(seed + 7),
                               len(jax.tree_util.keystr(path)) + a.size)
        lo, hi = draws[name]
        return jax.random.uniform(k, a.shape, minval=lo, maxval=hi).astype(a.dtype)

    return jax.tree_util.tree_map_with_path(draw, params)


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


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_forward_matches_reference(backend):
    params = _params(CFG)
    tokens = _tokens(CFG, 2, 16)
    _, logits = lm.forward(CFG, params, {"tokens": tokens, "labels": tokens},
                           backend=backend)
    want = reference.forward(CFG, params, tokens)
    scale = np.abs(_f32(want)).max()
    np.testing.assert_allclose(_f32(logits), _f32(want), rtol=0,
                               atol=1e-4 * scale)


def test_folded_multipliers_give_the_published_logits():
    """The program has no scalar multipliers: folded into the weights (the
    embedding times m_emb; wq times m_attn·sqrt(head_dim); every residual
    branch's output projection times m_res), it computes the published
    logits times m_emb·m_logits."""
    mult = {"embedding": 12.0, "attention": 1 / 128, "residual": 0.22,
            "logits": 16.0}
    published = _params(CFG, seed=3)
    folded = jax.tree_util.tree_map(lambda a: a, published)
    folded["embed"]["tok"] = published["embed"]["tok"] * mult["embedding"]
    for run in folded["layers"]:
        if "attn" in run:
            run["attn"]["wq"] = run["attn"]["wq"] * (
                mult["attention"] * CFG.d_head ** 0.5)
            run["attn"]["wo"] = run["attn"]["wo"] * mult["residual"]
        else:
            run["mamba"]["w_out"] = run["mamba"]["w_out"] * mult["residual"]
        for n in ("w_down", "s_down"):
            run["moe"][n] = run["moe"][n] * mult["residual"]
    tokens = _tokens(CFG, 2, 12, seed=3)
    _, logits = lm.forward(CFG, folded, {"tokens": tokens, "labels": tokens},
                           backend="xla")
    want = reference.forward(CFG, published, tokens, mult)
    got = _f32(logits) / (mult["embedding"] * mult["logits"])
    np.testing.assert_allclose(got, _f32(want), rtol=0,
                               atol=1e-4 * np.abs(_f32(want)).max())


def test_served_requests_match_the_reference(monkeypatch, interpret):
    """BatchedServer: prefill, then decode, two requests one after the
    other on one slot (the second starts from the zero recurrent state).
    Each served token is the reference's best at its position, and the
    logits of ``decode_step``, stepped over the same context, are the
    reference's full forward at every position."""
    monkeypatch.setitem(registry.ARCHS, "granite-hybrid-test", CFG)
    params = _params(CFG, seed=1)
    server = BatchedServer("granite-hybrid-test", batch=1, ctx=32,
                           params=params)
    rng = np.random.default_rng(5)
    step = jax.jit(lambda c, t, p: lm.decode_step(CFG, params, c, t, p))
    for length in (6, 9):
        prompt = rng.integers(0, CFG.vocab, length).tolist()
        server.prefill_prompt(0, prompt)
        served = server.decode(8)[0]
        context = prompt + prompt[-1:] + served[:-1]
        want = _f32(reference.forward(CFG, params,
                                      jnp.asarray([context], jnp.int32))[0])
        scale = np.abs(want).max()
        for i, tok in enumerate(served):
            row = want[length + i]
            assert row[tok] >= row.max() - 1e-4 * scale, (length, i)
        cache = init_params(lm.cache_specs(CFG, 1, 32), jax.random.PRNGKey(2))
        got = []
        for pos, tok in enumerate(context):
            logits, cache = step(cache, jnp.asarray([tok], jnp.int32),
                                 jnp.asarray([pos], jnp.int32))
            got.append(_f32(logits)[0, :want.shape[1]])
        np.testing.assert_allclose(np.stack(got), want, rtol=0,
                                   atol=1e-4 * scale)


@pytest.mark.parametrize("path", ["capacity", "chosen"])
def test_expert_shares_add_up_to_the_uncut_layer(path):
    """8 experts in 4 shares of 2: the parts of the layer's output that the
    shares compute, with the shared expert counted once, add up to the
    uncut layer's output."""
    cfg = dataclasses.replace(CFG, experts_held=0, expert_lo=0)
    p = init_params(L.moe_specs(cfg), jax.random.PRNGKey(4))
    T = 2 if path == "chosen" else 16        # chosen: T·k = 4 < 8 experts
    x = jax.random.normal(jax.random.PRNGKey(5), (1, T, cfg.d_model))
    backend = "interpret" if path == "chosen" else "xla"
    xt = x.reshape(T, -1)
    shared = (jax.nn.silu(xt @ p["s_gate"]) * (xt @ p["s_up"])) @ p["s_down"]

    def apply(c, q):
        fn = jax.jit(lambda q, x: L.moe_apply(q, x, cfg=c, backend=backend))
        takes = "moe_decode_experts" in str(jax.make_jaxpr(fn)(q, x))
        assert takes == (path == "chosen")
        return fn(q, x).reshape(T, -1)

    whole = apply(cfg, p)
    parts = shared
    for lo in range(0, 8, 2):
        share = dataclasses.replace(cfg, experts_held=2, expert_lo=lo)
        q = dict(p, **{n: p[n][lo:lo + 2] for n in L.EXPERT_STACKS})
        parts = parts + apply(share, q) - shared
    np.testing.assert_allclose(_f32(parts), _f32(whole), rtol=0,
                               atol=1e-5 * np.abs(_f32(whole)).max())


def test_choices_held_elsewhere_add_nothing_and_fetch_nothing():
    """The decode MoE kernel on a share: a choice of an expert held
    elsewhere adds nothing, and its grid steps repeat the block index of
    the step before, so no slab is fetched for it."""
    T, k, d, f, held = 2, 4, 64, 256, 3
    keys = jax.random.split(jax.random.PRNGKey(6), 5)
    stacks = [jax.random.normal(keys[0], (2, held, d, f)) * 0.1,
              jax.random.normal(keys[1], (2, held, d, f)) * 0.1,
              jax.random.normal(keys[2], (2, held, f, d)) * 0.1]
    x = jax.random.normal(keys[3], (T, d))
    w = jax.nn.softmax(jax.random.normal(keys[4], (T, k)), -1).reshape(-1)
    chosen = jnp.asarray([5, 0, 2, 7, 1, 6, 3, 4], jnp.int32)  # of E = 8
    lo = 1                                     # experts 1..3 held here
    ids = jnp.clip(chosen - lo, 0, held - 1)
    mine = (chosen >= lo) & (chosen < lo + held)
    layer = jnp.asarray([1], jnp.int32)
    got = moe_decode.moe_decode_experts(layer, ids, x, w, *stacks, mine,
                                        interpret=True)
    want = ref.moe_decode_experts(layer, ids, x, w, *stacks, mine)
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=1e-5, atol=1e-5)
    only = ref.moe_decode_experts(layer, ids, x, jnp.where(mine, w, 0.0),
                                  *stacks)
    np.testing.assert_allclose(_f32(got), _f32(only), rtol=1e-5, atol=1e-5)
    # the block index of every grid step, (choice, slice) in grid order
    slices = 3
    fetch, keep = (np.asarray(a) for a in
                   moe_decode._held_fetches(ids, mine, slices))
    steps = [(j, (int(fetch[j]), c if keep[j] < 0 else int(keep[j])))
             for j in range(len(ids)) for c in range(slices)]
    fetched = [block for n, (_, block) in enumerate(steps)
               if n == 0 or block != steps[n - 1][1]]
    held_blocks = [(int(ids[j]), c) for j in np.flatnonzero(np.asarray(mine))
                   for c in range(slices)]
    assert sorted(fetched) == sorted(held_blocks)


def _ssd_inputs(L_, B, H, P, N, seed=7):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(h=jax.random.normal(k[0], (L_, B, H, P, N)),
                x=jax.random.normal(k[1], (B, H, P)),
                dt=jax.nn.softplus(jax.random.normal(k[2], (B, H))),
                a=-jnp.exp(jax.random.normal(k[3], (H,))),
                b=jax.random.normal(k[4], (B, N)),
                c=jax.random.normal(k[5], (B, N)),
                d=jax.random.normal(k[6], (H,)))


def test_ssd_decode_matches_its_reference():
    i = _ssd_inputs(3, 2, 8, 16, 32)
    args = (i["h"], jnp.asarray([1], jnp.int32), jnp.asarray([False, True]),
            i["x"], i["dt"], i["a"], i["b"], i["c"], i["d"])
    y, h = ssd_decode.ssd_decode(*args, interpret=True)
    y_ref, h_ref = ref.ssd_decode(*args)
    np.testing.assert_allclose(_f32(y), _f32(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(_f32(h), _f32(h_ref), rtol=1e-5, atol=1e-5)
    # layers other than the one updated are left as they were
    np.testing.assert_array_equal(_f32(h[0]), _f32(i["h"][0]))
    np.testing.assert_array_equal(_f32(h[2]), _f32(i["h"][2]))


def test_ssd_decode_steps_are_the_sequential_recurrence():
    """Token by token from the zero state, the kernel gives the SSD
    recurrence (``ref.ssd_scan`` with x·dt and a·dt) plus D·x."""
    S, H, P, N = 6, 4, 8, 16
    k = jax.random.split(jax.random.PRNGKey(8), 6)
    x = jax.random.normal(k[0], (S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (S, H)))
    a = -jnp.exp(jax.random.normal(k[2], (H,)))
    b, c = jax.random.normal(k[3], (S, N)), jax.random.normal(k[4], (S, N))
    d = jax.random.normal(k[5], (H,))
    want = ref.ssd_scan(x * dt[..., None], a * dt, b, c) + x * d[:, None]
    h = jnp.zeros((1, 1, H, P, N))
    for t in range(S):
        y, h = ssd_decode.ssd_decode(
            h, jnp.zeros((1,), jnp.int32), jnp.asarray([t == 0]), x[t][None],
            dt[t][None], a, b[t][None], c[t][None], d, interpret=True)
        np.testing.assert_allclose(_f32(y[0]), _f32(want[t]), rtol=1e-4,
                                   atol=1e-4)


def test_nope_attention_matches_the_reference():
    """No position embedding: attention is the reference's at any offset
    of the positions, and one-token decode over the cache gives the last
    row of the full pass."""
    cfg = dataclasses.replace(CFG, n_layers=1, layer_types=("attention",))
    p = init_params(L.attention_specs(cfg), jax.random.PRNGKey(9))
    S = 10
    x = jax.random.normal(jax.random.PRNGKey(10), (1, S, cfg.d_model))
    pos = jnp.arange(S)[None]
    got = L.attention_apply(p, x, pos, cfg=cfg, backend="xla")
    shifted = L.attention_apply(p, x, pos + 37, cfg=cfg, backend="xla")
    with jax.default_matmul_precision("highest"):
        want = reference._attention(cfg, p, x, cfg.d_head ** -0.5)
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(_f32(shifted), _f32(got), rtol=1e-6, atol=1e-6)
    kv = jnp.zeros((1, S, cfg.n_kv, cfg.d_head))
    cache = {"k": kv, "v": kv}
    for t in range(S):
        y, cache = L.attention_decode(p, x[:, t], cache,
                                      jnp.asarray([t], jnp.int32), cfg=cfg,
                                      backend="interpret")
    np.testing.assert_allclose(_f32(y[0]), _f32(want[0, -1]), rtol=1e-5,
                               atol=1e-5)


def test_param_counts_of_the_published_model():
    """40 layers, all 72 experts: 32B parameters, 9B active (32B-A9B);
    granite-moe-1b-a400m's counts are unchanged."""
    full = dataclasses.replace(registry.get("granite-4.0-h-small"),
                               n_layers=40, experts_held=0)
    assert abs(full.param_count() - 32.21e9) < 0.05e9
    assert abs(full.active_param_count() - 8.80e9) < 0.05e9
    granite = registry.get("granite-moe-1b-a400m")
    assert (granite.param_count(), granite.active_param_count()) == \
        (1334628352, 428658688)


def test_smoke_twin_keeps_the_pattern_and_the_share():
    cfg = registry.get("granite-4.0-h-small")
    smoke = registry.get("granite-4.0-h-small", smoke=True)
    assert pattern_period(cfg.layer_types) == 10 == smoke.n_layers
    assert smoke.layer_runs() == cfg.layer_runs() == [
        ("mamba", 0, 5), ("attention", 5, 1), ("mamba", 6, 4)]
    assert smoke.n_experts // smoke.experts_held == \
        cfg.n_experts // cfg.experts_held == 4
    assert registry.get("granite-moe-1b-a400m", smoke=True).n_layers == 2


def test_a_stack_of_one_run_keeps_its_layout():
    """granite-moe-1b-a400m is one run of 24 attention layers: its
    parameters and cache keep the layout of a plain stack (the served
    weights and the benchmark's reference are built in it); a pattern
    of runs makes lists, one entry a run."""
    granite = registry.get("granite-moe-1b-a400m")
    assert granite.layer_runs() == [("attention", 0, 24)]
    layers = lm.lm_specs(granite)["layers"]
    assert set(layers) == {"ln1", "attn", "ln2", "moe"}
    assert layers["moe"]["w_gate"].shape == (24, 32, 1024, 512)
    assert set(lm.cache_specs(granite, 1, 64)) == {"k", "v"}
    runs = lm.cache_specs(CFG, 1, 64)
    assert [set(r) for r in runs] == [{"h", "conv"}, {"k", "v"}, {"h", "conv"}]
    assert runs[0]["h"].shape == (1, 1, CFG.ssm_heads, CFG.ssm_head_dim,
                                  CFG.ssm_state)


@pytest.mark.parametrize("arch,ctx", [("granite-hybrid-test", 16),
                                      ("mamba2-130m-smoke", 12)])
def test_decode_ahead_computes_the_steps_one_by_one(monkeypatch, arch, ctx):
    """Without ``eos`` the decode loop dispatches each step before the one
    before it reaches the host; with an ``eos`` it never emits, it steps
    one by one.  Two slots that stop at the context's end at different
    steps: the same tokens, positions, cache and recurrent state, bit for
    bit, and as many step calls."""
    monkeypatch.setitem(registry.ARCHS, "granite-hybrid-test", CFG)
    params = _params(CFG, seed=2) if arch == "granite-hybrid-test" else None

    def serve(eos):
        server = BatchedServer(arch, batch=2, ctx=ctx, seed=4, params=params)
        calls = []
        call = server._call
        monkeypatch.setattr(server, "_call",
                            lambda t: calls.append(1) or call(t))
        server.prefill_prompt(0, [5, 9, 2, 7])
        server.prefill_prompt(1, [11, 3])
        out = server.decode(20, eos=eos)
        leaves = [np.asarray(a) for a in jax.tree_util.tree_leaves(server.cache)]
        return out, server.pos.copy(), leaves, len(calls)

    ahead, stepped = serve(None), serve(-1)
    assert ahead[0] == stepped[0] and all(ahead[0])
    assert len(ahead[0][0]) != len(ahead[0][1])
    np.testing.assert_array_equal(ahead[1], stepped[1])
    for a, b in zip(ahead[2], stepped[2]):
        np.testing.assert_array_equal(a, b)
    assert ahead[3] == stepped[3]
