"""Each traffic loop end to end at a tiny size on the CPU, through the
same ``measure`` a chip run takes, with the program's Pallas kernels in
interpret mode; and the faults and the control that must turn ``correct``
false."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as bench_run

TINY_GRANITE = dict(
    system_arch="granite-moe-1b-a400m-smoke",
    hidden_size=64,
    intermediate_size=32,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    num_local_experts=4,
    num_experts_per_tok=2,
    vocab_size=512,
    max_position_embeddings=128,
    # random logits at this width lie within about 0.3 of each other, so the
    # published model's limit of 1.0 would pass any served token
    check_max_logit_gap=0.05,
)


@pytest.fixture
def cpu_run(monkeypatch, tmp_path):
    """measure() on the CPU devices, with the compile cache in tmp_path and
    JAX's global cache settings put back afterwards."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    monkeypatch.setenv("COX_AUTOTUNE_CACHE", "off")
    monkeypatch.setattr(bench_run, "TRACE_DIR", str(tmp_path / "trace"))
    saved = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )

    def run(cell, cfg=None, traffic=None, seconds=0.5, seed=3000000001):
        spec = bench_run.load_cell(cell)
        spec["cfg"].update(cfg or {})
        spec["traffic"].update(traffic or {})
        args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
        return bench_run.measure(spec, args, jax.devices())

    yield run
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])


@pytest.fixture
def interpret(monkeypatch):
    from repro.kernels import ops

    resolve = ops.resolve
    monkeypatch.setattr(
        ops, "resolve", lambda b="auto": "interpret" if b == "auto" else resolve(b)
    )


SMALL_COX = {
    "warpPrefixStats_n": 4096,
    "histogram64_n": 4096,
    "gridReduce_n": 4096,
    "vectorAdd_n": 5000,
}


@pytest.mark.parametrize(
    "cell,metrics",
    [
        ("cox-sdk.warp-2e20", {"cox_ms_per_launch", "setup_s"}),
        ("cox-sdk.chain-vadd", {"cox_ms_per_launch.chain", "setup_s"}),
    ],
)
def test_cox_loop(cpu_run, cell, metrics):
    res = cpu_run(cell, SMALL_COX)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == metrics
    assert all(c["value"] == 0 and c["limit"] == 0 for c in res["checks"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("ahead", [1, 4])
def test_cox_loop_counts_every_unit_sent(cpu_run, ahead):
    """However many units are in flight, the window closes on a whole
    round and counts every unit it sent, each waited for once."""
    res = cpu_run("cox-sdk.warp-2e20", SMALL_COX, {"ahead": ahead}, seconds=0.2)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] >= 3 and res["attempted"] % 3 == 0


def test_cox_answer_altered_where_produced(cpu_run, monkeypatch):
    from repro.core import streams

    result = streams.LaunchHandle.result

    def altered(self):
        return {k: v.at[0].add(1) for k, v in result(self).items()}

    monkeypatch.setattr(streams.LaunchHandle, "result", altered)
    res = cpu_run("cox-sdk.warp-2e20", SMALL_COX)
    assert not res["correct"]


SERVE_TRAFFIC = {"prompt_lens": [4, 8], "output_tokens": 8}


def test_serve_loop(cpu_run, interpret):
    res = cpu_run("granite-moe-1b-a400m.decode-b1", TINY_GRANITE, SERVE_TRAFFIC)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"serve_tok_s", "itl_p95_ms", "setup_s"}
    assert res["checks"]["max_logit_gap"]["value"] < 1e-3


def test_serve_token_altered_where_produced(cpu_run, monkeypatch):
    from repro.launch import serve

    step = serve.BatchedServer._step_all
    calls = []

    def altered(self):
        nxt = step(self)
        calls.append(1)
        return (nxt + 97) % self.cfg.vocab if len(calls) % 5 == 0 else nxt

    monkeypatch.setattr(serve.BatchedServer, "_step_all", altered)
    res = cpu_run("granite-moe-1b-a400m.decode-b1", TINY_GRANITE, SERVE_TRAFFIC)
    assert not res["correct"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_serve_step_returns_its_cache_unchanged(cpu_run, monkeypatch):
    from repro.launch import serve

    step = serve.BatchedServer._step_all

    def stale(self):
        old = jax.tree_util.tree_map(jnp.copy, self.cache)
        nxt = step(self)
        self.cache = old
        return nxt

    monkeypatch.setattr(serve.BatchedServer, "_step_all", stale)
    res = cpu_run("granite-moe-1b-a400m.decode-b1", TINY_GRANITE, SERVE_TRAFFIC)
    assert not res["correct"]


def test_float8_control_reads_above_the_program(cpu_run):
    """The control of bench/control.py at a tiny size: the float8
    forward's keys and values lie further from the reference's than the
    program's cache does."""
    from bench import control
    from bench.drivers.serve_loop import Driver
    from bench.spans import Spans

    spec = bench_run.load_cell("granite-moe-1b-a400m.decode-b1")
    spec["cfg"].update(TINY_GRANITE)
    spec["traffic"].update(SERVE_TRAFFIC)
    d = Driver(spec["cfg"], spec["traffic"], 3000000001, jax.devices(), Spans())
    d.setup()
    d.run(0.3)
    r = control.serve_readings(d)
    assert r["control"]["cache_rel_err"] > 3 * r["program"]["cache_rel_err"], r
    assert r["control"]["max_logit_gap"] >= r["program"]["max_logit_gap"], r


def test_bfloat16_control_fails_every_cox_program():
    import ml_dtypes

    from bench.programs.sdk import PROGRAMS

    rng = np.random.default_rng(5)
    n = 1 << 20
    ints = {
        "warpPrefixStats": (-6, 7, np.float32),
        "histogram64": (0, 64, np.int32),
        "gridReduce": (-8, 9, np.float32),
    }
    for name, p in PROGRAMS.items():
        if name == "vectorAdd":
            d = {k: rng.normal(size=n).astype(np.float32) for k in "ab"}
        else:
            lo, hi, dt = ints[name]
            d = {"x": rng.integers(lo, hi, size=n).astype(dt)}
        want = p.reference(d, n, 32)
        low = p.reference(d, n, 32, ml_dtypes.bfloat16)
        assert any(np.count_nonzero(low[k] != want[k]) for k in want), name
