"""Mellum2-12B-A2.5B's cell at its smoke size on the CPU, through the same
``measure`` a chip run takes, with the program's Pallas kernels in
interpret mode; the faults and the control that must turn ``correct``
false; and the readers of its three new metrics on hand-made planes."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import run as bench_run
from bench import work, work_mellum2
from bench.models import granite_hybrid
from bench.tests.test_bench_loops import cpu_run, interpret  # noqa: F401
from bench.tests.test_bench_readers import make_run, read, summary

CELL = "mellum2-12b-a2.5b.prefix8k-decode-b1"
# the smoke twin: one period [sliding, sliding, sliding, full] at width 64,
# window 16, 1 of 4 experts held
TINY = dict(
    system_arch="mellum2-12b-a2.5b-smoke",
    hidden_size=64,
    num_attention_heads=4,
    num_key_value_heads=2,
    head_dim=16,
    moe_intermediate_size=32,
    num_router_experts=4,
    num_experts=1,
    num_experts_per_tok=2,
    num_hidden_layers=4,
    sliding_window=16,
    vocab_size=512,
    max_position_embeddings=64,
    # random logits at this width lie within about 0.3 of each other
    check_max_logit_gap=0.05,
)
# a 24-token shared context: past the window before any prompt
TRAFFIC = {"prefix_tokens": 24, "prompt_lens": [4, 8], "output_tokens": 8}


def test_prefix_serve_loop(cpu_run, interpret):  # noqa: F811
    res = cpu_run(CELL, TINY, TRAFFIC)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"serve_tok_s", "itl_p95_ms", "setup_s"}
    checks = res["checks"]
    assert checks["max_logit_gap"]["value"] < 1e-3
    for name in ("cache_rel_err", "ring_rel_err"):
        assert 0 < checks[name]["value"] < checks[name]["limit"] / 2


def _fails(res, *names):
    assert not res["correct"]
    assert any(res["checks"][n]["value"] > res["checks"][n]["limit"] for n in names), \
        res["checks"]


def test_a_skipped_ring_restore_fails(cpu_run, monkeypatch):  # noqa: F811
    """Resuming the shared context by position alone, without copying the
    rings back: the sliding layers read the last request's rows."""
    from repro.launch import serve

    def resume(self, slot, snap):
        self.pos[slot], self.tokens[slot] = snap["pos"], snap["token"]
        self.resumed[slot] = True
        return self

    monkeypatch.setattr(serve.BatchedServer, "resume", resume)
    _fails(cpu_run(CELL, TINY, TRAFFIC), "ring_rel_err")


def test_a_window_of_one_key_more_fails(cpu_run, monkeypatch):  # noqa: F811
    """Rings of W + 1 rows: every sliding layer attends W + 1 keys."""
    from repro.models import lm

    specs = lm.cache_specs

    def wider(cfg, batch, seq_len):
        return specs(dataclasses.replace(cfg, window=cfg.window + 1), batch, seq_len)

    monkeypatch.setattr(lm, "cache_specs", wider)
    _fails(cpu_run(CELL, TINY, TRAFFIC), "ring_rel_err", "max_logit_gap")


def test_float8_keys_and_values_fail(cpu_run, monkeypatch):  # noqa: F811
    """Every K/V leaf of the cache rounded to float8 (e4m3, per-tensor
    scale) after each decode step, as a float8 cache would store it."""
    from repro.launch import serve

    step = serve.BatchedServer._step_all

    def low(self):
        nxt = step(self)
        self.cache = jax.tree_util.tree_map(
            lambda a: granite_hybrid._quantize(a.astype(jnp.float32)).astype(a.dtype),
            self.cache)
        return nxt

    monkeypatch.setattr(serve.BatchedServer, "_step_all", low)
    _fails(cpu_run(CELL, TINY, TRAFFIC), "cache_rel_err", "ring_rel_err")


def test_a_token_altered_where_produced_fails(cpu_run, monkeypatch):  # noqa: F811
    from repro.launch import serve

    step = serve.BatchedServer._step_all
    calls = []

    def altered(self):
        nxt = step(self)
        calls.append(1)
        return (nxt + 97) % self.cfg.vocab if len(calls) % 5 == 0 else nxt

    monkeypatch.setattr(serve.BatchedServer, "_step_all", altered)
    _fails(cpu_run(CELL, TINY, TRAFFIC), "max_logit_gap")


def test_float8_control_reads_above_the_program():
    """The control of bench/control_mellum2.py at the smoke size: the
    float8 forward's keys and values lie further from the reference's
    than the program's."""
    from bench import control_mellum2
    from bench.drivers.prefix_serve_loop import Driver
    from bench.spans import Spans

    spec = bench_run.load_cell(CELL)
    spec["cfg"].update(TINY)
    spec["traffic"].update(TRAFFIC)
    d = Driver(spec["cfg"], spec["traffic"], 3000000001, jax.devices(), Spans())
    d.setup()
    d.run(0.3)
    r = control_mellum2.readings(d)
    for name in ("cache_rel_err", "ring_rel_err"):
        assert r["control"][name] > 2 * r["program"][name], r
    assert r["token_altered"]["max_logit_gap"] > r["program"]["max_logit_gap"], r


def test_the_parent_program_fails_at_once(monkeypatch):
    """A program without the architecture refuses the cell before any
    weight is made."""
    from bench.drivers.prefix_serve_loop import Driver
    from repro.configs import registry

    monkeypatch.delitem(registry.ARCHS, "mellum2-12b-a2.5b")
    spec = bench_run.load_cell(CELL)
    with pytest.raises(KeyError):
        Driver(spec["cfg"], spec["traffic"], 1, jax.devices(), None)


# --- the readers ---------------------------------------------------------------

MELLUM = bench_run.load_cell(CELL)["cfg"]
RING = (
    "%decode_attn_ring.3 = (bf16[1,4,8,128]{3,2,1,0}, bf16[3,1,1024,4,128]{4,3,2,1,0},"
    " bf16[3,1,1024,4,128]{4,3,2,1,0}) custom-call(s32[1] %a, s32[1] %b),"
    ' custom_call_target="tpu_custom_call"'
)
FULL = (
    "%decode_attn_cache.9 = (bf16[1,4,8,128]{3,2,1,0}, bf16[1,1,16384,4,128]{4,3,2,1,0})"
    ' custom-call(s32[1] %a), custom_call_target="tpu_custom_call"'
)
# an op that reads the kernel's output names it too, and is not the kernel
CONSUMER = "%fusion.7 = bf16[1,4096] fusion(bf16[1,4,8,128] %decode_attn_ring.3), kind=kLoop"
# two decode steps at 8,300 and 8,301 positions, a 2-token prompt after 8,192
COUNTERS = {
    "steps": [(0, 8300), (0, 8301)],
    "prefills": [((0, 1), 2)],
    "prefix_tokens": 8192,
    "requests": 2,
}


def test_serve_ring_attn_ms_per_token_counts_decode_steps_and_prompt_tokens():
    ops = {"decode_attn_ring.3": (0.002, RING), "decode_attn_cache.9": (1.0, FULL),
           "fusion.7": (5.0, CONSUMER)}
    run = make_run(counters=COUNTERS, trace=summary(ops=ops), cfg=MELLUM)
    assert read("serve.ring_attn_ms_per_token", run) == pytest.approx(0.5)
    assert read("serve.decode_attn_ms_per_token", run) == pytest.approx(250.0)
    del ops["decode_attn_ring.3"]
    run = make_run(counters=COUNTERS, trace=summary(ops=ops), cfg=MELLUM)
    assert read("serve.ring_attn_ms_per_token", run) is None


def test_serve_ring_attn_roofline_reads_the_window_rows():
    """Four token passes past the window, 21 sliding layers: 84 calls of
    1,024 rows of K and V, 4 heads of 128, in bfloat16."""
    flops, nbytes = work_mellum2.ring_attention(MELLUM, 8300)
    assert work_mellum2.ring_attention(MELLUM, 100000) == (flops, nbytes)
    rows = 2 * 1024 * 4 * 128 * 2
    assert rows < nbytes < rows * 1.02
    assert flops == 4 * 1024 * 32 * 128
    assert work_mellum2.ring_layers(MELLUM) == 21
    run = make_run(counters=COUNTERS, trace=summary(), cfg=MELLUM)
    need = 84 * work.roofline_seconds(flops, nbytes, run.peaks)
    ops = {"decode_attn_ring.3": (4 * need, RING), "fusion.7": (5.0, CONSUMER)}
    run = make_run(counters=COUNTERS, trace=summary(ops=ops), cfg=MELLUM)
    assert read("serve.ring_attn_roofline", run) == pytest.approx(25.0)


def test_serve_prefix_restore_ms_per_request_reads_its_module():
    modules = {"jit_step": 0.5, "jit_prefix_restore": 0.004}
    run = make_run(counters=COUNTERS, trace=summary(modules=modules), cfg=MELLUM)
    assert read("serve.prefix_restore_ms_per_request", run) == pytest.approx(2.0)
    run = make_run(counters=COUNTERS, trace=summary(), cfg=MELLUM)
    assert read("serve.prefix_restore_ms_per_request", run) is None


def test_model_flops_count_the_held_share_and_the_window():
    """A token pass multiplies by 28 layers' attention and router, the held
    experts' expected 8·16/64 = 2 choices a layer, and the untied head;
    its scores span the whole context in the 7 full layers and the window
    in the 21 sliding ones."""
    attn = 2304 * 40 * 128 + 32 * 128 * 2304
    moe = 2304 * 64 + 2 * 3 * 2304 * 896
    base = 2 * (28 * (attn + moe) + 2304 * 98304)
    assert work_mellum2.token_flops(MELLUM, 0) == pytest.approx(base)
    scores = 4 * 32 * 128
    assert work_mellum2.token_flops(MELLUM, 8000) - base == pytest.approx(
        scores * (7 * 8000 + 21 * 1024))
