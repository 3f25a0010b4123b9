"""granite-4.0-h-small's cell at a tiny hybrid size on the CPU, through
the same ``measure`` a chip run takes, with the program's Pallas kernels
in interpret mode; the faults and the control that must turn ``correct``
false; and the readers of its two new metrics on hand-made planes."""

import jax
import numpy as np
import pytest

from bench import run as bench_run
from bench import work, work_hybrid
from bench.tests.test_bench_loops import cpu_run, interpret  # noqa: F401
from bench.tests.test_bench_readers import make_run, read, summary

CELL = "granite-4.0-h-small.decode-b1"
# the smoke twin: one period of 10 layers at width 64, 1 of 4 experts held
TINY_HYBRID = dict(
    system_arch="granite-4.0-h-small-smoke",
    hidden_size=64,
    num_attention_heads=4,
    num_key_value_heads=2,
    intermediate_size=32,
    shared_intermediate_size=48,
    num_router_experts=4,
    num_local_experts=1,
    num_experts_per_tok=2,
    vocab_size=512,
    mamba_n_heads=8,
    mamba_d_head=16,
    mamba_d_state=16,
    max_position_embeddings=128,
    # random logits at this width lie within about 0.3 of each other
    check_max_logit_gap=0.05,
)
TRAFFIC = {"prompt_lens": [4, 8], "output_tokens": 8}


def test_hybrid_serve_loop(cpu_run, interpret):  # noqa: F811
    res = cpu_run(CELL, TINY_HYBRID, TRAFFIC)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"serve_tok_s", "itl_p95_ms", "setup_s"}
    checks = res["checks"]
    assert checks["max_logit_gap"]["value"] < 1e-3
    assert 0 < checks["state_rel_err"]["value"] < checks["state_rel_err"]["limit"]
    assert 0 < checks["cache_rel_err"]["value"] < checks["cache_rel_err"]["limit"]


def test_hybrid_token_altered_where_produced(cpu_run, monkeypatch):  # noqa: F811
    from repro.launch import serve

    step = serve.BatchedServer._step_all
    calls = []

    def altered(self):
        nxt = step(self)
        calls.append(1)
        return (nxt + 97) % self.cfg.vocab if len(calls) % 5 == 0 else nxt

    monkeypatch.setattr(serve.BatchedServer, "_step_all", altered)
    res = cpu_run(CELL, TINY_HYBRID, TRAFFIC)
    assert not res["correct"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_hybrid_ssm_state_altered(cpu_run, monkeypatch):  # noqa: F811
    """A decode step that leaves a Mamba2 layer's state scaled (the first
    run's first layer) fails ``state_rel_err``."""
    from repro.launch import serve

    step = serve.BatchedServer._step_all

    def altered(self):
        nxt = step(self)
        run = dict(self.cache[0])
        run["h"] = run["h"].at[0].multiply(1.25)
        self.cache = [run] + list(self.cache[1:])
        return nxt

    monkeypatch.setattr(serve.BatchedServer, "_step_all", altered)
    res = cpu_run(CELL, TINY_HYBRID, TRAFFIC)
    assert not res["correct"]
    err = res["checks"]["state_rel_err"]
    assert err["value"] > err["limit"]


def test_float8_control_reads_above_the_program():
    """The control of bench/control_hybrid.py at a tiny size: the float8
    forward's keys, values and SSM states lie further from the
    reference's than the program's."""
    from bench import control_hybrid
    from bench.drivers.hybrid_serve_loop import Driver
    from bench.spans import Spans

    spec = bench_run.load_cell(CELL)
    spec["cfg"].update(TINY_HYBRID)
    spec["traffic"].update(TRAFFIC)
    d = Driver(spec["cfg"], spec["traffic"], 3000000001, jax.devices(), Spans())
    d.setup()
    d.run(0.3)
    r = control_hybrid.readings(d)
    for name in ("cache_rel_err", "state_rel_err"):
        assert r["control"][name] > 2 * r["program"][name], r
    assert r["token_altered"]["max_logit_gap"] > r["program"]["max_logit_gap"], r


def test_the_parent_program_fails_at_once(monkeypatch):
    """A program without the architecture refuses the cell before any
    weight is made."""
    from bench.drivers.hybrid_serve_loop import Driver
    from repro.configs import registry

    monkeypatch.delitem(registry.ARCHS, "granite-4.0-h-small")
    spec = bench_run.load_cell(CELL)
    with pytest.raises(KeyError):
        Driver(spec["cfg"], spec["traffic"], 1, jax.devices(), None)


# --- the readers ---------------------------------------------------------------

HYBRID = bench_run.load_cell(CELL)["cfg"]
KERNEL = (
    "%ssd_decode.28 = (f32[1,128,64]{2,1,0}, f32[5,1,128,64,128]{4,3,2,1,0})"
    " custom-call(s32[1] %a, s32[1] %b, f32[5,1,128,64,128] %c),"
    ' custom_call_target="tpu_custom_call"'
)
# an op that reads the kernel's output names it too, and is not the kernel
CONSUMER = "%fusion.7 = f32[1,8192] fusion(f32[1,128,64] %ssd_decode.28), kind=kLoop"
COUNTERS = {"steps": [(0, 100), (0, 101)], "prefills": [((0, 1), 2)]}


def test_serve_ssd_ms_per_token_counts_decode_steps_and_prompt_tokens():
    ops = {"ssd_decode.28": (0.002, KERNEL), "fusion.7": (5.0, CONSUMER)}
    run = make_run(counters=COUNTERS, trace=summary(ops=ops), cfg=HYBRID)
    assert read("serve.ssd_ms_per_token", run) == pytest.approx(0.5)
    del ops["ssd_decode.28"]
    run = make_run(counters=COUNTERS, trace=summary(ops=ops), cfg=HYBRID)
    assert read("serve.ssd_ms_per_token", run) is None


def test_serve_ssd_decode_roofline_from_state_bytes():
    """Four token passes, nine Mamba2 layers: 36 calls of 8.4 MB each."""
    flops, nbytes = work_hybrid.ssd_decode_work(HYBRID)
    state = 128 * 128 * 64 * 4
    assert 2 * state < nbytes < 2 * state * 1.01
    assert work_hybrid.mamba_layers(HYBRID) == 9
    run = make_run(counters=COUNTERS, trace=summary(), cfg=HYBRID)
    need = 36 * work.roofline_seconds(flops, nbytes, run.peaks)
    ops = {"ssd_decode.28": (2 * need, KERNEL), "fusion.7": (5.0, CONSUMER)}
    run = make_run(counters=COUNTERS, trace=summary(ops=ops), cfg=HYBRID)
    assert read("serve.ssd_decode_roofline", run) == pytest.approx(50.0)


def test_hybrid_model_flops_count_the_held_share():
    """A token pass multiplies by the nine Mamba2 mixers, the attention
    layer, ten routers and shared experts, the held experts' expected
    10·18/72 = 2.5 choices a layer, and the tied head."""
    m = 9 * 102_269_952 + 41_943_040
    moe = 10 * (4096 * 72 + 2.5 * 3 * 4096 * 768 + 3 * 4096 * 1536)
    head = 4096 * 100352
    per_weight = work_hybrid.token_flops(HYBRID, 0) - 9 * work_hybrid.ssd_decode_work(HYBRID)[0]
    assert per_weight == pytest.approx(2 * (m + moe + head), rel=1e-3)
    attention = work_hybrid.token_flops(HYBRID, 100) - work_hybrid.token_flops(HYBRID, 0)
    assert attention == pytest.approx(4 * 100 * 4096)
    assert np.isclose(work_hybrid.moe_params(HYBRID), moe / 10)
