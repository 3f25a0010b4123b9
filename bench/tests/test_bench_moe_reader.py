"""The reader of ``serve.moe_ms_per_token`` on a hand-made traced run, with
the decode MoE kernel and with only the capacity path's fusions."""

import pytest

from bench.tests.test_bench_readers import GRANITE, make_run, read, summary


def test_serve_moe_ms_per_token_counts_decode_steps_and_prompt_tokens():
    counters = {"steps": [(0, 100), (0, 101)], "prefills": [((0, 1), 2)]}
    kernel = (
        "%moe_decode_experts.3 = f32[16,1024] custom-call(s32[1] %a, s32[8] %b),"
        ' custom_call_target="tpu_custom_call"'
    )
    ops = {
        "moe_decode_experts.3": (0.002, kernel),
        "fusion.115": (5.0, "%fusion.115 = bf16[256,1024] fusion(bf16[256,1024] %x)"),
    }
    run = make_run(counters=counters, trace=summary(ops=ops), cfg=GRANITE)
    assert read("serve.moe_ms_per_token", run) == pytest.approx(0.5)
    # the parent's step, with the capacity path's fusions and no kernel
    del ops["moe_decode_experts.3"]
    run = make_run(counters=counters, trace=summary(ops=ops), cfg=GRANITE)
    assert read("serve.moe_ms_per_token", run) is None
