"""The reader of ``serve.decode_attn_ms_per_token`` on a hand-made traced
run: the in-place decode attention kernel found by its own name, the token
passes it divides by, and nothing where the step has no such kernel."""

import pytest

from bench.tests.test_bench_readers import GRANITE, make_run, read, summary

KERNEL = (
    "%decode_attn_cache.14 = (bf16[1,8,2,64], bf16[24,1,8,64,4096],"
    " bf16[24,1,8,64,4096]) custom-call(s32[1] %a, s32[1] %b, s32[1] %c),"
    ' custom_call_target="tpu_custom_call"'
)
# the parent's step: flash_decode under vmap, with a call named after it
FLASH = (
    "%vmap_flash_decode_.15 = bf16[8,2,64] custom-call(s32[1] %n,"
    " bf16[8,2,64] %q, bf16[8,4096,64] %k, bf16[8,4096,64] %v),"
    ' custom_call_target="tpu_custom_call"'
)
FUSION = "%fusion.3 = bf16[1,1024] fusion(bf16[1,1024] %x, s32[] %decode_attn_cache)"


def test_counts_decode_steps_and_prompt_tokens():
    counters = {"steps": [(0, 100), (0, 101), (0, 102)], "prefills": [((0, 1), 5)]}
    ops = {"decode_attn_cache.14": (0.004, KERNEL), "fusion.3": (5.0, FUSION)}
    run = make_run(counters=counters, trace=summary(ops=ops), cfg=GRANITE)
    assert read("serve.decode_attn_ms_per_token", run) == pytest.approx(0.5)


@pytest.mark.parametrize("other", [FLASH, FUSION])
def test_only_the_kernels_own_name_counts(other):
    counters = {"steps": [(0, 100)], "prefills": []}
    ops = {"other": (1.0, other)}
    run = make_run(counters=counters, trace=summary(ops=ops), cfg=GRANITE)
    assert read("serve.decode_attn_ms_per_token", run) is None
    ops["decode_attn_cache"] = (0.002, KERNEL.replace(".14", ""))
    run = make_run(counters=counters, trace=summary(ops=ops), cfg=GRANITE)
    assert read("serve.decode_attn_ms_per_token", run) == pytest.approx(2.0)
