"""Each per-layer metric's reader on a hand-made run, and on a run in which
it finds nothing to read."""

import json
import os

import pytest

from bench import run as bench_run
from bench import work
from bench.peaks import peaks
from bench.spans import Span, Spans
from bench.trace import TraceSummary

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    PER_LAYER = [m["name"] for m in json.load(_f)["per_layer"]]
with open(os.path.join(ROOT, "bench", "configs", "granite-moe-1b-a400m.json")) as _f:
    GRANITE = json.load(_f)
MS = 1_000_000
V5E = peaks("TPU v5 lite")
CACHE_OP = (
    "%vmap__.10 = bf16[8,2,64] custom-call(bf16[8,4096,64] %a, bf16[8,4096,64] %b),"
    ' custom_call_target="tpu_custom_call"'
)


def summary(busy=0.75, modules=None, ops=None):
    ops = ops or {}
    return TraceSummary(
        window_s=1.0,
        busy_s=busy,
        chips=1,
        module_s=modules or {"jit_a": 0.5},
        op_self_s={("jit_a(1)", k): s for k, (s, _) in ops.items()},
        op_text={("jit_a(1)", k): text for k, (_, text) in ops.items()},
        idle_gaps=[],
    )


def make_run(spans=(), counters=None, trace=None, cfg=None):
    record = Spans()
    record.records = [Span(n, a * MS, b * MS, attrs) for n, a, b, attrs in spans]
    return bench_run.Run(record, (0, 1000 * MS), counters or {}, trace, V5E, cfg)


def read(name, run):
    return bench_run.reader(name)(run)


@pytest.mark.parametrize("name", PER_LAYER)
def test_nothing_to_read_gives_nothing(name):
    assert read(name, make_run()) is None


# the COX readings, under the name of each cell kind that reports them
ALONE_AND_CHAIN = pytest.mark.parametrize("tag", ["", ".chain"])


@ALONE_AND_CHAIN
def test_cox_host_ms_per_launch_keeps_the_window(tag):
    spans = [("cox.launch", 10, 12, {}), ("cox.launch", 20, 24, {})]
    spans.append(("cox.launch", 1500, 1600, {}))  # after the window
    run = make_run(spans)
    assert read("cox.host_ms_per_launch" + tag, run) == pytest.approx(3.0)


@ALONE_AND_CHAIN
def test_cox_device_ms_per_launch(tag):
    run = make_run(counters={"launches": 10}, trace=summary())
    assert read("cox.device_ms_per_launch" + tag, run) == pytest.approx(50.0)


@ALONE_AND_CHAIN
def test_cox_roofline_counts_work_from_shapes(tag):
    spans = [("cox.launch", 10, 12, {"program": "vectorAdd"})] * 2
    counters = {"n": {"vectorAdd": 50_000}, "blocks": {"vectorAdd": 256}}
    need = 2 * work.roofline_seconds(*work.cox_program("vectorAdd", 50_000, 256), V5E)
    run = make_run(spans, counters, summary(modules={"jit_a": need * 4}))
    assert read("cox.roofline" + tag, run) == pytest.approx(25.0)


@pytest.mark.parametrize(
    "name", ["device_idle.cox", "device_idle.cox.chain", "device_idle.serve"]
)
def test_device_idle(name):
    assert read(name, make_run(trace=summary(busy=0.75))) == pytest.approx(25.0)


def test_serve_prefill_share_clips_to_the_window():
    spans = [("serve.prefill", -100, 100, {}), ("serve.prefill", 900, 1200, {})]
    assert read("serve.prefill_share", make_run(spans)) == pytest.approx(20.0)


def test_serve_pipeline_ms_per_step():
    spans = [("serve.pipeline", 5, 6, {}), ("serve.pipeline", 7, 10, {})]
    assert read("serve.pipeline_ms_per_step", make_run(spans)) == pytest.approx(2.0)


def test_serve_mfu():
    run = make_run(counters={"model_flops": 0.5 * V5E["peak_flops"]})
    assert read("serve.mfu", run) == pytest.approx(50.0)


def test_serve_decode_attn_roofline_finds_the_kernel_by_its_cache():
    counters = {"steps": [(0, 100), (0, 101)], "prefills": [((0, 1), 2)]}
    need = sum(
        work.roofline_seconds(*work.decode_attention(GRANITE, n), V5E)
        for n in (100, 101, 1, 2)
    )
    ops = {"vmap__.10": (need * 10, CACHE_OP), "fusion.1": (5.0, "%fusion.1 = f32")}
    run = make_run(counters=counters, trace=summary(ops=ops), cfg=GRANITE)
    assert read("serve.decode_attn_roofline", run) == pytest.approx(10.0)
