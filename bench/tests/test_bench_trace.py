"""The trace reduction, on hand-made intervals and planes and on a
recorded trace."""

import os
from types import SimpleNamespace as NS

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FIXTURE = os.path.join(DATA, "chain-vadd.xplane.pb")


def test_self_times_subtract_nested_ops():
    # a while loop 0..100 holding two body ops, then a copy 100..110
    events = [
        (0, 100, "while.1"),
        (10, 30, "fusion.2"),
        (40, 70, "fusion.3"),
        (100, 110, "copy.4"),
    ]
    assert trace._self_times(events) == {
        "while.1": 50,
        "fusion.2": 20,
        "fusion.3": 30,
        "copy.4": 10,
    }


def test_union_merges_overlaps():
    assert trace._union([(5, 8), (0, 3), (2, 4), (8, 9)]) == [(0, 4), (5, 9)]


def test_idle_gaps_go_to_the_innermost_open_span():
    busy = [(10, 20), (30, 40)]
    spans = [(0, 100, "cox.launch"), (22, 28, "cox.sync")]
    gaps = dict(trace._idle_gaps(busy, 0, 50, spans))
    # 0..10 and 40..50 in the launch, 20..30 (midpoint 25) in the sync
    assert gaps == {
        "cox.launch": pytest.approx(20e-9),
        "cox.sync": pytest.approx(10e-9),
    }


def test_idle_gaps_outside_every_span():
    assert trace._idle_gaps([], 0, 10, []) == [(trace.NO_SPAN, pytest.approx(10e-9))]


def test_names():
    assert trace.op_name("%fusion.12 = bf16[8]{0} fusion(...)") == "fusion.12"
    assert trace.module_name("jit_step(3063205380766041769)") == "jit_step"


def test_recorded_trace():
    """25 ms of a chain-vadd run traced on one TPU v5e, cut down to the
    planes and lines the reduction reads."""
    s = trace.reduce(FIXTURE)
    assert s.chips == 1
    assert 0 < s.busy_s < s.window_s
    assert s.module_s and all(v > 0 for v in s.module_s.values())
    assert sum(s.op_self_s.values()) == pytest.approx(s.busy_s, rel=0.05)
    assert all(module != trace.NO_MODULE for module, _ in s.op_self_s)
    names = {name for name, _ in s.idle_gaps}
    assert names & {"cox.launch", "cox.sync"}
    idle = sum(v for _, v in s.idle_gaps)
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)


def plane(name, **lines):
    """A trace plane from ``line name -> [(start, end, event name)]``."""
    return NS(
        name=name,
        lines=[
            NS(
                name=line,
                events=[NS(name=n, start_ns=a, duration_ns=b - a) for a, b, n in evs],
            )
            for line, evs in lines.items()
        ],
    )


def test_ops_of_two_modules_that_share_a_name_stay_apart():
    host = plane("/host:CPU", spans=[(0, 1000, "bench.trace")])
    tpu = plane(
        "/device:TPU:0",
        **{
            "XLA Modules": [(100, 300, "jit_step(11)"), (400, 500, "jit_run(22)")],
            "XLA Ops": [
                (100, 300, "%fusion.7 = bf16[24,32,512,1024] fusion(...)"),
                (400, 450, "%fusion.7 = f32[8] fusion(...)"),
                (450, 500, "%copy.1 = f32[8] copy(...)"),
            ],
        },
    )
    s = trace.reduce_planes([host, tpu])
    assert s.op_self_s == {
        ("jit_step(11)", "fusion.7"): pytest.approx(200e-9),
        ("jit_run(22)", "fusion.7"): pytest.approx(50e-9),
        ("jit_run(22)", "copy.1"): pytest.approx(50e-9),
    }
    assert s.ops_matching(r"bf16\[24,32") == pytest.approx(200e-9)
    assert s.ops_matching(r"f32\[8\] fusion") == pytest.approx(50e-9)
    assert s.module_s == {
        "jit_step": pytest.approx(200e-9),
        "jit_run": pytest.approx(100e-9),
    }
    top = s.top_ops(1)[0][0]
    assert s.op_label(top).startswith("jit_step(11): %fusion.7 = bf16")


def test_an_op_outside_every_module():
    host = plane("/host:CPU", spans=[(0, 100, "bench.trace")])
    tpu = plane("/device:TPU:0", **{"XLA Ops": [(10, 20, "%copy.1 = f32[8]")]})
    s = trace.reduce_planes([host, tpu])
    assert s.op_self_s == {(trace.NO_MODULE, "copy.1"): pytest.approx(10e-9)}
    assert s.busy_s == pytest.approx(10e-9)
