"""The readers of the program's own spans and module names
(``bench/program_spans.py``), on hand-made planes and on a trace recorded
from a program that writes none."""

import pytest

from bench import program_spans
from bench import run as bench_run
from bench.tests.test_bench_readers import make_run, summary
from bench.tests.test_bench_trace import FIXTURE, plane

MS = 1_000_000
WINDOW = (0, 100 * MS, "bench.trace")


class Untouchable:
    """A line whose events must not be walked."""

    name = "XLA Ops"

    @property
    def events(self):
        raise AssertionError("XLA Ops walked")


def tpu(name, modules):
    """A device plane with modules ``[(start ms, end ms, name)]`` and an
    ``XLA Ops`` line that may not be read."""
    p = plane(name, **{"XLA Modules": [(a * MS, b * MS, n) for a, b, n in modules]})
    p.lines.append(Untouchable())
    return p


def host(*spans):
    """The host plane: the window and spans ``(start ms, end ms, name)``."""
    events = [(a * MS, b * MS, n) for a, b, n in spans]
    return plane("/host:CPU", python=[WINDOW] + events)


@pytest.fixture
def traced(monkeypatch):
    """Read each metric from the given planes, as from a traced run."""

    def read(name, planes, counters=None):
        monkeypatch.setattr(program_spans, "find_xplane", lambda d: "trace.xplane.pb")
        monkeypatch.setattr(
            program_spans, "load", lambda path: program_spans.from_planes(planes)
        )
        run = make_run(counters=counters, trace=summary())
        return bench_run.reader(name)(run)

    return read


def test_spans_outside_the_window_are_left_out():
    planes = [
        host(
            (-5, 5, "cox.resolve"),  # starts before the window
            (10, 12, "cox.resolve"),
            (20, 24, "cox.resolve"),
            (98, 104, "cox.resolve"),  # ends after it
            (30, 31, "jit_run(1)"),  # not a program span
        ),
        tpu("/device:TPU:0", [(-10, 10, "jit_cox_a(1)"), (95, 120, "jit_cox_a(1)")]),
    ]
    pt = program_spans.from_planes(planes)
    assert pt.spans == {"cox.resolve": [(10 * MS, 12 * MS), (20 * MS, 24 * MS)]}
    assert pt.mean_ms("cox.resolve") == pytest.approx(3.0)
    assert pt.mean_ms("cox.dispatch") is None
    assert pt.modules == [[(0, 10 * MS, "jit_cox_a"), (95 * MS, 100 * MS, "jit_cox_a")]]


def test_a_trace_without_its_window_is_refused():
    with pytest.raises(ValueError, match="bench.trace"):
        program_spans.from_planes([plane("/host:CPU", python=[(0, 1, "cox.wait")])])


@pytest.mark.parametrize(
    "name, span",
    [
        ("cox.resolve_ms_per_launch.chain", "cox.resolve"),
        ("cox.dispatch_ms_per_launch.chain", "cox.dispatch"),
        ("serve.dispatch_ms_per_step", "serve.step.call"),
    ],
)
def test_mean_span_readers(traced, name, span):
    planes = [host((10, 11, span), (20, 23, span), (30, 31, "cox.enqueue"))]
    assert traced(name, planes) == pytest.approx(2.0)


def test_serve_host_ms_per_step_pairs_the_steps_of_one_request(traced):
    steps = [
        (10, 11, "serve.step.call"),
        (11, 18, "serve.step.wait"),
        (18, 19, "serve.step.emit"),
        (20, 21, "serve.step.call"),
        (21, 28, "serve.step.wait"),  # 3 ms after the first wait
        (30, 31, "serve.step.call"),
        (31, 38, "serve.step.wait"),  # 3 ms after the second
        # the next request: its first wait is not paired with the last
        (40, 45, "serve.prefill.call"),
        (45, 46, "serve.prefill.wait"),
        (50, 51, "serve.step.call"),
        (51, 58, "serve.step.wait"),
        (58, 62, "serve.step.emit"),
        (64, 65, "serve.step.call"),
        (65, 72, "serve.step.wait"),  # 7 ms after the request's first
    ]
    pt = program_spans.from_planes([host(*steps)])
    assert program_spans.decode_gaps_ms(pt) == pytest.approx([3.0, 3.0, 7.0])
    assert traced("serve.host_ms_per_step", [host(*steps)]) == pytest.approx(13 / 3)


def test_cox_module_gaps_over_two_chips(traced):
    chip0 = [
        (10, 20, "jit_cox_a(1)"),
        (25, 30, "jit_cox_b(2)"),  # 5 ms after a
        (31, 33, "jit_convert_element_type(3)"),  # not a COX program
        (40, 50, "jit_cox_a(1)"),  # 10 ms after b
    ]
    chip1 = [(10, 20, "jit_cox_a(1)"), (21, 30, "jit_cox_b(2)")]  # 1 ms
    planes = [host(), tpu("/device:TPU:1", chip1), tpu("/device:TPU:0", chip0)]
    pt = program_spans.from_planes(planes)
    assert [len(m) for m in pt.modules] == [4, 2]  # by plane name
    assert program_spans.cox_module_gaps_s(pt) == pytest.approx(8e-3)
    value = traced("cox.module_gap_ms_per_launch", planes, {"launches": 4})
    assert value == pytest.approx(2.0)


NEW = [
    "cox.resolve_ms_per_launch.chain",
    "cox.dispatch_ms_per_launch.chain",
    "serve.host_ms_per_step",
    "serve.dispatch_ms_per_step",
    "cox.module_gap_ms_per_launch",
]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_spans_gives_nothing(traced, name):
    planes = [
        host((10, 20, "cox.launch")),
        tpu("/device:TPU:0", [(1, 2, "jit_run(1)")]),
    ]
    assert traced(name, planes, {"launches": 4}) is None


@pytest.mark.parametrize("name", NEW)
def test_a_recorded_trace_of_the_older_program_gives_nothing(monkeypatch, name):
    """The chain-vadd trace of a program that wrote no spans of its own and
    named its COX modules ``jit_run``."""
    program_spans.load.cache_clear()
    monkeypatch.setattr(program_spans, "find_xplane", lambda d: FIXTURE)
    run = make_run(counters={"launches": 32}, trace=summary())
    assert bench_run.reader(name)(run) is None
    pt = program_spans.load(FIXTURE)
    assert pt.spans == {}
    names = {m for _, _, m in pt.modules[0]}
    assert "jit_run" in names
    assert not any(m.startswith(program_spans.COX_MODULE) for m in names)
