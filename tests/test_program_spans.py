"""The program's own profiler spans and executable names: a COX launch
chain, a graph replay and a served decode, traced on the CPU and read
back from the ``.xplane.pb`` the profiler wrote."""
import glob
import os
from collections import Counter

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

from repro.core import cox  # noqa: E402
from repro.core.streams import Dispatcher, Stream  # noqa: E402
from repro.launch.serve import BatchedServer  # noqa: E402

N, BLOCK = 1000, 256
GRID = -(-N // BLOCK)
CHAIN = 4
STEPS = 3
PROGRAM_SPANS = ("cox.", "serve.")


@cox.kernel
def vectorAdd(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32),
              b: cox.Array(cox.f32), n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        out[i] = a[i] + b[i]


def _chain(stream, a, b):
    """``CHAIN`` dependent vectorAdds (x <- x + b), then one wait."""
    x = a
    for _ in range(CHAIN):
        h = stream.launch(vectorAdd, grid=GRID, block=BLOCK,
                          args=(np.zeros(N, np.float32), x, b, np.int32(N)))
        x = h.outputs["out"]
    return h.result()["out"]


def _events(log_dir):
    """Every host event of the trace: (line, name, start, end)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(line.name, e.name, e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
    return out


def _inside(inner, outers):
    """True when ``inner`` lies within one of ``outers`` on its line."""
    line, _, a, b = inner
    return any(o[0] == line and o[2] <= a and b <= o[3] for o in outers)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Warm everything up, then trace one chain, one replay and a decode
    of ``STEPS`` tokens; the host events, the dispatcher and the graph."""
    rng = np.random.default_rng(0)
    a = rng.standard_normal(N).astype(np.float32)
    b = rng.standard_normal(N).astype(np.float32)
    d = Dispatcher()
    s = Stream("spans", d)
    g, gs = cox.Graph(name="spans"), Stream("spans-capture", d)
    with g.capture(gs):
        gs.launch(vectorAdd, grid=GRID, block=BLOCK,
                  args=(np.zeros(N, np.float32), a, b, np.int32(N)))
    exe = g.instantiate(d)
    server = BatchedServer("mamba2-130m-smoke", batch=1, ctx=32, seed=0)
    server.prefill_prompt(0, [3, 5])
    server.decode(STEPS)
    _chain(s, a, b)
    jax.block_until_ready(exe.replay(a=b))

    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    server.prefill_prompt(0, [3, 5])
    before = d.health()
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        chained = _chain(s, a, b)
        replayed = exe.replay(a=a)["out"]
        jax.block_until_ready(replayed)
        server.decode(STEPS)
    finally:
        jax.profiler.stop_trace()
    want = a
    for _ in range(CHAIN):
        want = want + b
    np.testing.assert_array_equal(np.asarray(chained), want)
    np.testing.assert_array_equal(np.asarray(replayed), a + b)
    return _events(log_dir), d, before


def test_each_span_counts_once_per_launch_and_step(traced):
    events, _, _ = traced
    counts = Counter(n for _, n, _, _ in events if n.startswith(PROGRAM_SPANS))
    assert counts["cox.resolve"] == CHAIN
    assert counts["cox.enqueue"] == CHAIN
    assert counts["cox.dispatch"] == CHAIN
    assert counts["cox.xla_call"] == CHAIN
    assert counts["cox.wait"] == 1
    assert counts["cox.graph.replay"] == 1
    assert counts["cox.restage"] == 0          # everything was staged
    for name in ("serve.step.call", "serve.step.wait", "serve.step.emit"):
        assert counts[name] == STEPS, name


def test_spans_nest(traced):
    events, _, _ = traced

    def named(name):
        return [e for e in events if e[1] == name]

    for e in named("cox.xla_call"):
        assert _inside(e, named("cox.dispatch"))
    # one decode step: call, then wait, then emit, in that order
    calls, waits, emits = (sorted(named(f"serve.step.{k}"), key=lambda e: e[2])
                           for k in ("call", "wait", "emit"))
    for c, w, m in zip(calls, waits, emits):
        assert c[3] <= w[2] and w[3] <= m[2]


def test_staged_programs_carry_the_kernel_name(traced):
    events, d, _ = traced
    names = {n for _, n, _, _ in events}
    assert "PjitFunction(cox_vectorAdd)" in names
    assert "PjitFunction(cox_graph_spans)" in names
    a = np.zeros(N, np.float32)
    req = vectorAdd.make_request(grid=GRID, block=BLOCK,
                                 args=(a, a, a, np.int32(N)))
    _, exe = d.stage(req)
    text = exe.lower(req.globals_, req.scalars).as_text()
    assert "module @jit_cox_vectorAdd" in text


def test_health_counts_stage_hits_and_misses(traced):
    _, d, before = traced
    after = d.health()
    # the traced chain found its program staged, launch by launch; the
    # graph replay stages nothing
    assert after["stage_hits"] - before["stage_hits"] >= CHAIN
    assert after["stage_misses"] == before["stage_misses"] == 2
