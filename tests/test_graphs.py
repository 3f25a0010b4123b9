"""CUDA graphs: stream capture → instantiate → replay (cox.Graph).

The load-bearing property is bitwise equivalence: a captured-then-
replayed schedule must produce exactly the arrays the eager stream
schedule produces — across backends and warp-exec modes — because the
execution model is functional (values flow between launches only
through explicit output refs), so fusing the DAG into one XLA program
may not change a single bit.  On top of that: rebound-input replay,
double-instantiate staging, capture-time legality (no synchronize, no
donation, no placeholder escape), and trace-cache sharing between
graphs and eager launches.
"""
import numpy as np
import pytest

from repro.core import cox
from repro.core.streams import Dispatcher, Stream
from repro.core.types import CoxUnsupported, GraphRef
from repro.launch.mesh import make_mesh

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


# the streams test kernel set: elementwise chain + shared-memory tile
@cox.kernel
def _saxpy(c, out: cox.Array(cox.f32), x: cox.Array(cox.f32),
           y: cox.Array(cox.f32), n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        out[i] = 2.5 * x[i] + y[i]


@cox.kernel
def _scale(c, out: cox.Array(cox.f32), x: cox.Array(cox.f32), n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        out[i] = x[i] * 3.0 + 1.0


@cox.kernel
def _tile_sum(c, out: cox.Array(cox.f32), x: cox.Array(cox.f32),
              n: cox.i32):
    tile = c.shared((256,), cox.f32)
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    v = 0.0
    if i < n:
        v = x[i]
    tile[c.thread_idx()] = v
    c.syncthreads()
    s = 0.0
    for k in range(256):
        s += tile[k]
    out[c.block_idx()] = s


@cox.kernel
def _hist(c, hist: cox.Array(cox.f32), data: cox.Array(cox.i32),
          n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        c.atomic_add(hist, data[i], 1.0)


@cox.kernel
def _coop_scan(c, out: cox.Array(cox.f32), scratch: cox.Array(cox.f32),
               a: cox.Array(cox.f32)):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    v = a[i] * 2.0
    scratch[i] = v
    c.grid_sync()
    w = scratch[(i + 64) % 256]
    out[i] = v + w


def _args(n=2048, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(n).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    return (np.zeros(n, np.float32), x, y, np.int32(n))


def _fresh():
    d = Dispatcher()
    return d, Stream("a", d), Stream("b", d)


def _chain_eager(stream, kw, o, x, y, n):
    """saxpy → scale → tile_sum issued eagerly on ``stream``."""
    h1 = stream.launch(_saxpy, grid=8, block=256, args=(o, x, y, n), **kw)
    h2 = stream.launch(_scale, grid=8, block=256,
                       args=(np.zeros_like(o), h1.outputs["out"], n), **kw)
    h3 = stream.launch(_tile_sum, grid=8, block=256,
                       args=(np.zeros(8, np.float32), h2.outputs["out"], n),
                       **kw)
    return h2.result()["out"], h3.result()["out"]


# ---------------------------------------------------------------------------
# bitwise equivalence: replay == eager, across backends × warp-exec
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["scan", "vmap"])
@pytest.mark.parametrize("warp_exec", ["serial", "batched"])
def test_replay_bitwise_equals_eager(backend, warp_exec):
    d, s, _ = _fresh()
    o, x, y, n = _args()
    kw = dict(backend=backend, warp_exec=warp_exec)
    want_mid, want_sum = _chain_eager(s, kw, o, x, y, n)

    g = cox.Graph()
    with g.capture(s):
        h1 = s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n), **kw)
        h2 = s.launch(_scale, grid=8, block=256,
                      args=(np.zeros_like(o), h1.outputs["out"], n), **kw)
        s.launch(_tile_sum, grid=8, block=256,
                 args=(np.zeros(8, np.float32), h2.outputs["out"], n), **kw)
    res = g.replay()
    # both upstream 'out's were consumed and elided, so the terminal
    # tile_sum output keeps the bare name; what remains besides it are
    # unconsumed pass-throughs (each node returns all its globals)
    assert "out" in res and not any(k.startswith("out_") for k in res)
    np.testing.assert_array_equal(np.asarray(res["out"]),
                                  np.asarray(want_sum))
    # and replay again — replay is pure, results stay identical
    res2 = g.replay()
    np.testing.assert_array_equal(np.asarray(res2["out"]),
                                  np.asarray(res["out"]))
    del want_mid


def test_replay_bitwise_equals_eager_sharded():
    mesh = make_mesh((1,), ("data",))
    d, s, _ = _fresh()
    o, x, y, n = _args()
    kw = dict(mesh=mesh, backend="sharded")
    h = s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n), **kw)
    want = h.result()["out"]
    g = cox.Graph()
    with g.capture(s):
        s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n), **kw)
    res = g.replay()
    np.testing.assert_array_equal(np.asarray(res["out"]), np.asarray(want))


def test_replay_bitwise_equals_eager_atomics_and_coop():
    """A grid-sync (multi-phase) kernel and an atomics kernel inside one
    capture — the fused program must thread the phase machinery and the
    delta merges exactly as the eager path does."""
    d, s, _ = _fresh()
    rng = np.random.default_rng(3)
    a = rng.normal(size=256).astype(np.float32)
    data = rng.integers(0, 64, size=600).astype(np.int32)
    coop_args = (np.zeros(256, np.float32), np.zeros(256, np.float32), a)
    hist_args = (np.zeros(64, np.float32), data, np.int32(600))
    want_coop = s.launch(_coop_scan, grid=4, block=64,
                         args=coop_args).result()["out"]
    want_hist = s.launch(_hist, grid=6, block=128,
                         args=hist_args).result()["hist"]
    g = cox.Graph()
    with g.capture(s):
        s.launch(_coop_scan, grid=4, block=64, args=coop_args)
        s.launch(_hist, grid=6, block=128, args=hist_args)
    res = g.replay()
    np.testing.assert_array_equal(np.asarray(res["out"]),
                                  np.asarray(want_coop))
    np.testing.assert_array_equal(np.asarray(res["hist"]),
                                  np.asarray(want_hist))


def test_capture_with_event_edges_across_streams():
    """A two-stream capture joined by an event edge — the captured DAG
    records the edge, and replay equals the eager two-stream run."""
    d, s1, s2 = _fresh()
    o, x, y, n = _args()
    ha = s1.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
    ev0 = s1.record_event()
    s2.wait_event(ev0)
    hb = s2.launch(_scale, grid=8, block=256,
                   args=(np.zeros_like(o), ha.outputs["out"], n))
    want = hb.result()["out"]

    g = cox.Graph()
    with g.capture(s1, s2):
        ca = s1.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
        ev = s1.record_event()
        s2.wait_event(ev)
        cb = s2.launch(_scale, grid=8, block=256,
                       args=(np.zeros_like(o), ca.outputs["out"], n))
        assert isinstance(cb.outputs["out"], GraphRef)
    # the event edge became a schedule dep of the second node
    assert g.nodes[0].idx in g.nodes[1].deps
    res = g.replay()
    np.testing.assert_array_equal(np.asarray(res["out"]),
                                  np.asarray(want))


def test_diamond_fanout_replay():
    """One producer feeding two consumers feeding a joint consumer —
    fan-out data edges, the DAG shape streams cannot express in one
    chain."""
    d, s, _ = _fresh()
    o, x, y, n = _args()
    g = cox.Graph()
    with g.capture(s):
        p = s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
        left = s.launch(_scale, grid=8, block=256,
                        args=(np.zeros_like(o), p.outputs["out"], n))
        right = s.launch(_scale, grid=8, block=256,
                         args=(np.zeros_like(o), p.outputs["out"], n))
        s.launch(_saxpy, grid=8, block=256,
                 args=(np.zeros_like(o), left.outputs["out"],
                       right.outputs["out"], n))
    res = g.replay()
    base = 2.5 * x + y
    leg = base * 3.0 + 1.0
    want = (2.5 * leg + leg).astype(np.float32)
    np.testing.assert_allclose(np.asarray(res["out"]), want,
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# rebinding
# ---------------------------------------------------------------------------


def test_replay_with_rebound_inputs():
    d, s, _ = _fresh()
    o, x, y, n = _args()
    g = cox.Graph()
    with g.capture(s):
        h1 = s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
        s.launch(_scale, grid=8, block=256,
                 args=(np.zeros_like(o), h1.outputs["out"], n))
    first = g.replay()
    x2 = np.asarray(x) * -1.5
    res = g.replay(x=x2)
    want = ((2.5 * x2 + y) * 3.0 + 1.0).astype(np.float32)
    np.testing.assert_allclose(np.asarray(res["out"]), want,
                               rtol=1e-5, atol=1e-5)
    # rebinding persists (cudaGraphExecKernelNodeSetParams semantics)
    res2 = g.replay()
    np.testing.assert_array_equal(np.asarray(res2["out"]),
                                  np.asarray(res["out"]))
    assert not np.array_equal(np.asarray(first["out"]),
                              np.asarray(res["out"]))


@cox.kernel
def _half_step(c, out: cox.Array(cox.f32), x: cox.Array(cox.f32),
               y: cox.Array(cox.f32), n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        out[i] = 0.5 * x[i] + y[i]


@pytest.mark.parametrize("depth", [1, 4, 16])
def test_chain_replay_rebound_bitwise_equals_eager(depth):
    # a depth-d chain of dependent launches captured once, then replayed
    # with its input rebound to the previous output, three steps deep:
    # each step bitwise-equal to issuing the chain eagerly
    d, s, _ = _fresh()
    o, x0, y, n = _args()

    def chain(x):
        h = s.launch(_half_step, grid=8, block=256, args=(o, x, y, n))
        for _ in range(depth - 1):
            h = s.launch(_half_step, grid=8, block=256,
                         args=(o, h.outputs["out"], y, n))
        return h

    g = cox.Graph()
    with g.capture(s):
        chain(x0)
    exe = g.instantiate()
    xe = xg = x0
    for _ in range(3):
        xe = np.asarray(chain(xe).result()["out"])
        xg = np.asarray(exe.replay(x=xg)["out"])
        np.testing.assert_array_equal(xg, xe)


def test_replay_rejects_unknown_input():
    d, s, _ = _fresh()
    o, x, y, n = _args()
    g = cox.Graph()
    with g.capture(s):
        s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
    with pytest.raises(KeyError):
        g.replay(bogus=np.zeros(4, np.float32))


def test_bare_name_rebinds_every_matching_input():
    """The same external buffer name on two nodes: a bare-name rebind
    updates both bindings; the suffixed name addresses just one."""
    d, s, _ = _fresh()
    o, x, y, n = _args(512)
    g = cox.Graph()
    with g.capture(s):
        s.launch(_scale, grid=2, block=256, args=(o, x, n))
        s.launch(_scale, grid=2, block=256, args=(np.zeros_like(o), x, n))
    exe = g.instantiate()
    assert "x_n0" in exe.input_names and "x_n1" in exe.input_names
    x2 = np.asarray(x) + 1.0
    res = exe.replay(x=x2)                # bare name: both nodes
    want = (x2 * 3.0 + 1.0).astype(np.float32)
    np.testing.assert_allclose(np.asarray(res["out_n0"]), want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(res["out_n1"]), want, rtol=1e-5)
    res = exe.replay(x_n1=np.asarray(x))  # suffixed: one node only
    np.testing.assert_allclose(np.asarray(res["out_n0"]), want, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(res["out_n1"]),
        (np.asarray(x) * 3.0 + 1.0).astype(np.float32), rtol=1e-5)


# ---------------------------------------------------------------------------
# staging: double-instantiate + cache sharing with eager launches
# ---------------------------------------------------------------------------


def test_double_instantiate_is_a_stage_hit():
    d, s, _ = _fresh()
    o, x, y, n = _args()
    g = cox.Graph()
    with g.capture(s):
        s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
    e1 = g.instantiate()
    hits = d.stage_hits
    e2 = g.instantiate()
    assert d.stage_hits == hits + 1        # same DAG: staged once
    assert e1._exe is e2._exe              # one executable...
    assert e1 is not e2                    # ...two rebindable instances
    e2.replay(x=np.zeros_like(x))
    r1 = e1.replay()                       # e1's bindings are untouched
    np.testing.assert_array_equal(
        np.asarray(r1["out"]),
        np.asarray(s.launch(_saxpy, grid=8, block=256,
                            args=(o, x, y, n)).result()["out"]))


def test_structurally_identical_recapture_shares_executable():
    d, s, _ = _fresh()
    o, x, y, n = _args()
    g1 = cox.Graph()
    with g1.capture(s):
        s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
    e1 = g1.instantiate()
    g2 = cox.Graph()
    with g2.capture(s):                    # same kernel/geometry/structure
        s.launch(_saxpy, grid=8, block=256, args=(o, y, x, n))
    e2 = g2.instantiate()
    assert e1._exe is e2._exe              # stage hit across captures
    # but each keeps its own captured bindings (x/y swapped)
    np.testing.assert_array_equal(
        np.asarray(e2.replay()["out"]),
        np.asarray(_saxpy.launch(grid=8, block=256, args=(o, y, x, n))["out"]))


def test_graph_shares_traces_with_eager_launches():
    """The cache-sharing contract: eager launches populate the raw-fn
    cache, a graph over the same launch shapes re-traces nothing — and
    graph entries never leak into the kernel's `_launch_cache` view."""
    d, s, _ = _fresh()
    o, x, y, n = _args()
    s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n)).result()
    misses = d.stage_fn_misses
    g = cox.Graph()
    with g.capture(s):
        s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
    g.instantiate()
    assert d.stage_fn_misses == misses     # the graph re-traced nothing
    assert d.stage_fn_hits >= 1
    # graph executables live in the shared LRU under a "graph" tag,
    # invisible to the per-kernel cache view
    assert any(k[0] == "graph" for k in d._staged)
    ck = next(iter(_saxpy._cache.values()))
    assert all(isinstance(k[0], tuple)
               for k in d.cache_view([ck]))


# ---------------------------------------------------------------------------
# capture-time legality
# ---------------------------------------------------------------------------


def test_capture_rejects_synchronize():
    d, s, _ = _fresh()
    o, x, y, n = _args()
    with cox.Graph().capture(s):
        s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
        with pytest.raises(CoxUnsupported):
            s.synchronize()
        with pytest.raises(CoxUnsupported):
            d.sync_all()
    assert not s.capturing                 # context manager still unwinds


def test_capture_rejects_donation():
    d, s, _ = _fresh()
    o, x, y, n = _args()
    g = cox.Graph()
    with g.capture(s):
        h1 = s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
        with pytest.raises(CoxUnsupported):
            s.launch(_scale, grid=8, block=256,
                     args=(np.zeros_like(o), h1.outputs["out"], n),
                     donate=True)


def test_capture_rejects_event_query_and_sync():
    d, s, _ = _fresh()
    o, x, y, n = _args()
    with cox.Graph().capture(s):
        s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
        ev = s.record_event()
        with pytest.raises(CoxUnsupported):
            ev.query()
        with pytest.raises(CoxUnsupported):
            ev.synchronize()


def test_capture_rejects_eager_event_wait():
    d, s1, s2 = _fresh()
    o, x, y, n = _args()
    h = s1.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
    eager_ev = s1.record_event()
    h.result()
    with cox.Graph().capture(s2):
        with pytest.raises(CoxUnsupported):
            s2.wait_event(eager_ev)        # CUDA invalidates the capture


def test_placeholder_escape_rejected():
    """A GraphRef consumed outside its capture must fail at enqueue —
    the placeholder never holds data."""
    d, s, _ = _fresh()
    o, x, y, n = _args()
    g = cox.Graph()
    with g.capture(s):
        h = s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
        ref = h.outputs["out"]
    with pytest.raises(CoxUnsupported):
        s.launch(_scale, grid=8, block=256,
                 args=(np.zeros_like(o), ref, n))
    with pytest.raises(CoxUnsupported):    # and never as a scalar
        s.launch(_scale, grid=8, block=256, args=(o, x, ref))


def test_captured_handle_has_no_results():
    d, s, _ = _fresh()
    o, x, y, n = _args()
    g = cox.Graph()
    with g.capture(s):
        h = s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
        with pytest.raises(CoxUnsupported):
            h.result()
        with pytest.raises(CoxUnsupported):
            h.done()


def test_empty_graph_and_nested_capture_rejected():
    d, s, _ = _fresh()
    g = cox.Graph()
    with pytest.raises(CoxUnsupported):
        g.instantiate()
    with g.capture(s):
        with pytest.raises(CoxUnsupported):
            s.begin_capture()              # already capturing
    o, x, y, n = _args()
    with g.capture(s):                     # re-open the same graph: fine
        s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
    g.instantiate()
    with pytest.raises(CoxUnsupported):    # instantiated graphs are frozen
        s.begin_capture(g)


def test_capture_does_not_dispatch():
    """Capture records the schedule without running it: nothing pends,
    nothing dispatches, and eager launches on other streams proceed."""
    d, s1, s2 = _fresh()
    o, x, y, n = _args()
    logged = len(d.dispatch_log)
    g = cox.Graph()
    with g.capture(s1):
        s1.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
        # an eager launch on a non-capturing stream still flows
        r = s2.launch(_scale, grid=8, block=256, args=(o, x, n)).result()
        np.testing.assert_allclose(np.asarray(r["out"]),
                                   np.asarray(x) * 3.0 + 1.0, rtol=1e-5)
    assert len(d.dispatch_log) == logged + 1   # only the eager launch
    assert not d._pending
    g.replay()
    assert len(d.dispatch_log) == logged + 1   # replay bypasses dispatch
