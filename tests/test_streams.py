"""CUDA streams & events: the async launch-dispatch layer.

Semantics under test (README "Streams & events"):

* in-order dispatch within a stream (program order);
* event edges enforce cross-stream ordering (`record` → `wait`);
* the default stream's legacy-sync semantics (ordered after every
  stream's tail, and every stream ordered after it);
* ``synchronize()`` idempotence;
* bitwise equality of any legal stream schedule vs serial issue, across
  the (scan/vmap) × (serial/batched) launch matrix;
* staging-cache sharing across streams (no duplicate staging for
  identical geometry);
* buffer donation: wired through the backends, observable via
  re-launch behavior (donated inputs are consumed), rejected where it
  cannot apply (sharded).
"""
import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import cox  # noqa: E402
from repro.core.streams import Dispatcher, Stream  # noqa: E402
from repro.core.types import CoxUnsupported  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


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
    """Shared-memory kernel (so warp_exec='batched' is exercisable)."""
    tile = c.shared((256,), cox.f32)
    t = c.thread_idx()
    i = c.block_idx() * c.block_dim() + t
    tile[t] = c.select(i < n, x[i], 0.0)
    c.syncthreads()
    if t == 0:
        s = 0.0
        for k in range(256):
            s += tile[k]
        out[c.block_idx()] = s


def _args(n=2048, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(n).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    return (np.zeros(n, np.float32), x, y, np.int32(n))


def _fresh():
    """A private dispatcher + streams, isolated from the module-level
    default (so dispatch_log / dependency assertions are exact)."""
    d = Dispatcher()
    return d, Stream("a", d), Stream("b", d)


# ---------------------------------------------------------------------------
# ordering: program order, event edges, legacy default-stream sync
# ---------------------------------------------------------------------------


def test_in_order_within_stream():
    d, s, _ = _fresh()
    o, x, y, n = _args()
    h1 = s.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
    h2 = s.launch(_scale, grid=8, block=256, args=(o, x, n))
    h3 = s.launch(_saxpy, grid=8, block=256, args=(o, y, x, n))
    # program order is the dependency chain
    assert h1.request.seq in h2.request.deps
    assert h2.request.seq in h3.request.deps
    d.flush()
    assert list(d.dispatch_log) == [h1.request.seq, h2.request.seq,
                                    h3.request.seq]


def test_event_edge_orders_across_streams():
    d, s1, s2 = _fresh()
    o, x, y, n = _args()
    # enqueue s2's independent work first so only the event edge can
    # order it after s1's tail
    ha = s1.launch(_saxpy, grid=4, block=256, args=(o, x, y, n))
    ev = s1.record_event()
    s2.wait_event(ev)
    hb = s2.launch(_scale, grid=4, block=256, args=(o, x, n))
    hc = s2.launch(_scale, grid=4, block=256, args=(o, y, n))
    assert ha.request.seq in hb.request.deps      # the event edge
    assert hb.request.seq in hc.request.deps      # then program order
    d.flush()
    order = d.dispatch_log
    assert order.index(ha.request.seq) < order.index(hb.request.seq)


def test_wait_on_unrecorded_event_is_noop():
    d, s1, s2 = _fresh()
    o, x, y, n = _args()
    ev = cox.Event()                      # never recorded
    s2.wait_event(ev)
    hb = s2.launch(_scale, grid=4, block=256, args=(o, x, n))
    assert hb.request.deps == ()          # no spurious edge
    d.sync_all()


def test_default_stream_legacy_sync():
    d, s1, s2 = _fresh()
    o, x, y, n = _args()
    h1 = s1.launch(_saxpy, grid=4, block=256, args=(o, x, y, n))
    # a default-stream launch is ordered after every stream's tail
    hd = d.default.launch(_saxpy, grid=4, block=256, args=(o, y, x, n))
    assert h1.request.seq in hd.request.deps
    # and every stream's next launch is ordered after the default tail
    h2 = s2.launch(_scale, grid=4, block=256, args=(o, x, n))
    assert hd.request.seq in h2.request.deps
    d.flush()
    order = d.dispatch_log
    assert (order.index(h1.request.seq) < order.index(hd.request.seq)
            < order.index(h2.request.seq))


def test_independent_streams_have_no_edges():
    d, s1, s2 = _fresh()
    o, x, y, n = _args()
    h1 = s1.launch(_saxpy, grid=4, block=256, args=(o, x, y, n))
    h2 = s2.launch(_scale, grid=4, block=256, args=(o, x, n))
    assert h1.request.deps == () and h2.request.deps == ()
    d.sync_all()


# ---------------------------------------------------------------------------
# synchronization
# ---------------------------------------------------------------------------


def test_synchronize_idempotent():
    d, s1, _ = _fresh()
    o, x, y, n = _args()
    h = s1.launch(_saxpy, grid=4, block=256, args=(o, x, y, n))
    s1.synchronize()
    n_dispatched = len(d.dispatch_log)
    s1.synchronize()                      # idle stream: no-op
    s1.synchronize()
    d.sync_all()
    d.sync_all()
    assert len(d.dispatch_log) == n_dispatched   # nothing re-dispatched
    r1 = h.result()
    r2 = h.result()                       # result() is repeatable too
    np.testing.assert_array_equal(np.asarray(r1["out"]),
                                  np.asarray(r2["out"]))


def test_event_synchronize_and_elapsed():
    d, s1, _ = _fresh()
    o, x, y, n = _args()
    start = cox.Event().record(s1)
    s1.launch(_saxpy, grid=4, block=256, args=(o, x, y, n))
    stop = s1.record_event()
    stop.synchronize()
    stop.synchronize()                    # idempotent
    ms = start.elapsed(stop)
    assert ms >= 0.0
    assert stop.query()


def test_event_elapsed_before_record_raises():
    ev = cox.Event()
    with pytest.raises(CoxUnsupported):
        ev.synchronize()


# ---------------------------------------------------------------------------
# bitwise equality: any legal stream schedule == serial issue
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["scan", "vmap"])
@pytest.mark.parametrize("warp_exec", ["serial", "batched"])
def test_stream_schedule_bitwise_equals_serial(backend, warp_exec):
    d, s1, s2 = _fresh()
    n = 2048
    o, x, y, n32 = _args(n)
    a1 = (o, x, n32)
    a2 = (np.zeros(8, np.float32), y, n32)
    kw = dict(backend=backend, warp_exec=warp_exec)
    # serial issue (launch + synchronize each; the classic path)
    want1 = _scale.launch(grid=8, block=256, args=a1, **kw)
    want2 = _tile_sum.launch(grid=8, block=256, args=a2, **kw)
    # two streams + an event edge — a different legal schedule
    h1 = s1.launch(_scale, grid=8, block=256, args=a1, **kw)
    ev = s1.record_event()
    s2.wait_event(ev)
    h2 = s2.launch(_tile_sum, grid=8, block=256, args=a2, **kw)
    got1, got2 = h1.result(), h2.result()
    np.testing.assert_array_equal(np.asarray(got1["out"]),
                                  np.asarray(want1["out"]))
    np.testing.assert_array_equal(np.asarray(got2["out"]),
                                  np.asarray(want2["out"]))


def test_handle_chaining_without_host_sync():
    d, s1, s2 = _fresh()
    o, x, y, n = _args()
    h1 = s1.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
    # feed h1's (still in-flight) flat output straight into s2's launch
    h2 = s2.launch(_scale, grid=8, block=256,
                   args=(o, h1.outputs["out"], n))
    want = (2.5 * x + y) * 3.0 + 1.0
    np.testing.assert_allclose(np.asarray(h2.result()["out"]), want,
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# staging-cache sharing
# ---------------------------------------------------------------------------


def test_cache_shared_across_streams():
    d, s1, s2 = _fresh()
    o, x, y, n = _args()
    h1 = s1.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
    h1.result()
    misses = d.stage_misses
    h2 = s2.launch(_saxpy, grid=8, block=256, args=(o, y, x, n))
    h3 = d.default.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
    h2.result()
    h3.result()
    assert d.stage_misses == misses       # identical geometry: no restaging
    assert d.stage_hits >= 2


def test_kernelfn_launch_cache_view_still_works():
    """The public `_launch_cache` introspection view keeps its shape:
    token first, phase count second, (plan, exe) values."""
    o, x, y, n = _args()
    _saxpy.launch(grid=2, block=256, args=(o, x, y, n))
    cache = _saxpy._launch_cache
    assert len(cache) >= 1
    for key, (plan, exe) in cache.items():
        choice, ws = key[0]
        assert choice in ("flat", "hier") and isinstance(ws, int)
        assert key[1] == 1                # single-phase kernel
        assert callable(exe)


# ---------------------------------------------------------------------------
# error surfacing
# ---------------------------------------------------------------------------


def test_stage_error_surfaces_at_that_requests_sync():
    """A bad request (explicit vmap for a ticket kernel) must raise at
    *its own* sync, not poison unrelated launches."""

    @cox.kernel
    def ticket(c, out: cox.Array(cox.f32), cnt: cox.Array(cox.f32)):
        t = c.atomic_add_old(cnt, 0, 1.0)
        out[c.block_idx()] = t

    d, s1, s2 = _fresh()
    o, x, y, n = _args()
    bad = s1.launch(ticket, grid=4, block=32,
                    args=(np.zeros(4, np.float32),
                          np.zeros(1, np.float32)),
                    backend="vmap")
    good = s2.launch(_saxpy, grid=8, block=256, args=(o, x, y, n))
    # the good launch's sync flushes everything but raises nothing
    r = good.result()
    np.testing.assert_allclose(np.asarray(r["out"]), 2.5 * x + y,
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(CoxUnsupported):
        bad.result()
    # surfacing the error reclaims the bookkeeping entry (no leak), on
    # the async .outputs path just like the sync .result() path
    assert bad.request.seq not in d._inflight
    bad2 = s1.launch(ticket, grid=4, block=32,
                     args=(np.zeros(4, np.float32),
                           np.zeros(1, np.float32)),
                     backend="vmap")
    with pytest.raises(CoxUnsupported):
        _ = bad2.outputs
    assert bad2.request.seq not in d._inflight


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------


def test_donate_correct_and_consumes_inputs():
    """Donation is observable through re-launch behavior: outputs stay
    correct, and a donated (1-D, aliased) input is deleted — re-using
    it is an error, exactly JAX's donated-buffer contract."""
    n = 1024
    x = jnp.arange(n, dtype=jnp.float32)
    y = jnp.ones((n,), jnp.float32)
    o = jnp.zeros((n,), jnp.float32)
    want = 2.5 * np.arange(n, dtype=np.float32) + 1.0
    r = _saxpy.launch(grid=4, block=256, args=(o, x, y, n),
                      donate=True)
    np.testing.assert_allclose(np.asarray(r["out"]), want, rtol=1e-6)
    # the flat binding of a 1-D jax input aliases the caller's buffer:
    # after donation it is deleted, and re-launching with it raises
    with pytest.raises(Exception):
        _saxpy.launch(grid=4, block=256,
                      args=(jnp.zeros((n,), jnp.float32), x, y, n))


def test_donate_chained_stream_relaunch():
    """The donation payoff: an in-order stream re-launching over its own
    previous outputs — each step consumes the last step's buffer."""
    d, s, _ = _fresh()
    n = 1024
    cur = jnp.zeros((n,), jnp.float32)
    x = jnp.arange(n, dtype=jnp.float32) / n
    h = s.launch(_saxpy, grid=4, block=256,
                 args=(cur, x, jnp.zeros((n,), jnp.float32), n))
    for _ in range(3):
        h = s.launch(_scale, grid=4, block=256,
                     args=(h.outputs["out"],
                           h.outputs["out"], n))
    got = h.result()["out"]
    want = np.asarray(2.5 * np.asarray(x), np.float32)
    for _ in range(3):
        want = want * 3.0 + 1.0
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


def test_donated_producer_output_does_not_break_bookkeeping():
    """Regression: a ``donate=True`` consumer deletes the producer's
    output buffer; the dispatcher's in-flight pruning and syncs must
    treat deleted outputs as complete instead of querying them."""
    d, s1, s2 = _fresh()
    n = 1024
    x = jnp.arange(n, dtype=jnp.float32) / n
    h1 = s1.launch(_scale, grid=4, block=256,
                   args=(jnp.zeros((n,), jnp.float32), x, n))
    h2 = s2.launch(_scale, grid=4, block=256,
                   args=(jnp.zeros((n,), jnp.float32),
                         h1.outputs["out"], n), donate=True)
    got = h2.result()["out"]              # flush + prune over deleted bufs
    d.sync_all()                          # and the stream/device syncs
    s1.synchronize()
    assert h1.done() and h2.done()
    want = (np.asarray(x) * 3.0 + 1.0) * 3.0 + 1.0
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


def test_donate_uncached_runtime_launch():
    from repro.core import runtime
    n = 512
    ck = _saxpy.compiled(block=256)
    x = jnp.arange(n, dtype=jnp.float32)
    y = jnp.ones((n,), jnp.float32)
    out = runtime.launch(ck, grid=2, block=256,
                         args=(jnp.zeros((n,), jnp.float32), x, y, n),
                         donate=True)
    np.testing.assert_allclose(
        np.asarray(out["out"]),
        2.5 * np.arange(n, dtype=np.float32) + 1.0, rtol=1e-6)
    with pytest.raises(Exception):
        jnp.asarray(x) + 1.0              # donated input was consumed


def test_donate_splits_launch_cache():
    """A donating executable aliases its inputs; it must never be
    served to a non-donating launch of the same geometry."""
    o, x, y, n = _args(512)
    _saxpy.launch(grid=2, block=128, args=(o, x, y, n))
    n1 = len(_saxpy._launch_cache)
    _saxpy.launch(grid=2, block=128, args=(o, x, y, n), donate=True)
    assert len(_saxpy._launch_cache) == n1 + 1


def test_request_kernel_pool_on_per_slot_streams():
    """The serving path's per-request kernel pool: histograms issued on
    per-slot streams, collected with one sync, totals exact."""
    from repro.launch.serve import RequestKernelPool
    pool = RequestKernelPool(2, nbins=8)
    pool.submit(0, [1, 2, 3, 9])
    pool.submit(1, [4, 4, 4])
    pool.submit(0, [])                    # empty request: no launch
    hists = pool.collect()
    assert len(hists) == 2
    np.testing.assert_array_equal(
        hists[0], np.bincount(np.array([1, 2, 3, 9]) % 8, minlength=8))
    np.testing.assert_array_equal(
        hists[1], np.bincount(np.array([4, 4, 4]) % 8, minlength=8))
    assert {h.stream.name for h in pool.handles} == {"req-slot0",
                                                     "req-slot1"}


def test_donate_rejected_on_sharded():
    mesh = make_mesh((1,), ("data",))
    o, x, y, n = _args(512)
    with pytest.raises(CoxUnsupported):
        _saxpy.launch(grid=2, block=128, args=(o, x, y, n),
                      donate=True, mesh=mesh)


# ---------------------------------------------------------------------------
# dispatch_log retention: bounded structurally, not by ad-hoc trims
# ---------------------------------------------------------------------------


def test_dispatch_log_is_bounded_deque():
    """A long launch loop must keep host bookkeeping flat: the log is a
    ``deque(maxlen=...)``, so it can never exceed its bound no matter
    how many launches a long-lived serving process issues — and it
    retains exactly the most recent dispatches, in order."""
    from collections import deque

    d = Dispatcher(dispatch_log_max=16)
    s = Stream("loop", d)
    assert isinstance(d.dispatch_log, deque)
    assert d.dispatch_log.maxlen == 16
    o, x, y, n = _args(256)
    handles = [s.launch(_saxpy, grid=1, block=64, args=(o, x, y, n))
               for _ in range(40)]
    s.synchronize()
    assert len(d.dispatch_log) == 16       # never grows past maxlen
    assert list(d.dispatch_log) == [h.request.seq for h in handles[-16:]]
    # the in-flight table drained too — no per-launch state survives
    assert not d._inflight and not d._pending
