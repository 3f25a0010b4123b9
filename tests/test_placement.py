"""Multi-device stream placement: streams mapped onto mesh devices.

The multi-device cases run out-of-process (the XLA host device count
must be fixed before jax initializes); the placement-independent
semantics — priorities, single-device no-op defaults, the device=/mesh=
contract, per-device sticky scoping — run in-process on the normal
single-device pool.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import cox
from repro.core.streams import Dispatcher
from repro.launch.mesh import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_worker(body: str) -> str:
    code = textwrap.dedent(body)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900, cwd=ROOT)
    assert r.returncode == 0, f"worker failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


PREAMBLE = """
    import jax, numpy as np
    from repro.core import cox
    from repro.core.streams import Dispatcher
    from repro.launch.mesh import device_pool
    # file-backed kernel (inspect.getsource can't see `python -c` code);
    # vec_madd computes out = 2*x + y
    from tests.multidevice_kernels import vec_madd as placeSaxpy
    assert len(jax.devices()) == 4

    grid, block = 8, 256
    n = grid * block
    rng = np.random.default_rng(0)
    x = rng.normal(size=n).astype(np.float32)
    y = rng.normal(size=n).astype(np.float32)
    o = np.zeros(n, np.float32)
    args = (o, x, y, n)
"""


# ---------------- multi-device (subprocess) ----------------


def test_round_robin_spread_and_bitwise_equality():
    # 4 streams over a 4-device pool: round-robin gives each stream its
    # own device (kept — affinity), every (backend, warp_exec) cell's
    # output is bitwise-equal to the unplaced single-device launch
    run_worker(PREAMBLE + """
    want = placeSaxpy.launch(grid=grid, block=block, args=args)["out"]
    d = Dispatcher(devices=device_pool(4))
    streams = [cox.Stream(f"s{i}", dispatcher=d) for i in range(4)]
    cells = [("scan", "serial"), ("scan", "batched"),
             ("vmap", "serial"), ("vmap", "batched")]
    for backend, we in cells:
        hs = [s.launch(placeSaxpy, grid=grid, block=block, args=args,
                       backend=backend, warp_exec=we) for s in streams]
        for h in hs:
            np.testing.assert_array_equal(
                np.asarray(h.result()["out"]), np.asarray(want),
                err_msg=f"{backend}/{we}")
    devs = [s.device for s in streams]
    assert all(dv is not None for dv in devs), devs
    assert len({dv.id for dv in devs}) == 4, devs  # spread, one each
    health = d.device_health()
    used = {k: c for k, c in health.items() if c["dispatches"] > 0}
    assert len(used) == 4, health
    # affinity: a second round keeps every stream on its device
    hs = [s.launch(placeSaxpy, grid=grid, block=block, args=args)
          for s in streams]
    for h in hs:
        h.result()
    assert [s.device.id for s in streams] == [dv.id for dv in devs]
    print("spread OK")
    """)


def test_pool_sizes_bitwise_equal_to_one_device_pool():
    # the same 4-stream program over 1/2/4/8-device pools (8 forced
    # host devices): every pool's outputs are bitwise-equal to the
    # 1-device pool's, and min(k, 4) devices take dispatches
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "placement_worker.py")],
        capture_output=True, text=True, env=env, timeout=900, cwd=ROOT)
    assert r.returncode == 0, f"worker failed:\n{r.stdout}\n{r.stderr}"
    for k in (1, 2, 4, 8):
        assert f"placement.devices_{k}: " in r.stdout, r.stdout
    assert "placement-ok" in r.stdout


def test_cross_device_event_and_data_edges():
    # producer pinned to device 0, consumer pinned to device 1: the
    # data edge crosses devices through an explicit transfer, the event
    # edge orders them, and the consumer's output lives on device 1
    run_worker(PREAMBLE + """
    d = Dispatcher(devices=device_pool(4))
    dev0, dev1 = d.devices[0], d.devices[1]
    s0 = cox.Stream("prod", dispatcher=d, device=dev0)
    s1 = cox.Stream("cons", dispatcher=d, device=dev1)
    h0 = s0.launch(placeSaxpy, grid=grid, block=block, args=args)
    ev = s0.record_event()
    s1.wait_event(ev)
    h1 = s1.launch(placeSaxpy, grid=grid, block=block,
                   args=(o, h0.outputs["out"], y, n))
    out = h1.result()["out"]
    assert set(out.devices()) == {dev1}, out.devices()
    want = 2.0 * (2.0 * x + y) + y
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)
    assert set(h0.result()["out"].devices()) == {dev0}
    print("cross-device edges OK")
    """)


def test_health_aware_routing_and_device_reset():
    # a sticky device fault poisons ONE device: placement routes new
    # work around it, the poisoned stream re-places off it, and
    # device_reset(device=...) restores just that device
    run_worker(PREAMBLE + """
    d = Dispatcher(devices=device_pool(4),
                   placement=cox.HealthAwarePlacement())
    s = cox.Stream("victim", dispatcher=d)
    with cox.faults.inject("vec_madd", site="sticky-device", times=1):
        h = s.launch(placeSaxpy, grid=grid, block=block, args=args)
        try:
            h.result()
            raise SystemExit("sticky fault did not surface")
        except cox.CoxDeviceError:
            pass
    bad = s.device
    health = d.health()
    assert len(health["sticky_devices"]) == 1, health["sticky_devices"]
    assert health["devices"][str(bad)]["failures"] == 1, health["devices"]
    # enqueue still works: healthy devices remain, placement avoids bad
    others = [cox.Stream(f"n{i}", dispatcher=d) for i in range(6)]
    hs = [st.launch(placeSaxpy, grid=grid, block=block, args=args)
          for st in others]
    for h2 in hs:
        np.testing.assert_array_equal(np.asarray(h2.result()["out"]),
                                      2.0 * x + y)
    assert all(st.device.id != bad.id for st in others), \\
        [(st.name, st.device) for st in others]
    # the poisoned stream itself routes off its old device and recovers
    h3 = s.launch(placeSaxpy, grid=grid, block=block, args=args)
    h3.result()
    assert s.device.id != bad.id, (s.device, bad)
    # single-device recovery: only the poisoned device's state clears
    d.device_reset(device=bad)
    assert d.health()["sticky_devices"] == {}
    fresh = cox.Stream("fresh", dispatcher=d)
    fresh.launch(placeSaxpy, grid=grid, block=block, args=args).result()
    print("health routing OK")
    """)


def test_graph_replay_on_placed_device():
    # a graph captured on a pinned stream inherits the pin: the fused
    # replay executable runs there and its outputs live there
    run_worker(PREAMBLE + """
    d = Dispatcher(devices=device_pool(4))
    dev2 = d.devices[2]
    s = cox.Stream("gcap", dispatcher=d, device=dev2)
    g = cox.Graph(name="placed-chain")
    with g.capture(s):
        h = s.launch(placeSaxpy, grid=grid, block=block, args=args)
        s.launch(placeSaxpy, grid=grid, block=block,
                 args=(o, h.outputs["out"], y, n))
    exe = g.instantiate()
    assert exe.device is dev2, exe.device
    out = exe.replay()["out"]
    assert set(out.devices()) == {dev2}, out.devices()
    he = s.launch(placeSaxpy, grid=grid, block=block, args=args)
    he2 = s.launch(placeSaxpy, grid=grid, block=block,
                   args=(o, he.outputs["out"], y, n))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(he2.result()["out"]))
    print("placed graph replay OK")
    """)


# ---------------- placement-independent semantics (in-process) ----------


@cox.kernel
def prioAdd(c, out: cox.Array(cox.f32), x: cox.Array(cox.f32),
            n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        out[i] = x[i] + 1.0


def _req(kern, n=256):
    x = np.arange(n, dtype=np.float32)
    return kern.make_request(grid=1, block=n,
                             args=(np.zeros(n, np.float32), x, n))


def test_priority_orders_ready_set():
    # among simultaneously-ready independent requests the dispatcher
    # serves lower priority numbers first (CUDA stream priorities);
    # enqueue via the dispatcher directly so nothing flushes early
    d = Dispatcher()
    lo = cox.Stream("lo", dispatcher=d, priority=5)
    hi = cox.Stream("hi", dispatcher=d, priority=-5)
    mid = cox.Stream("mid", dispatcher=d)
    hs = [d.enqueue(_req(prioAdd), lo),
          d.enqueue(_req(prioAdd), mid),
          d.enqueue(_req(prioAdd), hi)]
    d.flush()
    for h in hs:
        h.result()
    seqs = {h.request.seq: h.stream.name for h in hs}
    order = [seqs[s] for s in d.dispatch_log if s in seqs]
    assert order == ["hi", "mid", "lo"], order
    assert [h.request.priority for h in hs] == [5, 0, -5]


def test_program_order_beats_priority_within_stream():
    # priority never reorders one stream's in-order queue: a stream's
    # second launch stays behind its first even if a higher-priority
    # request from another stream lands between them
    d = Dispatcher()
    lo = cox.Stream("lo2", dispatcher=d, priority=5)
    hi = cox.Stream("hi2", dispatcher=d, priority=-5)
    h1 = d.enqueue(_req(prioAdd), lo)
    h2 = d.enqueue(_req(prioAdd), lo)
    h3 = d.enqueue(_req(prioAdd), hi)
    d.flush()
    for h in (h1, h2, h3):
        h.result()
    pos = {h.request.seq: i for i, h in enumerate((h1, h2, h3))}
    order = [pos[s] for s in d.dispatch_log if s in pos]
    assert order.index(0) < order.index(1), order
    assert order[0] == 2, order  # hi dispatched first overall


def test_single_device_pool_is_legacy_path():
    # one device in the pool: no placement, no transfers — request
    # device stays None and the stage key's device slot records that
    d = Dispatcher()
    assert len(d.devices) == 1
    s = cox.Stream("solo", dispatcher=d)
    h = s.launch(prioAdd, grid=1, block=256,
                 args=(np.zeros(256, np.float32),
                       np.arange(256, dtype=np.float32), 256))
    h.result()
    assert h.request.device is None
    assert h.request.stage_key()[-1] is None
    assert s.device is None


def test_explicit_device_pin_single_pool():
    # an explicit device= runs there even on a 1-device pool, and the
    # staged executable is keyed per-device
    dev0 = jax.devices()[0]
    d = Dispatcher()
    s = cox.Stream("pin", dispatcher=d, device=dev0)
    x = np.arange(256, dtype=np.float32)
    h = s.launch(prioAdd, grid=1, block=256,
                 args=(np.zeros(256, np.float32), x, 256))
    out = h.result()["out"]
    assert h.request.device is dev0
    assert h.request.stage_key()[-1] == dev0.id
    np.testing.assert_array_equal(np.asarray(out), x + 1.0)


def test_device_and_mesh_are_mutually_exclusive():
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(cox.CoxUnsupported, match="mutually exclusive"):
        prioAdd.make_request(grid=1, block=256,
                             args=(np.zeros(256, np.float32),
                                   np.arange(256, dtype=np.float32), 256),
                             device=jax.devices()[0], mesh=mesh)


def test_per_device_sticky_scoped_and_reset():
    # a sticky fault on a placed launch poisons that device, blocks the
    # (exhausted) pool, and device_reset(device=...) — not a full
    # reset — restores it
    dev0 = jax.devices()[0]
    d = Dispatcher()
    s = cox.Stream("sick", dispatcher=d, device=dev0)
    x = np.arange(256, dtype=np.float32)
    arr = (np.zeros(256, np.float32), x, 256)
    with cox.faults.inject("prioAdd", site="sticky-device", times=1):
        h = s.launch(prioAdd, grid=1, block=256, args=arr)
        with pytest.raises(cox.CoxDeviceError):
            h.result()
    assert list(d.health()["sticky_devices"]) == [str(dev0)]
    # every pool device is poisoned -> enqueue fails fast, CUDA-style
    s2 = cox.Stream("after", dispatcher=d)
    with pytest.raises(cox.CoxDeviceError):
        s2.launch(prioAdd, grid=1, block=256, args=arr)
    d.device_reset(device=dev0)
    assert d.health()["sticky_devices"] == {}
    out = s2.launch(prioAdd, grid=1, block=256, args=arr).result()["out"]
    np.testing.assert_array_equal(np.asarray(out), x + 1.0)
