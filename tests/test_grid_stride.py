"""Grid-stride lowering: resident waves loop over oversubscribed grids.

The tentpole contract: ``schedule='grid_stride'`` runs a fixed number
of resident block slots (``n_resident``) that loop over strided block
ids, so the host never materializes the O(grid) chunk tables and the
per-wave working set stays inside ``costmodel.FOOTPRINT_BUDGET``
regardless of grid size.  Wave *i* covers exactly the contiguous bids
of chunk row *i* of a ``chunk=n_resident`` chunked schedule, so the two
are bitwise-identical by construction — verified here across every
runnable suite kernel, all three backends × both warp-exec flavors,
atomics, a partial last wave, dim3 grids, captured-graph replay, and
placed multi-device runs.  The footprint verdict
(``costmodel.schedule_verdict``) and its provenance
(``schedule_source``) are pinned alongside; tests that need a small
budget monkeypatch the constant.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from benchmarks.kernels_suite import all_kernels
from repro.core import cox
from repro.core import costmodel
from repro.core.backends.plan import DEFAULT_CHUNK, LaunchPlan
from repro.core.runtime import resolve_launch, resolve_schedule
from repro.core.streams import Dispatcher, Stream
from repro.launch.mesh import make_mesh

jax = pytest.importorskip("jax")

VECTOR_ADD = next(k for k in all_kernels() if k.name == "vectorAdd")
HISTOGRAM = next(k for k in all_kernels() if k.name == "histogram64")
GRID_REDUCE = next(k for k in all_kernels() if k.name == "gridReduce")
RUNNABLE = [sk for sk in all_kernels() if sk.kernel is not None]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@cox.kernel
def _saxpy(c, out: cox.Array(cox.f32), x: cox.Array(cox.f32),
           y: cox.Array(cox.f32), n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        out[i] = 2.5 * x[i] + y[i]


def _saxpy_args(grid, block, seed=0):
    n = grid * block
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(n).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    return (np.zeros(n, np.float32), x, y, np.int32(n))


def _np(out):
    return {k: np.asarray(v) for k, v in out.items()}


# ---------------------------------------------------------------------------
# bitwise equivalence: grid-stride == chunked, backends × warp-exec
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["scan", "vmap"])
@pytest.mark.parametrize("warp_exec", ["serial", "batched"])
def test_stride_matches_chunked_bitwise(backend, warp_exec):
    # grid=10, n_resident=3: four waves, the last one a single live slot
    grid, block = 10, 64
    args = _saxpy_args(grid, block)
    kw = dict(grid=grid, block=block, args=args, backend=backend,
              warp_exec=warp_exec)
    want = _np(_saxpy.launch(**kw, chunk=3))
    got = _np(_saxpy.launch(**kw, schedule="grid_stride", n_resident=3))
    np.testing.assert_array_equal(got["out"], want["out"],
                                  err_msg=f"{backend}/{warp_exec}")


@pytest.mark.parametrize("sk", RUNNABLE, ids=lambda sk: sk.name)
def test_suite_stride_matches_chunked(sk):
    # every runnable suite kernel: a two-slot grid-stride wave against
    # the chunk-table walk, same arguments, bitwise (cooperative kernels
    # page their blocks through the two slots phase by phase)
    args = sk.make_args()
    kw = dict(grid=sk.grid, block=sk.block, args=args)
    want = _np(sk.kernel.launch(**kw, schedule="chunked"))
    got = _np(sk.kernel.launch(**kw, schedule="grid_stride", n_resident=2))
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_array_equal(got[k], want[k],
                                      err_msg=f"{sk.name}.{k}")


@pytest.mark.parametrize("warp_exec", ["serial", "batched"])
def test_stride_matches_chunked_sharded(warp_exec):
    mesh = make_mesh((1,), ("data",))
    grid, block = 10, 64
    args = _saxpy_args(grid, block)
    kw = dict(grid=grid, block=block, args=args, mesh=mesh,
              warp_exec=warp_exec)
    want = _np(_saxpy.launch(**kw, chunk=3))
    got = _np(_saxpy.launch(**kw, schedule="grid_stride", n_resident=3))
    np.testing.assert_array_equal(got["out"], want["out"],
                                  err_msg=f"sharded/{warp_exec}")


@pytest.mark.parametrize("backend", ["scan", "vmap"])
def test_stride_atomics_match(backend):
    # histogram64: atomic_add deltas must fold identically per wave
    sk = HISTOGRAM
    args = sk.make_args()
    kw = dict(grid=sk.grid, block=sk.block, args=args, backend=backend)
    want = _np(sk.kernel.launch(**kw))
    got = _np(sk.kernel.launch(**kw, schedule="grid_stride", n_resident=5))
    np.testing.assert_array_equal(got["hist"], want["hist"],
                                  err_msg=backend)
    assert got["hist"].sum() == np.asarray(args[2])


def test_stride_partial_last_wave():
    # grid=7, n_resident=4: wave 1 has three live slots and one pad —
    # padded bids must write nothing and contribute zero atomic delta
    grid, block = 7, 32
    args = _saxpy_args(grid, block, seed=2)
    kw = dict(grid=grid, block=block, args=args, backend="vmap")
    want = _np(_saxpy.launch(**kw))
    got = _np(_saxpy.launch(**kw, schedule="grid_stride", n_resident=4))
    np.testing.assert_array_equal(got["out"], want["out"])


@cox.kernel
def _saxpy2d(c, out: cox.Array(cox.f32), x: cox.Array(cox.f32),
             y: cox.Array(cox.f32), n: cox.i32):
    # CUDA 2-D grid idiom: linearize blockIdx x-fastest
    b = c.block_idx('x') + c.grid_dim('x') * c.block_idx('y')
    i = b * c.block_dim() + c.thread_idx()
    if i < n:
        out[i] = 2.5 * x[i] + y[i]


def test_stride_dim3_grid():
    # dim3 geometry linearizes before scheduling: (5, 2) == 10 blocks,
    # strided 3 at a time across both grid rows
    block = 64
    args = _saxpy_args(10, block)
    want = _np(_saxpy2d.launch(grid=(5, 2), block=block, args=args,
                               backend="vmap", chunk=3))
    got = _np(_saxpy2d.launch(grid=(5, 2), block=block, args=args,
                              backend="vmap", schedule="grid_stride",
                              n_resident=3))
    np.testing.assert_array_equal(got["out"], want["out"])
    np.testing.assert_allclose(
        want["out"], np.float32(2.5) * args[1] + args[2],
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("backend", ["scan", "vmap", "sharded"])
def test_stride_cooperative_pages_blocks_through_phases(backend):
    # multi-phase gridReduce with a 3-slot wave: all waves of phase p
    # complete before phase p+1, per-block persist state pages in and
    # out of the capacity window — results stay bitwise-equal to the
    # all-resident cooperative launch
    sk = GRID_REDUCE
    args = sk.make_args()
    kw = dict(grid=sk.grid, block=sk.block, args=args)
    if backend == "sharded":
        kw["mesh"] = make_mesh((1,), ("data",))
    else:
        kw["backend"] = backend
    want = _np(sk.kernel.launch(**kw))
    got = _np(sk.kernel.launch(**kw, schedule="grid_stride", n_resident=3))
    for k in want:
        np.testing.assert_array_equal(got[k], want[k],
                                      err_msg=f"{backend}.{k}")
    assert got["total"][0] == got["partial"].sum()


def test_stride_graph_replay_bitwise_equals_eager():
    d = Dispatcher()
    s = Stream("gs", d)
    grid, block = 10, 64
    args = _saxpy_args(grid, block, seed=4)
    kw = dict(backend="vmap", schedule="grid_stride", n_resident=3)
    want = s.launch(_saxpy, grid=grid, block=block, args=args,
                    **kw).result()["out"]
    g = cox.Graph()
    with g.capture(s):
        s.launch(_saxpy, grid=grid, block=block, args=args, **kw)
    res = g.replay()
    np.testing.assert_array_equal(np.asarray(res["out"]), np.asarray(want))
    res2 = g.replay()
    np.testing.assert_array_equal(np.asarray(res2["out"]),
                                  np.asarray(res["out"]))


def test_stride_placed_multi_device_bitwise():
    # 4 host devices: each mesh device strides its own contiguous bid
    # stripe; the cross-device merge must reproduce the single-device
    # launch exactly (grid=10 over 4 devices: uneven 3/3/3/1 stripes)
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from tests.multidevice_kernels import vec_madd
        assert len(jax.devices()) == 4
        grid, block = 10, 128
        n = grid * block
        rng = np.random.default_rng(0)
        x = rng.normal(size=n).astype(np.float32)
        y = rng.normal(size=n).astype(np.float32)
        args = (np.zeros(n, np.float32), x, y, n)
        want = vec_madd.launch(grid=grid, block=block, args=args)["out"]
        mesh = make_mesh((4,), ("data",))
        got = vec_madd.launch(grid=grid, block=block, args=args, mesh=mesh,
                              schedule="grid_stride", n_resident=2)["out"]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        print("stride-placed-ok")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900, cwd=ROOT)
    assert r.returncode == 0, f"worker failed:\n{r.stdout}\n{r.stderr}"
    assert "stride-placed-ok" in r.stdout


# ---------------------------------------------------------------------------
# the footprint verdict: oversubscribed grids auto-route to grid-stride
# ---------------------------------------------------------------------------


def test_oversubscribed_grid_never_materializes_table_over_budget(
        monkeypatch):
    # satellite regression: grid >> budget.  The chunk table alone is
    # ~4 MiB at grid 2**20; under a 64 KiB budget no chunk value can
    # fit, so the verdict must stride — and the stride footprint is
    # grid-independent, inside budget by construction.
    budget = 64 << 10
    monkeypatch.setattr(costmodel, "FOOTPRINT_BUDGET", budget)
    grid = 1 << 20
    ck = _saxpy.compiled(block=64)
    rl = resolve_launch(ck, grid=grid, block=64)
    shapes = {"out": (256,), "x": (256,), "y": (256,)}
    rl = resolve_schedule(ck, rl, shapes)
    assert rl.schedule == "grid_stride"
    assert rl.schedule_source == "heuristic"
    assert rl.n_resident is not None and rl.n_resident >= 1
    assert costmodel.stride_footprint(
        ck, shapes, n_resident=rl.n_resident,
        n_warps=rl.n_warps, warp_exec=rl.warp_exec) <= budget
    # every chunked alternative would have blown the budget on the
    # table term alone — the clamp loop cannot help, only striding can
    for chunk in costmodel.RESIDENT_CANDIDATES:
        assert costmodel.bid_table_bytes(grid, chunk) > budget
    # and the staged plan carries the stride schedule (chunk == wave
    # width, so any chunk-shaped state is O(n_resident), not O(grid))
    plan = LaunchPlan.build(ck, grid=grid, block=64, chunk=rl.chunk,
                            warp_exec=rl.warp_exec,
                            schedule=rl.schedule, n_resident=rl.n_resident)
    assert plan.schedule == "grid_stride"
    assert plan.chunk == plan.n_resident == rl.n_resident
    assert plan.n_stride_waves() == -(-grid // rl.n_resident)


def test_oversubscribed_launch_runs_and_matches(monkeypatch):
    # end-to-end: a tiny budget forces the stride path on a real
    # launch; the answer must not change
    grid, block = 16, 64
    args = _saxpy_args(grid, block, seed=6)
    want = _np(_saxpy.launch(grid=grid, block=block, args=args,
                             backend="vmap"))
    monkeypatch.setattr(costmodel, "FOOTPRINT_BUDGET", 64)
    req = _saxpy.make_request(grid=grid, block=block, args=args,
                              backend="vmap")
    assert req.rl.schedule == "grid_stride"
    assert req.rl.schedule_source == "heuristic"
    got = _np(_saxpy.launch(grid=grid, block=block, args=args,
                            backend="vmap"))
    np.testing.assert_array_equal(got["out"], want["out"])


def test_scan_verdict_keys_on_the_bid_sequence_alone():
    # scan holds one copy of global memory under every schedule; its
    # only O(grid) state is the arange it scans — stride width 1
    ck = _saxpy.compiled(block=64)
    shapes = {"out": (256,), "x": (256,), "y": (256,)}
    sched, n_res = costmodel.schedule_verdict(
        ck, shapes, grid=1 << 20, chunk=DEFAULT_CHUNK, n_warps=2,
        backend="scan", budget=64 << 10)
    assert (sched, n_res) == ("grid_stride", 1)
    sched, n_res = costmodel.schedule_verdict(
        ck, shapes, grid=64, chunk=DEFAULT_CHUNK, n_warps=2,
        backend="scan", budget=64 << 10)
    assert (sched, n_res) == ("chunked", None)


def test_explicit_schedule_is_never_overridden(monkeypatch):
    monkeypatch.setattr(costmodel, "FOOTPRINT_BUDGET", 64)
    grid, block = 16, 64
    args = _saxpy_args(grid, block)
    req = _saxpy.make_request(grid=grid, block=block, args=args,
                              backend="vmap", schedule="chunked")
    assert req.rl.schedule == "chunked"
    assert req.rl.schedule_source == "explicit"


def test_n_resident_implies_grid_stride():
    ck = _saxpy.compiled(block=64)
    rl = resolve_launch(ck, grid=10, block=64, n_resident=3)
    assert rl.schedule == "grid_stride"
    assert rl.schedule_source == "explicit"
    assert rl.n_resident == 3
    with pytest.raises(ValueError, match="n_resident"):
        resolve_launch(ck, grid=10, block=64, schedule="chunked",
                       n_resident=3)


def test_explicit_grid_stride_without_width_gets_the_sized_wave():
    grid, block = 10, 64
    args = _saxpy_args(grid, block)
    req = _saxpy.make_request(grid=grid, block=block, args=args,
                              backend="vmap", schedule="grid_stride")
    assert req.rl.schedule == "grid_stride"
    assert req.rl.n_resident is not None
    assert 1 <= req.rl.n_resident <= grid
