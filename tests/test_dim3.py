"""dim3 launch geometry, end to end.

The contract under test: launch geometry is CUDA ``dim3`` at every
interface (frontend intrinsics, plan, runtime, cache) while the
internal schedule stays *linear* — threads linearize x-fastest into
warps (``lin = x + bdim.x * (y + bdim.y * z)``), blocks linearize the
same way into the grid walk.  Covers the decomposition round-trip
(hypothesis-randomized geometries incl. partial last warps and
non-multiple-of-32 x*y blocks), the per-thread oracle, CUDA's launch
limits, launch-cache normalization (``grid=4`` == ``grid=(4,1,1)``),
and bitwise backend x warp_exec equivalence for the 2-D suite kernels.
"""
import numpy as np
import pytest

from benchmarks.kernels_suite import all_kernels
from repro.core import cox
from repro.core.oracle import run_grid as oracle_run
from repro.core.types import CoxUnsupported, Dim3, as_dim3

try:  # hypothesis drives the randomized-geometry properties in CI; a
    # seeded numpy fallback keeps them exercised where it is absent
    from hypothesis import assume, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# normalization + limits
# ---------------------------------------------------------------------------


def test_as_dim3_normalizes():
    assert as_dim3(5) == Dim3(5, 1, 1)
    assert as_dim3((7,)) == Dim3(7, 1, 1)
    assert as_dim3((2, 3)) == Dim3(2, 3, 1)
    assert as_dim3([2, 3, 4]) == Dim3(2, 3, 4)
    assert as_dim3(Dim3(1, 2, 3)) == Dim3(1, 2, 3)
    assert as_dim3(np.int64(6)) == Dim3(6, 1, 1)
    assert as_dim3((2, 3)).total == 6
    with pytest.raises(ValueError):
        as_dim3(0)
    with pytest.raises(ValueError):
        as_dim3((4, -1))
    with pytest.raises(ValueError):
        as_dim3((1, 2, 3, 4))
    with pytest.raises(TypeError):
        as_dim3("x")
    with pytest.raises(TypeError):
        as_dim3((1.5, 2))


@cox.kernel
def _k_copy(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32), n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        out[i] = a[i]


def _copy_args(n=64):
    return (np.zeros(n, np.float32), np.ones(n, np.float32), n)


def test_cuda_launch_limits_enforced():
    # total threads per block
    with pytest.raises(CoxUnsupported):
        _k_copy.launch(grid=1, block=(1024, 2), args=_copy_args())
    # per-axis block caps (total fine, z over 64)
    with pytest.raises(CoxUnsupported):
        _k_copy.launch(grid=1, block=(1, 1, 128), args=_copy_args())
    # grid y/z cap at 65535
    with pytest.raises(CoxUnsupported):
        _k_copy.launch(grid=(1, 70000), block=32, args=_copy_args())
    with pytest.raises(ValueError):
        _k_copy.launch(grid=0, block=32, args=_copy_args())


def test_axis_argument_validation():
    with pytest.raises(CoxUnsupported):
        @cox.kernel
        def _bad_lane(c, o: cox.Array(cox.f32)):
            i = c.lane_id('y')
            o[i] = 1.0
    with pytest.raises(CoxUnsupported):
        @cox.kernel
        def _bad_axis(c, o: cox.Array(cox.f32)):
            i = c.thread_idx('w')
            o[i] = 1.0
    with pytest.raises(CoxUnsupported):
        @cox.kernel
        def _bad_dynamic(c, o: cox.Array(cox.f32), ax: cox.i32):
            i = c.thread_idx(ax)
            o[i] = 1.0


# ---------------------------------------------------------------------------
# linearization / decomposition round-trip
# ---------------------------------------------------------------------------


@cox.kernel
def _k_geom(c, tx: cox.Array(cox.i32), ty: cox.Array(cox.i32),
            tz: cox.Array(cox.i32), bx: cox.Array(cox.i32),
            by: cox.Array(cox.i32), bz: cox.Array(cox.i32),
            cnt: cox.Array(cox.i32)):
    # re-linearize the decomposed ids x-fastest; a correct decomposition
    # makes g a bijection onto the launch's thread slots (cnt == 1)
    lin = c.thread_idx('x') + c.block_dim('x') * (
        c.thread_idx('y') + c.block_dim('y') * c.thread_idx('z'))
    blin = c.block_idx('x') + c.grid_dim('x') * (
        c.block_idx('y') + c.grid_dim('y') * c.block_idx('z'))
    nthreads = c.block_dim('x') * c.block_dim('y') * c.block_dim('z')
    g = blin * nthreads + lin
    tx[g] = c.thread_idx('x')
    ty[g] = c.thread_idx('y')
    tz[g] = c.thread_idx('z')
    bx[g] = c.block_idx('x')
    by[g] = c.block_idx('y')
    bz[g] = c.block_idx('z')
    cnt[g] += 1


def _geom_ref(grid3: Dim3, block3: Dim3):
    """Per-slot reference components, x-fastest linearization."""
    nt, nb = block3.total, grid3.total
    t = np.arange(nt, dtype=np.int32)
    b = np.arange(nb, dtype=np.int32)
    tx = t % block3.x
    ty = (t // block3.x) % block3.y
    tz = t // (block3.x * block3.y)
    bx = b % grid3.x
    by = (b // grid3.x) % grid3.y
    bz = b // (grid3.x * grid3.y)
    def tile(v):
        return np.tile(v, nb)

    def rep(v):
        return np.repeat(v, nt)
    return {"tx": tile(tx), "ty": tile(ty), "tz": tile(tz),
            "bx": rep(bx), "by": rep(by), "bz": rep(bz)}


def _check_geometry(grid, block, **launch_kw):
    grid3, block3 = as_dim3(grid), as_dim3(block)
    n = grid3.total * block3.total
    args = tuple(np.zeros(n, np.int32) for _ in range(7))
    out = _k_geom.launch(grid=grid, block=block, args=args, **launch_kw)
    ref = _geom_ref(grid3, block3)
    for k, want in ref.items():
        np.testing.assert_array_equal(np.asarray(out[k]), want,
                                      err_msg=f"{k} @ {grid3}x{block3}")
    # bijectivity: every slot written exactly once (also proves partial
    # last warps masked the dead lanes rather than scribbling)
    np.testing.assert_array_equal(np.asarray(out["cnt"]), np.ones(n))


@pytest.mark.parametrize("grid,block", [
    (2, 64),              # pure 1-D through the dim3 path
    ((2, 2), (16, 16)),   # the SDK's classic tile shape
    ((3, 2), (20, 3)),    # x*y = 60: 2 warps, partial last warp
    ((2, 1, 2), (33, 2)), # 66 threads: non-multiple-of-32 x*y, 3-D grid
    ((1, 2, 2), (7, 5, 3)),  # full 3-D, 105 threads
    ((5,), (1, 1, 64)),   # degenerate x, all threads along z
])
def test_geometry_round_trip_fixed(grid, block):
    _check_geometry(grid, block)


def test_geometry_round_trip_batched_warps():
    # the batched (n_warps, W) lane plane decomposes the same ids
    _check_geometry((2, 2), (16, 16), warp_exec="batched")
    _check_geometry((3, 2), (20, 3), warp_exec="batched")


def _pure_round_trip(bx, by, bz, lin):
    """decompose(lin) relinearizes to lin for every in-range linear id
    (the executor and oracle share this formula)."""
    x, y, z = lin % bx, (lin // bx) % by, lin // (bx * by)
    assert 0 <= x < bx and 0 <= y < by and 0 <= z < bz
    assert x + bx * (y + by * z) == lin


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(gx=st.integers(1, 3), gy=st.integers(1, 3), gz=st.integers(1, 2),
           bx=st.integers(1, 40), by=st.integers(1, 5), bz=st.integers(1, 3))
    def test_geometry_round_trip_random(gx, gy, gz, bx, by, bz):
        assume(bx * by * bz <= 128)
        _check_geometry((gx, gy, gz), (bx, by, bz))

    @settings(max_examples=200, deadline=None)
    @given(bx=st.integers(1, 64), by=st.integers(1, 64),
           bz=st.integers(1, 64), lin=st.integers(0, 1024 - 1))
    def test_decompose_relinearize_pure(bx, by, bz, lin):
        assume(lin < bx * by * bz)
        _pure_round_trip(bx, by, bz, lin)
else:
    def test_geometry_round_trip_random():
        rng = np.random.default_rng(1234)
        done = 0
        while done < 8:
            gx, gy, gz = rng.integers(1, 4), rng.integers(1, 4), \
                rng.integers(1, 3)
            bx, by, bz = rng.integers(1, 41), rng.integers(1, 6), \
                rng.integers(1, 4)
            if bx * by * bz > 128:
                continue
            _check_geometry((int(gx), int(gy), int(gz)),
                            (int(bx), int(by), int(bz)))
            done += 1

    def test_decompose_relinearize_pure():
        rng = np.random.default_rng(99)
        done = 0
        while done < 500:
            bx, by, bz = (int(v) for v in rng.integers(1, 65, size=3))
            lin = int(rng.integers(0, 1024))
            if lin >= bx * by * bz:
                continue
            _pure_round_trip(bx, by, bz, lin)
            done += 1


# ---------------------------------------------------------------------------
# oracle agreement + 1-D equivalence of bare intrinsics
# ---------------------------------------------------------------------------


def test_geom_probe_matches_oracle():
    grid, block = (2, 3), (8, 5)  # 40 threads: partial last warp
    n = 6 * 40
    args = tuple(np.zeros(n, np.int32) for _ in range(7))
    got = _k_geom.launch(grid=grid, block=block, args=args)
    ref = oracle_run(_k_geom.ir, grid=grid, block=block, args=args)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(got[k]), ref[k],
                                      err_msg=k)


def test_bare_intrinsics_are_axis_x():
    """A 1-D kernel launched with explicit dim3 tuples is bitwise
    identical to the bare int launch."""
    args = _copy_args()
    want = _k_copy.launch(grid=2, block=32, args=args)
    got = _k_copy.launch(grid=(2, 1, 1), block=(32,), args=args)
    np.testing.assert_array_equal(np.asarray(got["out"]),
                                  np.asarray(want["out"]))


# ---------------------------------------------------------------------------
# launch cache: normalized dim3 keys + stable compile token
# ---------------------------------------------------------------------------


@cox.kernel
def _k_cache(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32)):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    out[i] = a[i] + 1.0


def test_cache_hits_on_equivalent_dim3():
    args = (np.zeros(256, np.float32), np.ones(256, np.float32))
    _k_cache.launch(grid=4, block=64, args=args)
    n1 = len(_k_cache._launch_cache)
    _k_cache.launch(grid=(4, 1, 1), block=(64,), args=args)
    assert len(_k_cache._launch_cache) == n1      # grid=4 == (4,1,1): hit
    _k_cache.launch(grid=(2, 2), block=64, args=args)
    assert len(_k_cache._launch_cache) == n1 + 1  # same total, new shape:
    #                                               bid decomposition differs


def test_cache_token_is_stable_not_object_id():
    """The first key element is the pass-pipeline cache key, not an
    ``id()`` that a recycled allocation could alias."""
    args = (np.zeros(64, np.float32), np.ones(64, np.float32))
    _k_cache.launch(grid=1, block=64, args=args)
    tokens = {k[0] for k in _k_cache._launch_cache}
    for token in tokens:
        choice, ws = token
        assert choice in ("flat", "hier") and isinstance(ws, int)


def test_resolution_is_shared_between_api_and_runtime():
    """api.KernelFn.launch and runtime.launch resolve through the same
    path — same plan geometry, same resolved knobs, dim3 accepted by
    both."""
    from repro.core import runtime
    args = (np.zeros(256, np.float32), np.ones(256, np.float32))
    ck = _k_cache.compiled(block=(8, 8))
    rl = runtime.resolve_launch(ck, grid=(2, 2), block=(8, 8))
    assert rl.grid == Dim3(2, 2, 1) and rl.block == Dim3(8, 8, 1)
    # hybrid picks flat collapsing here (no warp features): the whole
    # 64-thread block is one "warp"
    assert rl.n_warps == -(-64 // ck.warp_size)
    assert rl.mode in ("normal", "jit")
    out = runtime.launch(ck, grid=(2, 2), block=(8, 8), args=args)
    want = _k_cache.launch(grid=(2, 2), block=(8, 8), args=args)
    np.testing.assert_array_equal(np.asarray(out["out"]),
                                  np.asarray(want["out"]))
    plans = [p for (p, _) in _k_cache._launch_cache.values()]
    assert any(p.grid == 4 and p.block == 64
               and p.grid_dim == Dim3(2, 2, 1)
               and p.block_dim == Dim3(8, 8, 1) for p in plans)


# ---------------------------------------------------------------------------
# the 2-D suite kernels: backend x warp_exec cells, bitwise
# ---------------------------------------------------------------------------

_DIM3_PICKS = ["MatrixMulCUDA", "transpose", "stencil2d"]


@pytest.mark.parametrize("name", _DIM3_PICKS)
def test_dim3_kernels_all_cells_bitwise_and_oracle(name):
    sk = next(k for k in all_kernels() if k.name == name)
    args = sk.make_args()
    base = sk.kernel.launch(grid=sk.grid, block=sk.block, args=args,
                            backend="scan", warp_exec="serial")
    ref = oracle_run(sk.kernel.ir, grid=sk.grid, block=sk.block, args=args)
    for k in ref:
        np.testing.assert_allclose(np.asarray(base[k], np.float32),
                                   np.asarray(ref[k], np.float32),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{name}.{k} vs oracle")
    if sk.check is not None:
        assert sk.check({k: np.asarray(v) for k, v in base.items()})
    for backend in ("scan", "vmap"):
        for we in ("serial", "batched"):
            got = sk.kernel.launch(grid=sk.grid, block=sk.block, args=args,
                                   backend=backend, warp_exec=we, chunk=3)
            for k in base:
                np.testing.assert_array_equal(
                    np.asarray(got[k]), np.asarray(base[k]),
                    err_msg=f"{name}.{k}: {backend}/{we} != scan/serial")


@pytest.mark.parametrize("name", _DIM3_PICKS)
def test_dim3_kernels_sharded_one_device_mesh(name):
    from repro.launch.mesh import make_mesh
    sk = next(k for k in all_kernels() if k.name == name)
    mesh = make_mesh((1,), ("data",))
    args = sk.make_args()
    want = sk.kernel.launch(grid=sk.grid, block=sk.block, args=args,
                            backend="scan")
    got = sk.kernel.launch(grid=sk.grid, block=sk.block, args=args,
                           mesh=mesh, chunk=3)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]),
                                      err_msg=f"{name}.{k}")


def test_natural_2d_matmul_equals_hand_flattened_1d():
    """The dim3 rewrite of MatrixMulCUDA computes bit-for-bit what the
    hand-flattened 1-D port computes (same linearized schedule, same
    operation order per thread)."""
    mm2 = next(k for k in all_kernels() if k.name == "MatrixMulCUDA")
    mm1 = next(k for k in all_kernels() if k.name == "matrixMul1D")
    args = mm2.make_args()
    got2 = mm2.kernel.launch(grid=mm2.grid, block=mm2.block, args=args)
    got1 = mm1.kernel.launch(grid=mm1.grid, block=mm1.block, args=args)
    np.testing.assert_array_equal(np.asarray(got2["out"]),
                                  np.asarray(got1["out"]))
