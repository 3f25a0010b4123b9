"""Backend equivalence: scan ≡ vmap ≡ sharded, serial ≡ batched warps.

The grid-execution backends (repro.core.backends) must agree exactly —
plain stores are single-writer-selected (no arithmetic on the payload),
so vmap/sharded outputs are bitwise-identical to the loop-carried scan
baseline; atomic deltas are integer-valued in these kernels, so their
sums are exact too.  The same bar holds one level down: warp-batched
execution (the (n_warps, W) lane plane) must be bitwise-identical to
the serial inter-warp loop across the full suite.  Covers the coverage
suite (warp-feature kernels included), atomics, grid sizes not
divisible by the chunk size, and the launch-cache / heuristic plumbing.
"""
import numpy as np
import pytest

from benchmarks.kernels_suite import EXTRA_KERNELS, all_kernels
from repro.core import cox
from repro.core import flat as cox_flat
from repro.core.kernel_ir import uses_grid_sync
from repro.core.backends import available_backends, get_backend
from repro.core.backends.plan import LaunchPlan
from repro.core.runtime import resolve_launch
from repro.core.types import CoxUnsupported

RUNNABLE = [sk for sk in all_kernels() if sk.kernel is not None]


def _launch(sk, args=None, **kw):
    # make_args draws fresh RNG data — callers comparing backends must
    # build args once and pass them to every launch
    out = sk.kernel.launch(grid=sk.grid, block=sk.block,
                           args=sk.make_args() if args is None else args,
                           **kw)
    return {k: np.asarray(v) for k, v in out.items()}


@pytest.mark.parametrize("sk", RUNNABLE, ids=lambda sk: sk.name)
def test_vmap_bitwise_matches_scan(sk):
    """Full suite, chunk=3 so most grids (1, 2, 8, 16, 64) leave a
    ragged -1-padded tail chunk.  Cooperative (grid-sync) kernels pin
    their own chunk schedule — every block resident per phase — so they
    run with the plan's forced chunk instead."""
    args = sk.make_args()
    coop = uses_grid_sync(sk.kernel.ir)
    want = _launch(sk, args, backend="scan")
    got = _launch(sk, args, backend="vmap", **({} if coop else {"chunk": 3}))
    for k in want:
        np.testing.assert_array_equal(got[k], want[k],
                                      err_msg=f"{sk.name}.{k}")


@pytest.mark.parametrize("name", ["vectorAdd", "MatrixMulCUDA", "reduce4",
                                  "shfl_scan_test", "VoteAnyKernel3",
                                  "histogram64", "blockCounter"])
def test_sharded_matches_scan_on_one_device_mesh(name):
    """shard_map × vmap recomposition on an in-process 1-device mesh
    (8-device semantics live in test_multidevice.py); representative
    features: plain, block-cg, warp-cg, shuffle, vote, atomics."""
    from repro.launch.mesh import make_mesh
    sk = next(k for k in all_kernels() if k.name == name)
    mesh = make_mesh((1,), ("data",))
    args = sk.make_args()
    want = _launch(sk, args, backend="scan")
    got = _launch(sk, args, mesh=mesh, chunk=3)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k],
                                      err_msg=f"{name}.{k}")


@pytest.mark.parametrize("chunk", [1, 2, 5, 7, 64])
def test_vmap_chunk_sizes_including_indivisible(chunk):
    sk = next(k for k in EXTRA_KERNELS if k.name == "histogram64")  # grid=16
    args = sk.make_args()
    want = _launch(sk, args, backend="scan")
    got = _launch(sk, args, backend="vmap", chunk=chunk)
    np.testing.assert_array_equal(got["hist"], want["hist"])


def test_atomics_plus_stores_in_one_kernel():
    sk = next(k for k in EXTRA_KERNELS if k.name == "blockCounter")
    args = sk.make_args()
    want = _launch(sk, args, backend="scan")
    for backend, kw in (("vmap", {"chunk": 3}), ("vmap", {"chunk": 8})):
        got = _launch(sk, args, backend=backend, **kw)
        np.testing.assert_array_equal(got["total"], want["total"])
        np.testing.assert_array_equal(got["partial"], want["partial"])
    assert want["total"][0] == 900


# ---------------------------------------------------------------------------
# warp-batched execution: the (n_warps, W) lane plane ≡ the serial
# inter-warp loop, bitwise, across the full suite
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sk", RUNNABLE, ids=lambda sk: sk.name)
def test_warp_batched_bitwise_matches_serial(sk):
    """Full suite through warp_exec='batched' vs 'serial' on the scan
    backend — shared memory, warp collectives, peels, atomics, partial
    warps included."""
    args = sk.make_args()
    want = _launch(sk, args, backend="scan", warp_exec="serial")
    got = _launch(sk, args, backend="scan", warp_exec="batched")
    for k in want:
        np.testing.assert_array_equal(got[k], want[k],
                                      err_msg=f"{sk.name}.{k}")


@pytest.mark.parametrize("name", ["MatrixMulCUDA", "reduce0", "reduce4",
                                  "histogram64", "blockCounter",
                                  "vectorAdd", "matrixMul1D", "transpose",
                                  "stencil2d", "saxpyHeavy",
                                  "warpPrefixStats", "gridReduce"])
def test_warp_batched_composes_with_block_vmap(name):
    """grid-chunk × warp × lane batching all at once: the vmap backend
    with batched warps must still equal scan with serial warps.
    Cooperative (grid-sync) kernels keep the plan's all-resident
    wave instead of chunk=3."""
    sk = next(k for k in all_kernels() if k.name == name)
    args = sk.make_args()
    coop = uses_grid_sync(sk.kernel.ir)
    want = _launch(sk, args, backend="scan", warp_exec="serial")
    got = _launch(sk, args, backend="vmap", warp_exec="batched",
                  **({} if coop else {"chunk": 3}))
    for k in want:
        np.testing.assert_array_equal(got[k], want[k],
                                      err_msg=f"{name}.{k}")


@pytest.mark.parametrize("name", ["reduce4", "warpPrefixStats"])
@pytest.mark.parametrize("warp_exec", ["serial", "batched"])
def test_scalar_collectives_match_simd(name, warp_exec):
    """Table 2's w/o-AVX baseline: the per-lane scalar collectives
    (``simd=False``) on the scan backend equal the vectorised ones,
    bitwise, under either warp execution (a warp-cg reduction and the
    collective-dense shuffle/vote composite)."""
    sk = next(k for k in all_kernels() if k.name == name)
    args = sk.make_args()
    want = _launch(sk, args, backend="scan", warp_exec="serial")
    got = _launch(sk, args, backend="scan", warp_exec=warp_exec,
                  simd=False)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k],
                                      err_msg=f"{sk.name}.{k}")


def test_warp_batched_on_one_device_mesh():
    from repro.launch.mesh import make_mesh
    sk = next(k for k in all_kernels() if k.name == "MatrixMulCUDA")
    mesh = make_mesh((1,), ("data",))
    args = sk.make_args()
    want = _launch(sk, args, backend="scan", warp_exec="serial")
    got = _launch(sk, args, mesh=mesh, chunk=3, warp_exec="batched")
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])


@cox.kernel
def _k_warpstage(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32)):
    # n_warps>=4 acceptance kernel: shared memory + warp collective +
    # block barrier + cross-warp shared reads after the barrier
    tile = c.shared((4,), cox.f32)
    tid = c.thread_idx()
    v = a[c.block_idx() * c.block_dim() + tid]
    s = c.red_add(v)
    if c.lane_id() == 0:
        tile[c.warp_id()] = s
    c.syncthreads()
    t = tile[tid % 4]
    out[c.block_idx() * c.block_dim() + tid] = v + t


@cox.kernel
def _k_warpstage_partial(c, out: cox.Array(cox.f32),
                         a: cox.Array(cox.f32), n: cox.i32):
    # same shape but with a partial last warp (launched at block=112:
    # 4 warps, the last one half dead)
    tile = c.shared((4,), cox.f32)
    tid = c.thread_idx()
    i = c.block_idx() * c.block_dim() + tid
    v = 0.0
    if i < n:
        v = a[i]
    s = c.red_add(v)
    if c.lane_id() == 0:
        tile[c.warp_id()] = s
    c.syncthreads()
    t = tile[tid % 4]
    if i < n:
        out[i] = v + t


def test_warp_batched_multiwarp_shared_collective_barrier():
    """The acceptance shape: n_warps >= 4, shared memory, warp
    collectives and block barriers — batched ≡ serial bitwise, and the
    auto heuristic actually picks batched for it."""
    from repro.core import flat as cf
    rng = np.random.default_rng(3)
    a = rng.integers(-8, 9, 256).astype(np.float32)
    args = (np.zeros(256, np.float32), a)
    want = _k_warpstage.launch(grid=2, block=128, args=args,
                               warp_exec="serial")
    got = _k_warpstage.launch(grid=2, block=128, args=args,
                              warp_exec="batched")
    np.testing.assert_array_equal(np.asarray(got["out"]),
                                  np.asarray(want["out"]))
    assert cf.choose_warp_exec(_k_warpstage.ir, n_warps=4) == "batched"


def test_warp_batched_partial_last_warp():
    rng = np.random.default_rng(4)
    n = 200  # block=112 -> 4 warps, last warp half dead; tail dead too
    a = rng.integers(-8, 9, 224).astype(np.float32)
    args = (np.zeros(224, np.float32), a, n)
    want = _k_warpstage_partial.launch(grid=2, block=112, args=args,
                                       warp_exec="serial")
    got = _k_warpstage_partial.launch(grid=2, block=112, args=args,
                                      warp_exec="batched")
    np.testing.assert_array_equal(np.asarray(got["out"]),
                                  np.asarray(want["out"]))


@cox.kernel
def _k_store_in_while(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32),
                      n: cox.i32):
    # stores inside a While body cannot use the store log (log entries
    # can't escape a lax.while trace) — they must take the masked path
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    j = 0
    while j < i % 5:
        out[i * 5 + j] = a[i] + c.f32(j)
        j = j + 1


@cox.kernel
def _k_store_then_load(c, out: cox.Array(cox.f32), acc: cox.Array(cox.f32),
                       a: cox.Array(cox.f32)):
    # same-lane reload after a store in one PR: the stored array is
    # loaded in the PR, so it must not be logged (a logged store skips
    # the per-warp copy the reload would read)
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    acc[i] = a[i] * 2.0
    v = acc[i]
    out[i] = v + 1.0


@pytest.mark.parametrize("kern,args_fn", [
    (_k_store_in_while,
     lambda rng: (np.zeros(1280, np.float32),
                  rng.normal(size=256).astype(np.float32), 1280)),
    (_k_store_then_load,
     lambda rng: (np.zeros(128, np.float32), np.zeros(128, np.float32),
                  rng.normal(size=128).astype(np.float32))),
], ids=["store-in-while", "store-then-load"])
def test_store_log_ineligible_paths_stay_exact(kern, args_fn):
    rng = np.random.default_rng(9)
    args = args_fn(rng)
    want = kern.launch(grid=4, block=64, args=args, warp_exec="serial")
    for backend in ("scan", "vmap"):
        got = kern.launch(grid=4, block=64, args=args, backend=backend,
                          warp_exec="batched")
        for k in want:
            np.testing.assert_array_equal(
                np.asarray(got[k]), np.asarray(want[k]),
                err_msg=f"{kern.name}.{k} [{backend}]")


def test_pr_plan_classifies_store_paths():
    from repro.core.execute import _pr_plan
    from repro.core.regions import BlockPR
    ck = _k_store_then_load.compiled(block=64)
    plans = [_pr_plan(ck, n) for n in ck.machine.nodes
             if isinstance(n, BlockPR)]
    logged = {a for p in plans for a in p.logged}
    masked = {a for p in plans for a in p.masked}
    assert "out" in logged          # written, never read -> log path
    assert "acc" in masked          # reloaded after store -> masked path
    ck2 = _k_store_in_while.compiled(block=64)
    plans2 = [_pr_plan(ck2, n) for n in ck2.machine.nodes
              if isinstance(n, BlockPR)]
    assert "out" in {a for p in plans2 for a in p.masked}
    assert "out" not in {a for p in plans2 for a in p.logged}


def test_choose_warp_exec_heuristic():
    from repro.core import flat as cf
    from repro.core.regions import warp_peel_count
    mm = next(k for k in all_kernels() if k.name == "MatrixMulCUDA")
    r4 = next(k for k in all_kernels() if k.name == "reduce4")
    # shared-memory kernel, peel-free machine: batched
    ck = mm.kernel.compiled(block=mm.block)
    assert warp_peel_count(ck.machine) == 0
    assert cf.choose_warp_exec(mm.kernel.ir, n_warps=8,
                               machine=ck.machine) == "batched"
    # single warp: nothing to batch
    assert cf.choose_warp_exec(mm.kernel.ir, n_warps=1) == "serial"
    # no shared memory (streaming SPMD): per-PR lane work too small
    assert cf.choose_warp_exec(_k_id.ir, n_warps=8) == "serial"
    # peel-heavy warp graphs: batched switch runs every branch — serial
    ck4 = r4.kernel.compiled(block=r4.block)
    assert warp_peel_count(ck4.machine) > 0
    assert cf.choose_warp_exec(r4.kernel.ir, n_warps=8,
                               machine=ck4.machine) == "serial"
    # explicit requests pass through (peels and all)
    assert cf.choose_warp_exec(r4.kernel.ir, n_warps=8,
                               requested="batched") == "batched"
    assert cf.choose_warp_exec(mm.kernel.ir, n_warps=8,
                               requested="serial") == "serial"
    with pytest.raises(ValueError):
        cf.choose_warp_exec(mm.kernel.ir, n_warps=8, requested="simd")


def test_choose_warp_exec_shmem_budget():
    from repro.core import flat as cf

    @cox.kernel
    def _k_bigshared(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32)):
        tile = c.shared((40000,), cox.f32)
        tid = c.thread_idx()
        tile[tid] = a[tid]
        c.syncthreads()
        out[tid] = tile[tid]

    # 160 KB of shared memory x 32 warps = 5 MB > the 4 MiB budget
    assert cf.shared_footprint(_k_bigshared.ir) == 160000
    assert cf.choose_warp_exec(_k_bigshared.ir, n_warps=32) == "serial"
    assert cf.choose_warp_exec(_k_bigshared.ir, n_warps=4) == "batched"


def test_warp_batched_rejects_atomic_old_capture():
    """Ticket semantics need a serial warp order: auto routes to
    serial, an explicit batched request is rejected — at the heuristic,
    at plan build, and in make_block_fn (defense in depth)."""
    from repro.core import flat as cf
    from repro.core.execute import make_block_fn
    assert cf.choose_warp_exec(_k_ticket.ir, n_warps=4) == "serial"
    with pytest.raises(CoxUnsupported):
        cf.choose_warp_exec(_k_ticket.ir, n_warps=4, requested="batched")
    ck = _k_ticket.compiled(block=64)
    with pytest.raises(CoxUnsupported):
        LaunchPlan.build(ck, grid=4, block=64, warp_exec="batched")
    with pytest.raises(CoxUnsupported):
        make_block_fn(ck, n_warps=2, warp_exec="batched")


def test_launch_plan_requires_resolved_knobs():
    ck = _k_id.compiled(block=64)
    with pytest.raises(ValueError):
        LaunchPlan.build(ck, grid=2, block=64, mode="auto")
    with pytest.raises(ValueError):
        LaunchPlan.build(ck, grid=2, block=64, warp_exec="auto")
    plan = LaunchPlan.build(ck, grid=2, block=64)
    assert plan.warp_exec == "serial" and plan.mode == "normal"


@pytest.mark.parametrize("chunk", ["auto", 0, -3, 2.5])
def test_bad_chunk_is_rejected(chunk):
    # chunk= takes an int >= 1 or None; 'auto' is no chunk value
    ck = _k_id.compiled(block=64)
    with pytest.raises(ValueError, match="chunk must be an int >= 1"):
        resolve_launch(ck, grid=4, block=64, chunk=chunk)


def test_launch_cache_splits_on_warp_exec():
    sk = next(k for k in all_kernels() if k.name == "MatrixMulCUDA")
    args = sk.make_args()
    sk.kernel.launch(grid=sk.grid, block=sk.block, args=args,
                     warp_exec="serial")
    n1 = len(sk.kernel._launch_cache)
    sk.kernel.launch(grid=sk.grid, block=sk.block, args=args,
                     warp_exec="batched")
    assert len(sk.kernel._launch_cache) == n1 + 1
    sk.kernel.launch(grid=sk.grid, block=sk.block, args=args,
                     warp_exec="batched")
    assert len(sk.kernel._launch_cache) == n1 + 1


# ---------------------------------------------------------------------------
# atomic old-value capture (ticket pattern) — serial-only semantics
# ---------------------------------------------------------------------------


@cox.kernel
def _k_ticket(c, tickets: cox.Array(cox.i32), counter: cox.Array(cox.i32)):
    if c.thread_idx() == 0:
        t = c.atomic_add_old(counter, 0, 1)
        tickets[c.block_idx()] = t


def test_atomic_old_capture_is_serial_only():
    """Captured atomic old values are unique only under serial
    execution (on CUDA the ticket pattern is valid and deterministic):
    the auto heuristic must route such kernels to scan, the delta-merge
    backends must reject them outright, and scan must hand out exactly
    the tickets 0..grid-1."""
    assert cox_flat.captures_atomic_old(_k_ticket.ir)
    assert cox_flat.choose_backend(_k_ticket.ir, grid=8) == "scan"
    args = (np.full(8, -1, np.int32), np.zeros(1, np.int32))
    out = _k_ticket.launch(grid=8, block=32, args=args)
    assert sorted(np.asarray(out["tickets"]).tolist()) == list(range(8))
    assert np.asarray(out["counter"])[0] == 8
    for kw in ({"backend": "vmap"}, {"backend": "vmap", "chunk": 1}):
        with pytest.raises(CoxUnsupported):
            _k_ticket.launch(grid=8, block=32, args=args, **kw)


def test_atomic_old_capture_rejected_on_mesh():
    """A mesh forces the sharded backend, whose merge cannot reproduce
    ticket semantics either — reject at build, never run silently."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    args = (np.full(8, -1, np.int32), np.zeros(1, np.int32))
    with pytest.raises(CoxUnsupported):
        _k_ticket.launch(grid=8, block=32, args=args, mesh=mesh)


def test_plain_atomics_without_capture_still_take_vmap():
    """The scan-only carve-out is ticket kernels, not all atomics."""
    atomic_k = next(k for k in all_kernels() if k.name == "histogram64")
    assert not cox_flat.captures_atomic_old(atomic_k.kernel.ir)
    assert cox_flat.choose_backend(atomic_k.kernel.ir, grid=16) == "vmap"


# ---------------------------------------------------------------------------
# dispatch heuristic + plumbing
# ---------------------------------------------------------------------------


@cox.kernel
def _k_id(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32)):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    out[i] = a[i]


def test_choose_backend_heuristic():
    # streaming SPMD kernel: loop-carried scan wins regardless of grid
    assert cox_flat.choose_backend(_k_id.ir, grid=1) == "scan"
    assert cox_flat.choose_backend(_k_id.ir, grid=8) == "scan"
    # blockwise internal work (shared-memory tiles / atomics): vmap,
    # unless there is only one block
    shared_k = next(k for k in all_kernels() if k.name == "MatrixMulCUDA")
    atomic_k = next(k for k in all_kernels() if k.name == "histogram64")
    assert cox_flat.choose_backend(shared_k.kernel.ir, grid=16) == "vmap"
    assert cox_flat.choose_backend(atomic_k.kernel.ir, grid=16) == "vmap"
    assert cox_flat.choose_backend(shared_k.kernel.ir, grid=1) == "scan"
    assert cox_flat.choose_backend(_k_id.ir, grid=8, mesh=object()) \
        == "sharded"
    assert cox_flat.choose_backend(_k_id.ir, grid=8, requested="scan") \
        == "scan"
    with pytest.raises(ValueError):
        cox_flat.choose_backend(_k_id.ir, grid=8, requested="sharded")
    with pytest.raises(ValueError):
        cox_flat.choose_backend(_k_id.ir, grid=8, mesh=object(),
                                requested="vmap")
    with pytest.raises(ValueError):
        cox_flat.choose_backend(_k_id.ir, grid=8, requested="pthread")


def test_choose_mode_auto_unrolls_single_warp():
    assert cox_flat.choose_mode(_k_id.ir, n_warps=1, requested="auto") \
        == "jit"
    assert cox_flat.choose_mode(_k_id.ir, n_warps=8, requested="auto") \
        == "normal"
    assert cox_flat.choose_mode(_k_id.ir, n_warps=1, requested="normal") \
        == "normal"
    # 'auto' is the signature default, end to end
    import inspect
    from repro.core.api import KernelFn
    from repro.core.runtime import build_launcher, launch
    assert inspect.signature(cox_flat.choose_mode) \
        .parameters["requested"].default == "auto"
    for fn in (KernelFn.launch, build_launcher, launch):
        assert inspect.signature(fn).parameters["mode"].default == "auto"


def test_mode_auto_resolves_to_jit_for_single_warp_launch():
    """A default (mode='auto') single-warp launch stages a jit-mode
    plan — the resolved knob is what lands in the LaunchPlan."""
    args = (np.zeros(32, np.float32), np.ones(32, np.float32))
    _k_id.launch(grid=1, block=32, args=args)
    plans = [p for (p, _) in _k_id._launch_cache.values()]
    assert any(p.mode == "jit" and p.block == 32 for p in plans)


def test_backend_registry():
    assert set(available_backends()) == {"scan", "vmap", "sharded"}
    with pytest.raises(ValueError):
        get_backend("pthread")


def test_launch_plan_chunking():
    ck = _k_id.compiled(block=64)
    plan = LaunchPlan.build(ck, grid=5, block=64, chunk=2)
    table = plan.chunked_bids()
    assert table.shape == (3, 2)
    assert table.tolist() == [[0, 1], [2, 3], [4, -1]]
    dev = plan.device_bid_table(2)     # per=3, padded to chunk multiple 4
    assert dev.shape == (2, 4)
    assert dev[0].tolist() == [0, 1, 2, -1]
    assert dev[1].tolist() == [3, 4, -1, -1]


def test_launch_cache_hits_on_repeat_and_splits_on_geometry():
    a = np.ones(128, np.float32)
    _k_id.launch(grid=2, block=64, args=(np.zeros(128, np.float32), a))
    n1 = len(_k_id._launch_cache)
    _k_id.launch(grid=2, block=64, args=(np.zeros(128, np.float32), a))
    assert len(_k_id._launch_cache) == n1          # repeat launch: cache hit
    _k_id.launch(grid=2, block=64, args=(np.zeros(128, np.float32), a),
                 backend="vmap")
    assert len(_k_id._launch_cache) == n1 + 1      # new backend: new entry


def test_mesh_key_is_content_based():
    """Two equivalent meshes must share a launch-cache key: id()-based
    keys can be recycled after GC and alias stale executables."""
    from repro.launch.mesh import make_mesh
    from repro.core.api import _mesh_key
    m1 = make_mesh((1,), ("data",))
    m2 = make_mesh((1,), ("data",))
    k1, k2 = _mesh_key(m1), _mesh_key(m2)
    assert k1 == k2
    hash(k1)
    assert _mesh_key(None) is None


def test_scalar_args_do_not_retrace():
    """Scalar uniforms are traced arguments of the cached executable, so
    new scalar values reuse the staged computation."""
    sk = next(k for k in all_kernels() if k.name == "vectorAdd")
    out0, a, b, _ = sk.make_args()
    sk.kernel.launch(grid=sk.grid, block=sk.block, args=(out0, a, b, 512))
    n1 = len(sk.kernel._launch_cache)
    got = sk.kernel.launch(grid=sk.grid, block=sk.block,
                           args=(out0, a, b, 100))
    assert len(sk.kernel._launch_cache) == n1
    want = np.asarray(a[:100]) + np.asarray(b[:100])
    np.testing.assert_allclose(np.asarray(got["out"])[:100], want)
    np.testing.assert_array_equal(np.asarray(got["out"])[100:],
                                  np.zeros(412, np.float32))
