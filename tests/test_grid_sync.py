"""Cooperative-grid sync: ``c.grid_sync()`` / ``this_grid().sync()``.

The tentpole contract: a grid barrier phase-splits the kernel
(repro.core.phases) into one executable per inter-sync segment; global
memory and per-block persistent state (carried locals + shared memory)
thread between phases; all three backends × both warp-execution flavors
are bitwise-identical to the phase-split per-thread oracle; and the
cooperative-launch constraint (every block resident per phase) is
enforced with clear errors, as are the static-alignment rules (no sync
inside divergent control flow or loops).
"""
import numpy as np
import pytest

from benchmarks.kernels_suite import all_kernels
from repro.core import cox
from repro.core.backends.plan import LaunchPlan
from repro.core.oracle import run_grid as oracle_run
from repro.core.phases import split_phases
from repro.core.types import COOP_MAX_RESIDENT_BLOCKS, CoxUnsupported

GRID_REDUCE = next(k for k in all_kernels() if k.name == "gridReduce")


def _launch(sk, args, **kw):
    out = sk.kernel.launch(grid=sk.grid, block=sk.block, args=args, **kw)
    return {k: np.asarray(v) for k, v in out.items()}


# ---------------------------------------------------------------------------
# the acceptance kernel: two-pass grid-wide reduce, no host round-trip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["scan", "vmap"])
@pytest.mark.parametrize("warp_exec", ["serial", "batched"])
def test_grid_reduce_bitwise_matches_oracle(backend, warp_exec):
    sk = GRID_REDUCE
    args = sk.make_args()
    ref = oracle_run(sk.kernel.ir, grid=sk.grid, block=sk.block, args=args)
    got = _launch(sk, args, backend=backend, warp_exec=warp_exec)
    for k in ref:
        np.testing.assert_array_equal(got[k], np.asarray(ref[k]),
                                      err_msg=f"{backend}/{warp_exec}.{k}")
    assert got["total"][0] == np.asarray(args[2])[:args[3]].sum()


@pytest.mark.parametrize("warp_exec", ["serial", "batched"])
def test_grid_reduce_sharded_one_device_mesh(warp_exec):
    from repro.launch.mesh import make_mesh
    sk = GRID_REDUCE
    mesh = make_mesh((1,), ("data",))
    args = sk.make_args()
    want = _launch(sk, args, backend="scan", warp_exec="serial")
    got = _launch(sk, args, mesh=mesh, warp_exec=warp_exec)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])


def test_grid_reduce_hier_collapse_matches_flat():
    # both collapse strategies phase-split identically
    sk = GRID_REDUCE
    args = sk.make_args()
    want = _launch(sk, args, collapse="flat")
    got = _launch(sk, args, collapse="hier")
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])


# ---------------------------------------------------------------------------
# per-thread locals and atomics across the barrier
# ---------------------------------------------------------------------------


@cox.kernel
def _k_carried(c, out: cox.Array(cox.f32), scratch: cox.Array(cox.f32),
               a: cox.Array(cox.f32)):
    # v is loaded before the sync and stored after it: CUDA semantics say
    # the register lives for the thread's lifetime, so v must be carried
    # per-thread through the phase split (as a (n_warps, W) block plane)
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    v = a[i] * 2.0
    scratch[i] = v
    c.grid_sync()
    w = scratch[(i + 64) % 256]
    out[i] = v + w


def test_carried_locals_cross_the_sync():
    rng = np.random.default_rng(3)
    a = rng.normal(size=256).astype(np.float32)
    args = (np.zeros(256, np.float32), np.zeros(256, np.float32), a)
    ref = oracle_run(_k_carried.ir, grid=4, block=64, args=args)
    for backend in ("scan", "vmap"):
        got = _k_carried.launch(grid=4, block=64, args=args, backend=backend)
        for k in ref:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(ref[k]),
                                          err_msg=f"{backend}.{k}")
    # phase 1 reads another block's phase-0 write — the barrier guarantee
    np.testing.assert_array_equal(
        np.asarray(ref["out"]), a * 2.0 + np.roll(a * 2.0, -64))


@cox.kernel
def _k_atomic_sync(c, hist: cox.Array(cox.f32), flags: cox.Array(cox.f32),
                   data: cox.Array(cox.i32), n: cox.i32):
    # atomics before the sync, reads of the settled totals after it: the
    # vmap/sharded delta merges must fold at the phase boundary
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        c.atomic_add(hist, data[i], 1.0)
    c.grid_sync()
    if i < 64:
        flags[i] = 1.0 if hist[i] > 8.0 else 0.0


def test_atomics_settle_at_the_phase_boundary():
    rng = np.random.default_rng(5)
    data = rng.integers(0, 64, size=600).astype(np.int32)
    args = (np.zeros(64, np.float32), np.zeros(64, np.float32), data, 600)
    ref = oracle_run(_k_atomic_sync.ir, grid=6, block=128, args=args)
    for backend in ("scan", "vmap"):
        got = _k_atomic_sync.launch(grid=6, block=128, args=args,
                                    backend=backend)
        for k in ref:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(ref[k]),
                                          err_msg=f"{backend}.{k}")


@cox.kernel
def _k_cg_alias(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32)):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    out[i] = a[i] + 1.0
    c.this_grid().sync()
    out[i] = out[i] + out[(i + 32) % 128]


def test_this_grid_sync_alias_parses_to_a_grid_barrier():
    assert len(split_phases(_k_cg_alias.ir)) == 2
    a = np.arange(128, dtype=np.float32)
    args = (np.zeros(128, np.float32), a)
    ref = oracle_run(_k_cg_alias.ir, grid=4, block=32, args=args)
    got = _k_cg_alias.launch(grid=4, block=32, args=args)
    np.testing.assert_array_equal(np.asarray(got["out"]),
                                  np.asarray(ref["out"]))


@cox.kernel
def _k_trailing_sync(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32)):
    i = c.thread_idx()
    out[i] = a[i] * 3.0
    c.grid_sync()


def test_trailing_sync_yields_an_empty_final_phase():
    assert len(split_phases(_k_trailing_sync.ir)) == 2
    a = np.ones(32, np.float32)
    got = _k_trailing_sync.launch(grid=1, block=32,
                                  args=(np.zeros(32, np.float32), a))
    np.testing.assert_array_equal(np.asarray(got["out"]), a * 3.0)


# ---------------------------------------------------------------------------
# static-alignment rejections: clear errors, not wrong answers
# ---------------------------------------------------------------------------


@cox.kernel
def _k_sync_in_if(c, out: cox.Array(cox.f32)):
    if c.block_idx() == 0:
        c.grid_sync()
    out[c.thread_idx()] = 1.0


@cox.kernel
def _k_sync_in_loop(c, out: cox.Array(cox.f32)):
    t = 0
    while t < 4:
        c.grid_sync()
        t = t + 1
    out[c.thread_idx()] = 1.0


def test_sync_inside_divergent_control_flow_rejected():
    with pytest.raises(CoxUnsupported, match="divergent control flow"):
        _k_sync_in_if.launch(grid=2, block=32, args=(np.zeros(32),))


def test_sync_inside_loop_rejected():
    with pytest.raises(CoxUnsupported, match="loop body"):
        _k_sync_in_loop.launch(grid=2, block=32, args=(np.zeros(32),))


def test_return_before_sync_rejected():
    import repro.core.kernel_ir as K
    from repro.core.types import BarrierLevel
    bad = K.Kernel("bad", list(_k_trailing_sync.ir.params), [], [
        K.Return(), K.Barrier(BarrierLevel.GRID)])
    with pytest.raises(CoxUnsupported, match="return before"):
        split_phases(bad)


# ---------------------------------------------------------------------------
# cooperative-launch constraint: every block resident per phase
# ---------------------------------------------------------------------------


def test_resident_capacity_enforced_when_chunked_pinned():
    """An explicit schedule='chunked' pins the all-resident wave, so a
    grid beyond the capacity still fails CUDA's occupancy rule."""
    sk = GRID_REDUCE
    with pytest.raises(CoxUnsupported, match="resident capacity"):
        sk.kernel.launch(grid=COOP_MAX_RESIDENT_BLOCKS + 1, block=sk.block,
                         args=sk.make_args(), schedule="chunked")


def test_resident_capacity_lowers_to_grid_stride():
    """Left on auto, a cooperative grid beyond the resident capacity is
    grid-strided — a capacity-sized wave pages blocks through each
    phase — instead of rejected (the PR 4 hard cap is now a lowering
    decision)."""
    from repro.core.runtime import resolve_launch
    ck = GRID_REDUCE.kernel.compiled(collapse="hier")
    rl = resolve_launch(ck, grid=COOP_MAX_RESIDENT_BLOCKS + 1,
                        block=GRID_REDUCE.block)
    assert rl.schedule == "grid_stride"
    assert rl.schedule_source == "cooperative"
    assert rl.n_resident == COOP_MAX_RESIDENT_BLOCKS
    assert rl.chunk == COOP_MAX_RESIDENT_BLOCKS


def test_explicit_chunk_that_splits_the_grid_rejected():
    sk = GRID_REDUCE
    with pytest.raises(CoxUnsupported, match="resident per"):
        sk.kernel.launch(grid=sk.grid, block=sk.block, args=sk.make_args(),
                         backend="vmap", chunk=3)


def test_coop_plan_pins_chunk_to_the_grid():
    ck = GRID_REDUCE.kernel.compiled(collapse="hier")
    plan = LaunchPlan.build(ck, grid=8, block=128)
    assert plan.n_phases == 2
    assert plan.chunk == 8
    assert plan.chunked_bids().shape == (1, 8)


# ---------------------------------------------------------------------------
# phase-split plumbing: single-phase identity + cache keys
# ---------------------------------------------------------------------------


def test_single_phase_kernels_compile_to_the_pre_phase_program():
    sk = next(k for k in all_kernels() if k.name == "vectorAdd")
    assert split_phases(sk.kernel.ir) == [sk.kernel.ir]
    ck = sk.kernel.compiled(collapse="hier")
    assert ck.phases == () and ck.n_phases == 1 and ck.carried == ()
    plan = LaunchPlan.build(ck, grid=2, block=256)
    assert plan.n_phases == 1 and plan.persist_spec() is None
    assert len(plan.block_fns(track_writes=False)) == 1


def test_launch_cache_keys_distinguish_phase_counts():
    sk_coop = GRID_REDUCE
    sk_plain = next(k for k in all_kernels() if k.name == "vectorAdd")
    sk_coop.kernel.launch(grid=sk_coop.grid, block=sk_coop.block,
                          args=sk_coop.make_args())
    sk_plain.kernel.launch(grid=sk_plain.grid, block=sk_plain.block,
                           args=sk_plain.make_args())
    # the phase count sits right after the compile token in every key
    coop_keys = list(sk_coop.kernel._launch_cache)
    plain_keys = list(sk_plain.kernel._launch_cache)
    assert all(k[1] == 2 for k in coop_keys)
    assert all(k[1] == 1 for k in plain_keys)
    # repeat launches hit the staged executable (no new entries)
    n = len(coop_keys)
    sk_coop.kernel.launch(grid=sk_coop.grid, block=sk_coop.block,
                          args=sk_coop.make_args())
    assert len(sk_coop.kernel._launch_cache) == n
