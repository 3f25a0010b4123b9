"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; ``--json PATH`` also
writes every row (plus the structured backend-sweep matrix) to a
machine-readable JSON file (default path ``BENCH_PR10.json``) so the
perf trajectory is recorded across PRs.  ``--sections a,b`` runs a
subset; ``--smoke`` is the CI regression guard (1 timing iteration,
flagship kernels only).

  coverage      — Table 1: 31-kernel suite, flat vs hierarchical support
  flat_vs_hier  — Fig. 12: hierarchical overhead on warp-free kernels
  simd_vote     — Table 2: warp vote with vectorized vs scalar collectives
  jit_mode      — Fig. 13: JIT (unrolled) vs normal (fori) mode
  backend_sweep — grid-execution backends × warp execution: scan vs vmap
                  (vs sharded when >1 device) × serial vs batched warps,
                  equal outputs asserted + timing per cell
  streams       — async launch dispatch: two independent memory-bound
                  kernels on two cox streams vs serial issue, bitwise
                  equality asserted + overlap ratio per pipeline depth
  graph_replay  — CUDA graphs: a depth-d chain of dependent launches
                  captured once into a cox.Graph and replayed per token
                  vs eager per-launch dispatch, bitwise asserted
  placement     — multi-device stream placement: 4 streams round-robined
                  over 1/2/4/8-device pools (subprocess, 8 forced host
                  devices), bitwise equality vs the 1-device pool
                  asserted + throughput ratio per pool size
  grid_stride   — resident waves over oversubscribed grids: 64k–256k
                  blocks under a forced-small COX_FOOTPRINT_BUDGET,
                  the cost-model-routed grid-stride schedule vs the
                  unconstrained chunk-table walk and the clamped-chunk
                  fallback it replaces, bitwise asserted per cell
  autotune      — measured knob tuning vs the hand heuristics: each pick
                  kernel launched with the heuristic knobs, then with
                  autotune=True (cold: candidate cells measured into a
                  fresh cache; warm: zero-measurement cache hit
                  asserted), bitwise equality + never-slower recorded
                  per cell with op/mem estimates and achieved GFLOPS
  scalability   — Fig. 14: blocks across host devices (subprocess, 8 dev)
  roofline      — §Roofline terms from results/dryrun_all.json (if present)
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.core import cox  # noqa: E402
from repro.core.flat import FlatUnsupported, supports_flat  # noqa: E402
from repro.core.types import CoxUnsupported  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

# timing knobs (--smoke turns both down) and the JSON collectors
WARMUP = 2
ITERS = 10
SMOKE = False
RESULTS = []         # every CSV row, as dicts
SWEEP_RESULTS = []   # structured backend_sweep matrix
STREAM_RESULTS = []  # structured streams-overlap cells
GRAPH_RESULTS = []   # structured graph-replay cells
PLACEMENT_RESULTS = []  # structured multi-device placement cells
AUTOTUNE_RESULTS = []   # structured heuristic-vs-tuned cells
FAILED = []             # sections whose child process failed
GRID_STRIDE_RESULTS = []  # structured oversubscribed-schedule cells

# device-pool sizes every placement run must cover — module-level so the
# CI regression gate (benchmarks/check_smoke.py) can assert coverage
PLACEMENT_DEVICES = (1, 2, 4, 8)

# chain depths every graph_replay run must cover — module-level so the
# CI regression gate (benchmarks/check_smoke.py) can assert coverage
GRAPH_DEPTHS = (1, 4, 16)

# backend_sweep kernel picks — module-level so the CI regression gate
# (benchmarks/check_smoke.py) can assert the smoke run covered them
SWEEP_SMOKE_PICKS = ("MatrixMulCUDA", "matrixMul1D", "transpose",
                     "warpPrefixStats", "blockCounter", "gridReduce")
SWEEP_FULL_PICKS = ("vectorAdd", "MatrixMulCUDA", "matrixMul1D",
                    "transpose", "stencil2d", "reduce0", "reduce4",
                    "histogram64", "blockCounter", "saxpyHeavy",
                    "warpPrefixStats", "gridReduce")

# autotune kernel picks — module-level so the CI regression gate can
# assert the committed baseline covered them (a mix of chunk-sensitive
# vmap kernels and warp-batched candidates)
AUTOTUNE_PICKS = ("MatrixMulCUDA", "transpose", "warpPrefixStats",
                  "saxpyHeavy")

# grid_stride kernels and oversubscribed grid sizes every run must
# cover — module-level so the CI regression gate can assert coverage;
# the smoke run covers the first grid only (the quarter-million-block
# clamped cells need full timing iterations to be worth recording)
STRIDE_KERNELS = ("strideSaxpy", "strideHist")
STRIDE_GRIDS = (1 << 16, 1 << 18)
STRIDE_SMOKE_GRIDS = (1 << 16,)


def _time_call(fn, *args, warmup=None, iters=None):
    for _ in range(WARMUP if warmup is None else warmup):
        fn(*args)
    ts = []
    for _ in range(ITERS if iters is None else iters):
        t0 = time.perf_counter()
        out = fn(*args)
        _block(out)
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e6  # µs


def _block(out):
    import jax
    jax.tree_util.tree_map(
        lambda x: x.block_until_ready() if hasattr(x, "block_until_ready")
        else x, out)


def _row(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}", flush=True)
    RESULTS.append({"name": name, "us": round(us, 1), "derived": derived})


def _total(dim) -> int:
    """Linear size of an ``int | (x, y[, z])`` dim3 geometry."""
    return dim if isinstance(dim, int) else int(np.prod(dim))


# ---------------------------------------------------------------------------


def coverage():
    """Table 1: which kernels each collapsing strategy supports."""
    from benchmarks.kernels_suite import KERNELS
    from repro.core.oracle import run_grid as oracle_run
    n_flat = n_hier = n_total = 0
    for sk in KERNELS:
        n_total += 1
        if sk.kernel is None:
            _row(f"coverage.{sk.name}", 0.0,
                 f"features={sk.features};flat=no;cox=no;"
                 f"reason={sk.unsupported_reason[:40]}")
            continue
        flat_ok = supports_flat(sk.kernel.ir)
        args = sk.make_args()
        t0 = time.perf_counter()
        try:
            out = sk.kernel.launch(grid=sk.grid, block=sk.block, args=args,
                                   collapse="hier")
            hier_ok = True
            # verify against the per-thread oracle
            ref = oracle_run(sk.kernel.ir, grid=sk.grid, block=sk.block,
                             args=args)
            for k in ref:
                got = np.asarray(out[k], np.float32)
                want = np.asarray(ref[k], np.float32)
                assert np.allclose(got, want, rtol=1e-4, atol=1e-4), \
                    f"{sk.name}.{k} mismatch"
            if sk.check is not None:
                assert sk.check(out), f"{sk.name} check failed"
        except CoxUnsupported:
            hier_ok = False
        us = (time.perf_counter() - t0) * 1e6
        n_flat += flat_ok
        n_hier += hier_ok
        _row(f"coverage.{sk.name}", us,
             f"features={sk.features or 'none'};"
             f"flat={'yes' if flat_ok else 'no'};"
             f"cox={'yes' if hier_ok else 'no'}")
    _row("coverage.TOTAL", 0.0,
         f"flat={n_flat}/{n_total}({100*n_flat//n_total}%);"
         f"cox={n_hier}/{n_total}({100*n_hier//n_total}%);"
         f"paper: POCL 39%, DPCT 68%, COX 90%")


# ---------------------------------------------------------------------------


def flat_vs_hier():
    """Fig. 12: hierarchical-collapsing overhead on warp-free kernels."""
    from benchmarks.kernels_suite import KERNELS
    picks = ["vectorAdd", "MatrixMulCUDA", "reduce0"]
    ratios = []
    for sk in KERNELS:
        if sk.name not in picks:
            continue
        args = sk.make_args()

        def run(mode):
            return sk.kernel.launch(grid=sk.grid, block=sk.block,
                                    args=args, collapse=mode)

        us_flat = _time_call(lambda: run("flat"))
        us_hier = _time_call(lambda: run("hier"))
        ratios.append(us_hier / us_flat)
        _row(f"flat_vs_hier.{sk.name}", us_hier,
             f"flat_us={us_flat:.1f};overhead={us_hier / us_flat:.2f}x")
    _row("flat_vs_hier.MEAN", 0.0,
         f"overhead={statistics.mean(ratios):.2f}x;paper=1.13x")


# ---------------------------------------------------------------------------


def simd_vote():
    """Table 2: vote_all / vote_any with SIMD (lane-vector) vs scalar
    (per-lane loop) collective implementations.

    Two granularities: the whole kernel launch (includes grid machinery,
    like the paper's timing) and the collective function itself in
    isolation (the paper's instruction-count story)."""
    import jax
    import jax.numpy as jnp
    from repro.core import collectives as C
    from benchmarks.kernels_suite import KERNELS

    for nm in ("VoteAllKernel2", "VoteAnyKernel1"):
        sk = next(k for k in KERNELS if k.name == nm)
        args = sk.make_args()

        def run(simd):
            return sk.kernel.launch(grid=sk.grid, block=sk.block, args=args,
                                    simd=simd, collapse="hier")

        us_simd = _time_call(lambda: run(True))
        us_scalar = _time_call(lambda: run(False))
        _row(f"simd_vote.{nm}", us_simd,
             f"scalar_us={us_scalar:.1f};"
             f"speedup={us_scalar / us_simd:.2f}x;paper=10x")

    # micro: the collective alone, 8192 warps at once
    buf = jnp.asarray(
        np.random.default_rng(0).integers(0, 2, (8192, 32)).astype(bool))
    for fname in ("vote_all", "vote_any"):
        fv = jax.jit(jax.vmap(lambda b: C.VECTORIZED[fname](b, W=32)))
        fs = jax.jit(jax.vmap(lambda b: C.SCALAR[fname](b, W=32)))
        us_v = _time_call(lambda: fv(buf))
        us_s = _time_call(lambda: fs(buf))
        _row(f"simd_vote.micro_{fname}", us_v,
             f"scalar_us={us_s:.1f};speedup={us_s / us_v:.2f}x;paper=10x")


# ---------------------------------------------------------------------------


def jit_mode():
    """Fig. 13: JIT mode (block size burned in, loops unrolled) vs
    normal mode (fori inter-warp loop)."""
    from benchmarks.kernels_suite import KERNELS
    for nm in ("vectorAdd", "MatrixMulCUDA", "reduce4"):
        sk = next(k for k in KERNELS if k.name == nm)
        args = sk.make_args()

        def run(mode):
            return sk.kernel.launch(grid=sk.grid, block=sk.block, args=args,
                                    mode=mode, collapse="hier")

        us_jit = _time_call(lambda: run("jit"))
        us_normal = _time_call(lambda: run("normal"))
        _row(f"jit_mode.{nm}", us_jit,
             f"normal_us={us_normal:.1f};"
             f"jit_speedup={us_normal / us_jit:.2f}x")


# ---------------------------------------------------------------------------


def backend_sweep():
    """Grid-execution backend × warp-execution axis: the same kernels
    through every (backend, warp_exec) cell, equal outputs asserted,
    median call time per cell.  The vmap column is the block-parallel
    payoff (paper §4's pthread-per-block, recast as a chunked jax.vmap);
    the batched-warp column is the same trick one level down — the
    inter-warp loop vectorized into one (n_warps, W) lane plane."""
    import jax
    from benchmarks.kernels_suite import all_kernels

    backends = ["scan", "vmap"]
    mesh = None
    if not SMOKE and len(jax.devices()) > 1:
        mesh = make_mesh((len(jax.devices()),), ("data",))
        backends.append("sharded")

    picks = SWEEP_SMOKE_PICKS if SMOKE else SWEEP_FULL_PICKS
    for sk in all_kernels():
        if sk.name not in picks:
            continue
        args = sk.make_args()
        n_warps = -(-_total(sk.block) // 32)

        def run(backend, warp_exec="serial", simd=True):
            kw = {"mesh": mesh} if backend == "sharded" else {}
            return sk.kernel.launch(grid=sk.grid, block=sk.block, args=args,
                                    backend=backend, warp_exec=warp_exec,
                                    simd=simd, **kw)

        # what the all-auto heuristics resolve to — recorded so the CI
        # gate can flag an autotune pick that lands on the slowest
        # measured cell (make_request resolves eagerly, no dispatch)
        rl_auto = sk.kernel.make_request(grid=sk.grid, block=sk.block,
                                         args=args).rl
        auto_cell = f"{rl_auto.backend}_{rl_auto.warp_exec}"
        auto_chunk = rl_auto.chunk

        base = run("scan")
        times = {}
        cells = [(b, we, True) for b in backends
                 for we in ("serial", "batched")]
        if sk.kernel.uses_warp_features():
            # Table-2's w/o-AVX baseline × warp execution: the scalar
            # collectives' per-lane loops are non-fusable op chains, so
            # the batched plane divides their instance count by n_warps
            cells += [("scan", we, False) for we in ("serial", "batched")]
        for b, we, simd in cells:
            out = run(b, we, simd)
            for k in base:
                np.testing.assert_array_equal(
                    np.asarray(out[k]), np.asarray(base[k]),
                    err_msg=f"{sk.name}.{k}: {b}/{we}/simd={simd} "
                            f"!= scan/serial")
            cell = f"{b}_{we}" + ("" if simd else "_noavx")
            times[cell] = _time_call(
                lambda b=b, we=we, simd=simd: run(b, we, simd))
        derived = ";".join(f"{c}_us={t:.1f}" for c, t in times.items())
        wb = times["scan_serial"] / times["scan_batched"]
        derived += f";vmap_speedup={times['scan_serial'] / times['vmap_serial']:.2f}x"
        derived += f";warp_batch_speedup={wb:.2f}x"
        derived += f";auto_cell={auto_cell}"
        entry = {
            "kernel": sk.name, "grid": sk.grid, "block": sk.block,
            "n_warps": n_warps, "features": sk.features or "none",
            "auto_cell": auto_cell,
            "auto_chunk": auto_chunk,
            "chunk_source": rl_auto.chunk_source,
            "auto_schedule": rl_auto.schedule,
            "schedule_source": rl_auto.schedule_source,
            "auto_n_resident": rl_auto.n_resident,
            "times_us": {c: round(t, 1) for c, t in times.items()},
            "warp_batch_speedup_scan": round(wb, 2),
            "warp_batch_speedup_vmap": round(
                times["vmap_serial"] / times["vmap_batched"], 2),
        }
        if "scan_serial_noavx" in times:
            entry["warp_batch_speedup_scan_noavx"] = round(
                times["scan_serial_noavx"] / times["scan_batched_noavx"], 2)
            derived += (f";warp_batch_noavx_speedup="
                        f"{entry['warp_batch_speedup_scan_noavx']:.2f}x")
        _row(f"backend_sweep.{sk.name}", times["vmap_batched"], derived)
        SWEEP_RESULTS.append(entry)

    # dim3 overhead check: the natural 2-D matrixMul vs the hand-
    # flattened 1-D port of the same kernel (acceptance: within 10%)
    by_name = {e["kernel"]: e for e in SWEEP_RESULTS}
    mm2, mm1 = by_name.get("MatrixMulCUDA"), by_name.get("matrixMul1D")
    if mm2 and mm1:
        ratios = {c: mm2["times_us"][c] / mm1["times_us"][c]
                  for c in mm2["times_us"] if c in mm1["times_us"]}
        _row("backend_sweep.matmul_2d_vs_1d", 0.0,
             ";".join(f"{c}_ratio={r:.2f}x" for c, r in ratios.items()))


# ---------------------------------------------------------------------------


def streams():
    """Async streams: two independent memory-bound kernels (saxpy and a
    scale — streaming stores, ~zero arithmetic intensity) issued on two
    ``cox.Stream``\\ s (enqueue both, synchronize after) vs serial issue
    (launch + synchronize each, the pre-stream ``KernelFn.launch``
    discipline).  Outputs are asserted bitwise-equal first — any legal
    stream schedule must match serial issue.  On a single XLA device the
    win is host/device pipelining: while kernel A executes, the host
    binds and dispatches B (and materializes A's result), exactly CUDA's
    copy/compute-overlap story.  ``depth`` is the per-stream in-order
    pipeline length (pairs in flight before the sync) — deeper queues
    amortize more host work, so the ratio grows with depth."""
    import gc
    from repro.core import cox

    @cox.kernel
    def streamSaxpy(c, out: cox.Array(cox.f32), x: cox.Array(cox.f32),
                    y: cox.Array(cox.f32), n: cox.i32):
        i = c.block_idx() * c.block_dim() + c.thread_idx()
        if i < n:
            out[i] = 2.5 * x[i] + y[i]

    @cox.kernel
    def streamScale(c, out: cox.Array(cox.f32), x: cox.Array(cox.f32),
                    n: cox.i32):
        i = c.block_idx() * c.block_dim() + c.thread_idx()
        if i < n:
            out[i] = x[i] * 3.0 + 1.0

    grid, block = 32, 256
    n = grid * block
    x = np.arange(n, dtype=np.float32) / n
    y = np.ones(n, np.float32)
    o = np.zeros(n, np.float32)
    a1, a2 = (o, x, y, n), (o, x, n)
    s1, s2 = cox.Stream("bench-s1"), cox.Stream("bench-s2")

    def serial(depth):
        outs = []
        for _ in range(depth):
            r1 = streamSaxpy.launch(grid=grid, block=block, args=a1)
            outs.append(np.asarray(r1["out"]))
            r2 = streamScale.launch(grid=grid, block=block, args=a2)
            outs.append(np.asarray(r2["out"]))
        return outs

    def streamed(depth):
        hs = []
        for _ in range(depth):
            hs.append(s1.launch(streamSaxpy, grid=grid, block=block,
                                args=a1))
            hs.append(s2.launch(streamScale, grid=grid, block=block,
                                args=a2))
        return [np.asarray(h.result()["out"]) for h in hs]

    # bitwise: any legal stream schedule == serial issue
    for got, want in zip(streamed(2), serial(2)):
        np.testing.assert_array_equal(got, want)

    # medians need many alternated samples: the pair runs in ~2.5 ms, so
    # scheduler jitter on a shared host is a large fraction of one trial
    iters = 1 if SMOKE else max(ITERS * 12, 120)
    gc.disable()
    try:
        for depth in (1, 2, 4):
            ts, to = [], []
            for _ in range(iters):
                t0 = time.perf_counter()
                serial(depth)
                ts.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                streamed(depth)
                to.append(time.perf_counter() - t0)
            serial_us = statistics.median(ts) * 1e6
            stream_us = statistics.median(to) * 1e6
            ratio = serial_us / stream_us
            _row(f"streams.pair_depth{depth}", stream_us,
                 f"serial_us={serial_us:.1f};overlap={ratio:.2f}x;"
                 f"kernels=streamSaxpy+streamScale;n={n}")
            STREAM_RESULTS.append({
                "pair": "streamSaxpy+streamScale", "depth": depth,
                "grid": grid, "block": block, "n": n,
                "serial_us": round(serial_us, 1),
                "stream_us": round(stream_us, 1),
                "overlap_x": round(ratio, 2),
            })
    finally:
        gc.enable()


# ---------------------------------------------------------------------------


def graph_replay():
    """CUDA graphs: a depth-d chain of *dependent* saxpy launches (one
    token's worth of pipeline work) dispatched eagerly — d per-launch
    bind/stage/dispatch round-trips through the stream — vs captured
    once into a ``cox.Graph`` and **replayed** per token with the
    carried input rebound (``replay(x=...)``).  Replay is one staged
    XLA call regardless of depth (XLA fused across the launch
    boundaries at instantiate), so the win grows with chain depth —
    the ``cudaGraphLaunch`` story.  Bitwise equality of replay vs
    eager is asserted on carried state before any timing."""
    import gc
    from repro.core import cox

    @cox.kernel
    def graphStep(c, out: cox.Array(cox.f32), x: cox.Array(cox.f32),
                  y: cox.Array(cox.f32), n: cox.i32):
        i = c.block_idx() * c.block_dim() + c.thread_idx()
        if i < n:
            out[i] = 0.5 * x[i] + y[i]

    grid, block = 32, 256
    n = grid * block
    x0 = np.arange(n, dtype=np.float32) / n
    y = np.ones(n, np.float32)
    o = np.zeros(n, np.float32)
    s = cox.Stream("bench-graph")

    def chain(depth, x):
        h = s.launch(graphStep, grid=grid, block=block, args=(o, x, y, n))
        for _ in range(depth - 1):
            h = s.launch(graphStep, grid=grid, block=block,
                         args=(o, h.outputs["out"], y, n))
        return h

    # medians need many alternated samples (same rationale as streams)
    iters = 1 if SMOKE else max(ITERS * 12, 120)
    for depth in GRAPH_DEPTHS:
        g = cox.Graph(name=f"bench-chain{depth}")
        with g.capture(s):
            chain(depth, x0)
        exe = g.instantiate()

        def eager(x, depth=depth):
            return np.asarray(chain(depth, x).result()["out"])

        def replay(x, exe=exe):
            return np.asarray(exe.replay(x=x)["out"])

        # bitwise: replayed graph == eager launches, carried three deep
        xe, xg = x0, x0
        for _ in range(3):
            xe, xg = eager(xe), replay(xg)
            np.testing.assert_array_equal(xg, xe)

        gc.disable()
        try:
            te, tg = [], []
            for _ in range(iters):
                t0 = time.perf_counter()
                eager(x0)
                te.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                replay(x0)
                tg.append(time.perf_counter() - t0)
        finally:
            gc.enable()
        eager_us = statistics.median(te) * 1e6
        replay_us = statistics.median(tg) * 1e6
        ratio = eager_us / replay_us
        _row(f"graph_replay.chain_depth{depth}", replay_us,
             f"eager_us={eager_us:.1f};speedup={ratio:.2f}x;"
             f"kernel=graphStep;n={n}")
        GRAPH_RESULTS.append({
            "kernel": "graphStep", "depth": depth, "grid": grid,
            "block": block, "n": n,
            "eager_us": round(eager_us, 1),
            "replay_us": round(replay_us, 1),
            "speedup_x": round(ratio, 2),
        })


# ---------------------------------------------------------------------------


def _host_device_worker(section: str, script: str, *args: str) -> str:
    """Run ``benchmarks/<script>`` in a child interpreter with 8 forced
    CPU host devices (the device count must be set before jax
    initializes) and return its stdout, re-emitting its CSV rows.

    Only on the CPU platform: a child started after this process has
    touched an accelerator cannot get it.  A child that fails is
    recorded as a ``<section>.FAILED`` row and makes :func:`main` exit
    non-zero."""
    import jax
    platform = jax.devices()[0].platform
    if platform != "cpu":
        raise SystemExit(
            f"{section}: runs a child process on forced CPU host devices, "
            f"which cannot share the {platform} device this process holds; "
            f"run this section with JAX_PLATFORMS=cpu")
    worker = os.path.join(os.path.dirname(__file__), script)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, worker, *args], capture_output=True,
                       text=True, env=env, timeout=1200)
    for line in r.stdout.splitlines():
        # re-emit through _row so the worker's rows reach --json too
        parts = line.split(",", 2)
        if len(parts) == 3:
            try:
                _row(parts[0], float(parts[1]), parts[2])
                continue
            except ValueError:
                pass
        if not line.startswith("PLACEMENT_JSON "):
            print(line, flush=True)
    if r.returncode != 0:
        _row(f"{section}.FAILED", 0.0, r.stderr[-200:].replace("\n", ";"))
        FAILED.append(section)
    return r.stdout


def placement():
    """Multi-device stream placement: the same 4-stream program over
    1/2/4/8-device pools (8-dev subprocess).  The worker asserts
    bitwise equality against the 1-device pool and reports the
    throughput ratio; each entry records the host's core count because
    XLA host devices time-share physical cores (the scaling gate is
    cpus-conditional)."""
    iters = 3 if SMOKE else max(ITERS, 5)
    out = _host_device_worker("placement", "placement_worker.py",
                              "--iters", str(iters))
    for line in out.splitlines():
        if line.startswith("PLACEMENT_JSON "):
            PLACEMENT_RESULTS.extend(
                json.loads(line[len("PLACEMENT_JSON "):]))


# ---------------------------------------------------------------------------


def autotune():
    """Measured knob tuning (repro.core.autotune) vs the hand
    heuristics.  Per pick kernel: launch with the heuristic knobs, then
    with ``autotune=True`` against a fresh cache (cold pass — the
    candidate grid is measured and the winner persisted), assert the
    tuned outputs bitwise-equal the heuristic ones, then time both
    picks.  A warm re-resolve in the same process must hit the cache
    with zero new measurement launches — counter-asserted here, and
    again across processes by the CI autotune job.  Each cell records
    the cost model's op/mem estimates and the achieved GFLOPS so
    check_smoke.py can gate estimate accuracy and never-slower."""
    import tempfile
    from benchmarks.kernels_suite import all_kernels
    from repro.core import autotune as at
    from repro.core import costmodel

    tmp = tempfile.mkdtemp(prefix="cox-autotune-bench-")
    cache_file = os.path.join(tmp, "autotune.json")
    prev = os.environ.get(at.ENV_CACHE)
    os.environ[at.ENV_CACHE] = cache_file
    at.reset()
    try:
        for sk in all_kernels():
            if sk.name not in AUTOTUNE_PICKS:
                continue
            args = sk.make_args()

            def run(tune):
                return sk.kernel.launch(grid=sk.grid, block=sk.block,
                                        args=args, autotune=tune)

            req = sk.kernel.make_request(grid=sk.grid, block=sk.block,
                                         args=args)
            heur_rl = req.rl
            heur_cell = (f"{heur_rl.backend}_{heur_rl.warp_exec}"
                         f"_c{heur_rl.chunk}")
            base = run(False)
            tuned_out = run(True)       # cold: measures candidate cells
            for k in base:
                np.testing.assert_array_equal(
                    np.asarray(tuned_out[k]), np.asarray(base[k]),
                    err_msg=f"{sk.name}.{k}: tuned != heuristic")
            m_cold = at.stats()["measurements"]
            req_t = sk.kernel.make_request(grid=sk.grid, block=sk.block,
                                           args=args, autotune=True)
            assert at.stats()["measurements"] == m_cold, \
                f"{sk.name}: warm re-resolve issued measurement launches"
            tuned_rl = req_t.rl
            tuned_cell = (f"{tuned_rl.backend}_{tuned_rl.warp_exec}"
                          f"_c{tuned_rl.chunk}")
            heur_us = _time_call(lambda: run(False))
            tuned_us = _time_call(lambda: run(True))
            rec = next((r for k, r in at.entries().items()
                        if k.startswith(sk.name + "|")), {})
            est = costmodel.estimate(req.ck, tuned_rl, req.shapes,
                                     mode="xla")
            gflops = est.op_estimate / tuned_us / 1e3  # us -> GFLOPS
            ratio = heur_us / tuned_us
            _row(f"autotune.{sk.name}", tuned_us,
                 f"heur_us={heur_us:.1f};heur={heur_cell};"
                 f"tuned={tuned_cell};speedup={ratio:.2f}x;"
                 f"gflops={gflops:.3f}")
            AUTOTUNE_RESULTS.append({
                "kernel": sk.name, "grid": sk.grid, "block": sk.block,
                "heur_cell": heur_cell, "tuned_cell": tuned_cell,
                "heur_us": round(heur_us, 1),
                "tuned_us": round(tuned_us, 1),
                "speedup_x": round(ratio, 2),
                "op_estimate": est.op_estimate,
                "mem_estimate": est.mem_estimate,
                "estimate_source": est.source,
                "gflops": round(gflops, 4),
                "chunk_source": tuned_rl.chunk_source,
                # the tuner's own per-candidate measurements (µs) — the
                # chunk-mispick gate reads these cells
                "candidate_times_us": rec.get("times_us", {}),
            })
        st = at.stats()
        _row("autotune.STATS", 0.0,
             f"misses={st['misses']};hits={st['hits']};"
             f"measurements={st['measurements']};"
             f"disk_writes={st['disk_writes']}")
        assert os.path.exists(cache_file), "autotune cache never written"
    finally:
        if prev is None:
            os.environ.pop(at.ENV_CACHE, None)
        else:
            os.environ[at.ENV_CACHE] = prev


# ---------------------------------------------------------------------------


def grid_stride():
    """Grid-stride lowering on oversubscribed grids: a fixed wave of
    resident block slots loops over strided block ids instead of the
    host materializing an O(grid) chunk table.  Per (kernel, grid)
    three cells, all launched over the same small bound working set so
    the *schedule machinery* dominates the wall time:

    * ``chunked8`` — the unconstrained chunk-table walk at the default
      wave width (``chunk=8``), the pre-budget baseline;
    * ``clamp1``   — the clamped-chunk fallback the autotuner used to
      take when no chunk fit the footprint budget (``chunk=1``: one
      merge pass per *block*, grid of them — the failure mode the
      stride schedule replaces);
    * ``stride``   — all knobs on auto under a forced-small
      ``COX_FOOTPRINT_BUDGET`` (the satellite env override): the cost
      model must route to grid-stride on its own, and the resolved
      provenance is recorded for the CI gate.

    Bitwise equality across all three cells is asserted before any
    timing; ``benchmarks/check_smoke.py`` gates the committed baseline
    on stride never losing to clamp and beating it >= 1.3x on at least
    one kernel."""
    from repro.core import costmodel

    @cox.kernel
    def strideSaxpy(c, out: cox.Array(cox.f32), x: cox.Array(cox.f32),
                    y: cox.Array(cox.f32), n: cox.i32):
        i = c.block_idx() * c.block_dim() + c.thread_idx()
        if i < n:
            out[i] = 2.5 * x[i] + y[i]

    @cox.kernel
    def strideHist(c, hist: cox.Array(cox.f32), data: cox.Array(cox.i32),
                   n: cox.i32):
        i = c.block_idx() * c.block_dim() + c.thread_idx()
        if i < n:
            c.atomic_add(hist, data[i], 1.0)

    # deliberately tiny blocks + working set: per-block execution cost
    # on XLA-CPU is scatter-bound and schedule-invariant, so the wave
    # loop's fixed overhead — the term grid-stride amortizes over
    # n_resident slots — only shows when blocks are cheap
    block, n = 8, 256
    rng = np.random.default_rng(0)
    x = rng.standard_normal(n).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    data = rng.integers(0, 64, size=n).astype(np.int32)
    cases = {
        "strideSaxpy": (strideSaxpy, "out",
                        (np.zeros(n, np.float32), x, y, np.int32(n))),
        "strideHist": (strideHist, "hist",
                       (np.zeros(64, np.float32), data, np.int32(n))),
    }
    grids = STRIDE_SMOKE_GRIDS if SMOKE else STRIDE_GRIDS
    # any chunk's bid table is >= 256 KiB at 64k blocks, so a 128 KiB
    # budget forces the stride verdict on the bench-sized working set
    budget = 128 << 10
    prev = os.environ.get(costmodel.ENV_BUDGET)
    os.environ[costmodel.ENV_BUDGET] = str(budget)
    try:
        for name in STRIDE_KERNELS:
            kf, key, args = cases[name]
            for grid in grids:

                def run(grid=grid, kf=kf, args=args, **kw):
                    return kf.launch(grid=grid, block=block, args=args,
                                     backend="vmap", **kw)

                rl = kf.make_request(grid=grid, block=block, args=args,
                                     backend="vmap").rl
                assert rl.schedule == "grid_stride", \
                    f"{name} g{grid}: verdict stayed {rl.schedule!r} " \
                    f"under a {budget}-byte budget"
                out_c8 = run(chunk=8)
                out_c1 = run(chunk=1)
                out_gs = run()
                for tag, out in (("clamp1", out_c1), ("stride", out_gs)):
                    np.testing.assert_array_equal(
                        np.asarray(out[key]), np.asarray(out_c8[key]),
                        err_msg=f"{name} g{grid}: {tag} != chunked8")
                times = {
                    "chunked8_us": _time_call(lambda run=run: run(chunk=8)),
                    "clamp1_us": _time_call(lambda run=run: run(chunk=1)),
                    "stride_us": _time_call(lambda run=run: run()),
                }
                vs_clamp = times["clamp1_us"] / times["stride_us"]
                vs_c8 = times["chunked8_us"] / times["stride_us"]
                _row(f"grid_stride.{name}_g{grid}", times["stride_us"],
                     f"chunked8_us={times['chunked8_us']:.1f};"
                     f"clamp1_us={times['clamp1_us']:.1f};"
                     f"stride_vs_clamp={vs_clamp:.2f}x;"
                     f"stride_vs_chunked={vs_c8:.2f}x;"
                     f"n_resident={rl.n_resident};"
                     f"source={rl.schedule_source};budget={budget}")
                GRID_STRIDE_RESULTS.append({
                    "kernel": name, "grid": grid, "block": block, "n": n,
                    "budget": budget,
                    "schedule": rl.schedule,
                    "schedule_source": rl.schedule_source,
                    "n_resident": rl.n_resident,
                    "chunked8_us": round(times["chunked8_us"], 1),
                    "clamp1_us": round(times["clamp1_us"], 1),
                    "stride_us": round(times["stride_us"], 1),
                    "stride_vs_clamp_x": round(vs_clamp, 2),
                    "stride_vs_chunked_x": round(vs_c8, 2),
                })
    finally:
        if prev is None:
            os.environ.pop(costmodel.ENV_BUDGET, None)
        else:
            os.environ[costmodel.ENV_BUDGET] = prev


# ---------------------------------------------------------------------------


def scalability():
    """Fig. 14: multi-block kernels across host devices (8-dev
    subprocess)."""
    _host_device_worker("scalability", "scalability_worker.py")


# ---------------------------------------------------------------------------


def roofline():
    """§Roofline: three terms per dry-run cell (prefers the corrected
    single-pod baseline, falls back to the multi-pod record)."""
    base = os.path.join(os.path.dirname(__file__), "..", "results")
    for name in ("roofline_base.json", "dryrun_all.json"):
        path = os.path.join(base, name)
        if os.path.exists(path):
            from benchmarks.roofline import emit_rows
            emit_rows(path)
            return
    _row("roofline.SKIPPED", 0.0, "run repro.launch.dryrun --all first")


SECTIONS = {
    "coverage": coverage,
    "flat_vs_hier": flat_vs_hier,
    "simd_vote": simd_vote,
    "jit_mode": jit_mode,
    "backend_sweep": backend_sweep,
    "streams": streams,
    "graph_replay": graph_replay,
    "placement": placement,
    "autotune": autotune,
    "grid_stride": grid_stride,
    "scalability": scalability,
    "roofline": roofline,
}


def main(argv=None) -> None:
    global WARMUP, ITERS, SMOKE
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--json", nargs="?", const="BENCH_PR10.json", default=None,
                   metavar="PATH",
                   help="write machine-readable results (default path "
                        "BENCH_PR10.json when the flag is given bare)")
    p.add_argument("--sections", default=None,
                   help=f"comma-separated subset of {sorted(SECTIONS)}")
    p.add_argument("--smoke", action="store_true",
                   help="CI smoke: 1 timing iteration, flagship kernels "
                        "only (pair with --sections backend_sweep)")
    args = p.parse_args(argv)
    compile_cache.enable()
    if args.smoke:
        WARMUP, ITERS, SMOKE = 1, 1, True
    names = (list(SECTIONS) if args.sections is None
             else [s.strip() for s in args.sections.split(",") if s.strip()])
    for name in names:
        if name not in SECTIONS:
            p.error(f"unknown section {name!r}; available: {sorted(SECTIONS)}")
    for name in names:
        SECTIONS[name]()
    if args.json:
        from benchmarks import roofline as _roofline
        from repro.core import autotune as _at
        payload = {
            "schema": "cox-bench-v5",
            "smoke": SMOKE,
            "iters": ITERS,
            "sections": names,
            "rows": RESULTS,
            "backend_sweep": SWEEP_RESULTS,
            "streams": STREAM_RESULTS,
            "graph_replay": GRAPH_RESULTS,
            "placement": PLACEMENT_RESULTS,
            "autotune": AUTOTUNE_RESULTS,
            "grid_stride": GRID_STRIDE_RESULTS,
            "autotune_stats": _at.stats(),
            # live per-stage-key counters from the dispatcher, placed on
            # the host roofline (estimates vs CPU peaks); rows carrying
            # measured wall time also report the attained roof fraction
            "telemetry": _roofline.from_telemetry(
                cox.get_dispatcher().telemetry()),
            # fault-tolerance counters for the whole run: a clean bench
            # must never have taken a degradation-ladder rung (a rung
            # means the timed configuration is not the resolved one)
            "dispatch_health": cox.get_dispatcher().health(),
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"wrote {args.json} ({len(RESULTS)} rows, "
              f"{len(SWEEP_RESULTS)} sweep entries)", flush=True)
    if FAILED:
        raise SystemExit(f"child process failed in: {', '.join(FAILED)}")


if __name__ == "__main__":
    main()
