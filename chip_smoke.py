"""Chip smoke test: the COX launch path and a full-width served model on
one TPU, through the entry points a user calls.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # sharded launches + stream placement
                                      # over four chips, and nothing else

Everything runs in this one process: a chip belongs to the process that
first touches JAX, so no phase starts a child.  Each phase prints one
line of what it checked; a failed check raises and the process exits
non-zero.  The last line of standard output is the JSON contract line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script refuses to run: it never carries on with the
CPU.  It reads nothing outside the checkout and makes its data from
``--seed``.

Phases (one chip):

* **device** — JAX's first device is a TPU; kind, count, compile cache
  and the roofline peaks the benchmark keys on this kind.
* **cox** — five CUDA SDK-style kernels, one per mechanism, launched
  through ``KernelFn.launch`` on ``scan``, ``vmap``/``serial`` and
  ``vmap``/``batched`` (knobs pinned, so the degradation ladder cannot
  move a launch): the three runs must be bitwise-equal and equal a
  numpy reference; histogram64 also runs grid-strided.
* **graph** — a captured two-launch graph replays bitwise-equal to
  eager issue on a stream, also after rebinding its input.
* **health** — the dispatcher took no ladder rung, retry or failure.
* **serve** — ``serve_requests`` on granite-moe-1b-a400m at full width
  (all 24 layers, 32 experts top-8, GQA 16/8, bf16, random weights
  from the seed) with the COX token pipeline captured as a graph; the
  served step runs Pallas kernels, its prefill + first-decode logits
  agree with the plain XLA reference at highest precision, and its
  kernels alone agree with theirs at the step's shapes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from benchmarks import kernels_suite as suite  # noqa: E402
from benchmarks.placement_worker import placeFma  # noqa: E402
from benchmarks.roofline import device_peaks  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.core import cox  # noqa: E402
from repro.core.streams import Dispatcher  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh, make_mesh  # noqa: E402
from repro.launch.serve import serve_requests  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.models.params import init_params  # noqa: E402
from repro.parallel import steps as steps_mod  # noqa: E402

# --- sizes -----------------------------------------------------------------
# vectorAdd: the CUDA SDK sample's default, 50,000 elements as 196 × 256.
# transpose: the SDK sample's 1024 × 1024 matrix (4 MB), 16 × 16 tiles.
# warpPrefixStats, histogram64, gridReduce: 2**20 elements (4 MB) each.
# The SDK histogram's 64 MB input and reductionMultiBlockCG's 2**25
# elements are cut: every vmap wave carries a full copy of each global
# per block, so the work grows as grid × |globals| (ROADMAP Speed 3).
# gridReduce's 8192 blocks exceed the 4096 resident slots of a
# cooperative launch, so they page through 32 resident slots per phase.
FULL = dict(vector_n=50_000, transpose_n=1024, warp_n=1 << 20,
            hist_n=1 << 20, reduce_n=1 << 20, reduce_resident=32)
SERVE = dict(arch="granite-moe-1b-a400m", batch=8, ctx=2048, requests=8,
             tokens=32, prompt=8)

# Every COX check below is exact (tolerance 0): inputs are drawn so each
# float operation is exact — single correctly rounded adds (vectorAdd),
# moves (transpose), or small integers whose sums stay far below 2**24
# (warpPrefixStats, histogram counts, gridReduce) — so any difference
# from numpy, or between executors, is a bug and not rounding.

# The serve step's kernels alone, at its shapes, by relative L2 error
# against kernels/ref.py.  Bound 1e-2: both sides round their bf16
# output (2**-9 relative per element, about 2e-3 in L2); the kernel
# sums in another order, so a few elements round the other way.
KERNEL_REL_TOL = 1e-2

# The served logits, by relative L2 error over every prefill and
# first-decode position (teacher-forced, see served_logits).  Bound 0.5:
# 24 random-weight MoE layers amplify rounding, and top-8 routing over
# 32 experts turns ulp-level differences into different experts.  On the
# CPU, this decode step with its Pallas kernels in interpret mode (the
# same math, differing only in where it rounds) gives 0.22 against XLA,
# from 1.2e-2 at the first position to 0.35 at the later ones; the same
# step with a decode kernel that attends one cache position too many
# gives 0.77, and 1.17 at the first position.  Kernel faults smaller
# than that are KERNEL_REL_TOL's to catch.
LOGITS_REL_TOL = 0.5

CELLS = (("scan", "serial"), ("vmap", "serial"), ("vmap", "batched"))


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _bits(x: np.ndarray) -> np.ndarray:
    return x.view(np.uint32) if x.dtype == np.float32 else x


def _host(out) -> dict:
    return {k: np.asarray(v) for k, v in out.items()}


def same_bits(a: dict, b: dict) -> bool:
    return a.keys() == b.keys() and all(
        a[k].dtype == b[k].dtype and a[k].shape == b[k].shape
        and np.array_equal(_bits(a[k]), _bits(b[k])) for k in a)


# --- numpy references --------------------------------------------------------


def _ref_warp_prefix_stats(a: np.ndarray) -> np.ndarray:
    """warpPrefixStats (benchmarks/kernels_suite.py), one row per warp."""
    v = a.reshape(-1, 32).astype(np.float64)
    lane = np.arange(32)

    def shfl_down(z, off):      # lanes past the warp keep their own value
        src = np.minimum(lane + off, 31)
        return np.where(lane + off < 32, z[:, src], z)

    x = np.repeat(v.sum(1, keepdims=True), 32, 1)      # xor butterfly sum
    y = np.repeat((v * v).max(1, keepdims=True), 32, 1)  # butterfly max
    z = v + 1.0
    for off in (1, 2, 4):
        z = z + shfl_down(z, off)
    m = v.max(1, keepdims=True)
    n = v.min(1, keepdims=True)
    r = z.sum(1, keepdims=True)
    b = 8.0 * y                                        # red_add(width=8)
    lead = (x + m)[:, 0].reshape(-1, 8)                # tile[warp] per block
    tid = np.arange(256)
    t = lead[:, tid % 8].reshape(-1, 32)
    return (x + y + z + m + n + r + b + t).reshape(-1).astype(np.float32)


def cox_cases(sizes: dict, rng) -> list:
    """(name, kernel, grid, block, args, extra launch knobs, reference,
    checked outputs) per mechanism."""
    n = sizes["vector_n"]
    a = rng.normal(size=n).astype(np.float32)
    b = rng.normal(size=n).astype(np.float32)
    vec = ("vectorAdd", suite.vectorAdd, -(-n // 256), 256,
           (np.zeros(n, np.float32), a, b, n), {},
           {"out": a + b}, f"n={n}")

    t = sizes["transpose_n"]
    mat = rng.normal(size=(t, t)).astype(np.float32)
    tr = ("transpose", suite.transpose, (t // 16, t // 16), (16, 16),
          (np.zeros((t, t), np.float32), mat, t), {},
          {"odata": mat.T.reshape(-1)}, f"{t}x{t}")

    w = sizes["warp_n"]
    wa = rng.integers(-6, 7, size=w).astype(np.float32)
    wps = ("warpPrefixStats", suite.warpPrefixStats, w // 256, 256,
           (np.zeros(w, np.float32), wa), {},
           {"out": _ref_warp_prefix_stats(wa)}, f"n={w}")

    h = sizes["hist_n"]
    data = rng.integers(0, 64, size=h).astype(np.int32)
    hist = ("histogram64", suite.histogram64, -(-h // 256), 256,
            (np.zeros(64, np.float32), data, h), {"schedule": "chunked"},
            {"hist": np.bincount(data, minlength=64).astype(np.float32)},
            f"n={h}")

    r = sizes["reduce_n"]
    rd = rng.integers(-8, 9, size=r).astype(np.float32)
    grid = r // 128
    partial = rd.reshape(grid, 128).sum(1, dtype=np.float64)
    red = ("gridReduce", suite.gridReduce, grid, 128,
           (np.zeros(1, np.float32), np.zeros(grid, np.float32), rd, r),
           {"n_resident": sizes["reduce_resident"]},
           {"partial": partial.astype(np.float32),
            "total": np.array([partial.sum()], np.float32)},
           f"n={r} resident={sizes['reduce_resident']}")
    return [vec, tr, wps, hist, red]


# --- phases ------------------------------------------------------------------


def phase_device(chips: int):
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (first device is "
                         f"{dev.platform!r}); refusing to run without one")
    check(len(devs) >= chips, f"--chips {chips} but JAX sees {len(devs)}")
    cache = compile_cache.enable()
    peaks = device_peaks(dev)
    print(f"[device] platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devs)} compile_cache={cache} "
          f"peaks={peaks['peak_flops']:.3e} FLOP/s, "
          f"{peaks['mem_bw']:.3e} B/s", flush=True)
    return dev


def phase_cox(sizes: dict, seed: int) -> None:
    rng = np.random.default_rng(seed)
    for name, k, grid, block, args, knobs, ref, what in cox_cases(sizes,
                                                                  rng):
        outs = []
        for backend, warp_exec in CELLS:
            outs.append(_host(k.launch(grid=grid, block=block, args=args,
                                       backend=backend, warp_exec=warp_exec,
                                       **knobs)))
        for (backend, warp_exec), o in zip(CELLS[1:], outs[1:]):
            check(same_bits(outs[0], o),
                  f"{name}: {backend}/{warp_exec} differs from scan/serial")
        for key, want in ref.items():
            check(np.array_equal(outs[0][key].reshape(-1), want.reshape(-1)),
                  f"{name}: '{key}' differs from the numpy reference")
        line = (f"[cox] {name} {what} grid={grid} block={block}: "
                f"scan/serial == vmap/serial == vmap/batched bitwise, "
                f"== numpy exactly")
        if name == "histogram64":
            strided = _host(k.launch(grid=grid, block=block, args=args,
                                     backend="vmap", warp_exec="serial",
                                     schedule="grid_stride"))
            check(same_bits(strided, outs[1]),
                  "histogram64: grid_stride differs from chunked")
            line += "; grid_stride == chunked bitwise"
        print(line, flush=True)


def phase_graph(sizes: dict, seed: int) -> None:
    """transpose(transpose(A)) captured once as a two-node graph with a
    data edge, replayed, then replayed with a rebound input."""
    t = sizes["transpose_n"]
    rng = np.random.default_rng(seed + 1)
    a0 = rng.normal(size=(t, t)).astype(np.float32)
    a1 = rng.normal(size=(t, t)).astype(np.float32)
    knobs = dict(grid=(t // 16, t // 16), block=(16, 16), backend="vmap",
                 warp_exec="serial")
    stream = cox.Stream("smoke-graph")

    def issue(a):
        h1 = stream.launch(suite.transpose, args=(np.zeros((t, t),
                                                           np.float32), a, t),
                           **knobs)
        h2 = stream.launch(suite.transpose,
                           args=(np.zeros((t, t), np.float32),
                                 h1.outputs["odata"], t), **knobs)
        return h1, h2

    eager = [{k: np.asarray(v) for k, v in h.result().items()}
             for h in issue(a0)]
    graph = cox.Graph(name="smoke-transpose2")
    with graph.capture(stream):
        issue(a0)
    replay = _host(graph.replay())
    check(np.array_equal(_bits(replay["odata"].reshape(-1)),
                         _bits(eager[1]["odata"].reshape(-1))),
          "graph replay differs from eager issue")
    check(np.array_equal(replay["odata"].reshape(t, t), a0),
          "transpose∘transpose is not the identity")
    rebound = _host(graph.replay(idata=a1))
    eager1 = np.asarray(issue(a1)[1].result()["odata"])
    check(np.array_equal(_bits(rebound["odata"].reshape(-1)),
                         _bits(eager1.reshape(-1))),
          "rebound graph replay differs from eager issue")
    print(f"[graph] transpose->transpose {t}x{t} captured, replayed and "
          f"rebound: bitwise == eager issue", flush=True)


def phase_health(label: str) -> None:
    h = cox.get_dispatcher().health()
    bad = {k: h[k] for k in ("degradations", "retries", "failures",
                             "timeouts") if h[k]}
    check(not bad and h["sticky"] is None,
          f"dispatcher health after {label}: {bad}, sticky={h['sticky']}")
    print(f"[health] after {label}: degradations=0 retries=0 failures=0 "
          f"timeouts=0 sticky=None; set-up: peak_bytes_in_use so far="
          f"{_peak_bytes()}", flush=True)


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def served_logits(cfg, mesh, params, toks, ctx: int, backend: str = "auto"):
    """The serve step's logits at every prompt position (prefill) and the
    first decode position, teacher-forced: each position's step on the
    ``backend`` path, on XLA at default precision, and on XLA at highest
    precision all start from the highest-precision path's cache.  Returns
    (backend, xla@default, xla@highest) logits, each (T, B, V).

    Teacher forcing keeps what is compared to one step.  Run free, each
    path grows its own cache, and a near-tie that flips one top-8 routing
    decision at one position changes every later position's keys: on
    random weights one bf16 ulp on 1% of the embedding entries then moves
    the ninth position's logits by 60% (rel L2, granite-moe-1b-a400m on
    the CPU), so a free-running comparison cannot tell rounding from a
    fault."""
    _, bundle = steps_mod.make_serve_step(cfg, mesh)
    rules = bundle["rules"]
    cache = init_params(lm.cache_specs(cfg, toks.shape[1], ctx),
                        jax.random.PRNGKey(1))

    def step_fn(backend):
        def step(params, cache, tok, pos):
            return lm.decode_step(cfg, params, cache, tok, pos, rules=rules,
                                  backend=backend)
        return step

    kernel_step = jax.jit(lambda *a: step_fn(backend)(*a)[0])
    default_step = jax.jit(lambda *a: step_fn("xla")(*a)[0])
    highest_step = jax.jit(step_fn("xla"), donate_argnums=(1,))
    out = ([], [], [])
    for t, tok in enumerate(jnp.asarray(toks)):
        pos = jnp.full((toks.shape[1],), t, jnp.int32)
        out[0].append(np.asarray(kernel_step(params, cache, tok, pos)))
        out[1].append(np.asarray(default_step(params, cache, tok, pos)))
        with jax.default_matmul_precision("highest"):
            logits, cache = highest_step(params, cache, tok, pos)
        out[2].append(np.asarray(logits))
    return tuple(np.stack(o).astype(np.float32) for o in out)


def check_serve_kernels(cfg, batch: int, ctx: int, seed: int,
                        backend: str) -> str:
    """The serve step's kernels at its own shapes — rmsnorm over (B, d)
    and the GQA decode attention vmapped over B with cache lengths from
    1 to ``ctx`` — against ``kernels/ref.py`` at highest precision."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (batch, cfg.d_model), jnp.bfloat16)
    w = 1.0 + 0.1 * jax.random.normal(k[1], (cfg.d_model,))
    q = jax.random.normal(k[2], (batch, cfg.n_heads, cfg.d_head),
                          jnp.bfloat16)
    kv = (jax.random.normal(k[3], (batch, ctx, cfg.n_kv, cfg.d_head),
                            jnp.bfloat16),
          jax.random.normal(k[4], (batch, ctx, cfg.n_kv, cfg.d_head),
                            jnp.bfloat16))
    lens = jnp.asarray(np.linspace(1, ctx, batch), jnp.int32)

    def run(b):
        return {"rmsnorm": ops.rmsnorm(x, w, backend=b),
                "decode_attention": jax.vmap(
                    lambda *a: ops.decode_attention(*a, backend=b))(
                        q, *kv, lens)}

    got = run(backend)
    with jax.default_matmul_precision("highest"):
        want = run("xla")
    rels = []
    for name in got:
        g, r = (np.asarray(a, np.float32) for a in (got[name], want[name]))
        rel = float(np.linalg.norm(g - r) / np.linalg.norm(r))
        check(rel <= KERNEL_REL_TOL,
              f"{name} ({backend}) vs reference: rel L2 {rel:.3e}")
        rels.append(f"{name} {rel:.3e}")
    return ", ".join(rels)


def phase_serve(serve: dict, seed: int, *, require_kernels: bool = True):
    arch, batch, ctx = serve["arch"], serve["batch"], serve["ctx"]
    cfg = registry.get(arch)
    backend = ops.resolve()
    if require_kernels:
        check(backend == "pallas",
              f"kernels.ops.resolve() is {backend!r}, not 'pallas'")
    mesh = make_host_mesh(data=1, model=1)
    shape = ShapeConfig(f"serve_{ctx}", ctx, batch, "decode")
    jitted, _, abstract = steps_mod.jit_serve_step(cfg, mesh, shape)
    t0 = time.perf_counter()
    compiled = jitted.lower(*abstract).compile()
    compile_s = time.perf_counter() - t0
    n_custom = compiled.as_text().count("tpu_custom_call")
    if require_kernels:
        check(n_custom > 0, "the compiled serve step has no tpu_custom_call")
    print(f"[serve] {arch}: {cfg.n_layers} layers, {cfg.n_experts} experts "
          f"top-{cfg.top_k}, GQA {cfg.n_heads}/{cfg.n_kv}, "
          f"{cfg.param_count() / 1e9:.3f} B params; kernels={backend}; "
          f"serve step has {n_custom} tpu_custom_call; set-up: serve-step "
          f"compile {compile_s:.1f} s", flush=True)
    del compiled

    t0 = time.perf_counter()
    out = serve_requests(arch, batch=batch, ctx=ctx,
                         n_requests=serve["requests"],
                         max_tokens=serve["tokens"], seed=seed,
                         postproc=True, graph=True)
    wall_s = time.perf_counter() - t0
    want_tokens = serve["requests"] * serve["tokens"]
    check(out["completed"] == serve["requests"],
          f"served {out['completed']} of {serve['requests']} requests")
    check(out["tokens"] == want_tokens,
          f"served {out['tokens']} tokens, expected {want_tokens}")
    check(out["postproc"]["hist_tokens"] == want_tokens,
          f"postprocess histograms binned {out['postproc']['hist_tokens']}")
    check(out["graph"]["replayed"]
          and out["graph"]["hist_tokens"] == want_tokens,
          f"token pipeline graph: {out['graph']}")
    print(f"[serve] served {out['completed']} requests x "
          f"{serve['tokens']} tokens at batch={batch} ctx={ctx}: "
          f"postprocess histograms bin {out['postproc']['hist_tokens']} "
          f"tokens, graph replay == eager over {out['graph']['steps']} "
          f"steps; set-up + serving wall {wall_s:.1f} s (compiles "
          f"included, not a measurement); peak_bytes_in_use so far="
          f"{_peak_bytes()}", flush=True)

    # the same decode step on both kernel paths, on the same weights
    _, bundle = steps_mod.make_serve_step(cfg, mesh)
    params = jax.device_put(init_params(bundle["specs"],
                                        jax.random.PRNGKey(seed)),
                            bundle["param_sh"])
    rng = np.random.default_rng(seed + 2)
    toks = rng.integers(1, cfg.vocab, size=(serve["prompt"] + 1, batch),
                        dtype=np.int32)
    got, floor, want = served_logits(cfg, mesh, params, toks, ctx)
    del params
    check(np.isfinite(got).all(), "served logits are not finite")

    def rel(a, b=want):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    check(rel(got) <= LOGITS_REL_TOL,
          f"served logits vs xla@highest: rel L2 {rel(got):.3e} > "
          f"{LOGITS_REL_TOL}")
    per_pos = " ".join(f"{rel(g, w):.2e}" for g, w in zip(got, want))
    kernels = check_serve_kernels(cfg, batch, ctx, seed, backend)
    print(f"[serve] prefill ({serve['prompt']} tokens) + first decode "
          f"logits, {backend} path vs xla@highest: rel L2 {rel(got):.3e} "
          f"<= {LOGITS_REL_TOL} (by position: {per_pos}; xla@default vs "
          f"xla@highest: {rel(floor):.3e}); kernels alone vs reference: "
          f"{kernels} <= {KERNEL_REL_TOL:.0e}; set-up: peak_bytes_in_use="
          f"{_peak_bytes()}", flush=True)


def phase_four_chips(sizes: dict, seed: int) -> None:
    """Sharded launches over a 4-chip mesh and stream placement over a
    4-device pool, each bitwise against a single-device scan run."""
    devs = tuple(jax.devices()[:4])
    check(len(devs) == 4, f"need 4 devices, JAX sees {len(devs)}")
    mesh = make_mesh((4,), ("data",), devices=devs)
    rng = np.random.default_rng(seed)
    cases = {c[0]: c for c in cox_cases(sizes, rng)}
    for name in ("vectorAdd", "histogram64", "gridReduce"):
        _, k, grid, block, args, knobs, ref, what = cases[name]
        want = _host(k.launch(grid=grid, block=block, args=args,
                              backend="scan", warp_exec="serial", **knobs))
        got = k.launch(grid=grid, block=block, args=args, mesh=mesh,
                       backend="sharded", warp_exec="serial", **knobs)
        held = {d for v in got.values() for d in v.sharding.device_set}
        check(held == set(devs),
              f"{name}: sharded outputs live on {len(held)} devices")
        check(same_bits(_host(got), want),
              f"{name}: sharded over 4 chips differs from scan")
        for key, w in ref.items():
            check(np.array_equal(want[key].reshape(-1), w.reshape(-1)),
                  f"{name}: '{key}' differs from the numpy reference")
        print(f"[4chips] sharded {name} {what}: outputs on all 4 devices, "
              f"bitwise == single-device scan == numpy", flush=True)

    # the placement benchmark's 4-stream program over a 4-device pool
    grid, block, depth = 32, 256, 2
    n = grid * block
    per_stream = [(np.zeros(n, np.float32),
                   rng.normal(size=n).astype(np.float32),
                   rng.normal(size=n).astype(np.float32), n)
                  for _ in range(4)]
    disp = Dispatcher(devices=devs)
    streams = [cox.Stream(f"smoke-place{i}", dispatcher=disp)
               for i in range(4)]
    handles = [(a, st.launch(placeFma, grid=grid, block=block, args=a,
                             backend="scan", warp_exec="serial"))
               for _ in range(depth) for st, a in zip(streams, per_stream)]
    held = set()
    for a, h in handles:
        out = h.result()["out"]
        held |= set(out.devices())
        want = np.asarray(placeFma.launch(grid=grid, block=block, args=a,
                                          backend="scan",
                                          warp_exec="serial")["out"])
        check(np.array_equal(_bits(np.asarray(out)), _bits(want)),
              "placed launch differs from the single-device scan run")
    used = {d for d, c in disp.device_health().items()
            if c.get("dispatches", 0) > 0}
    check(len(used) == 4 and held == set(devs),
          f"placement used {sorted(used)}, outputs on {len(held)} devices")
    health = disp.health()
    check(health["degradations"] == 0 and health["failures"] == 0,
          f"placement dispatcher health: {health}")
    print(f"[4chips] placement: 4 streams x {depth} launches over a "
          f"4-device pool, dispatches on all 4 devices, outputs on all 4, "
          f"bitwise == single-device scan", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded and placement phases "
                         "over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = phase_device(args.chips)
    if args.chips == 4:
        phase_four_chips(FULL, args.seed)
        phase_health("the four-chip phases")
    else:
        phase_cox(FULL, args.seed)
        phase_graph(FULL, args.seed)
        phase_health("the COX phases")
        phase_serve(SERVE, args.seed)
        phase_health("serving")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
