"""Serving driver: batched greedy decoding with a continuous slot pool.

Requests enter a fixed-size batch of decode slots; finished sequences
free their slot for the next queued request (continuous batching).  The
serve step is the same jitted function the dry-run lowers.

Per-request kernel work rides **cox streams** (`--postproc`): each
decode slot owns a stream, and a finished request's postprocessing
kernel (a token histogram here — the stand-in for dedup/stats/safety
passes) is *enqueued* on its slot's stream and left in flight while the
server keeps decoding.  Independent requests' kernels overlap with each
other and with the decode steps; everything is synchronized once at the
end (``RequestKernelPool.collect``).

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m-smoke \
        --batch 4 --ctx 128 --requests 8 --tokens 16 --postproc
"""
from __future__ import annotations

import argparse
import contextlib
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..configs import registry
from ..configs.base import ShapeConfig
from ..core import cox
from ..models.params import init_params
from ..parallel import steps as steps_mod
from .mesh import make_host_mesh
from . import compile_cache
from . import specs as S


@cox.kernel
def _token_hist(c, hist: cox.Array(cox.i32), toks: cox.Array(cox.i32),
                n: cox.i32, nbins: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        c.atomic_add(hist, toks[i] % nbins, 1)


# the per-token pipeline kernels (--graph captures this 3-launch DAG
# once and replays it per decode step): masked histogram accumulate →
# running total → per-bin stats over the settled counts
@cox.kernel
def _tok_hist_add(c, hist: cox.Array(cox.i32), toks: cox.Array(cox.i32),
                  n: cox.i32, nbins: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        if toks[i] >= 0:                  # -1 marks an idle decode slot
            c.atomic_add(hist, toks[i] % nbins, 1)


@cox.kernel
def _tok_hist_total(c, tot: cox.Array(cox.i32), hist: cox.Array(cox.i32),
                    nbins: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < nbins:
        c.atomic_add(tot, 0, hist[i])


@cox.kernel
def _tok_hist_stats(c, sq: cox.Array(cox.i32), hist: cox.Array(cox.i32),
                    tot: cox.Array(cox.i32), nbins: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < nbins:
        sq[i] = hist[i] * hist[i] + tot[0]


class TokenPipeline:
    """Per-decode-step token statistics as a 3-kernel DAG on one cox
    stream: histogram-accumulate (carried across steps) → total →
    per-bin stats.  ``graph=True`` captures the DAG once and replays it
    per step — one staged-executable call per token instead of three
    binds/launches — with the step's tokens and the carried histogram
    rebound each replay; ``graph=False`` issues the three launches
    eagerly.  Both modes are bitwise-identical by the graph-replay
    equivalence contract."""

    def __init__(self, batch: int, nbins: int = 64, *, graph: bool = False):
        self.batch = batch
        self.nbins = nbins
        self.use_graph = graph
        # priority -1: the per-token stats pipeline is latency-sensitive
        # (it gates the decode loop's step cadence) — the Kahn ready-set
        # dispatches it before the bulk postprocess pool's launches
        self.stream = cox.Stream(name="tok-pipeline", priority=-1)
        self.hist = np.zeros(nbins, np.int32)
        self.last: Dict[str, np.ndarray] = {}
        self._graph: Optional[cox.Graph] = None
        self.steps = 0

    def _launch_dag(self, toks: np.ndarray):
        """Issue the 3-kernel DAG on the stream (capturing or eager)."""
        block = 64
        s, nb = self.stream, self.nbins
        h0 = s.launch(_tok_hist_add, grid=-(-self.batch // block),
                      block=block,
                      args=(self.hist, toks, self.batch, nb))
        h1 = s.launch(_tok_hist_total, grid=-(-nb // block), block=block,
                      args=(np.zeros(1, np.int32), h0.outputs["hist"], nb))
        h2 = s.launch(_tok_hist_stats, grid=-(-nb // block), block=block,
                      args=(np.zeros(nb, np.int32), h1.outputs["hist"],
                            h1.outputs["tot"], nb))
        return h2

    def step(self, tokens: np.ndarray, active: np.ndarray) -> None:
        """Fold one decode step's emitted tokens (idle slots masked to
        -1) into the running statistics."""
        toks = np.where(active, tokens, -1).astype(np.int32)
        self.steps += 1
        if self.use_graph:
            if self._graph is None:       # capture once, replay per token
                self._graph = cox.Graph(name="tok-pipeline")
                with self._graph.capture(self.stream):
                    self._launch_dag(toks)
                res = self._graph.replay()
            else:
                res = self._graph.replay(toks=toks, hist=self.hist)
            self.hist = res["hist"]       # carried via node 2's pass-through
            self.last = {"tot": res["tot"], "sq": res["sq"]}
            return
        h2 = self._launch_dag(toks)
        out = h2.arrays()                 # async: futures, no host block
        self.hist = out["hist"]
        self.last = {"tot": out["tot"], "sq": out["sq"]}

    def collect(self) -> Dict[str, np.ndarray]:
        """Materialize the final statistics (one sync)."""
        return {"hist": np.asarray(self.hist),
                **{k: np.asarray(v) for k, v in self.last.items()}}


class RequestKernelPool:
    """Per-request kernel postprocessing on per-slot cox streams.

    ``submit`` enqueues the request's kernel on its slot's stream and
    returns immediately (the handle is a future — XLA async dispatch);
    the serving loop never blocks on postprocessing.  ``collect``
    synchronizes every stream once, at the end.

    A faulting slot is **isolated**, not fatal: its typed
    :class:`~repro.core.errors.CoxError` surfaces at that handle's own
    sync, the failed request is retired, the slot's stream is reset
    (un-poisoned) so it stays usable, and the remaining slots complete
    normally.  ``health`` carries the pool counters.

    On a multi-device pool the slot streams spread across devices:
    each stream is a distinct placement unit, so the dispatcher's
    round-robin policy deals slots over the healthy devices and
    independent requests' kernels run truly concurrently (priority 1:
    postprocessing is bulk work, dispatched after the latency-sensitive
    token pipeline)."""

    def __init__(self, n_slots: int, nbins: int = 64):
        self.nbins = nbins
        self.streams = [cox.Stream(name=f"req-slot{i}", priority=1)
                        for i in range(n_slots)]
        self.handles: List[cox.LaunchHandle] = []
        self._meta: List[tuple] = []      # (slot, n_tokens) per handle
        self.ok_tokens = 0                # tokens binned by completed slots
        self.health: Dict[str, Any] = {
            "submitted": 0, "completed": 0, "failed": 0,
            "failed_slots": [], "errors": []}

    def submit(self, slot: int, tokens: List[int]) -> None:
        toks = np.asarray(tokens, np.int32)
        n = int(toks.size)
        if n == 0:
            return
        block = 64
        h = self.streams[slot].launch(
            _token_hist, grid=-(-n // block), block=block,
            args=(np.zeros(self.nbins, np.int32), toks, n, self.nbins))
        self.handles.append(h)
        self._meta.append((slot, n))
        self.health["submitted"] += 1

    def collect(self) -> List[np.ndarray]:
        """Synchronize all streams and return each completed request's
        histogram (in completion order), isolating faulting slots."""
        hists: List[np.ndarray] = []
        for (slot, n), h in zip(self._meta, self.handles):
            try:
                hists.append(np.asarray(h.result()["hist"]))
                self.health["completed"] += 1
                self.ok_tokens += n
            except cox.CoxError as e:
                # the failed request is already retired by its surfaced
                # sync; reset clears any residual stream poisoning so
                # the slot can serve the next request
                self.health["failed"] += 1
                self.health["failed_slots"].append(slot)
                self.health["errors"].append(repr(e))
                self.streams[slot].reset()
        return hists


def prefill_program(raw_step):
    """``prefill(params, cache, tok_mat, pos0, mask) -> (cache, pos)``:
    ``lax.scan`` of the raw (un-jitted) serve step over a (T, B) token
    matrix, each slot's position advanced by its ``mask``; the math is
    identical to stepping token by token."""
    def prefill(params, cache, tok_mat, pos0, mask):
        def body(carry, toks):
            cache, pos = carry
            _, cache = raw_step(params, cache, toks, pos)
            return (cache, pos + mask), None

        (cache, pos), _ = jax.lax.scan(body, (cache, pos0), tok_mat)
        return cache, pos

    return prefill


class BatchedServer:
    def __init__(self, arch: str, *, batch: int = 4, ctx: int = 128,
                 mesh=None, seed: int = 0, params=None):
        self.cfg = registry.get(arch)
        self.shape = ShapeConfig(f"serve_{ctx}", ctx, batch, "decode")
        self.mesh = mesh or make_host_mesh(data=1, model=1)
        self.step_fn, self.bundle, _ = steps_mod.jit_serve_step(
            self.cfg, self.mesh, self.shape)
        if params is None:
            params = init_params(self.bundle["specs"],
                                 jax.random.PRNGKey(seed))
        self.params = jax.device_put(params, self.bundle["param_sh"])
        self.batch = batch
        self.ctx = ctx
        # one batched-prefill executable per prompt length (shapes differ)
        self._prefill_cache: Dict[int, Any] = {}
        self.reset()

    def reset(self):
        cache_tree = S.cache_spec_tree(self.cfg, self.shape)
        from ..models.params import init_params as ip
        self.cache = jax.device_put(
            ip(cache_tree, jax.random.PRNGKey(1)),
            self.bundle["rules"].tree_shardings(cache_tree))
        self.pos = np.zeros((self.batch,), np.int32)
        self.tokens = np.zeros((self.batch,), np.int32)
        self.active = np.zeros((self.batch,), bool)
        self.outputs: List[List[int]] = [[] for _ in range(self.batch)]
        # the decode step dispatched ahead of its turn, and the decode
        # call's token limit while it may dispatch ahead (see _step_all)
        self._ahead = None
        self._until: Optional[int] = None

    def _build_prefill(self, T: int):
        """One jitted program for a whole T-token prompt
        (``prefill_program``), cache donated across the scan.  Replaces
        T host round-trips (one jitted dispatch per prompt token) with a
        single call."""
        return jax.jit(prefill_program(self.bundle["raw_step"]),
                       donate_argnums=(1,))

    def prefill_prompt(self, slot: int, prompt: List[int]):
        """Feed a prompt through the decode path in ONE step per slot: a
        single scanned+jitted call consumes the whole prompt (same
        per-token math as the decode loop, batched on device)."""
        self.pos[slot] = 0
        self.outputs[slot] = []
        self.active[slot] = True
        T = len(prompt)
        if T == 0:
            return self
        fn = self._prefill_cache.get(T)
        if fn is None:
            fn = self._prefill_cache[T] = self._build_prefill(T)
        # other slots keep stepping with their current (stale) token,
        # exactly as the old token-by-token loop did
        tok_mat = np.tile(self.tokens.astype(np.int32), (T, 1))
        tok_mat[:, slot] = np.asarray(prompt, np.int32)
        mask = self.active.astype(np.int32)
        with TraceAnnotation("serve.prefill.call"):
            self.cache, pos = fn(self.params, self.cache,
                                 jnp.asarray(tok_mat),
                                 jnp.asarray(self.pos), jnp.asarray(mask))
        with TraceAnnotation("serve.prefill.wait"):
            self.pos = np.array(pos)    # writable host copy
        self.tokens[slot] = prompt[-1]
        return self

    def _call(self, tokens):
        """Dispatch one decode step of every slot at ``self.pos`` (host or
        device tokens); its tokens, still on the device.  Host arrays go
        in as copies: the device may read them after the loop has moved
        on and changed ``self.pos`` and ``self.tokens`` in place."""
        with TraceAnnotation("serve.step.call"):
            toks = jnp.asarray(np.array(tokens) if isinstance(tokens, np.ndarray)
                               else tokens)
            pos = jnp.asarray(self.pos.copy())
            nxt, self.cache = self.step_fn(self.params, self.cache, toks,
                                           pos)
        return nxt

    def _step_all(self):
        """One decode step of every slot; its tokens, on the host.  Inside
        ``decode`` without an ``eos`` (``self._until`` set), which slots
        the step leaves active follows from counts and positions alone, so
        the step after it is dispatched first, fed these tokens on the
        device: the chip runs it while the host emits and runs the
        pipelines.  The chip computes the same steps on the same inputs
        either way."""
        nxt = self._ahead if self._ahead is not None else self._call(self.tokens)
        self._ahead = None
        was_active = self.active.copy()
        self.pos += was_active
        if self._until is not None:
            count = np.array([len(o) for o in self.outputs])
            stays = (was_active & (count + 1 < self._until)
                     & (self.pos < self.ctx - 1))
            if stays.any():
                self._ahead = self._call(
                    nxt if was_active.all()
                    else jnp.where(was_active, nxt, self.tokens.copy()))
        with TraceAnnotation("serve.step.wait"):
            return np.asarray(nxt)

    def decode(self, max_tokens: int, eos: Optional[int] = None,
               pipelines: Optional[List["TokenPipeline"]] = None):
        self._until = max_tokens if eos is None else None
        for _ in range(max_tokens):
            nxt = self._step_all()
            was_active = self.active.copy()
            with TraceAnnotation("serve.step.emit"):
                for i in range(self.batch):
                    if not self.active[i]:
                        continue
                    t = int(nxt[i])
                    self.outputs[i].append(t)
                    self.tokens[i] = t
                    if eos is not None and t == eos:
                        self.active[i] = False
                    if len(self.outputs[i]) >= max_tokens:
                        self.active[i] = False
                    if self.pos[i] >= self.ctx - 1:
                        self.active[i] = False
            for p in pipelines or ():
                p.step(nxt, was_active)
            if not self.active.any():
                break
        self._until = None
        return self.outputs


def serve_requests(arch: str, *, batch: int, ctx: int, n_requests: int,
                   max_tokens: int, seed: int = 0, postproc: bool = False,
                   graph: bool = False, chaos: bool = False) -> Dict[str, Any]:
    """Continuous batching over a queue of synthetic prompt requests.

    With ``postproc=True`` every finished request's token histogram is
    issued on that slot's cox stream and left in flight — per-request
    kernel work overlaps across requests and with subsequent decode
    steps; one synchronize at the end collects everything.

    With ``graph=True`` the per-token stats pipeline (3 dependent
    kernels per decode step) is stream-captured once into a
    ``cox.Graph`` and *replayed* every token — one fused XLA call
    instead of three launches' worth of host-side dispatch.  A shadow
    eager pipeline runs the same steps and the final statistics are
    asserted bitwise-equal.

    With ``chaos=True`` (requires ``postproc``) the first postprocess
    launch is forced to fail via ``cox.faults`` — the fault-injection
    drill: the faulting slot is isolated and every other slot must
    complete with its histogram totals intact."""
    if chaos and not postproc:
        raise ValueError("chaos=True requires postproc=True "
                         "(it faults the postprocess pool)")
    rng = np.random.default_rng(seed)
    server = BatchedServer(arch, batch=batch, ctx=ctx, seed=seed)
    pool = RequestKernelPool(batch) if postproc else None
    pipelines: List[TokenPipeline] = []
    if graph:
        pipelines = [TokenPipeline(batch, graph=True),
                     TokenPipeline(batch, graph=False)]
    queue = [list(rng.integers(1, server.cfg.vocab, size=8))
             for _ in range(n_requests)]
    done: List[List[int]] = []
    t0 = time.time()
    with contextlib.ExitStack() as stack:
        if chaos:
            # deterministically fail the first postprocess dispatch
            stack.enter_context(cox.faults.inject(
                "_token_hist", site="dispatch", index=0, times=1))
        while queue or server.active.any():
            for slot in range(batch):
                if not server.active[slot] and queue:
                    server.prefill_prompt(slot, queue.pop(0))
            server.decode(max_tokens, pipelines=pipelines)
            for slot in range(batch):
                if not server.active[slot] and server.outputs[slot]:
                    done.append(server.outputs[slot])
                    if pool is not None:
                        pool.submit(slot, server.outputs[slot])
                    server.outputs[slot] = []
        out: Dict[str, Any] = {}
        if pool is not None:
            hists = pool.collect()      # one sync for all streams
            out["postproc"] = {
                "requests": len(hists),
                "hist_tokens": int(sum(int(h.sum()) for h in hists)),
                "failed": pool.health["failed"],
                "health": dict(pool.health),
            }
    dt = time.time() - t0
    total_tokens = sum(len(o) for o in done)
    out.update({"completed": len(done), "tokens": total_tokens,
                "wall_s": dt, "tok_per_s": total_tokens / max(dt, 1e-9)})
    out["dispatch_health"] = cox.get_dispatcher().health()
    if pool is not None:
        # the completed histograms were binned from exactly the tokens
        # their requests emitted — a faulted slot subtracts only its own
        assert out["postproc"]["hist_tokens"] == pool.ok_tokens
        if not chaos:
            assert pool.health["failed"] == 0
            assert out["postproc"]["hist_tokens"] == total_tokens
            # a clean run must never lean on the fault-tolerance
            # machinery: a ladder rung here would mask a real regression
            dh = out["dispatch_health"]
            assert dh["degradations"] == 0 and dh["sticky"] is None, dh
        else:
            # one injected fault; the blast radius is CUDA-faithful —
            # the faulting slot's stream is poisoned, so every request
            # it had in flight fails as a CoxDependencyError descendant,
            # and every *other* slot completes untouched
            h = pool.health
            assert h["failed"] >= 1 and set(h["failed_slots"]) == {0}, h
            assert h["completed"] == h["submitted"] - h["failed"], h
            roots = [e for e in h["errors"]
                     if not e.startswith("CoxDependencyError")]
            assert len(roots) == 1 and "injected" in roots[0], h
            # ...and the per-device counters confirm the fault stayed
            # confined to ONE device (slot 0's placement) — the other
            # devices' failure counters are untouched
            dev_fail = [d for d, c in
                        out["dispatch_health"]["devices"].items()
                        if c.get("failures", 0)]
            assert len(dev_fail) == 1, out["dispatch_health"]
    if graph:
        g_stats, e_stats = (p.collect() for p in pipelines)
        for k in g_stats:               # replay ≡ eager, bitwise
            assert np.array_equal(g_stats[k], e_stats[k]), k
        assert int(g_stats["hist"].sum()) == total_tokens
        out["graph"] = {"steps": pipelines[0].steps,
                        "hist_tokens": int(g_stats["hist"].sum()),
                        "replayed": pipelines[0]._graph is not None}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ctx", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--postproc", action="store_true",
                    help="per-request postprocess kernels on per-slot "
                         "cox streams (overlapped, one final sync)")
    ap.add_argument("--graph", action="store_true",
                    help="capture the per-token stats pipeline once as a "
                         "cox.Graph and replay it every decode step "
                         "(verified bitwise against eager launches)")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-injection drill: force the first "
                         "postprocess launch to fail and assert the "
                         "remaining slots complete with correct totals "
                         "(requires --postproc)")
    args = ap.parse_args()
    compile_cache.enable()
    out = serve_requests(args.arch, batch=args.batch, ctx=args.ctx,
                         n_requests=args.requests, max_tokens=args.tokens,
                         postproc=args.postproc, graph=args.graph,
                         chaos=args.chaos)
    msg = (f"served {out['completed']} requests, {out['tokens']} tokens, "
           f"{out['tok_per_s']:.1f} tok/s")
    if args.postproc:
        msg += (f" (+{out['postproc']['requests']} postproc kernels, "
                f"{out['postproc']['hist_tokens']} tokens binned, "
                f"{out['postproc']['failed']} faulted)")
    if args.graph:
        msg += (f" (graph replay: {out['graph']['steps']} steps, "
                f"{out['graph']['hist_tokens']} tokens binned, "
                f"bitwise == eager)")
    # per-device placement health: one cell per device the dispatcher
    # placed work on (multi-device pools spread the slot streams)
    devs = out["dispatch_health"].get("devices", {})
    if devs:
        cells = ", ".join(
            f"{name}: {c['dispatches']}d/{c['failures']}f/"
            f"{c['degradations']}g" for name, c in sorted(devs.items()))
        msg += f" [devices: {cells}]"
    print(msg)


if __name__ == "__main__":
    main()
