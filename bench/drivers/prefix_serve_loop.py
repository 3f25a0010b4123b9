"""Closed-loop single-stream serving of Mellum2-12B-A2.5B through
``BatchedServer`` with a shared context reused across requests: the loop
of ``serve_loop`` (prefill, then ``decode(output_tokens)`` with the COX
token pipeline, one request after the other), where every request
appends to the same ``prefix_tokens``-token context.

Set-up prefills the seeded shared context once (``prefill_prompt``) and
takes ``BatchedServer.snapshot`` of the slot.  Each request then resumes
the snapshot (``BatchedServer.resume``: the full layers' K/V by position
alone, the sliding layers' rings copied back), prefills its prompt on
top and decodes.  Positions per request run from ``prefix_tokens`` to
``prefix_tokens`` + prompt + outputs: every ring is full, and the full
layers read the whole context.

What differs from ``serve_loop``: the weights and the reference come
from ``bench/models/mellum2.py``, and the reference runs the full
forward over context + prompt + outputs; the cache holds K/V of every
position for the full layers and a ring of ``sliding_window`` rows for
each sliding layer, so the checks compare the full layers' K/V of the
last request (``cache_rel_err``) and, new here, each sliding layer's
ring rows against the reference K/V of the positions they hold
(``ring_rel_err``); the model FLOPs count what this chip computes of
the stated deployment (``bench/work_mellum2.py``).

Traffic parameters: ``serve_loop``'s, and ``prefix_tokens``.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import numpy as np

from bench import seeds, work_mellum2
from bench.drivers import serve_loop
from bench.models import mellum2
from repro.configs import registry
from repro.launch.serve import BatchedServer, TokenPipeline

# the program's registry entry must carry the configuration's sizes
_SAME = {
    "n_layers": "num_hidden_layers",
    "d_model": "hidden_size",
    "n_heads": "num_attention_heads",
    "n_kv": "num_key_value_heads",
    "d_head": "head_dim",
    "d_expert": "moe_intermediate_size",
    "n_experts": "num_router_experts",
    "experts_held": "num_experts",
    "expert_lo": "first_held_expert",
    "top_k": "num_experts_per_tok",
    "vocab": "vocab_size",
    "norm_eps": "rms_norm_eps",
    "window": "sliding_window",
    "tie_embeddings": "tie_word_embeddings",
}
# the program's names of the published layer types
_KINDS = {"sliding_attention": "sliding_attention", "full_attention": "attention"}


class Driver(serve_loop.Driver):
    def __init__(self, cfg: Dict, traffic: Dict, seed: int, devices, spans):
        self.cfg, self.traffic, self.seed, self.spans = cfg, traffic, seed, spans
        arch = registry.get(cfg["system_arch"])
        n = cfg["num_hidden_layers"]
        rope = cfg["rope_parameters"]
        yarn = rope["full_attention"]
        want = dict(
            {a: cfg[b] for a, b in _SAME.items()},
            layer_types=tuple(_KINDS[k] for k in cfg["layer_types"][:n]),
            rope_theta=(rope["sliding_attention"]["rope_theta"], yarn["rope_theta"]),
            yarn=(yarn["factor"], yarn["original_max_position_embeddings"],
                  yarn["beta_fast"], yarn["beta_slow"], yarn["attention_factor"]),
        )
        y = arch.yarn
        have = dict(
            {a: getattr(arch, a) for a in _SAME},
            layer_types=tuple(arch.layer_types[:n]),
            rope_theta=(arch.rope_theta,) * 2,
            yarn=y and (y.factor, y.original_max, y.beta_fast, y.beta_slow,
                        y.attention_factor),
        )
        wrong = {a: (have[a], want[a]) for a in want if have[a] != want[a]}
        if wrong:
            raise SystemExit(f"the program's {cfg['system_arch']} differs: {wrong}")
        self.out_tokens = int(traffic["output_tokens"])
        self.requests = self._requests()
        self.done: List[Dict] = []

    # --- set-up -------------------------------------------------------------

    def _shared(self) -> None:
        """Prefill the seed's shared context and snapshot the slot."""
        rng = seeds.rng(self.seed, 5)
        n = int(self.traffic["prefix_tokens"])
        self.prefix = rng.integers(0, self.cfg["vocab_size"], size=n).tolist()
        self.server.prefill_prompt(0, self.prefix)
        self.snap = self.server.snapshot(0)

    def setup(self) -> None:
        weights = mellum2.make_weights(self.cfg, self.seed)
        self.server = BatchedServer(
            self.cfg["system_arch"],
            batch=int(self.traffic["batch"]),
            ctx=int(self.cfg["max_position_embeddings"]),
            params=weights,
        )
        del weights
        self._shared()
        self.pipe = TokenPipeline(int(self.traffic["batch"]), graph=True)
        warm = serve_loop._Recorder()
        for t in sorted(set(self.traffic["prompt_lens"])):
            self.server.resume(0, self.snap)
            self.server.prefill_prompt(0, [1] * int(t))
            self.server.decode(2, pipelines=[self.pipe, warm])
        self.hist0 = np.asarray(self.pipe.collect()["hist"]).copy()

    def reseed(self, seed: int) -> None:
        """Serve another seed's weights, shared context and requests on
        the set-up server (its programs stay compiled)."""
        self.seed = seed
        self.requests = self._requests()
        self.done = []
        weights = mellum2.make_weights(self.cfg, seed)
        self.server.params = jax.device_put(weights, self.server.bundle["param_sh"])
        del weights
        self.server.reset()
        self._shared()

    # --- the window ---------------------------------------------------------

    def run(self, seconds: float) -> Dict:
        pipe = serve_loop._Timed(self.pipe, self.spans)
        start = time.perf_counter_ns()
        deadline = start + int(seconds * 1e9)
        block = len(self.traffic["prompt_lens"])
        while not (
            self.done
            and self.done[-1]["times"][-1] >= deadline
            and len(self.done) % block == 0
        ):
            prompt = next(self.requests)
            rec = serve_loop._Recorder()
            with self.spans("serve.restore"):
                self.server.resume(0, self.snap)
            t0 = time.perf_counter_ns()
            with self.spans("serve.prefill", tokens=len(prompt)):
                self.server.prefill_prompt(0, prompt)
            t1 = time.perf_counter_ns()
            with self.spans("serve.decode"):
                outputs = self.server.decode(self.out_tokens, pipelines=[pipe, rec])
            self.done.append(
                {
                    "prompt": prompt,
                    "served": list(outputs[0]),
                    "prefill_ns": (t0, t1),
                    "times": rec.times,
                }
            )
        end = self.done[-1]["times"][-1]
        self.window = (start, end)
        return {"start_ns": start, "end_ns": end, "requests": len(self.done)}

    # --- results ------------------------------------------------------------

    def _steps(self):
        """(request, index, returned at, kv_len) of every decode step."""
        p = len(self.prefix)
        for r, i, when, kv in super()._steps():
            yield r, i, when, p + kv

    def _context(self, r: Dict) -> List[int]:
        """What the program fed: the shared context, the prompt, its last
        token again in the first decode step, then each served token but
        the last."""
        return self.prefix + r["prompt"] + r["prompt"][-1:] + r["served"][:-1]

    def contexts(self, sample: List[Dict]):
        """The context fed at each position, and the token served there
        (-1 where none was), padded to whole blocks of the reference."""
        width = len(self.prefix) + max(self.traffic["prompt_lens"]) + self.out_tokens
        width = -(-width // mellum2.BLOCK) * mellum2.BLOCK
        tokens = np.zeros((len(sample), width), np.int32)
        served = np.full((len(sample), width), -1, np.int32)
        for b, r in enumerate(sample):
            ctx, t = self._context(r), len(self.prefix) + len(r["prompt"])
            tokens[b, : len(ctx)] = ctx
            served[b, t : t + len(r["served"])] = r["served"]
        return tokens, served

    def counters(self) -> Dict:
        """Work in the window for the readers: model FLOPs of every token
        pass the window computed, prompt tokens included."""
        start, end = self.window
        p = len(self.prefix)
        flops = sum(
            work_mellum2.token_flops(self.cfg, kv)
            for _, _, when, kv in self._steps()
            if start <= when <= end
        )
        for r in self.done:
            if start <= r["prefill_ns"][1] <= end:
                flops += sum(
                    work_mellum2.token_flops(self.cfg, p + kv)
                    for kv in range(1, len(r["prompt"]) + 1)
                )
        return {
            "model_flops": flops,
            "requests": len(self.done),
            "prefix_tokens": p,
            "steps": [(when, kv) for _, _, when, kv in self._steps()],
            "prefills": [(r["prefill_ns"], len(r["prompt"])) for r in self.done],
        }

    def keep_cache(self) -> None:
        """Copy to the host what the checks read of slot 0's cache: the
        full layers' keys and values over the last request's positions,
        and the sliding layers' rings whole."""
        length = len(self._context(self.done[-1]))
        full, ring = ([], []), ([], [])
        for (kind, _), run in zip(mellum2.runs(self.cfg), self.server.cache):
            keep, span = (ring, slice(None)) if kind == mellum2.RING_KIND \
                else (full, slice(0, length))
            for i, n in enumerate("kv"):
                keep[i].append(np.asarray(run[n][:, 0, span], np.float32))
        self.kv = tuple(np.concatenate(a) for a in full)
        self.ring = tuple(np.concatenate(a) for a in ring)

    def release(self) -> None:
        """Keep what the checks read (the pipeline's histogram and the
        cache's share of the last request), then free the server."""
        self.hist = np.asarray(self.pipe.collect()["hist"])
        self.keep_cache()
        del self.server, self.pipe, self.snap
        gc.collect()

    def compare(self, quantize: bool = False) -> Dict[str, float]:
        """The widest logit gap of a served token over a sample of the
        window's requests, the worst full layer's relative error of the
        last request's keys and values, and the worst sliding layer's
        relative error of its ring rows against the reference's keys and
        values of the positions they hold; with ``quantize``, the float8
        control's readings of the same numbers."""
        sample = self._sample()
        tokens, served = self.contexts(sample)
        length = len(self._context(sample[0]))
        weights = mellum2.make_weights(self.cfg, self.seed)
        gaps, full, ring = mellum2.reference(self.cfg, weights, tokens, served, length)
        rows = mellum2.ring_positions(self.cfg, length)
        got_gaps = gaps
        got_full = self.kv
        got_ring = tuple(a[:, rows] for a in self.ring)
        if quantize:
            got_gaps, got_full, got_ring = mellum2.reference(
                self.cfg, weights, tokens, served, length, quantize=True
            )
        del weights

        def worst(got, want):
            return max(mellum2.relative_error(g, w) for g, w in zip(got, want))

        return {
            "max_logit_gap": float(got_gaps[served >= 0].max()),
            "cache_rel_err": worst(got_full, full),
            "ring_rel_err": worst(got_ring, ring),
        }

    def checks(self) -> List[Dict]:
        found = self.compare()
        emitted = np.concatenate([np.asarray(r["served"], np.int64) for r in self.done])
        want = np.bincount(emitted % len(self.hist), minlength=len(self.hist))
        hist_mismatch = int(np.abs((self.hist - self.hist0) - want).sum())
        return [
            {"name": n, "value": v, "limit": float(self.cfg[f"check_{n}"])}
            for n, v in found.items()
        ] + [
            {"name": "short_requests", "value": self.failed(), "limit": 0},
            {"name": "pipeline_hist_mismatch", "value": hist_mismatch, "limit": 0},
        ]
