"""Closed-loop single-stream serving through ``BatchedServer``.

Each request is ``prefill_prompt`` then ``decode(output_tokens)`` with
the COX token pipeline (``TokenPipeline(graph=True)``) and a recorder
that timestamps every step.  The next request starts when the last one
has finished.

Traffic parameters (``bench/traffic/<mix>.json``):

* ``batch``: decode slots of the server;
* ``prompt_lens``: prompt lengths, in equal counts: the requests come in
  blocks that hold each length once, in an order drawn from the seed;
* ``output_tokens``: tokens decoded per request;
* ``sample``: finished requests compared with the reference, the one
  with the longest prompt among them.

Token ids are uniform over the vocabulary.  The window closes at the end
of the first whole block of requests that ends after ``--seconds``, so
every run serves the same mix of lengths, whole blocks of it.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, Iterator, List

import jax
import numpy as np

from bench import seeds
from bench.models import granite
from repro.configs import registry
from repro.launch.serve import BatchedServer, TokenPipeline

# the program's registry entry must carry the configuration's sizes
_SAME = {
    "n_layers": "num_hidden_layers",
    "d_model": "hidden_size",
    "n_heads": "num_attention_heads",
    "n_kv": "num_key_value_heads",
    "d_expert": "intermediate_size",
    "n_experts": "num_local_experts",
    "top_k": "num_experts_per_tok",
    "vocab": "vocab_size",
}


class _Recorder:
    """A pipeline that only notes when each decode step returned."""

    def __init__(self):
        self.times: List[int] = []

    def step(self, tokens, active) -> None:
        self.times.append(time.perf_counter_ns())


class _Timed:
    """The COX token pipeline inside a ``serve.pipeline`` span."""

    def __init__(self, pipe, spans):
        self.pipe, self.spans = pipe, spans

    def step(self, tokens, active) -> None:
        with self.spans("serve.pipeline"):
            self.pipe.step(tokens, active)


class Driver:
    def __init__(self, cfg: Dict, traffic: Dict, seed: int, devices, spans):
        self.cfg, self.traffic, self.seed, self.spans = cfg, traffic, seed, spans
        arch = registry.get(cfg["system_arch"])
        wrong = {
            a: (getattr(arch, a), b)
            for a, b in _SAME.items()
            if getattr(arch, a) != cfg[b]
        }
        if wrong:
            raise SystemExit(f"the program's {cfg['system_arch']} differs: {wrong}")
        self.out_tokens = int(traffic["output_tokens"])
        self.requests = self._requests()
        self.done: List[Dict] = []

    def _requests(self) -> Iterator[List[int]]:
        """Prompts without end, in blocks that hold each length once."""
        rng = seeds.rng(self.seed, 2)
        while True:
            for t in rng.permutation(list(self.traffic["prompt_lens"])):
                yield rng.integers(0, self.cfg["vocab_size"], size=int(t)).tolist()

    # --- set-up -------------------------------------------------------------

    def setup(self) -> None:
        weights = granite.make_weights(self.cfg, self.seed)
        self.server = BatchedServer(
            self.cfg["system_arch"],
            batch=int(self.traffic["batch"]),
            ctx=int(self.cfg["max_position_embeddings"]),
            params=weights,
        )
        del weights
        self.pipe = TokenPipeline(int(self.traffic["batch"]), graph=True)
        warm = _Recorder()
        for t in sorted(set(self.traffic["prompt_lens"])):
            self.server.prefill_prompt(0, [1] * int(t))
            self.server.decode(2, pipelines=[self.pipe, warm])
        self.hist0 = np.asarray(self.pipe.collect()["hist"]).copy()

    def reseed(self, seed: int) -> None:
        """Serve another seed's weights and requests on the set-up server
        (its programs stay compiled)."""
        self.seed = seed
        self.requests = self._requests()
        self.done = []
        weights = granite.make_weights(self.cfg, seed)
        self.server.params = jax.device_put(weights, self.server.bundle["param_sh"])
        del weights
        self.server.reset()

    # --- the window ---------------------------------------------------------

    def run(self, seconds: float) -> Dict:
        pipe = _Timed(self.pipe, self.spans)
        start = time.perf_counter_ns()
        deadline = start + int(seconds * 1e9)
        block = len(self.traffic["prompt_lens"])
        while not (
            self.done
            and self.done[-1]["times"][-1] >= deadline
            and len(self.done) % block == 0
        ):
            prompt = next(self.requests)
            rec = _Recorder()
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
        for r in self.done:
            t = len(r["prompt"])
            for i, when in enumerate(r["times"]):
                # the program's first decode step feeds the last prompt
                # token again, at position len(prompt)
                yield r, i, when, t + i + 1

    def end_to_end(self) -> Dict[str, float]:
        start, end = self.window
        tokens = sum(1 for _, _, when, _ in self._steps() if start <= when <= end)
        gaps = [
            (b - a) * 1e-6
            for r in self.done
            for a, b in zip(r["times"], r["times"][1:])
            if start <= a and b <= end
        ]
        return {
            "serve_tok_s": tokens / ((end - start) * 1e-9),
            "itl_p95_ms": float(np.percentile(gaps, 95)),
        }

    def counters(self) -> Dict:
        """Work in the window for the readers: model FLOPs of every token
        the window computed, prefill tokens included."""
        from bench import work

        start, end = self.window
        flops = sum(
            work.granite_token_flops(self.cfg, kv)
            for _, _, when, kv in self._steps()
            if start <= when <= end
        )
        for r in self.done:
            if start <= r["prefill_ns"][1] <= end:
                flops += sum(
                    work.granite_token_flops(self.cfg, kv)
                    for kv in range(1, len(r["prompt"]) + 1)
                )
        return {
            "model_flops": flops,
            "requests": len(self.done),
            "steps": [(when, kv) for _, _, when, kv in self._steps()],
            "prefills": [(r["prefill_ns"], len(r["prompt"])) for r in self.done],
        }

    def release(self) -> None:
        """Keep what the checks read (the pipeline's histogram, and the
        keys and values the last request left in the cache), then free
        the server."""
        self.hist = np.asarray(self.pipe.collect()["hist"])
        length = len(self._context(self.done[-1]))
        cache = self.server.cache
        self.kv = tuple(np.asarray(cache[n][:, 0, :length], np.float32) for n in "kv")
        del self.server, self.pipe, cache
        gc.collect()

    def _sample(self) -> List[Dict]:
        """The last request (its keys and values are still in the cache),
        one with the longest prompt, and others drawn from the seed."""
        rng = seeds.rng(self.seed, 3)
        last = len(self.done) - 1
        picked = [last]
        longest = max(len(r["prompt"]) for r in self.done)
        if len(self.done[last]["prompt"]) != longest:
            same = [i for i, r in enumerate(self.done) if len(r["prompt"]) == longest]
            picked.append(int(rng.choice(same)))
        rest = [i for i in range(len(self.done)) if i not in picked]
        more = min(len(rest), int(self.traffic["sample"]) - len(picked))
        picked += [int(i) for i in rng.choice(rest, size=more, replace=False)]
        return [self.done[i] for i in picked]

    @staticmethod
    def _context(r: Dict) -> List[int]:
        """What the program fed: the prompt in prefill, then its last token
        again in the first decode step, then each served token but the last."""
        return r["prompt"] + r["prompt"][-1:] + r["served"][:-1]

    def contexts(self, sample: List[Dict]):
        """The context fed at each position, and the token served there
        (-1 where none was), padded to the longest prompt plus the outputs."""
        width = max(self.traffic["prompt_lens"]) + self.out_tokens
        tokens = np.zeros((len(sample), width), np.int32)
        served = np.full((len(sample), width), -1, np.int32)
        for b, r in enumerate(sample):
            ctx, t = self._context(r), len(r["prompt"])
            tokens[b, : len(ctx)] = ctx
            served[b, t : t + len(r["served"])] = r["served"]
        return tokens, served

    def compare(self, quantize: bool = False) -> Dict[str, float]:
        """The widest logit gap of a served token, over a sample of the
        window's requests, and the worst layer's relative error of the
        last request's cached keys and values, against the reference; with
        ``quantize``, the control's readings of the same two numbers."""
        sample = self._sample()
        tokens, served = self.contexts(sample)
        weights = granite.make_weights(self.cfg, self.seed)
        gaps, k, v = granite.reference(self.cfg, weights, tokens, served)
        length = len(self._context(sample[0]))
        if quantize:
            low_gaps, low_k, low_v = granite.reference(
                self.cfg, weights, tokens, served, quantize=True
            )
            out = {
                "max_logit_gap": float(low_gaps[served >= 0].max()),
                "cache_rel_err": granite.cache_error(low_k, low_v, k, v, length),
            }
        else:
            out = {
                "max_logit_gap": float(gaps[served >= 0].max()),
                "cache_rel_err": granite.cache_error(*self.kv, k, v, length),
            }
        del weights
        return out

    def checks(self) -> List[Dict]:
        found = self.compare()
        emitted = np.concatenate([np.asarray(r["served"], np.int64) for r in self.done])
        want = np.bincount(emitted % len(self.hist), minlength=len(self.hist))
        hist_mismatch = int(np.abs((self.hist - self.hist0) - want).sum())
        return [
            {
                "name": "max_logit_gap",
                "value": found["max_logit_gap"],
                "limit": float(self.cfg["check_max_logit_gap"]),
            },
            {
                "name": "cache_rel_err",
                "value": found["cache_rel_err"],
                "limit": float(self.cfg["check_cache_rel_err"]),
            },
            {"name": "short_requests", "value": self.failed(), "limit": 0},
            {"name": "pipeline_hist_mismatch", "value": hist_mismatch, "limit": 0},
        ]

    def attempted(self) -> int:
        return len(self.done)

    def failed(self) -> int:
        return sum(len(r["served"]) != self.out_tokens for r in self.done)
