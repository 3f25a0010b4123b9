"""Closed-loop single-stream serving of granite-4.0-h-small through
``BatchedServer``: the loop of ``serve_loop`` (prefill, then
``decode(output_tokens)`` with the COX token pipeline, one request after
the other), on the Mamba2/attention hybrid.

What differs from ``serve_loop``: the weights and the reference come
from ``bench/models/granite_hybrid.py``; the cache holds K/V for the
attention layers and an f32 SSM state and conv tail for each Mamba2
layer, so the checks compare the attention layers' keys and values
(``cache_rel_err``) and, new here, each Mamba2 layer's final SSM state
for the last request (``state_rel_err``); and the model FLOPs count
what this chip computes of the stated deployment (``bench/work_hybrid.py``).

Traffic parameters as ``serve_loop``'s.
"""

from __future__ import annotations

import gc
from typing import Dict, List

import jax
import numpy as np

from bench import work_hybrid
from bench.drivers import serve_loop
from bench.models import granite_hybrid
from repro.configs import registry
from repro.launch.serve import BatchedServer, TokenPipeline

# the program's registry entry must carry the configuration's sizes
_SAME = {
    "n_layers": "num_hidden_layers",
    "d_model": "hidden_size",
    "n_heads": "num_attention_heads",
    "n_kv": "num_key_value_heads",
    "d_expert": "intermediate_size",
    "d_shared": "shared_intermediate_size",
    "n_experts": "num_router_experts",
    "experts_held": "num_local_experts",
    "expert_lo": "first_held_expert",
    "top_k": "num_experts_per_tok",
    "vocab": "vocab_size",
    "ssm_heads": "mamba_n_heads",
    "ssm_head_dim": "mamba_d_head",
    "ssm_state": "mamba_d_state",
    "conv_k": "mamba_d_conv",
    "conv_bias": "mamba_conv_bias",
    "norm_eps": "rms_norm_eps",
}


class Driver(serve_loop.Driver):
    def __init__(self, cfg: Dict, traffic: Dict, seed: int, devices, spans):
        self.cfg, self.traffic, self.seed, self.spans = cfg, traffic, seed, spans
        arch = registry.get(cfg["system_arch"])
        n = cfg["num_hidden_layers"]
        want = dict(
            {a: cfg[b] for a, b in _SAME.items()},
            ssm_inner=cfg["mamba_expand"] * cfg["hidden_size"],
            rope=cfg["position_embedding_type"] != "nope",
            layer_types=tuple(cfg["layer_types"][:n]),
        )
        have = dict(
            {a: getattr(arch, a) for a in want},
            layer_types=tuple(arch.layer_types[:n]),
        )
        wrong = {a: (have[a], want[a]) for a in want if have[a] != want[a]}
        if wrong:
            raise SystemExit(f"the program's {cfg['system_arch']} differs: {wrong}")
        self.out_tokens = int(traffic["output_tokens"])
        self.requests = self._requests()
        self.done: List[Dict] = []

    # --- set-up -------------------------------------------------------------

    def setup(self) -> None:
        weights = granite_hybrid.make_weights(self.cfg, self.seed)
        self.server = BatchedServer(
            self.cfg["system_arch"],
            batch=int(self.traffic["batch"]),
            ctx=int(self.cfg["max_position_embeddings"]),
            params=weights,
        )
        del weights
        self.pipe = TokenPipeline(int(self.traffic["batch"]), graph=True)
        warm = serve_loop._Recorder()
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
        weights = granite_hybrid.make_weights(self.cfg, seed)
        self.server.params = jax.device_put(weights, self.server.bundle["param_sh"])
        del weights
        self.server.reset()

    # --- results ------------------------------------------------------------

    def counters(self) -> Dict:
        """Work in the window for the readers: model FLOPs of every token
        pass the window computed, prefill tokens included."""
        start, end = self.window
        flops = sum(
            work_hybrid.token_flops(self.cfg, kv)
            for _, _, when, kv in self._steps()
            if start <= when <= end
        )
        for r in self.done:
            if start <= r["prefill_ns"][1] <= end:
                flops += sum(
                    work_hybrid.token_flops(self.cfg, kv)
                    for kv in range(1, len(r["prompt"]) + 1)
                )
        return {
            "model_flops": flops,
            "requests": len(self.done),
            "steps": [(when, kv) for _, _, when, kv in self._steps()],
            "prefills": [(r["prefill_ns"], len(r["prompt"])) for r in self.done],
        }

    def keep_cache(self) -> None:
        """Copy to the host what the checks read of the cache: the
        attention layers' keys and values over the last request's
        positions, and every Mamba2 layer's SSM state, of slot 0, as
        (heads, d_state, d_head) (the program keeps it (d_head, d_state))."""
        length = len(self._context(self.done[-1]))
        ks, vs, states = [], [], []
        for (kind, _), run in zip(granite_hybrid.runs(self.cfg), self.server.cache):
            if kind == "mamba":
                states.append(np.asarray(run["h"][:, 0], np.float32).swapaxes(-1, -2))
            else:
                ks.append(np.asarray(run["k"][:, 0, :length], np.float32))
                vs.append(np.asarray(run["v"][:, 0, :length], np.float32))
        self.kv = (np.concatenate(ks), np.concatenate(vs))
        self.states = np.concatenate(states)

    def release(self) -> None:
        """Keep what the checks read (the pipeline's histogram and the
        cache's share of the last request), then free the server."""
        self.hist = np.asarray(self.pipe.collect()["hist"])
        self.keep_cache()
        del self.server, self.pipe
        gc.collect()

    def compare(self, quantize: bool = False) -> Dict[str, float]:
        """The widest logit gap of a served token over a sample of the
        window's requests, the worst attention layer's relative error of
        the last request's keys and values, and the worst Mamba2 layer's
        relative error of its final SSM state, against the reference; with
        ``quantize``, the float8 control's readings of the same numbers."""
        sample = self._sample()
        tokens, served = self.contexts(sample)
        length = len(self._context(sample[0]))
        weights = granite_hybrid.make_weights(self.cfg, self.seed)
        gaps, k, v, states = granite_hybrid.reference(
            self.cfg, weights, tokens, served, length
        )
        got_gaps, got_kv, got_states = gaps, self.kv, self.states
        if quantize:
            got_gaps, low_k, low_v, got_states = granite_hybrid.reference(
                self.cfg, weights, tokens, served, length, quantize=True
            )
            got_kv = (low_k, low_v)
        del weights
        return {
            "max_logit_gap": float(got_gaps[served >= 0].max()),
            "cache_rel_err": granite_hybrid.cache_error(*got_kv, k, v, length),
            "state_rel_err": granite_hybrid.relative_error(got_states, states),
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
