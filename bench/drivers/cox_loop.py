"""Closed-loop driver for COX SDK programs.

One unit of work is ``chain`` launches issued back to back on one
``cox.Stream``, each fed the previous launch's output (``x <- x + b``
for vectorAdd), and then one wait for the last output.  With
``chain`` 1 that is a single launch from its call until its outputs are
ready.  Units go round-robin over the traffic's programs and over a pool
of seeded input sets held on the device, with ``ahead`` units dispatched
and not yet waited for: a new unit is issued each time the oldest one's
wait returns, so the chip stays fed while the host stands still.  Once
``--seconds`` have passed, no unit is issued after the end of the round
over the programs under way; the window closes when the last unit sent
is ready, so every run does whole rounds and counts all it sent.

Traffic parameters (``bench/traffic/<mix>.json``):

* ``programs``: names in :data:`bench.programs.sdk.PROGRAMS`;
* ``chain``: launches per unit;
* ``ahead``: units in flight (default 1: one unit, then its wait);
* ``input_sets``: input sets per program, made from the seed;
* ``sample``: launches per program whose outputs are kept, drawn from
  the seed by reservoir sampling, and compared after the window.

The launches leave every resolver knob on auto, as a user leaves them.
"""

from __future__ import annotations

import collections
import contextlib
import time
from typing import Dict, List

import jax
import numpy as np

from bench import seeds
from bench.programs.sdk import PROGRAMS
from repro.core import cox

KNOBS = ("backend", "warp_exec", "mode", "chunk", "schedule", "n_resident")


class Driver:
    def __init__(self, cfg: Dict, traffic: Dict, seed: int, devices, spans):
        self.cfg, self.traffic, self.seed, self.spans = cfg, traffic, seed, spans
        self.programs = [PROGRAMS[p] for p in traffic["programs"]]
        self.n = {p.name: cfg[f"{p.name}_n"] for p in self.programs}
        self.chain = int(traffic.get("chain", 1))
        self.ahead = int(traffic.get("ahead", 1))
        self.stream = cox.Stream("bench")
        self.kept: Dict[str, List] = {p.name: [] for p in self.programs}
        self.launches: List[Dict] = []

    # --- set-up -------------------------------------------------------------

    def setup(self) -> None:
        sets = int(self.traffic["input_sets"])

        def make(key):
            return {
                p.name: [
                    p.make_inputs(jax.random.fold_in(key, 1000 * j + s), self.n[p.name])
                    for s in range(sets)
                ]
                for j, p in enumerate(self.programs)
            }

        self.inputs = jax.jit(make)(seeds.key(self.seed))
        self.args = {
            p.name: [p.args(d, self.n[p.name]) for d in self.inputs[p.name]]
            for p in self.programs
        }
        self.resolved = {}
        for p in self.programs:
            req = p.kernel.make_request(
                grid=p.grid(self.n[p.name]),
                block=p.block,
                args=self.args[p.name][0],
            )
            self.resolved[p.name] = {k: getattr(req.rl, k, None) for k in KNOBS}
        for _ in range(2):  # the second pass finds every program staged
            for i in range(len(self.programs)):
                jax.block_until_ready(self._unit(i, 0, record=False)[1])

    # --- one unit -----------------------------------------------------------

    def _issue(self, i: int, which: int, record: bool = True):
        """Dispatch unit ``i`` on input set ``which``; its program and the
        handle of its last launch."""
        p = self.programs[i]
        args = self.args[p.name][which]
        span = self._span(record)
        x = None
        for _ in range(self.chain):
            call = args if x is None else (args[0], x) + tuple(args[2:])
            with span("cox.launch", program=p.name):
                handle = self.stream.launch(
                    p.kernel,
                    grid=p.grid(self.n[p.name]),
                    block=p.block,
                    args=call,
                )
                if self.chain > 1:
                    x = handle.outputs["out"]
        return p, handle

    def _wait(self, handle, record: bool = True):
        with self._span(record)("cox.sync"):
            return handle.result()

    def _unit(self, i: int, which: int, record: bool = True):
        p, handle = self._issue(i, which, record)
        return p, self._wait(handle, record)

    def _span(self, record: bool):
        return self.spans if record else (lambda name, **kw: contextlib.nullcontext())

    # --- the window ---------------------------------------------------------

    def run(self, seconds: float) -> Dict:
        self.health0 = _dispatcher_health()
        rng = seeds.rng(self.seed, 1)
        sample = int(self.traffic["sample"])
        seen = {p.name: 0 for p in self.programs}
        sets = int(self.traffic["input_sets"])
        rounds = len(self.programs)
        pending = collections.deque()
        start = time.perf_counter_ns()
        deadline = start + int(seconds * 1e9)
        k = 0
        closing = False
        while True:
            while not closing and len(pending) < self.ahead:
                if k % rounds == 0 and time.perf_counter_ns() >= deadline:
                    closing = True
                    break
                which = (k // rounds) % sets
                t0 = time.perf_counter_ns()
                p, handle = self._issue(k % rounds, which)
                pending.append((p, which, handle, t0))
                k += 1
            if not pending:
                break
            p, which, handle, t0 = pending.popleft()
            out = self._wait(handle)
            t1 = time.perf_counter_ns()
            self.launches.append({"program": p.name, "start_ns": t0, "end_ns": t1})
            m = seen[p.name]
            if m < sample:
                self.kept[p.name].append((which, out))
            elif (r := rng.integers(0, m + 1)) < sample:
                self.kept[p.name][r] = (which, out)
            seen[p.name] = m + 1
        self.window = (start, t1)
        return {"start_ns": start, "end_ns": t1, "units": k}

    # --- results ------------------------------------------------------------

    def end_to_end(self) -> Dict[str, float]:
        start, end = self.window
        units = len(self.launches)
        return {"cox_ms_per_launch": (end - start) * 1e-6 / (units * self.chain)}

    def counters(self) -> Dict:
        return {
            "launches": len(self.launches) * self.chain,
            "chain": self.chain,
            "n": dict(self.n),
            "blocks": {p.name: p.block for p in self.programs},
            "resolved": self.resolved,
            "health": self._health(),
        }

    def release(self) -> None:
        self.inputs_host = {
            name: [{k: np.asarray(v) for k, v in d.items()} for d in sets]
            for name, sets in self.inputs.items()
        }
        self.kept_host = {
            name: [(w, {k: np.asarray(v) for k, v in out.items()}) for w, out in kept]
            for name, kept in self.kept.items()
        }
        del self.inputs, self.args, self.kept

    def checks(self) -> List[Dict]:
        """Elements of the kept outputs that differ from numpy, bit for bit
        (a -0.0 for a 0.0 or a NaN counts), per program."""
        out = []
        for p in self.programs:
            refs: Dict[int, Dict[str, np.ndarray]] = {}
            wrong = 0
            for which, got in self.kept_host[p.name]:
                if which not in refs:
                    d = self.inputs_host[p.name][which]
                    refs[which] = p.reference(d, self.n[p.name], self.chain)
                for key, want in refs[which].items():
                    g, w = got[key].reshape(-1), want.reshape(-1)
                    if g.dtype != w.dtype or g.size != w.size:
                        wrong += w.size
                    else:
                        differ = g.view(np.uint32) != w.view(np.uint32)
                        wrong += int(np.count_nonzero(differ))
            out.append({"name": f"mismatch.{p.name}", "value": wrong, "limit": 0})
        return out

    def attempted(self) -> int:
        return len(self.launches) * self.chain

    def failed(self) -> int:
        return int(self._health()["failures"])

    def _health(self) -> Dict:
        """The dispatcher's counters since the window opened (they count
        for the whole process)."""
        now = _dispatcher_health()
        return {k: now[k] - self.health0[k] for k in now}


def _dispatcher_health() -> Dict:
    h = cox.get_dispatcher().health()
    return {k: h[k] for k in ("degradations", "retries", "failures", "timeouts")}
