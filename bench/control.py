"""Readings that set a cell's limits: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 ... --seconds 8

For each seed, in one process, the cell's timed path runs a short window
at the cell's own load, and the numbers that ``bench/run.py`` compares
are read twice: once for what the program produced, once for the
control, the reference computed one precision below what the
configuration states, put in the program's place:

* COX cells (float32): the numpy reference in bfloat16, against the
  float32 reference, over the same kept launches;
* the served model (bfloat16): the published forward with every product's
  operands rounded to float8 (e4m3, per-tensor scale); at each compared
  position, the gap of the token the float8 forward puts first, and the
  error of its keys and values.  It also reads the fault of a served token
  altered where it is produced.

One JSON line per seed.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import numpy as np  # noqa: E402


def cox_readings(driver) -> dict:
    import ml_dtypes

    program = {c["name"]: c["value"] for c in driver.checks()}
    control = {}
    for p in driver.programs:
        wrong = {}
        for which, _ in driver.kept_host[p.name]:
            if which not in wrong:
                d, n = driver.inputs_host[p.name][which], driver.n[p.name]
                want = p.reference(d, n, driver.chain)
                low = p.reference(d, n, driver.chain, ml_dtypes.bfloat16)
                differ = [np.count_nonzero(low[k] != want[k]) for k in want]
                wrong[which] = int(sum(differ))
        kept = driver.kept_host[p.name]
        control[f"mismatch.{p.name}"] = sum(wrong[w] for w, _ in kept)
    return {"program": program, "control": control}


def serve_readings(driver) -> dict:
    """The program's and the control's readings, and the widest gap of a
    token altered where it is produced: one served token per sampled
    request, at a position drawn from the seed, moved half the vocabulary
    on."""
    from bench import seeds
    from bench.models import granite

    length = len(driver._context(driver.done[-1]))
    cache = driver.server.cache
    driver.kv = tuple(np.asarray(cache[n][:, 0, :length], np.float32) for n in "kv")
    tokens, served = driver.contexts(driver._sample())
    rng = seeds.rng(driver.seed, 4)
    vocab = driver.cfg["vocab_size"]
    altered = np.full_like(served, -1)
    for b in range(len(served)):
        p = rng.choice(np.flatnonzero(served[b] >= 0))
        altered[b, p] = (served[b, p] + vocab // 2) % vocab
    weights = granite.make_weights(driver.cfg, driver.seed)
    gaps, _, _ = granite.reference(driver.cfg, weights, tokens, altered)
    del weights
    return {
        "program": driver.compare(),
        "control": driver.compare(quantize=True),
        "token_altered": {"max_logit_gap": float(gaps[altered >= 0].max())},
        "requests": len(driver.done),
    }


def main(argv=None) -> int:
    import importlib

    import jax

    from bench import run as bench_run
    from bench.spans import Spans
    from repro.launch import compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = bench_run.load_cell(args.workload)
    devices = bench_run.devices_for(int(spec["cell"]["chips"]))
    os.environ["COX_AUTOTUNE_CACHE"] = "off"
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    drive = importlib.import_module(f"bench.drivers.{spec['traffic']['driver']}")
    serving = spec["traffic"]["driver"] == "serve_loop"
    driver = None
    for seed in args.seeds:
        if serving and driver is not None:
            driver.reseed(seed)
        else:
            driver = drive.Driver(spec["cfg"], spec["traffic"], seed, devices, Spans())
            driver.setup()
        driver.run(args.seconds)
        if serving:
            out = serve_readings(driver)
        else:
            driver.release()
            out = cox_readings(driver)
        print(json.dumps({"workload": args.workload, "seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
