"""Readings that set the limits of Mellum2-12B-A2.5B's cell: the
program's and the control's.

    python3 bench/control_mellum2.py \
        --workload mellum2-12b-a2.5b.prefix8k-decode-b1 --seeds 11 12 13 --seconds 8

For each seed, in one process, the cell's timed path runs a short window
at the cell's own load (set-up included: the seed's shared context is
prefilled and snapshotted), and the numbers that ``bench/run.py``
compares (``max_logit_gap``, ``cache_rel_err``, ``ring_rel_err``) are
read twice: once for what the program produced, once for the control,
the published forward with every matrix product's operands rounded to
float8 (e4m3, per-tensor scale), one precision below the
configuration's bfloat16, put in the program's place.  It also reads the
gap of a served token altered where it is produced: one per sampled
request, at a position drawn from the seed, moved half the vocabulary
on.

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


def readings(driver) -> dict:
    """The program's and the control's readings after a window, and the
    altered token's gap; the server's weights are freed for the
    reference and put back by the next ``reseed``."""
    from bench import seeds
    from bench.models import mellum2

    driver.keep_cache()
    driver.server.params = None
    sample = driver._sample()
    tokens, served = driver.contexts(sample)
    rng = seeds.rng(driver.seed, 4)
    vocab = driver.cfg["vocab_size"]
    altered = np.full_like(served, -1)
    for b in range(len(served)):
        p = rng.choice(np.flatnonzero(served[b] >= 0))
        altered[b, p] = (served[b, p] + vocab // 2) % vocab
    length = len(driver._context(sample[0]))
    weights = mellum2.make_weights(driver.cfg, driver.seed)
    gaps = mellum2.reference(driver.cfg, weights, tokens, altered, length)[0]
    del weights
    return {
        "program": driver.compare(),
        "control": driver.compare(quantize=True),
        "token_altered": {"max_logit_gap": float(gaps[altered >= 0].max())},
        "requests": len(driver.done),
    }


def main(argv=None) -> int:
    import jax

    from bench import run as bench_run
    from bench.drivers.prefix_serve_loop import Driver
    from bench.spans import Spans
    from repro.launch import compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = bench_run.load_cell(args.workload)
    devices = bench_run.devices_for(int(spec["cell"]["chips"]))
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    driver = None
    for seed in args.seeds:
        if driver is None:
            driver = Driver(spec["cfg"], spec["traffic"], seed, devices, Spans())
            driver.setup()
        else:
            driver.reseed(seed)
        driver.run(args.seconds)
        out = readings(driver)
        print(json.dumps({"workload": args.workload, "seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
