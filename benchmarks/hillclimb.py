"""§Perf hillclimbing driver: hypothesis → change → re-lower → re-analyse.

Runs the three selected (arch × shape) cells through their candidate
variants (sharding strategy, remat policy, SSD chunk size), records all
three roofline terms per variant into results/hillclimb.json, and prints
the before/after log that EXPERIMENTS.md §Perf reproduces.

Each variant is a *config/sharding* change only — the model math is
identical (tested); the dry-run artifacts are re-lowered and re-compiled
per variant.
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

EXPERIMENTS = [
    # (arch, shape, variant-name, strategy, overrides, hypothesis)
    ("yi-34b", "train_4k", "baseline-tp", "tp", {},
     "paper-era default: DP16×TP16, sequence-parallel residuals"),
    ("yi-34b", "train_4k", "fsdp", "fsdp", {},
     "TP activation all-gathers (65k tok/dev × d) dwarf weight traffic; "
     "FSDP swaps them for per-layer weight gathers: predict ~3x coll ↓"),
    ("yi-34b", "train_4k", "fsdp-noremat", "fsdp", {"remat": "none"},
     "FSDP frees HBM (1 seq/chip): drop full remat, predict ~25% flops ↓"),

    ("zamba2-1.2b", "train_4k", "baseline-tp", "tp", {},
     "worst roofline fraction of the fleet (0.08)"),
    ("zamba2-1.2b", "train_4k", "chunk64", "tp", {"ssd_chunk": 64},
     "SSD L-matrices are S*C elements: halving C halves that traffic; "
     "predict ~25-40% memory-term ↓ on the SSD share"),
    ("zamba2-1.2b", "train_4k", "fsdp", "fsdp", {},
     "d_model=2048/16 TP shards are tiny; batch-everywhere removes TP "
     "collectives entirely for the mamba trunk"),
    ("zamba2-1.2b", "train_4k", "fsdp-chunk64", "fsdp", {"ssd_chunk": 64},
     "compose both wins"),

    ("mamba2-130m", "train_4k", "baseline-tp", "tp", {},
     "the paper-representative cell: SSD scan = COX warp-collective "
     "structure (intra-chunk = intra-warp, carried state = cross-PR var)"),
    ("mamba2-130m", "train_4k", "fsdp", "fsdp", {},
     "130M params: TP=16 on d=768 leaves MXU tiles tiny and pays "
     "all-gathers; FSDP makes every matmul full-width"),
    ("mamba2-130m", "train_4k", "fsdp-chunk256", "fsdp", {"ssd_chunk": 256},
     "bigger chunks raise SSD arithmetic intensity (C x C matmuls), "
     "fewer inter-chunk state round-trips; predict memory-term ↓"),
]


def main():
    from repro.launch.dryrun import HOST_DEVICES_FLAG, run_cell
    # the device count must be set before JAX creates its first backend
    os.environ.setdefault("XLA_FLAGS", HOST_DEVICES_FLAG)
    from benchmarks.roofline import terms

    out = []
    for arch, shape, variant, strategy, overrides, hyp in EXPERIMENTS:
        rec = run_cell(arch, shape, multi_pod=False, strategy=strategy,
                       overrides=overrides)
        rec["variant"] = variant
        rec["hypothesis"] = hyp
        if rec["status"] == "ok":
            t = terms(rec)
            rec["terms"] = {k: v for k, v in t.items()
                            if isinstance(v, (int, float, str))}
            print(f"{arch} × {shape} [{variant}]: "
                  f"compute={t['t_compute']:.3f}s mem={t['t_memory']:.3f}s "
                  f"coll={t['t_collective']:.3f}s dom={t['dominant']} "
                  f"frac={t['roofline_fraction']:.3f}", flush=True)
        else:
            print(f"{arch} × {shape} [{variant}]: {rec['status']} "
                  f"{rec.get('error', '')[:200]}", flush=True)
        out.append(rec)
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "hillclimb.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", path)


if __name__ == "__main__":
    main()
