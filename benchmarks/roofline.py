"""§Roofline: derive the three roofline terms per (arch × shape) from the
dry-run's compiled artifacts (results/dryrun_all.json).

Terms (seconds, per step, per chip — cost/collective numbers from the
partitioned per-device HLO):

    compute    = HLO_FLOPs / peak_FLOPs            (197 TFLOP/s bf16, v5e)
    memory     = HLO_bytes / HBM_bw                (819 GB/s)
    collective = collective_bytes / link_bw        (~50 GB/s/link ICI)

MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) over the step's global
tokens; the ratio MODEL_FLOPS / (chips · HLO_FLOPs) shows how much of
the compiled compute is "useful" (catches remat/redundancy waste; >1 is
possible when XLA undercounts fused ops, <1 shows remat or padding).

CPU-backend caveat (recorded in EXPERIMENTS.md): XLA-CPU legalizes bf16
into f32 copies, inflating `bytes accessed` roughly 2× vs a TPU build;
FLOP counts are dtype-independent and transfer as-is.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud TPU v5e documentation (bf16 FLOP/s, HBM bytes/s).
DEVICE_PEAKS = {
    "TPU v5 lite": {"peak_flops": 197e12, "mem_bw": 819e9},
}
PEAK_FLOPS = DEVICE_PEAKS["TPU v5 lite"]["peak_flops"]
HBM_BW = DEVICE_PEAKS["TPU v5 lite"]["mem_bw"]
LINK_BW = 50e9           # bytes/s / link (ICI)

# host-CPU roofline, used only when the platform is the CPU: a
# conservative per-core AVX2 FMA peak and one DDR channel's worth of
# bandwidth, scaled by visible cores
CPU_CORE_FLOPS = 32e9    # FLOP/s/core (8-lane f32 FMA @ ~2 GHz)
CPU_CORE_BW = 12e9       # bytes/s/core (shared-bus share)

_COLL_KEYS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
              "collective-permute")


def cpu_peaks() -> Dict[str, float]:
    cores = os.cpu_count() or 1
    return {"peak_flops": CPU_CORE_FLOPS * cores,
            "mem_bw": CPU_CORE_BW * cores}


def device_peaks(device=None) -> Dict[str, float]:
    """Peaks of ``device`` (default: JAX's first device): the CPU table
    on the CPU platform, else the published entry for its device kind.
    A device kind missing from :data:`DEVICE_PEAKS` is an error, never
    a default."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "cpu":
        return cpu_peaks()
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device.device_kind!r}") from None


def from_telemetry(rows, peak_flops: float = None,
                   mem_bw: float = None) -> list:
    """Roofline rows from the dispatcher's *live* counters
    (``cox.get_dispatcher().telemetry()``) instead of dry-run JSON:
    each stage-key row's op/mem estimates against the peaks of the
    device JAX runs on (:func:`device_peaks`) give t_compute/t_memory
    and the dominant term.  The returned dicts
    keep the telemetry fields (kernel, backend, warp_exec, chunk,
    launches, gflops) so the bench JSON can embed them verbatim."""
    peaks = device_peaks()
    pf = peak_flops if peak_flops is not None else peaks["peak_flops"]
    bw = mem_bw if mem_bw is not None else peaks["mem_bw"]
    out = []
    for rec in rows:
        t_comp = rec.get("op_estimate", 0.0) / pf
        t_mem = rec.get("mem_estimate", 0.0) / bw
        dominant = "compute" if t_comp >= t_mem else "memory"
        bound = max(t_comp, t_mem)
        row = dict(rec)
        row.update(t_compute=t_comp, t_memory=t_mem, dominant=dominant,
                   roofline_fraction=(t_comp / bound if bound else 0.0))
        out.append(row)
    return out


def model_flops(rec: Dict[str, Any]) -> float:
    """6·N(_active)·D over the step's global tokens."""
    n = rec.get("active_param_count") or rec.get("param_count") or 0
    shape = rec["shape"]
    tokens = {"train_4k": 4096 * 256, "prefill_32k": 32768 * 32,
              "decode_32k": 128, "long_500k": 1}[shape]
    mult = 6.0 if shape in ("train_4k",) else 2.0  # fwd-only for serving
    if shape == "prefill_32k":
        mult = 6.0  # prefill cell lowers the training graph (fwd+bwd)
    return mult * n * tokens


def terms(rec: Dict[str, Any], chips: int = 256) -> Dict[str, Any]:
    """Prefer the while-aware corrected numbers (scan bodies × trips);
    fall back to raw cost_analysis for old records."""
    coll = rec.get("coll_bytes_corrected")
    if coll is None:
        coll = sum(rec["collectives"].get(k, 0) for k in _COLL_KEYS)
    flops = rec.get("flops_corrected") or rec["flops"]
    mem_bytes = rec.get("out_bytes_corrected")
    if mem_bytes is not None:
        mem_bytes *= 2.0  # outputs ≈ writes; ×2 for the read side
    else:
        mem_bytes = rec["bytes_accessed"]
    t_comp = flops / PEAK_FLOPS
    t_mem = mem_bytes / HBM_BW
    t_coll = coll / LINK_BW
    dominant = max(("compute", t_comp), ("memory", t_mem),
                   ("collective", t_coll), key=lambda kv: kv[1])[0]
    mf = model_flops(rec)
    useful = mf / (chips * flops) if flops else 0.0
    bound = max(t_comp, t_mem, t_coll)
    frac = t_comp / bound if bound else 0.0  # roofline fraction (compute share)
    return {"t_compute": t_comp, "t_memory": t_mem, "t_collective": t_coll,
            "dominant": dominant, "model_flops": mf, "useful": useful,
            "roofline_fraction": frac, "coll_bytes": coll, "flops": flops}


_SUGGEST = {
    "compute": "cast more matmuls to bf16 MXU shapes / cut remat recompute",
    "memory": "raise arithmetic intensity: fuse norms/rope into matmul "
              "epilogues, keep residuals bf16, shrink saved activations",
    "collective": "reshard to cut all-gathers (sequence-parallel residuals),"
                  " overlap DP all-reduce with backward, compress grads",
}


def emit_rows(path: str):
    with open(path) as f:
        recs = json.load(f)
    for rec in recs:
        if rec.get("mesh") != "16x16":
            continue  # roofline table is single-pod per the brief
        name = f"roofline.{rec['arch']}.{rec['shape']}"
        if rec["status"] != "ok":
            print(f"{name},0.0,status={rec['status']}")
            continue
        t = terms(rec)
        us = max(t["t_compute"], t["t_memory"], t["t_collective"]) * 1e6
        print(f"{name},{us:.1f},"
              f"compute_s={t['t_compute']:.3e};mem_s={t['t_memory']:.3e};"
              f"coll_s={t['t_collective']:.3e};dominant={t['dominant']};"
              f"useful={t['useful']:.2f};fix={_SUGGEST[t['dominant']][:40]}",
              flush=True)


def markdown_table(path: str) -> str:
    with open(path) as f:
        recs = json.load(f)
    lines = ["| arch | shape | compute (s) | memory (s) | collective (s) | "
             "dominant | MODEL_FLOPS | useful | note |",
             "|---|---|---|---|---|---|---|---|---|"]
    for rec in recs:
        if rec.get("mesh") != "16x16":
            continue
        if rec["status"] == "skipped":
            lines.append(f"| {rec['arch']} | {rec['shape']} | — | — | — | "
                         f"skipped | — | — | {rec['reason'][:60]} |")
            continue
        if rec["status"] != "ok":
            lines.append(f"| {rec['arch']} | {rec['shape']} | — | — | — | "
                         f"ERROR | — | — | {rec['error'][:60]} |")
            continue
        t = terms(rec)
        lines.append(
            f"| {rec['arch']} | {rec['shape']} | {t['t_compute']:.3e} | "
            f"{t['t_memory']:.3e} | {t['t_collective']:.3e} | "
            f"{t['dominant']} | {t['model_flops']:.2e} | "
            f"{t['useful']:.2f} | {_SUGGEST[t['dominant']][:48]} |")
    return "\n".join(lines)


if __name__ == "__main__":
    import sys
    p = sys.argv[1] if len(sys.argv) > 1 else \
        os.path.join(os.path.dirname(__file__), "..", "results",
                     "dryrun_all.json")
    print(markdown_table(p))
