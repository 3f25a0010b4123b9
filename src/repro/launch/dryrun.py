"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this prints/records:
  * compiled.memory_analysis()  — proves the program fits per-device HBM
  * compiled.cost_analysis()    — HLO FLOPs / bytes for §Roofline
  * collective bytes parsed from the optimized HLO (all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both \
      --out results/dryrun.json
"""
import argparse
import json
import os
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

from ..configs import registry
from ..configs.base import LONG_CONTEXT_OK, SHAPES
from ..parallel import steps as steps_mod
from .mesh import make_production_mesh

# 512 forced CPU host devices for the production meshes: set by the
# command-line entry points only, before JAX creates its first backend
HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count=512"

# ---------------------------------------------------------------------------
# HLO collective-byte accounting
# ---------------------------------------------------------------------------

_COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                   "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\(?([^)]*?)\)?\s*"
                       r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                       r"collective-permute)")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _tensor_bytes(ty: str) -> int:
    """bytes of one tensor type like 'bf16[256,1024]{1,0}'."""
    m = re.match(r"(\w+)\[([\d,]*)\]", ty.strip())
    if not m:
        return 0
    dt, dims = m.group(1), m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes of every collective op in optimized HLO."""
    out = {k: 0 for k in _COLLECTIVE_OPS}
    out["count"] = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+)$", stripped)
        if not m:
            continue
        rhs = m.group(1)
        opm = re.search(r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute)(?:-start|-done)?\(", rhs)
        if not opm:
            continue
        if "-done(" in rhs:
            continue  # avoid double counting async pairs
        op = opm.group(1)
        tys = re.findall(r"\w+\[[\d,]*\](?:\{[^}]*\})?", rhs[:opm.start()])
        b = sum(_tensor_bytes(t) for t in tys)
        out[op] += b
        out["count"] += 1
    return out


# ---------------------------------------------------------------------------


def build_cell(arch: str, shape_name: str, mesh, backend: str = "xla",
               smoke: bool = False, strategy: str = "tp",
               overrides: Optional[Dict[str, Any]] = None):
    import dataclasses
    cfg = registry.get(arch, smoke=smoke)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    if shape.kind == "train" or shape.kind == "prefill":
        if shape.kind == "prefill":
            # prefill lowers the training forward without the optimizer —
            # use the train step graph with loss only (representative of a
            # batched prefill); decode shapes exercise serve_step.
            pass
        jitted, bundle, abstract = steps_mod.jit_train_step(
            cfg, mesh, shape, backend=backend, strategy=strategy)
        lowered = jitted.lower(*abstract)
    else:
        jitted, bundle, abstract = steps_mod.jit_serve_step(
            cfg, mesh, shape, backend=backend, strategy=strategy)
        lowered = jitted.lower(*abstract)
    return cfg, lowered, bundle


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             backend: str = "xla", smoke: bool = False,
             keep_hlo: bool = False, strategy: str = "tp",
             overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "strategy": strategy,
                           "overrides": dict(overrides or {}),
                           "mesh": "2x16x16" if multi_pod else "16x16"}
    if shape_name == "long_500k" and arch not in LONG_CONTEXT_OK:
        rec["status"] = "skipped"
        rec["reason"] = ("full quadratic attention at 524288 ctx — "
                         "sub-quadratic variant not specified by source "
                         "config (DESIGN.md §Arch-applicability)")
        return rec
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        cfg, lowered, bundle = build_cell(arch, shape_name, mesh,
                                          backend=backend, smoke=smoke,
                                          strategy=strategy,
                                          overrides=overrides)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        # while-aware attribution: scan bodies × trip count (cost_analysis
        # counts them once — see repro.launch.hlo_analysis)
        from .hlo_analysis import analyze as hlo_analyze
        corrected = hlo_analyze(hlo)
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "argument_size": int(mem.argument_size_in_bytes),
            "output_size": int(mem.output_size_in_bytes),
            "temp_size": int(mem.temp_size_in_bytes),
            "alias_size": int(mem.alias_size_in_bytes),
            "generated_code_size": int(mem.generated_code_size_in_bytes),
            "collectives": coll,
            "flops_corrected": corrected["flops"],
            "coll_bytes_corrected": corrected["coll_bytes"],
            "out_bytes_corrected": corrected["out_bytes"],
            "coll_per_op_corrected": {
                k.split(".", 1)[1]: v for k, v in corrected.items()
                if k.startswith("coll.")},
            "replication_notes": list(bundle["rules"].notes)[:20],
            "param_count": registry.get(arch, smoke=smoke).param_count(),
            "active_param_count":
                registry.get(arch, smoke=smoke).active_param_count(),
        })
        if keep_hlo:
            rec["hlo"] = hlo
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug report
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["trace"] = traceback.format_exc()[-2000:]
    return rec


def main(argv=None):
    os.environ["XLA_FLAGS"] = HOST_DEVICES_FLAG
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["on", "off", "both"],
                    default="off")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--strategy", default="tp", choices=["tp", "fsdp"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    archs = registry.names() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    pods = {"on": [True], "off": [False], "both": [False, True]}[
        args.multi_pod]
    results = []
    for mp in pods:
        for arch in archs:
            for sh in shapes:
                rec = run_cell(arch, sh, multi_pod=mp, smoke=args.smoke,
                               strategy=args.strategy)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    per_dev = (rec["argument_size"] + rec["output_size"]
                               + rec["temp_size"])
                    extra = (f"flops={rec['flops']:.3e} "
                             f"bytes={rec['bytes_accessed']:.3e} "
                             f"mem/dev={per_dev / 2 ** 30:.2f}GiB "
                             f"coll={sum(rec['collectives'][k] for k in _COLLECTIVE_OPS) / 2 ** 20:.1f}MiB "
                             f"compile={rec['compile_s']:.0f}s")
                elif status == "error":
                    extra = rec["error"][:200]
                else:
                    extra = rec["reason"][:80]
                print(f"[{rec['mesh']}] {arch} × {sh}: {status} {extra}",
                      flush=True)
                results.append(rec)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    bad = [r for r in results if r["status"] == "error"]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
