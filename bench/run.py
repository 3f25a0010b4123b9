"""The benchmark: one cell of ``BENCHMARK.json`` on the chips of this host.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration is
``bench/configs/<config>.json``, its traffic ``bench/traffic/<mix>.json``,
whose ``driver`` names the loop in ``bench/drivers/``, and each per-layer
metric a reader ``bench/metrics/<metric>.py``.  An end-to-end metric
``<name>.<tag>`` is the driver's ``<name>`` under a bound of its own, for
the cells it lists.  Adding a cell, a configuration, a mix or a metric
adds files and entries only.

The run sets up (weights and inputs from the seed, on the device; every
shape the cell uses warmed up), measures for ``--seconds``, reads the
peak device memory, frees the program's state, and compares what the
timed path produced with the plain reference.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared, with its limit.  The checks are also
the last lines of standard error.

It refuses to run (exit 2, no result) where JAX finds no TPU or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

TRACE_DIR = os.path.join(ROOT, "bench_out", "trace")


class Refused(Exception):
    pass


def load_cell(name: str) -> dict:
    """The cell, its configuration, its traffic and its metrics, by name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def reports(metric, end_to_end):
        if "workloads" in metric:
            return name in metric["workloads"]
        return metric["moves"] in end_to_end if "moves" in metric else True

    e2e = [m for m in spec["end_to_end"] if reports(m, ())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if reports(m, e2e_names)]
    return {
        "cell": cell,
        "cfg": cfg,
        "traffic": traffic,
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def reader(metric: str):
    """The ``read(run)`` of ``bench/metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices_for(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (first device is {devs[0].platform!r})")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


class Run:
    """What a metric reader sees of one run."""

    def __init__(self, spans, window, counters, trace, peaks, cfg):
        self.spans, self.window, self.counters = spans, window, counters
        self.trace, self.peaks, self.cfg = trace, peaks, cfg

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def in_window(self, name: str) -> list:
        """The spans named ``name`` that started inside the window."""
        lo, hi = self.window
        return [s for s in self.spans.named(name) if lo <= s.start_ns <= hi]

    def mean_span_ms(self, name: str):
        """Mean length in ms of the spans ``name`` started in the window."""
        spans = self.in_window(name)
        return 1e3 * sum(s.seconds for s in spans) / len(spans) if spans else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_cell(args.workload)
        devices = devices_for(int(spec["cell"]["chips"]))
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr, flush=True)
        return 2
    os.environ["COX_AUTOTUNE_CACHE"] = "off"
    result = measure(spec, args, devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def driver_value(measured: dict, name: str):
    """The driver's reading of end-to-end metric ``name``, or of its
    ``<name>`` where it is ``<name>.<tag>``."""
    if name in measured:
        return measured[name]
    return measured.get(name.split(".", 1)[0])


def _log(text: str) -> None:
    print(f"bench: {text}", file=sys.stderr, flush=True)


def measure(spec: dict, args, devices) -> dict:
    """Set up, measure, compare; the result line as a dict."""
    import jax

    from bench import peaks as peaks_mod
    from bench import trace as trace_mod
    from bench.spans import Spans
    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = devices[0]
    peaks = peaks_mod.peaks(dev.device_kind) if dev.platform == "tpu" else None
    spans = Spans(annotate=bool(args.trace))
    drive = importlib.import_module(f"bench.drivers.{spec['traffic']['driver']}")
    driver = drive.Driver(spec["cfg"], spec["traffic"], args.seed, devices, spans)
    driver.setup()
    # what set-up made lives to the end: no collection in the window walks it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    resolved = json.dumps(getattr(driver, "resolved", {}))
    _log(f"{spec['cell']['name']} set up in {setup_s:.3f} s; resolved {resolved}")

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        seconds = min(args.seconds, float(spec["traffic"]["trace_seconds"]))
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        try:
            with spans("bench.trace"):
                window = driver.run(seconds)
        finally:
            jax.profiler.stop_trace()
    else:
        window = driver.run(args.seconds)
    gc.unfreeze()

    memory = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(m.get("peak_bytes_in_use", 0) for m in memory)
    e2e = driver.end_to_end()
    counters = driver.counters()
    scalars = {k: v for k, v in counters.items() if not isinstance(v, list)}
    _log(f"counters {json.dumps(scalars)}")
    driver.release()
    checks = driver.checks()

    summary = None
    if args.trace:
        summary = trace_mod.reduce(trace_mod.find_xplane(TRACE_DIR))
        run = Run(
            spans,
            (window["start_ns"], window["end_ns"]),
            counters,
            summary,
            peaks,
            spec["cfg"],
        )
        metrics = {}
        for m in spec["per_layer"]:
            value = reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {}
        for m in spec["end_to_end"]:
            name = m["name"]
            value = setup_s if name == "setup_s" else driver_value(e2e, name)
            if value is None:
                raise RuntimeError(f"the driver did not measure {m['name']}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(peak_bytes),
    }
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks),
        "attempted": int(driver.attempted()),
        "failed": int(driver.failed()),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            # each op by its module and the start of its HLO text
            "device_ops": [
                [summary.op_label(k)[:200], v] for k, v in summary.top_ops(10)
            ],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps[:10]],
        }
    result["checks"] = {
        c["name"]: {"value": c["value"], "limit": c["limit"]} for c in checks
    }
    return result


if __name__ == "__main__":
    sys.exit(main())
