"""bench/run.py refuses to measure without a TPU, and without the program."""

import os
import shutil
import subprocess
import sys

from bench import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = [
    "--workload",
    "cox-sdk.warp-2e20",
    "--seed",
    "3000000001",
    "--seconds",
    "1",
    "--trace",
    "0",
]


def test_refuses_on_the_cpu(capsys):
    assert bench_run.main(ARGS) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "refused" in out.err and "no TPU" in out.err


def test_a_tagged_metric_reads_the_drivers_untagged_one():
    measured = {"cox_ms_per_launch": 1.5, "cox_launch_p95_ms": 2.0}
    assert bench_run.driver_value(measured, "cox_ms_per_launch.chain") == 1.5
    assert bench_run.driver_value(measured, "cox_launch_p95_ms") == 2.0
    assert bench_run.driver_value({"a.b": 3.0, "a": 4.0}, "a.b") == 3.0
    assert bench_run.driver_value(measured, "serve_tok_s") is None


def test_refuses_an_unknown_workload(capsys):
    assert bench_run.main(["--workload", "no-such.cell"] + ARGS[2:]) == 2
    assert capsys.readouterr().out == ""


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(ROOT, "bench"),
        tmp_path / "bench",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "bench/run.py"] + ARGS,
        cwd=tmp_path,
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert done.returncode != 0
    assert done.stdout == ""
