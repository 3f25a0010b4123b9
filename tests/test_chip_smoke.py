"""``chip_smoke.py`` on the CPU: it refuses to run, and its phases hold
at tiny sizes (the chip runs them at full size)."""
import os
import shutil
import subprocess
import sys
import textwrap
import types

import jax
import pytest

import chip_smoke as cs
from benchmarks import roofline
from repro.core import streams
from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(vector_n=1000, transpose_n=64, warp_n=4096, hist_n=4096,
            reduce_n=128 * 40, reduce_resident=8)


def _run(args, cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, env=env, timeout=600, cwd=cwd)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_tpu_or_checkout(tmp_path, where):
    # in the checkout on the CPU: no TPU; alone: none of the repo
    if where == "alone":
        shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    else:
        cwd = ROOT
    r = _run(["chip_smoke.py"], cwd)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_cox_and_graph_phases_tiny(capsys, monkeypatch):
    # a fresh process-wide dispatcher: failures other test files in this
    # worker drove on purpose must not count against the health check
    monkeypatch.setattr(streams, "_DISPATCHER", streams.Dispatcher())
    cs.phase_cox(TINY, seed=3)
    cs.phase_graph(TINY, seed=3)
    cs.phase_health("tiny COX phases")
    out = capsys.readouterr().out
    assert out.count("[cox]") == 5 and out.count("[graph]") == 1
    assert "grid_stride == chunked" in out


def test_serve_phase_tiny(capsys):
    cs.phase_serve(dict(arch="granite-moe-1b-a400m-smoke", batch=2, ctx=64,
                        requests=3, tokens=4, prompt=4), seed=0,
                   require_kernels=False)
    out = capsys.readouterr().out
    assert "served 3 requests x 4 tokens" in out
    assert "rel L2" in out


def test_serve_kernels_check_interpret():
    from repro.configs import registry
    cfg = registry.get("granite-moe-1b-a400m-smoke")
    line = cs.check_serve_kernels(cfg, 2, 128, 0, "interpret")
    assert "rmsnorm" in line and "decode_attention" in line


def test_four_chip_phase_on_virtual_devices():
    code = textwrap.dedent(f"""
        import chip_smoke as cs
        cs.phase_four_chips({TINY!r}, seed=1)
        cs.phase_health("four devices")
    """)
    r = _run(["-c", code], ROOT, {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": os.pathsep.join([os.path.join(ROOT, "src"), ROOT])})
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.count("outputs on all 4 devices") == 3
    assert "dispatches on all 4 devices" in r.stdout


def test_checks_raise_not_assert():
    with pytest.raises(cs.SmokeFailure):
        cs.check(False, "boom")


def test_device_peaks_by_kind():
    cpu = roofline.device_peaks(types.SimpleNamespace(platform="cpu",
                                                      device_kind="cpu"))
    assert cpu == roofline.cpu_peaks()
    v5e = roofline.device_peaks(types.SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite"))
    assert v5e == {"peak_flops": 197e12, "mem_bw": 819e9}
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.device_peaks(types.SimpleNamespace(platform="tpu",
                                                    device_kind="TPU v9"))


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    was = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        want = os.path.join(ROOT, ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv(compile_cache.ENV, want)
    try:
        assert compile_cache.enable() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
