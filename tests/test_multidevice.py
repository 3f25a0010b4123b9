"""Multi-device semantics (8 host devices via subprocess — the device
count must be fixed before jax initializes, so these run out-of-process):
COX grid launch sharded over a mesh equals single-device execution;
atomics merge with psum; MoE EP on a 2×4 mesh matches the local path."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_worker(body: str) -> str:
    code = textwrap.dedent(body)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900, cwd=ROOT)
    assert r.returncode == 0, f"worker failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


def test_cox_grid_sharded_matches_single():
    run_worker("""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        import tests.multidevice_kernels as mk
        from repro.core.oracle import run_grid as oracle_run
        assert len(jax.devices()) == 8
        a = np.arange(2048, dtype=np.float32)
        b = np.ones(2048, np.float32)
        out0 = np.zeros(2048, np.float32)
        args = (out0, a, b, 2000)
        mesh = make_mesh((8,), ("data",))
        got = mk.vec_madd.launch(grid=8, block=256, args=args, mesh=mesh)
        want = mk.vec_madd.launch(grid=8, block=256, args=args)
        np.testing.assert_allclose(np.asarray(got["out"]),
                                   np.asarray(want["out"]), rtol=1e-6)
        ref = oracle_run(mk.vec_madd.ir, grid=8, block=256, args=args)
        np.testing.assert_allclose(np.asarray(got["out"]), ref["out"],
                                   rtol=1e-5)
        print("grid-sharded OK")
    """)


def test_cox_atomics_psum_merge():
    run_worker("""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        import tests.multidevice_kernels as mk
        a = np.random.default_rng(0).integers(0, 16, 1024).astype(np.int32)
        hist0 = np.zeros(16, np.float32)
        mesh = make_mesh((8,), ("data",))
        got = mk.histogram.launch(grid=8, block=128, args=(hist0, a, 1024),
                                  mesh=mesh)
        want = np.bincount(a, minlength=16).astype(np.float32)
        np.testing.assert_allclose(np.asarray(got["hist"]), want)
        print("atomics OK")
    """)


def test_cox_grid_sync_sharded_8dev():
    # cooperative grid barrier across a real mesh: each device keeps its
    # slice of the grid resident across phases, and the per-phase
    # masked-psum merge is what lets phase-1 blocks on one device read
    # phase-0 partials written on every other device
    run_worker("""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from benchmarks.kernels_suite import all_kernels
        from repro.core.oracle import run_grid as oracle_run
        assert len(jax.devices()) == 8
        sk = next(k for k in all_kernels() if k.name == "gridReduce")
        args = sk.make_args()
        mesh = make_mesh((8,), ("data",))
        got = sk.kernel.launch(grid=sk.grid, block=sk.block, args=args,
                               mesh=mesh)
        ref = oracle_run(sk.kernel.ir, grid=sk.grid, block=sk.block,
                         args=args)
        for k in ref:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(ref[k]), err_msg=k)
        print("grid-sync sharded OK")
    """)


def test_moe_ep_on_2x4_mesh():
    run_worker("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import registry
        from repro.models import layers as L
        from repro.models.params import default_rules, init_params
        cfg = registry.get("granite-moe-1b-a400m", smoke=True)  # 4 experts
        p = init_params(L.moe_specs(cfg), jax.random.PRNGKey(1))
        x = jnp.asarray(np.random.default_rng(2).normal(
            size=(4, 8, cfg.d_model)).astype(np.float32))
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = default_rules(mesh)
        got = L.moe_apply(p, x, cfg=cfg, rules=rules)
        want = L.moe_apply(p, x, cfg=cfg, rules=None)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-4, atol=2e-4)
        print("moe EP OK")
    """)


def test_train_step_on_2x4_mesh():
    run_worker("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import registry
        from repro.configs.base import ShapeConfig
        from repro.parallel import steps as steps_mod
        from repro.models.params import init_params
        from repro.optim import adamw
        from repro.data.pipeline import TokenSource, DataConfig
        cfg = registry.get("qwen2.5-14b", smoke=True)
        mesh = make_mesh((2, 4), ("data", "model"))
        shape = ShapeConfig("t", 64, 4, "train")
        jitted, bundle, abstract = steps_mod.jit_train_step(cfg, mesh, shape)
        params = jax.device_put(
            init_params(bundle["specs"], jax.random.PRNGKey(0)),
            bundle["param_sh"])
        opt = jax.device_put(adamw.init_state(params, bundle["opt_cfg"]),
                             bundle["opt_sh"])
        src = TokenSource(cfg, shape, DataConfig())
        b = {k: jnp.asarray(v) for k, v in src.batch_at(0).items()}
        params, opt, m = jitted(params, opt, b)
        assert np.isfinite(float(m["loss"]))
        print("sharded train step OK, loss", float(m["loss"]))
    """)
