"""Substrate tests: checkpoint atomicity/elasticity, fault-tolerant
restart, deterministic data, optimizer + gradient compression, and an
end-to-end mini training convergence check."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import CheckpointManager
from repro.configs.base import ShapeConfig
from repro.configs import registry
from repro.data.pipeline import DataConfig, TokenSource
from repro.ft.watchdog import FailureInjector, StepWatchdog, retry_loop
from repro.launch.train import train
from repro.launch.mesh import make_mesh
from repro.optim import adamw


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.ones((2, 2), jnp.bfloat16),
                  "step": jnp.int32(7)}}
    mgr.save(5, tree, blocking=True)
    assert mgr.latest_step() == 5
    like = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    back = mgr.restore(5, like)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_atomic_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"x": jnp.full((4,), s, jnp.float32)}, blocking=True)
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_")
                   and not d.endswith(".tmp"))
    assert len(steps) == 2  # gc keeps last 2
    assert mgr.latest_step() == 4
    # corrupt-shape detection
    like = {"x": jax.ShapeDtypeStruct((5,), jnp.float32)}
    with pytest.raises(ValueError):
        mgr.restore(4, like)


def test_checkpoint_elastic_resharding(tmp_path):
    """Restore onto a different sharding (elastic: mesh change)."""
    mgr = CheckpointManager(str(tmp_path))
    x = jnp.arange(16, dtype=jnp.float32)
    mgr.save(0, {"x": x}, blocking=True)
    mesh = make_mesh((1,), ("data",))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    back = mgr.restore(0, {"x": jax.ShapeDtypeStruct((16,), jnp.float32)},
                       {"x": sh})
    assert back["x"].sharding == sh
    np.testing.assert_array_equal(np.asarray(back["x"]), np.asarray(x))


def test_data_pipeline_deterministic_and_resumable():
    cfg = registry.get("mamba2-130m", smoke=True)
    shape = ShapeConfig("t", 64, 4, "train")
    s1 = TokenSource(cfg, shape, DataConfig(seed=1))
    s2 = TokenSource(cfg, shape, DataConfig(seed=1))
    b1 = s1.batch_at(17)
    b2 = s2.batch_at(17)  # independent instance, same step -> same data
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = s1.batch_at(18)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    assert b1["tokens"].max() < cfg.vocab
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


def test_watchdog_strikes():
    wd = StepWatchdog(deadline_s=0.01, max_strikes=1)
    wd.start(0)
    import time
    time.sleep(0.05)
    with pytest.raises(TimeoutError):
        wd.check()


def test_retry_loop_restarts(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(9, {"x": jnp.zeros(1)}, blocking=True)
    calls = []

    def run_from(start):
        calls.append(start)
        if len(calls) == 1:
            raise RuntimeError("injected node failure")
        return 99

    assert retry_loop(run_from, ckpt_mgr=mgr) == 99
    assert calls == [10, 10]  # resumed from latest ckpt both times


def test_train_resume_after_injected_failure(tmp_path):
    """End-to-end drill: crash at step 12, auto-restart from step 9."""
    inj = FailureInjector({12: RuntimeError("simulated device loss")})
    out = train("mamba2-130m-smoke", steps=16, batch=4, seq=64,
                ckpt_dir=str(tmp_path), ckpt_every=5, injector=inj,
                log_every=100)
    assert out["final_step"] == 15
    # loop ran past the failure; more loss entries than steps (replayed)
    assert len(out["losses"]) >= 16


def test_adamw_compression_error_feedback():
    cfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=0, total_steps=100,
                            grad_compress=True, clip_norm=0.0,
                            weight_decay=0.0)
    params = {"w": jnp.ones((8,), jnp.float32)}
    st = adamw.init_state(params, cfg)
    g = {"w": jnp.full((8,), 1e-3, jnp.float32)}
    # many tiny identical grads: without error feedback int8 would crush
    # them to zero forever; with EF they accumulate and get applied.
    p = params
    for _ in range(50):
        p, st, _ = adamw.update(g, st, p, cfg)
    assert float(p["w"][0]) < 1.0  # the updates got through


def test_train_loss_decreases():
    out = train("granite-moe-1b-a400m-smoke", steps=40, batch=8, seq=64,
                log_every=100,
                opt_cfg=adamw.AdamWConfig(lr=5e-3, warmup_steps=5,
                                          total_steps=40))
    first = np.mean(out["losses"][:5])
    last = np.mean(out["losses"][-5:])
    assert last < first - 0.05, f"no learning: {first:.3f} -> {last:.3f}"
