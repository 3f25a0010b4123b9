"""Training driver: sharded pjit train loop with checkpoint/restart,
straggler watchdog, deterministic resume, and failure drills.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m-smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..checkpoint.ckpt import CheckpointManager
from ..configs import registry
from ..configs.base import ShapeConfig
from ..data.pipeline import DataConfig, TokenSource
from ..ft.watchdog import FailureInjector, StepWatchdog, retry_loop
from ..models.params import init_params
from ..optim import adamw
from ..parallel import steps as steps_mod
from . import compile_cache
from .mesh import make_host_mesh


def train(arch: str, *, steps: int = 100, batch: int = 8, seq: int = 128,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 25,
          data_cfg: Optional[DataConfig] = None,
          mesh=None, seed: int = 0, log_every: int = 10,
          injector: Optional[FailureInjector] = None,
          deadline_s: float = 300.0,
          opt_cfg: Optional[adamw.AdamWConfig] = None) -> Dict[str, Any]:
    cfg = registry.get(arch)
    shape = ShapeConfig(f"train_{seq}", seq, batch, "train")
    mesh = mesh or make_host_mesh(data=len(jax.devices()), model=1)
    opt_cfg = opt_cfg or adamw.AdamWConfig(total_steps=steps)

    jitted, bundle, abstract = steps_mod.jit_train_step(
        cfg, mesh, shape, opt_cfg=opt_cfg)
    source = TokenSource(cfg, shape, data_cfg or DataConfig(seed=seed))
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None

    losses: list = []
    state: Dict[str, Any] = {}

    def init_state():
        params = init_params(bundle["specs"], jax.random.PRNGKey(seed))
        params = jax.device_put(params, bundle["param_sh"])
        opt = adamw.init_state(params, opt_cfg)
        opt = jax.device_put(opt, bundle["opt_sh"])
        return params, opt

    def run_from(start_step: int) -> int:
        params = opt = None
        if start_step > 0 and mgr is not None and mgr.latest_step() is not None:
            ck = mgr.latest_step()
            blob = mgr.restore(ck, {"params": abstract[0],
                                    "opt": abstract[1]},
                               {"params": bundle["param_sh"],
                                "opt": bundle["opt_sh"]})
            params, opt = blob["params"], blob["opt"]
            start_step = ck + 1
        if params is None:
            params, opt = init_state()
            start_step = 0

        wd = StepWatchdog(deadline_s)
        for step in range(start_step, steps):
            if injector is not None:
                injector.maybe_fail(step)
            batch_np = source.batch_at(step)
            batch_dev = {k: jnp.asarray(v) for k, v in batch_np.items()}
            wd.start(step)
            t0 = time.time()
            params, opt, metrics = jitted(params, opt, batch_dev)
            loss = float(metrics["loss"])
            wd.stop()
            wd.check()
            losses.append(loss)
            if step % log_every == 0 or step == steps - 1:
                print(f"[train {arch}] step {step} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"dt {time.time() - t0:.2f}s", flush=True)
            if mgr is not None and (step + 1) % ckpt_every == 0:
                mgr.save(step, {"params": params, "opt": opt})
        if mgr is not None:
            mgr.save(steps - 1, {"params": params, "opt": opt},
                     blocking=True)
        state["params"] = params
        return steps - 1

    if mgr is not None:
        final = retry_loop(run_from, ckpt_mgr=mgr)
    else:
        final = run_from(0)
    return {"final_step": final, "losses": losses,
            "params": state.get("params")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()
    out = train(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                seed=args.seed)
    print(f"done: final_step={out['final_step']} "
          f"loss {out['losses'][0]:.3f} -> {out['losses'][-1]:.3f}")


if __name__ == "__main__":
    main()
