"""End-to-end training driver with fault tolerance.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
      --steps 200 --ckpt-dir /tmp/ckpt

Features exercised here (and tested in tests/test_system.py):
  * auto-resume from the newest valid checkpoint (restart-safe),
  * deterministic step-addressed data (replays exactly after restart),
  * step watchdog (straggler flagging) + periodic checkpoints,
  * elastic remesh: restoring onto a different mesh works because params
    are stored with logical PartitionSpecs.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, smoke_config
from repro.data.pipeline import SyntheticLM
from repro.distributed.fault import StepWatchdog
from repro.distributed.sharding import MeshRules, to_named_shardings
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_params, make_train_step, opt_pspecs
from repro.models.config import ShapeConfig
from repro.optim.adamw import AdamWConfig, adamw_init


def train(arch: str, *, steps: int = 100, seq_len: int = 64,
          global_batch: int = 4, smoke: bool = True,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
          log_every: int = 10, seed: int = 0, lr: float = 3e-4,
          mesh=None, stop_after: Optional[int] = None):
    cfg = smoke_config(arch) if smoke else get_config(arch)
    mesh = mesh or make_host_mesh()
    rules = MeshRules.for_mesh(mesh)
    shape = ShapeConfig("custom", "train", seq_len, global_batch)
    opt_cfg = AdamWConfig(lr_peak=lr, warmup_steps=max(2, steps // 20),
                          total_steps=steps)

    with mesh:
        params, pspecs = build_params(cfg, rules, abstract=False, seed=seed)
        params = jax.device_put(params, to_named_shardings(mesh, pspecs))
        opt_state = adamw_init(params, opt_cfg)
        data = SyntheticLM(cfg, shape, seed=seed)
        step_fn = jax.jit(make_train_step(cfg, rules, opt_cfg),
                          donate_argnums=(0, 1))

        mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
        start_step = 0
        if mgr is not None and mgr.latest_step() is not None:
            start_step, state = mgr.restore(
                mesh=mesh, pspecs={"params": pspecs,
                                   "opt": opt_pspecs(pspecs)})
            params, opt_state = state["params"], state["opt"]
            print(f"[train] resumed from step {start_step}", flush=True)

        wd = StepWatchdog(tolerance=3.0)
        losses = []
        for step in range(start_step, steps):
            wd.start(step)
            batch = data.sharded_batch(step, mesh, rules)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            dt = wd.stop()
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % log_every == 0 or step == steps - 1:
                print(f"[train] step={step} loss={loss:.4f} "
                      f"dt={dt:.3f}s", flush=True)
            if mgr is not None and (step + 1) % ckpt_every == 0:
                mgr.save(step + 1, {"params": params, "opt": opt_state})
            if stop_after is not None and step + 1 - start_step >= stop_after:
                print(f"[train] stopping early at step {step + 1} "
                      "(simulated preemption)", flush=True)
                break
        if wd.flagged:
            print(f"[train] straggler steps flagged: {wd.flagged}", flush=True)
        if mgr is not None:
            mgr.save(min(step + 1, steps), {"params": params,
                                            "opt": opt_state})
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "straggler_flags": list(wd.flagged)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="qwen3-8b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    enable_compile_cache()
    out = train(args.arch, steps=args.steps, seq_len=args.seq_len,
                global_batch=args.global_batch, smoke=args.smoke,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                lr=args.lr)
    print(f"[train] done: final_loss={out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
