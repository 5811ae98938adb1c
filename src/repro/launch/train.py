"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \\
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/run1

Real-hardware runs use the production mesh; on the CPU container the driver
runs smoke-scale models end-to-end (the quickstart example trains one to
visibly decreasing loss).  The loop wires together every fault-tolerance
feature: periodic atomic checkpoints, preemption handler, deterministic
resume of the data stream, straggler watchdog.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ParallelConfig, ShapeConfig, TrainConfig
from repro.data import DataIterator
from repro.launch.compile_cache import enable_compile_cache
from repro.models.common import HOST_MESH, split_params
from repro.models.model import LM
from repro.runtime.fault import StepWatchdog
from repro.runtime.train_lib import init_train_state, make_train_step


def train(arch: str, *, smoke: bool = True, steps: int = 100, batch: int = 8,
          seq: int = 128, ckpt_dir: str | None = None, ckpt_every: int = 50,
          lr: float = 3e-3, microbatches: int = 1, log_every: int = 10,
          seed: int = 0):
    cfg = get_config(arch, smoke=smoke)
    shape = ShapeConfig("custom", "train", seq, batch)
    tcfg = TrainConfig(lr=lr, warmup_steps=max(steps // 20, 5),
                       total_steps=steps, checkpoint_every=ckpt_every)
    pcfg = ParallelConfig(microbatches=microbatches)
    lm = LM(cfg, HOST_MESH)

    params, pspecs, opt, ospecs = init_train_state(lm, tcfg,
                                                   jax.random.key(seed))
    data = DataIterator(cfg, shape, seed=seed)
    step = 0
    mgr = None
    if ckpt_dir:
        mgr = CheckpointManager(ckpt_dir, keep=3)
        mgr.install_preemption_handler()
        latest = mgr.latest_step()
        if latest is not None:
            step, state, extra = mgr.restore_latest({"params": params,
                                                     "opt": opt})
            params, opt = state["params"], state["opt"]
            data.load_state_dict(extra["data"])
            print(f"resumed from step {step}")

    train_step = jax.jit(make_train_step(lm, tcfg, pcfg),
                         donate_argnums=(0, 1))
    wd = StepWatchdog()
    losses = []
    while step < steps:
        batch_data = next(data)
        wd.start()
        params, opt, metrics = train_step(params, opt, batch_data)
        loss = float(metrics["loss"])
        wd.stop()
        losses.append(loss)
        step += 1
        if step % log_every == 0 or step == steps:
            print(f"step {step:5d} loss {loss:.4f} lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.3f}")
        if mgr and (step % ckpt_every == 0 or mgr.preempted):
            mgr.save(step, {"params": params, "opt": opt},
                     extra={"data": data.state_dict(),
                            "watchdog": wd.summary()})
            if mgr.preempted:
                print(f"preempted: emergency checkpoint at step {step}")
                return {"step": step, "losses": losses, "preempted": True}
    if mgr:
        mgr.save(step, {"params": params, "opt": opt},
                 extra={"data": data.state_dict(),
                        "watchdog": wd.summary()})
    print("watchdog:", wd.summary())
    return {"step": step, "losses": losses, "preempted": False,
            "params": params}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    enable_compile_cache()
    out = train(a.arch, smoke=a.smoke, steps=a.steps, batch=a.batch,
                seq=a.seq, ckpt_dir=a.ckpt_dir, ckpt_every=a.ckpt_every,
                lr=a.lr, microbatches=a.microbatches, seed=a.seed)
    first, last = np.mean(out["losses"][:5]), np.mean(out["losses"][-5:])
    print(f"loss: first5={first:.4f} last5={last:.4f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")


if __name__ == "__main__":
    main()
