"""End-to-end training driver (the RecoNIC 'host application').

Wires every substrate together: config -> mesh -> sharded params/opt ->
data pipeline -> train loop with doorbell-batched gradient sync,
async checkpointing, heartbeat/straggler monitoring and elastic restart.

CPU-scale usage (the ~100M-model e2e example drives this)::

  PYTHONPATH=src python -m repro.launch.train --arch train-100m \
      --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs.base import TrainConfig
from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.core.rdma.cost_model import TPU_V5E
from repro.core.rdma.doorbell import choose_bucket_bytes, plan_buckets
from repro.core.streaming.classifier import (TrafficClass, TrafficRouter,
                                             TransferDesc)
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.models.transformer import init_params
from repro.runtime.fault_tolerance import ElasticController, HeartbeatMonitor
from repro.train.optimizer import init_adam
from repro.train.train_step import make_train_step


def run(arch: str, steps: int, batch: int, seq: int, ckpt_dir: str = "",
        resume: bool = False, log_every: int = 10, lr: float = 3e-4,
        microbatches: int = 1, seed: int = 0,
        ckpt_every: int = 50, data_cycle: int = 0) -> dict:
    """``data_cycle`` > 0 cycles through that many fixed batches
    (memorization demo — loss provably decreases in a few hundred steps);
    0 streams fresh batches (true pretraining; loss curves need far more
    than a CPU-scale budget to move)."""
    cfg = get_config(arch)
    tcfg = TrainConfig(learning_rate=lr, warmup_steps=max(steps // 20, 5),
                       total_steps=steps, microbatches=microbatches,
                       remat=True, zero1=False, sequence_parallel=False,
                       seed=seed)

    key = jax.random.PRNGKey(seed)
    params = init_params(cfg, key)
    opt = init_adam(params)
    pipe = SyntheticPipeline(DataConfig(
        seed=seed, vocab_size=cfg.vocab_size, batch=batch, seq_len=seq))

    start_step = 0
    cm = CheckpointManager(ckpt_dir) if ckpt_dir else None
    if cm and resume and cm.latest_step() is not None:
        (params, opt), start_step = cm.restore((params, opt))
        print(f"resumed from step {start_step}")

    step_fn = jax.jit(make_train_step(cfg, tcfg))

    # RecoNIC telemetry: classify the traffic this job generates per step
    router = TrafficRouter()
    leaf_bytes = [int(x.size) * 4 for x in jax.tree.leaves(params)]
    bucket_bytes, t_pred = choose_bucket_bytes(
        leaf_bytes, n_devices=max(jax.device_count(), 2),
        alpha_s=TPU_V5E.alpha_dispatch, link_bw=TPU_V5E.ici_bw_per_link)
    buckets = plan_buckets(leaf_bytes, bucket_bytes or (16 << 20))
    print(f"grad sync plan: {len(leaf_bytes)} tensors -> {len(buckets)} "
          f"buckets (doorbell batching), predicted sync {t_pred*1e3:.2f}ms")

    monitor = HeartbeatMonitor(n_hosts=1, timeout=3600.0)
    controller = ElasticController(monitor, model_parallel=1)
    losses, times = [], []
    t_start = time.time()
    for step in range(start_step, steps):
        b = pipe.batch_at(step % data_cycle if data_cycle else step)
        batch_dev = {k: jnp.asarray(v) for k, v in b.items()}
        t0 = time.time()
        loss, params, opt = step_fn(params, opt, batch_dev)
        dt = time.time() - t0
        monitor.beat(0, dt)
        controller.step(step, {0: dt})
        router.route([TransferDesc(TrafficClass.BULK_GRAD,
                                   sum(leaf_bytes)),
                      TransferDesc(TrafficClass.HOST_IO,
                                   batch_dev["tokens"].size * 4)])
        losses.append(float(loss))
        times.append(dt)
        if step % log_every == 0 or step == steps - 1:
            print(f"step {step:5d}  loss {losses[-1]:.4f}  "
                  f"{dt*1e3:7.1f} ms/step")
        if cm and step and step % ckpt_every == 0:
            cm.save(step, (params, opt), blocking=False)
    if cm:
        cm.save(steps, (params, opt), blocking=True)

    return {"arch": arch, "steps": steps,
            "first_loss": losses[0], "last_loss": losses[-1],
            "mean_step_s": float(np.mean(times[1:])) if len(times) > 1
            else times[0],
            "total_s": time.time() - t_start,
            "buckets": len(buckets),
            "traffic": {tc.value: dict(c) for tc, c in
                        router.counters.items() if c["count"]}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="train-100m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--data-cycle", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    enable_compile_cache()
    res = run(args.arch, args.steps, args.batch, args.seq, args.ckpt_dir,
              args.resume, lr=args.lr, microbatches=args.microbatches,
              data_cycle=args.data_cycle)
    print(json.dumps(res, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    assert res["last_loss"] < res["first_loss"], "training must reduce loss"


if __name__ == "__main__":
    main()
