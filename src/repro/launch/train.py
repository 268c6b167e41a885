"""Production training launcher: sharded train loop + fault tolerance.

Features exercised here (and tested in tests/test_train_loop.py,
tests/test_elastic.py):
  · auto-resume from the latest valid checkpoint (bit-exact: data-pipeline
    state rides in the checkpoint)
  · async checkpointing every N steps, atomic publish, keep-k GC
  · straggler watchdog: per-step wall-time EMA, slow steps logged
  · elastic restore: checkpoints are sharding-agnostic; restoring onto a
    different mesh re-shards via device_put
  · XLA latency-hiding scheduler flags for compute/comm overlap (TPU)

Usage (CPU example run):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --reduced --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import os
import time

# Compute/comm overlap: latency-hiding scheduler (effective on TPU; harmless
# on CPU). Must be set before jax initializes.
_LHS_FLAGS = ("--xla_tpu_enable_latency_hiding_scheduler=true "
              "--xla_tpu_megacore_fusion_allow_ags=true ")
os.environ.setdefault("XLA_FLAGS", "")
if "latency_hiding" not in os.environ["XLA_FLAGS"] \
        and os.environ.get("REPRO_TPU"):
    os.environ["XLA_FLAGS"] += " " + _LHS_FLAGS

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import base as cb
from repro.data import TokenPipeline
from repro.distrib import sharding as shd
from repro.launch.steps import make_train_step
from repro.models.model_zoo import Model, set_activation_sharding
from repro.optim import adamw


class StragglerWatchdog:
    """Flags steps slower than factor x EMA (at pod scale: host attribution
    + preemption hooks; here: detection + logging, tested)."""

    def __init__(self, factor: float = 2.0, alpha: float = 0.2):
        self.factor = factor
        self.alpha = alpha
        self.ema = None
        self.events = []

    def observe(self, step: int, dt: float) -> bool:
        slow = self.ema is not None and dt > self.factor * self.ema
        if slow:
            self.events.append((step, dt, self.ema))
        self.ema = dt if self.ema is None else \
            (1 - self.alpha) * self.ema + self.alpha * dt
        return slow


def train(cfg, steps: int, global_batch: int, seq_len: int,
          ckpt_dir: str | None = None, ckpt_every: int = 20,
          mesh=None, act_dtype=jnp.float32, use_flash: bool = False,
          gw_align: bool = False, log_every: int = 10, keep: int = 3,
          schedule_total: int | None = None, base_lr: float = 3e-4):
    model = Model(cfg)
    pipe = TokenPipeline(cfg, seq_len, global_batch)
    total = schedule_total or steps
    step_fn = make_train_step(model, base_lr=base_lr, act_dtype=act_dtype,
                              remat=True, use_flash=use_flash,
                              gw_align=gw_align,
                              warmup=max(1, min(100, total // 10)),
                              total_steps=total)
    mgr = CheckpointManager(ckpt_dir, keep=keep) if ckpt_dir else None

    if mesh is not None:
        dp = shd.data_axes(mesh)
        set_activation_sharding(
            True, dp=dp,
            dp_size=int(np.prod([mesh.shape[a] for a in dp])),
            model_size=mesh.shape["model"])
        abstract = model.abstract_params()
        axes = model.param_axes()
        param_sh = shd.param_shardings(axes, abstract, mesh)
        opt_sh = adamw.AdamWState(shd.replicated(mesh), param_sh, param_sh)
        jit_step = jax.jit(step_fn, in_shardings=(param_sh, opt_sh, None),
                           out_shardings=(param_sh, opt_sh, None),
                           donate_argnums=(0, 1))
    else:
        param_sh = opt_sh = None
        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    # ---- init or resume ----------------------------------------------------
    start = 0
    params = opt_state = None
    if mgr is not None and mgr.latest_step() is not None:
        start = mgr.latest_step()
        target = {"params": model.abstract_params(),
                  "opt": adamw.abstract_state(model.abstract_params())}
        target = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), target)
        sh = {"params": param_sh, "opt": opt_sh} if param_sh else None
        restored, extra = mgr.restore(start, target, sh)
        params, opt_state = restored["params"], restored["opt"]
        pipe.load_state_dict(extra["pipeline"])
        print(f"[resume] from step {start}")
    if params is None:
        params = model.init(jax.random.PRNGKey(0))
        if mesh is not None:
            params = jax.device_put(params, param_sh)
        opt_state = adamw.init(params)

    watchdog = StragglerWatchdog()
    history = []
    for step in range(start, steps):
        batch = jax.tree.map(jnp.asarray, pipe.global_batch_at(step))
        t0 = time.time()
        params, opt_state, metrics = jit_step(params, opt_state, batch)
        metrics = jax.tree.map(float, jax.device_get(metrics))
        dt = time.time() - t0
        if watchdog.observe(step, dt):
            print(f"[straggler] step {step}: {dt:.2f}s vs ema "
                  f"{watchdog.ema:.2f}s")
        history.append(metrics)
        if log_every and step % log_every == 0:
            print(f"step {step:5d} loss {metrics['loss']:.4f} "
                  f"ce {metrics['ce']:.4f} gnorm {metrics['gnorm']:.2f} "
                  f"{dt*1e3:.0f}ms")
        pipe.step = step + 1
        if mgr is not None and (step + 1) % ckpt_every == 0:
            mgr.save(step + 1, {"params": params, "opt": opt_state},
                     extra={"pipeline": pipe.state_dict()}, blocking=False)
    if mgr is not None:
        mgr.wait()
        mgr.save(steps, {"params": params, "opt": opt_state},
                 extra={"pipeline": pipe.state_dict()})
    return params, opt_state, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--gw-align", action="store_true",
                    help="enable the SPAR-GW representation alignment loss")
    ap.add_argument("--use-flash", action="store_true")
    args = ap.parse_args()
    cfg = cb.get_reduced(args.arch) if args.reduced else cb.get_arch(args.arch)
    train(cfg, args.steps, args.batch, args.seq, ckpt_dir=args.ckpt_dir,
          ckpt_every=args.ckpt_every, gw_align=args.gw_align,
          use_flash=args.use_flash)


if __name__ == "__main__":
    main()
