"""End-to-end training driver.

Runs any assigned arch (full or smoke config) on any mesh: plain global-batch
training (pjit) with checkpoint/restart, or PTB-FLA mode (--fl tdm|...)
where node groups are satellites doing local steps + TDM exchange — see
launch/fl_train.py.

Examples (CPU):
  PYTHONPATH=src python -m repro.launch.train --arch gemma2-9b --smoke \
      --steps 30 --seq 64 --batch 8
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-780m --smoke \
      --steps 20 --ckpt /tmp/ck --restore
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import checkpoint as ckpt_lib
from repro.configs import archs
from repro.data import pipeline
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as shlib
from repro.launch import steps as steps_lib
from repro.models.config import ShapeConfig
from repro.optim import adamw


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--ckpt", type=str, default=None)
    p.add_argument("--ckpt-every", type=int, default=10)
    p.add_argument("--restore", action="store_true")
    p.add_argument("--log-every", type=int, default=1)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    cfg = archs.get(args.arch)
    if args.smoke:
        cfg = archs.smoke_cfg(cfg)
    shape = ShapeConfig("custom", "train", args.seq, args.batch)
    opt_cfg = adamw.OptConfig(
        peak_lr=args.lr, warmup_steps=5, decay_steps=max(args.steps, 10)
    )

    n_dev = len(jax.devices())
    rules = None
    if n_dev > 1:
        axes = {"data": min(n_dev, max(1, args.batch)), "model": 1}
        mesh = mesh_lib.make_mesh((axes["data"], 1), ("data", "model"))
        rules = shlib.rules_for(mesh, cfg.fsdp)

    train_step = jax.jit(
        steps_lib.build_train_step(cfg, opt_cfg, rules), donate_argnums=(0,)
    )

    state = steps_lib.init_state(jax.random.PRNGKey(args.seed), cfg, opt_cfg)
    start_step = 0
    if args.ckpt and args.restore and ckpt_lib.latest_step(args.ckpt) is not None:
        start_step, state = ckpt_lib.restore(args.ckpt, target=state)
        print(f"restored checkpoint at step {start_step}")

    stream = pipeline.SyntheticStream(cfg, shape, seed=args.seed)
    losses = []
    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = {k: jnp.asarray(v) for k, v in stream.batch(step).items()}
        state, metrics = train_step(state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % args.log_every == 0:
            print(
                f"step {step:4d} loss {loss:8.4f} "
                f"gnorm {float(metrics['grad_norm']):8.3f} "
                f"lr {float(metrics['lr']):.2e}",
                flush=True,
            )
        if args.ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt_lib.save(args.ckpt, step + 1, state)
    ckpt_lib.wait_all()
    dt = time.time() - t0
    if losses:
        print(
            f"done: {args.steps - start_step} steps in {dt:.1f}s; "
            f"loss {losses[0]:.4f} -> {losses[-1]:.4f}"
        )
    else:
        print(f"nothing to do: restored step {start_step} >= --steps {args.steps}")
    return losses


if __name__ == "__main__":
    main()
