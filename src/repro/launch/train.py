"""Training entrypoint (single-host execution; the production mesh path is
exercised by dryrun.py).

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
        --steps 100 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt

Features on display: deterministic sharded data pipeline, AdamW(+8bit),
async checkpointing with resume, WCET phase accounting, straggler detection.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core.wcet import WcetTracker
from repro.data import DataConfig, ShardedLoader, SyntheticLM
from repro.distributed import ShardCtx
from repro.distributed.fault_tolerance import StragglerDetector
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.optim.optimizer import cosine_schedule
from repro.training import init_state, make_train_step, opt_config_for, \
    state_shardings


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh() if len(jax.devices()) > 1 else None
    ctx = ShardCtx.for_mesh(mesh, "train") if mesh else ShardCtx.single()
    model = build(cfg, ctx)
    ocfg = opt_config_for(
        cfg, lr=cosine_schedule(args.lr, args.steps // 10, args.steps))

    tracker = WcetTracker("train")
    straggler = StragglerDetector()
    with tracker.phase("init"):
        params, opt_state = init_state(model, ocfg, jax.random.key(args.seed))
        step_fn = jax.jit(make_train_step(model, ocfg, args.accum),
                          donate_argnums=(0, 1))
        loader = ShardedLoader(
            SyntheticLM(cfg.vocab_size, seed=args.seed),
            DataConfig(global_batch=args.batch, seq_len=args.seq))

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if ckpt and args.resume and ckpt.latest_step() is not None:
        start = ckpt.latest_step()
        tpl = {"params": params, "opt": opt_state}
        restored = ckpt.restore(start, tpl)
        params, opt_state = restored["params"], restored["opt"]
        print(f"[train] resumed from step {start}")

    for step in range(start, args.steps):
        batch = loader.device_batch(step)
        with tracker.phase("trigger"):
            params, opt_state, metrics = step_fn(params, opt_state, batch)
        with tracker.phase("wait"):
            metrics = jax.tree.map(float, jax.block_until_ready(metrics))
        slow = straggler.observe(0, tracker.stats["wait"].best_ns)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"[train] step={step} loss={metrics['loss']:.4f} "
                  f"ce={metrics['ce']:.4f} gnorm={metrics['grad_norm']:.3f} "
                  f"lr={metrics['lr']:.2e}{' STRAGGLER' if slow else ''}")
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save_async(step + 1, {"params": params, "opt": opt_state},
                            {"arch": cfg.name})
    if ckpt:
        ckpt.save_async(args.steps, {"params": params, "opt": opt_state},
                        {"arch": cfg.name})
        ckpt.wait()
    with tracker.phase("dispose"):
        del params, opt_state
    print("[train] wcet:", {k: f"avg={v.avg_ns/1e6:.1f}ms "
                            f"worst={v.worst_ns/1e6:.1f}ms"
                            for k, v in tracker.stats.items()})
    return metrics


if __name__ == "__main__":
    main()
