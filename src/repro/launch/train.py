"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --reduced --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Integrates every substrate layer: synthetic data pipeline (async prefetch),
model zoo, AdamW + grad accumulation + clipping, ZeRO-1 sharding on the
active mesh, async incremental checkpointing (delta+CRC), heartbeat +
straggler tracking, and restart-from-checkpoint on failure.
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointConfig, CheckpointManager
from repro.configs import get_config
from repro.core import make_device
from repro.data.pipeline import Prefetcher, SyntheticLMDataset
from repro.distributed.annotate import use_rules
from repro.distributed.fault import Heartbeat, StragglerDetector, run_with_restarts
from repro.distributed.sharding import rules_for_mesh
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models.api import build_model
from repro.optim.adamw import AdamW, cosine_schedule


def train(args) -> int:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh()
    rules = rules_for_mesh(mesh)
    model = build_model(cfg, mesh=mesh, remat=not args.no_remat)
    opt = AdamW(lr=cosine_schedule(args.lr, warmup=20, total=max(args.steps, 21)))
    step_fn = jax.jit(
        make_train_step(model, opt, micro_steps=args.micro_steps),
        donate_argnums=(0, 1),
    )

    # checkpoint traffic (kernel CRCs when enabled) shares one engine pool
    device = make_device(n_instances=getattr(args, "instances", 1),
                         policy=getattr(args, "policy", "round_robin"))
    ckpt = CheckpointManager(
        CheckpointConfig(directory=args.ckpt_dir, full_every=args.full_every,
                         replicas=args.replicas, async_save=True,
                         crc_impl=getattr(args, "crc_impl", "zlib")),
        device=device,
    )
    dataset = SyntheticLMDataset(cfg, args.batch, args.seq, seed=args.seed)
    hb = Heartbeat(str(Path(args.ckpt_dir) / "hb"), rank=0)
    straggler = StragglerDetector()

    def run(start_step: int) -> int:
        rng = jax.random.key(args.seed)
        params = model.init(rng)
        opt_state = opt.init(params)
        if start_step > 0:
            s, tree = ckpt.restore(treedef_like={"params": params, "opt": opt_state})
            params, opt_state = tree["params"], tree["opt"]
            start_step = s
            print(f"[train] resumed from step {s}")
        prefetch = Prefetcher(dataset, start_step=start_step)
        losses = []
        try:
            with mesh, use_rules(mesh, rules):
                for i in range(start_step, args.steps):
                    t0 = time.perf_counter()
                    step_i, batch = next(prefetch)
                    params, opt_state, metrics = step_fn(params, opt_state, batch)
                    loss = float(metrics["loss"])
                    losses.append(loss)
                    dt = time.perf_counter() - t0
                    straggler.record(0, dt)
                    hb.beat(i)
                    if (i + 1) % args.ckpt_every == 0:
                        ckpt.save(i + 1, {"params": params, "opt": opt_state})
                    if (i + 1) % args.log_every == 0:
                        print(
                            f"step {i+1:5d} loss {loss:.4f} gnorm "
                            f"{float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms",
                            flush=True,
                        )
        finally:
            prefetch.stop()
        ckpt.save(args.steps, {"params": params, "opt": opt_state})
        ckpt.wait()
        print(f"[train] done; first loss {losses[0]:.4f} last loss {losses[-1]:.4f}; "
              f"ckpt stats {ckpt.stats}")
        return args.steps

    return run_with_restarts(run, ckpt.latest_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--no-reduced", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--micro-steps", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--full-every", type=int, default=4)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--instances", type=int, default=1)
    ap.add_argument("--policy", default="round_robin",
                    choices=["round_robin", "least_loaded", "sticky"])
    ap.add_argument("--crc-impl", default="zlib", choices=["zlib", "kernel"])
    args = ap.parse_args()
    use_compile_cache()
    train(args)


if __name__ == "__main__":
    main()
