"""Serving driver: continuous batching with the Vhost-style 3-stage pipeline.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --requests 16 --slots 4 --max-new 8 [--no-reduced]

``--no-reduced`` serves the architecture at its published widths; the
default is the reduced preset.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core import make_device
from repro.launch.compile_cache import use_compile_cache
from repro.models.api import build_model
from repro.serving.pipeline import Request, VhostStyleServer


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--no-reduced", dest="reduced", action="store_false")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-cache", type=int, default=128)
    ap.add_argument("--instances", type=int, default=2)
    ap.add_argument("--policy", default="least_loaded",
                    choices=["round_robin", "least_loaded", "sticky"])
    ap.add_argument("--seed", type=int, default=0)
    return ap


def serve(args) -> VhostStyleServer:
    """Serve ``args.requests`` random prompts to completion; returns the
    drained server (its params, metrics and device)."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(args.seed))
    server = VhostStyleServer(
        model, params, slots=args.slots, max_cache_len=args.max_cache,
        device=make_device(n_instances=args.instances, policy=args.policy),
    )

    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    for i in range(args.requests):
        server.enqueue(
            Request(req_id=i,
                    prompt=rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32),
                    max_new_tokens=args.max_new)
        )
    steps = server.run_until_drained()
    dt = time.perf_counter() - t0
    m = server.metrics
    ps = server.device.policy_stats
    placed = ", ".join(f"{k}={v}" for k, v in sorted(ps["decisions"].items()))
    print(f"served {m['completed']}/{args.requests} requests in {steps} pipeline steps "
          f"(cold wall {dt:.2f}s incl. compiles); decoded {m['decoded_tokens']} tokens; "
          f"copy bursts {m['copy_bursts']}; "
          f"policy {ps['policy']} placements [{placed}]")
    if m["completed"] != args.requests:
        raise RuntimeError(f"served {m['completed']} of {args.requests} requests")
    return server


def main(argv=None):
    args = parser().parse_args(argv)
    use_compile_cache()
    serve(args)


if __name__ == "__main__":
    main()
