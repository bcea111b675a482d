"""Find the knee of an open-loop cell on the chip: the highest offered rate
with no QueueFull and no backlog that grows through the window.

    python bench/sweep.py --workload vhost-imix.steady --seed <n> --seconds 5

Sets the cell up once, measures its closed-loop capacity with 4 and 8
bursts in flight, then offers Poisson load at fractions of that capacity
and prints one JSON line per rate.  A backlog counts as growing where the
mean latency of the window's last quarter of bursts is over twice that of
its first quarter.  The cell's mix file takes 0.8 x the knee found here.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import record, spec, traffic  # noqa: E402
from bench.readers import percentile  # noqa: E402

FRACTIONS = (0.5, 0.6, 0.7, 0.8, 0.85, 0.9, 0.95, 1.0, 1.1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 3
    bm = spec.load_benchmark()
    cell = spec.cell(bm, args.workload)
    cfg = spec.config(bm, cell["config"])
    mix = traffic.load(cell["traffic"])
    sut = spec.system(cfg).System(cfg, mix, args.seed, traced=False)
    capacity = 0.0
    for in_flight in (4, 8):
        sut.mix = dict(copy.deepcopy(mix), loop="closed", in_flight=in_flight)
        run = record.Run()
        sut.window(args.seconds, record.Spans(False), run)
        rate = run.attempted / run.window_s
        capacity = max(capacity, rate)
        print(json.dumps({"closed_in_flight": in_flight, "bursts_per_s": rate}), flush=True)
    knee = None
    for f in FRACTIONS:
        sut.mix = copy.deepcopy(mix)
        sut.mix["arrivals"]["rate_per_s"] = f * capacity
        run = record.Run()
        sut.window(args.seconds, record.Spans(False), run)
        lat = run.latencies_s
        q = max(len(lat) // 4, 1)
        first = sum(lat[:q]) / q
        last = sum(lat[-q:]) / q
        growing = last > 2 * first
        ok = run.failed == 0 and not growing
        if ok:
            knee = f * capacity
        print(json.dumps({
            "rate_per_s": f * capacity, "bursts": run.attempted, "failed": run.failed,
            "p50_ms": 1e3 * percentile(lat, 50), "p99_ms": 1e3 * percentile(lat, 99),
            "first_quarter_ms": 1e3 * first, "last_quarter_ms": 1e3 * last,
            "gen_late_p99_ms": 1e3 * percentile(run.gen_late_s, 99), "sustained": ok,
        }), flush=True)
    print(json.dumps({"capacity_per_s": capacity, "knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
