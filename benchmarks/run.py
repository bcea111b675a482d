"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig2,fig16] \
        [--quick] [--json BENCH.json] [--trace DIR]

Prints ``name,us_per_call,derived`` CSV rows and writes
results/bench/bench.json (``--json PATH`` writes the same machine-readable
rows to PATH — what the CI bench-smoke job archives).  ``--quick`` asks
modules that support it (``rows(quick=True)``) for a reduced sweep.  Any
module that raises fails the run (non-zero exit), so benchmark drift fails
the build instead of scrolling by.  Each module's docstring names the paper
claims it validates; EXPERIMENTS.md §Paper-validation summarizes the
outcomes.
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import json
import sys
import time
from pathlib import Path

from repro.launch.compile_cache import use_compile_cache

MODULES = [
    "table1_ops",
    "fig2_transfer_size",
    "fig3_batch",
    "fig4_wq_depth",
    "fig5_latency_breakdown",
    "fig6_memory_tiers",
    "fig7_pes",
    "fig9_wq_config",
    "fig10_multi_instance",
    "fig11_umwait",
    "fig12_cache_pollution",
    "fig13_cross_numa",
    "fig14_ts_bs",
    "fig16_vhost",
    "fig17_openloop",
    "appendix_checkpoint",
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--out", default="results/bench")
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweeps for modules whose rows() takes quick=")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the machine-readable rows to PATH")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="modules whose rows() takes trace_dir= attach an "
                         "obs.Sampler and drop per-run time-series CSVs here")
    args = ap.parse_args()
    use_compile_cache()
    only = [s.strip() for s in args.only.split(",") if s.strip()]

    all_rows = []
    errors = 0
    print("name,us_per_call,derived")
    for mod_name in MODULES:
        if only and not any(mod_name.startswith(o) for o in only):
            continue
        t0 = time.time()
        mod = importlib.import_module(f"benchmarks.{mod_name}")
        try:
            kwargs = {}
            params = inspect.signature(mod.rows).parameters
            if args.quick and "quick" in params:
                kwargs["quick"] = True
            if args.trace and "trace_dir" in params:
                kwargs["trace_dir"] = args.trace
            rows = mod.rows(**kwargs)
        except Exception as e:  # noqa: BLE001 — report, fail the run at exit
            print(f"{mod_name}/ERROR,0,{type(e).__name__}: {e}", flush=True)
            errors += 1
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.2f},{derived}", flush=True)
            all_rows.append({"name": name, "us_per_call": us, "derived": derived})
        print(f"# {mod_name} done in {time.time()-t0:.1f}s", file=sys.stderr)

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "bench.json").write_text(json.dumps(all_rows, indent=1))
    if args.json:
        Path(args.json).write_text(json.dumps(all_rows, indent=1))
    if errors:
        print(f"# {errors} benchmark module(s) failed", file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
