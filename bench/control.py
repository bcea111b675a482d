"""The controls of ``correct``: the reference put in the program's place,
computed one precision step down, must come out not correct.

Each system's control is ``CONTROL`` of ``bench/cases/<system>.py``,
which says what the step down is for its data.  The control replaces
kernel entry points of ``repro.kernels.ops``, so submission, queues, PE
dispatch and completion still run as in the cell.

    python bench/control.py --workload <cell> --seconds 5 --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402


def main(argv=None) -> int:
    from bench import cases, run, spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bm = spec.load_benchmark()
    hooks = cases.of(spec.config(bm, spec.cell(bm, args.workload)["config"]))
    for seed in args.seeds:
        with cases.replaced(hooks.CONTROL):
            result, _ = run.run_cell(args.workload, seed, args.seconds, False)
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          "checks": result["checks"]}), flush=True)
        gc.collect()  # the last seed's pools go before the next seed's are made
    return 0


if __name__ == "__main__":
    sys.exit(main())
