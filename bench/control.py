"""The controls of ``correct``: the reference put in the program's place,
computed one precision step down, must come out not correct.

The configurations state no floating precision but a bit-exact copy, so
the step down is the contract's own ladder applied to the data: packets
are bytes (8 bits) and go through 4 bits, keeping each byte's high
nibble; KV pages are bfloat16 and are rounded to float8 e4m3, the KV
quantisation a later change could be tempted by.  The rounding is
``lax.reduce_precision``: the TPU compiler removes a bfloat16 -> float8 ->
bfloat16 convert pair as a no-op, so a cast round trip would not round.  The control replaces
the program's kernel entry points (``repro.kernels.ops.memcpy`` and
``batch_copy``), so submission, queues, PE dispatch and completion still
run as in the cell.

    python bench/control.py --workload <cell> --seconds 5 --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


@functools.partial(jax.jit, static_argnames=("interpret", "n_pe"))
def int4_memcpy(x, *, interpret=None, n_pe=1):
    return (x >> 4) << 4


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(1,))
def int4_batch_copy(src_pool, dst_pool, src_idx, dst_idx, *, interpret=None):
    return dst_pool.at[dst_idx].set((src_pool[src_idx] >> 4) << 4)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(1,))
def fp8_batch_copy(src_pool, dst_pool, src_idx, dst_idx, *, interpret=None):
    pages = jax.lax.reduce_precision(src_pool[src_idx], exponent_bits=4, mantissa_bits=3)
    return dst_pool.at[dst_idx].set(pages)


CONTROLS = {
    "vhost": {"memcpy": int4_memcpy, "batch_copy": int4_batch_copy},
    "kvswap": {"batch_copy": fp8_batch_copy},
}


@contextlib.contextmanager
def replaced(functions):
    """Put ``functions`` in place of the program's kernel entry points of
    the same names for the duration of the block."""
    from repro.kernels import ops

    saved = {name: getattr(ops, name) for name in functions}
    try:
        for name, fn in functions.items():
            setattr(ops, name, fn)
        yield
    finally:
        for name, fn in saved.items():
            setattr(ops, name, fn)


def main(argv=None) -> int:
    from bench import run, spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bm = spec.load_benchmark()
    system = spec.config(bm, spec.cell(bm, args.workload)["config"])["system"]
    for seed in args.seeds:
        with replaced(CONTROLS[system]):
            result, _ = run.run_cell(args.workload, seed, args.seconds, False)
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          "checks": result["checks"]}), flush=True)
        gc.collect()  # the last seed's pools go before the next seed's are made
    return 0


if __name__ == "__main__":
    sys.exit(main())
