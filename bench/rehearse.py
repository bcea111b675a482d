"""Compile every program the cells run for a described v5e chip, with no
chip attached, and print each one's ``memory_analysis()``.

    JAX_PLATFORMS=cpu python bench/rehearse.py

What the TPU compiler refuses here costs no chip time.  The KV pools are
sized from what this prints: the swap program's arguments plus its
temporaries have to fit the chip's HBM beside what else the process holds.
"""
from __future__ import annotations

import json
import math
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

GIB = 1 << 30


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import spec, traffic
    from bench.systems import kvswap
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=chip)

    def report(name, fn, *args, **static):
        m = fn.lower(*args, **static).compile().memory_analysis()
        print(json.dumps({"program": name, "argument_gib": m.argument_size_in_bytes / GIB,
                          "output_gib": m.output_size_in_bytes / GIB,
                          "alias_gib": m.alias_size_in_bytes / GIB,
                          "temp_gib": m.temp_size_in_bytes / GIB}), flush=True)

    bm = spec.load_benchmark()
    for size in traffic.load("imix-steady")["sizes"]["values"]:
        report(f"memcpy u8[{size}]", ops.memcpy, shape((size,), jnp.uint8), interpret=False)
    burst = traffic.load("64b-closed")["burst"]
    idx = shape((burst,), jnp.int32)
    report(f"batch_copy u8[{burst},64] (fused 64 B burst)", ops.batch_copy,
           shape((burst, 64), jnp.uint8), shape((burst, 64), jnp.uint8), idx, idx, interpret=False)
    cfg = spec.config(bm, "kv-offload-dsmoe16b")
    page = (cfg["block_size"], 2 * cfg["num_key_value_heads"] * cfg["head_dim"])
    page_bytes = math.prod(page) * 2
    dev = int(cfg["device_pool_gib"] * GIB) // page_bytes
    host = int(cfg["swap_space_gib"] * GIB) // page_bytes
    mix = traffic.load("sessions-long")
    counts = sorted({cfg["num_hidden_layers"] * -(-int(t) // cfg["block_size"])
                     for t in traffic.session_tokens(mix, 0)})
    for n in (counts[0], counts[-1]):
        for name, (p, q) in (("swap_out", (dev, host)), ("swap_in", (host, dev))):
            report(f"batch_copy {name} bf16[{p}|{q},{page[0]},{page[1]}] x {n} pages",
                   ops.batch_copy, shape((p,) + page, jnp.bfloat16),
                   shape((q,) + page, jnp.bfloat16), shape((n,), jnp.int32),
                   shape((n,), jnp.int32), interpret=False)
    fill = jax.jit(kvswap._fill, static_argnums=(3,))
    report(f"kv pool fill bf16[{dev},{page[0]},{page[1]}]", fill, shape((), jnp.uint32),
           shape((dev,), jnp.int32), shape((dev,), jnp.int32), page)
    report("kv check block", jax.jit(kvswap._mismatched_pages),
           shape((dev,) + page, jnp.bfloat16), *[shape((kvswap.CHECK_BLOCK,), jnp.int32)] * 3,
           shape((kvswap.CHECK_BLOCK,), jnp.bool_), shape((), jnp.uint32))
    return 0


if __name__ == "__main__":
    sys.exit(main())
