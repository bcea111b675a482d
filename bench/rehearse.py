"""Compile every program the cells run for a described v5e chip, with no
chip attached, and print each one's ``memory_analysis()``.

    JAX_PLATFORMS=cpu python bench/rehearse.py

What the TPU compiler refuses here costs no chip time.  Each configuration
of BENCHMARK.json is handed, with the traffic mixes of its cells, to its
system's ``rehearse`` in ``bench/cases/<system>.py``, which names the
programs and takes their sizes from those files.
"""
from __future__ import annotations

import json
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
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import cases, spec, traffic

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
    for c in bm["configs"]:
        cfg = spec.config(bm, c["name"])
        mixes = [traffic.load(w["traffic"]) for w in bm["workloads"] if w["config"] == c["name"]]
        cases.of(cfg).rehearse(cfg, mixes, report, shape)
    return 0


if __name__ == "__main__":
    sys.exit(main())
