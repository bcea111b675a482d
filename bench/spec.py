"""Find everything a cell needs by the names in ``BENCHMARK.json``."""
from __future__ import annotations

import importlib
import importlib.util
import json
import re
from pathlib import Path
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load_benchmark(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bm: Dict, workload: str) -> Dict:
    for w in bm["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bm['workloads']]}")


def config(bm: Dict, name: str, root: Path = ROOT) -> Dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def system(cfg: Dict):
    """The adapter module named by the configuration's ``system``."""
    return importlib.import_module(f"bench.systems.{cfg['system']}")


def metrics(bm: Dict, workload: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports.  A metric
    without a ``workloads`` list is reported wherever the end-to-end metric
    it moves is (every cell, for an end-to-end metric)."""
    e2e = [m for m in bm["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if kind == "end_to_end":
        return e2e
    have = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if (workload in m["workloads"] if "workloads" in m else m["moves"] in have)]


def reader(name: str) -> Callable:
    """``read(run)`` of ``bench/metrics/<name>.py``.  A quantity split by
    the end-to-end metric it moves (``<quantity>.<suffix>``) falls back to
    the one reader ``bench/metrics/<quantity>.py``."""
    for stem in (name, name.rsplit(".", 1)[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.is_file():
            break
    else:
        raise FileNotFoundError(f"no reader for metric {name!r}: {path} is missing")
    mod_spec = importlib.util.spec_from_file_location(f"bench.metrics.{stem}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
