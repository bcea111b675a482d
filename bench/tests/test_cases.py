"""Every system brings its checks' hooks in ``bench/cases/<system>.py``,
and a cell added as entries of BENCHMARK.json and a traffic file only is
taken up by them: its test size, its faults and its control."""
import copy

import pytest

from bench import cases, spec, traffic
from repro.kernels import ops

BM = spec.load_benchmark()


@pytest.mark.parametrize("cfg", BM["configs"], ids=lambda c: c["name"])
def test_every_system_has_its_hooks(cfg):
    hooks = cases.of(spec.config(BM, cfg["name"]))
    for name in ("small", "FAULTS", "CONTROL", "rehearse"):
        assert hasattr(hooks, name), (hooks.__file__, name)
    assert hooks.FAULTS and hooks.CONTROL
    for fault in hooks.FAULTS.values():
        assert fault.ops or callable(fault.patch), fault
        for fn in (fault.patch, fault.check):
            assert fn is None or callable(fn)
    for entries in [f.ops for f in hooks.FAULTS.values()] + [hooks.CONTROL]:
        for name, fn in entries.items():
            assert callable(getattr(ops, name, None)) and callable(fn), name


def test_a_missing_system_names_its_file():
    with pytest.raises(FileNotFoundError, match=r"bench/cases/no_such_system\.py"):
        cases.of({"system": "no_such_system"})


def test_a_cell_added_as_entries_only_is_taken_up(monkeypatch):
    """A second KV cell, on a copy of the long cell's mix with 16-256-token
    sessions, that exists only in a patched BENCHMARK.json and mix loader."""
    long_ = next(w for w in BM["workloads"]
                 if spec.config(BM, w["config"])["system"] == "kvswap")
    added = dict(long_, name=long_["name"].rsplit(".", 1)[0] + ".added", traffic="sessions-added")
    bm = copy.deepcopy(BM)
    bm["workloads"].append(added)
    for m in bm["end_to_end"]:
        if long_["name"] in m.get("workloads", []):
            m["workloads"].append(added["name"])
    mix = traffic.load(long_["traffic"])
    mix["sessions"].update(min_tokens=16, max_tokens=256)
    load = traffic.load
    monkeypatch.setattr(spec, "load_benchmark", lambda root=spec.ROOT: copy.deepcopy(bm))
    monkeypatch.setattr(traffic, "load", lambda name: copy.deepcopy(mix) if name == added["traffic"]
                        else load(name))
    assert not (traffic.TRAFFIC_DIR / f"{added['traffic']}.json").exists()

    hooks, cfg, small = cases.small_cell(added["name"])
    assert (hooks, cfg) == cases.small_cell(long_["name"])[:2]
    assert small["sessions"]["min_tokens"] == 16 and small["sessions"]["max_tokens"] == 160
    result, checks = cases.run_small(added["name"], 2**31 + 5)
    assert result["attempted"] > 0 and result["correct"], checks
    assert {"setup_s", "goodput_GBps.kv"} <= set(result["metrics"])
    # "altered" shows in every window; "unchanged" goes unseen in a window
    # whose rotations bring the page table back to where it started
    with cases.planted(hooks.FAULTS["altered"], monkeypatch):
        result, checks = cases.run_small(added["name"], 2**31 + 5)
    assert not result["correct"], (checks, result["attempted"])
    assert added["name"] not in (spec.ROOT / "BENCHMARK.json").read_text()
