"""BENCHMARK.json keeps to its contract, and the harness finds every
configuration, traffic mix, system adapter and metric reader by name."""
import json
import re

import pytest

from bench import spec, traffic

BM = spec.load_benchmark()
CELLS = [w["name"] for w in BM["workloads"]]
METRICS = BM["end_to_end"] + BM["per_layer"]


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert BM["command"] == ["python3", "bench/run.py"]
    assert 1 <= BM["run_seconds"] <= 51


def test_names_and_units_use_allowed_characters():
    names = ([c["name"] for c in BM["configs"]] + CELLS + [m["name"] for m in METRICS]
             + [w["traffic"] for w in BM["workloads"]] + [w["config"] for w in BM["workloads"]]
             + [k for c in BM["configs"] for k in c["reduced"]])
    for name in names:
        assert spec.NAME.match(name), name
    for m in METRICS:
        assert spec.UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in BM[group]]
        assert len(seen) == len(set(seen)), group
    for text in [c["why"] for c in BM["configs"] + BM["workloads"]] + [m["layer"] for m in BM["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("cfg", BM["configs"], ids=lambda c: c["name"])
def test_config_file_and_system_are_found(cfg):
    loaded = spec.config(BM, cfg["name"])
    assert loaded["name"] == cfg["name"]
    assert cfg["file"].startswith("bench/configs/")
    for key in cfg["reduced"]:
        assert key in loaded and key in loaded["reduced"], key
    assert hasattr(spec.system(loaded), "System")


@pytest.mark.parametrize("cell", BM["workloads"], ids=lambda w: w["name"])
def test_cell_finds_traffic_and_reports_enough(cell):
    mix = traffic.load(cell["traffic"])
    assert mix["loop"] in ("open", "closed")
    assert cell["chips"] in (1, 4)
    e2e = [m["name"] for m in spec.metrics(BM, cell["name"], "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.metrics(BM, cell["name"], "per_layer")


def test_at_most_half_the_cells_take_four_chips():
    four = [w["name"] for w in BM["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2), four


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    assert callable(spec.reader(metric["name"]))
    for w in metric.get("workloads", []):
        assert w in CELLS
    if "moves" in metric:
        assert metric["moves"] in [m["name"] for m in BM["end_to_end"]]
        for w in metric.get("workloads", CELLS):
            assert metric["moves"] in [m["name"] for m in spec.metrics(BM, w, "end_to_end")], w


def test_bounds_and_sources():
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BM["per_layer"]:
        assert "bound" not in m
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    roofline = [m for m in BM["per_layer"] if m["name"].endswith("_roofline")]
    assert all(m["unit"] == "%" for m in roofline)


def test_unknown_names_fail_loudly():
    with pytest.raises(KeyError):
        spec.cell(BM, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        traffic.load("no-such-mix")
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric")


def test_benchmark_file_is_small_and_plain():
    raw = (spec.ROOT / "BENCHMARK.json").read_bytes()
    assert len(raw) <= 64 * 1024
    assert json.loads(raw) == BM
    assert not re.search(r"[^\x00-\x7f]", raw.decode())
