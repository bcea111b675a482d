"""Metric readers: percentiles over all samples, rates over the whole
window, lifecycle gaps, idle share and roofline share."""
import types

import numpy as np
import pytest

from bench import kernel_bytes, record, spec


def read(name, run):
    return spec.reader(name)(run)


def test_percentiles_take_every_sample():
    lat = list(np.linspace(0.001, 0.100, 1000))
    run = record.Run(latencies_s=lat)
    srt = np.sort(lat)
    # linear interpolation between order statistics, over all 1000 samples
    assert read("latency_p99_ms", run) == pytest.approx(1e3 * (srt[989] + 0.01 * (srt[990] - srt[989])))
    assert read("latency_p50_ms", run) == pytest.approx(1e3 * (srt[499] + srt[500]) / 2)
    assert read("latency_p99_ms", record.Run()) is None


def test_goodput_is_all_bytes_over_the_whole_window():
    run = record.Run(bytes_done=3_000_000_000, window_s=2.0)
    assert read("goodput_GBps", run) == pytest.approx(1.5)
    assert read("goodput_GBps", record.Run()) is None


def test_a_split_quantity_falls_back_to_its_one_reader():
    run = record.Run(bytes_done=3_000_000_000, window_s=2.0)
    assert read("goodput_GBps.kv", run) == read("goodput_GBps", run)
    assert spec.reader("pe_us.kv").__module__ == spec.reader("pe_us").__module__
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_quantity.kv")


def test_setup_and_generator_lateness():
    assert read("setup_s", record.Run(setup_s=12.5)) == 12.5
    run = record.Run(gen_late_s=[0.0] * 99 + [0.5])
    assert read("gen_late_p99_ms", run) == pytest.approx(1e3 * 0.01 * 0.5)


def _trace(**marks):
    return types.SimpleNamespace(marks=marks)


def test_lifecycle_gaps_are_means_over_traces_that_have_both_marks():
    run = record.Run(traces=[
        _trace(validate0=1.0, accept=1.000010, dispatch=1.000030, exec1=1.000130),
        _trace(validate0=2.0, accept=2.000030, dispatch=2.000040, exec1=2.000090),
        _trace(validate0=3.0),  # shed before acceptance: no submit gap
    ])
    assert read("submit_us.steady", run) == pytest.approx(20.0)
    assert read("submit_us.closed", run) == pytest.approx(20.0)
    assert read("submit_us.kv", run) == pytest.approx(20.0)
    assert read("wq_wait_us.steady", run) == pytest.approx(15.0)
    assert read("pe_us.steady", run) == pytest.approx(75.0)
    assert read("pe_us.closed", record.Run()) is None


def test_kvpool_self_time_subtracts_its_descriptor():
    run = record.Run(
        spans=[("swap_out", 10.0, 10.2), ("swap_in", 10.2, 10.5), ("submit", 0.0, 1.0)],
        traces=[_trace(validate0=10.05, observed=10.15), _trace(validate0=10.25, observed=10.45)])
    assert read("kvpool_self_us", run) == pytest.approx(1e6 * (0.1 + 0.1) / 2)


def test_idle_share_and_roofline():
    page_bytes = kernel_bytes.batch_copy(1000, (16, 4096), 2)
    assert page_bytes == 2 * 1000 * 128 * 1024
    run = record.Run(device={"busy_s": 0.25, "window_s": 1.0, "programs": {"batch_copy": 0.5}},
                     kernel_bytes={"batch_copy": page_bytes},
                     peaks={"hbm_bytes_per_s": 819e9})
    assert read("device_idle_pct.steady", run) == pytest.approx(75.0)
    assert read("device_idle_pct.closed", run) == pytest.approx(75.0)
    assert read("batch_copy_roofline", run) == pytest.approx(100 * page_bytes / 819e9 / 0.5)
    # no device trace, no program in it, or no bytes: nothing to read
    assert read("device_idle_pct.closed", record.Run()) is None
    assert read("batch_copy_roofline", record.Run(device={"programs": {}})) is None
