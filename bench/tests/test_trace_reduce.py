"""The trace reduction on a trace recorded on one v5e: one second of the
KV swap cell (8 swaps), whose numbers were read by hand from the same
file with plain loops over its events."""
from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).resolve().parent / "data" / "kvswap_1s.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(str(DATA))


def test_window_busy_and_idle(reduced):
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx(1.252275716, abs=1e-9)
    assert reduced["busy_s"] == pytest.approx(1.204335718, abs=1e-9)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert idle == pytest.approx(0.047939998, abs=1e-9)


def test_device_time_per_program(reduced):
    assert set(reduced["programs"]) == {"batch_copy", "convert_element_type"}
    assert reduced["programs"]["batch_copy"] == pytest.approx(1.204326584, abs=1e-9)
    assert reduced["programs"]["convert_element_type"] == pytest.approx(9.526e-06, abs=1e-12)


def test_breakdown(reduced):
    ops = reduced["device_ops"]
    assert len(ops) == trace_reduce.TOP
    assert ops[0] == ["batch_copy/reshape.21", pytest.approx(0.105255075, abs=1e-9)]
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    gaps = reduced["idle_gaps"]
    assert len(gaps) == trace_reduce.TOP
    assert gaps[0] == ["bench.swap_in", pytest.approx(0.006285472, abs=1e-9)]
    assert {name for name, _ in gaps} <= {"bench.swap_in", "bench.swap_out"}
    assert sum(s for _, s in gaps) <= reduced["window_s"] - reduced["busy_s"] + 1e-9


def test_helpers():
    assert trace_reduce.program_name("jit_batch_copy(8192054374)") == "batch_copy"
    assert trace_reduce.op_name("%copy.7 = u32[4]{0} copy(u32[4]{0} %x)") == "copy.7"
    assert trace_reduce.union([(3, 4), (0, 2), (1, 3), (6, 7)]) == [(0, 4), (6, 7)]
