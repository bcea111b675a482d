"""Readers of the program's own host spans, and the naming of idle gaps by
what the runtime was doing, on hand-built runs and a synthetic profile."""
import types

import pytest

from bench import explain, record, spec
from repro.obs import DescTrace, HostSpan

MS = 1_000_000  # ns


def read(name, run):
    return spec.reader(name)(run)


def _tracer(spans, anchors=()):
    return types.SimpleNamespace(
        host_spans=lambda prefix="": [s for s in spans if s.name.startswith(prefix)],
        anchors=lambda: list(anchors))


def _trace(tracer, desc_id, op="batch", **marks):
    return types.SimpleNamespace(tracer=tracer, desc_id=desc_id, op=op, marks=marks)


def _run(spans, traces_of):
    tracer = _tracer(spans)
    return record.Run(spans=[("wait", 10.0, 20.0)], traces=traces_of(tracer))


def test_gc_pause_is_the_sum_of_the_windows_collections():
    spans = [HostSpan("gc.gen2", 11.0, 11.1), HostSpan("gc.gen0", 12.0, 12.001),
             HostSpan("gc.gen2", 5.0, 5.5),  # set-up, before the window
             HostSpan("pe.kernel:memcpy", 11.0, 11.2)]
    run = _run(spans, lambda tr: [_trace(tr, 1)])
    assert read("gc_pause_ms.steady", run) == pytest.approx(101.0)
    assert read("gc_pause_ms.kv", run) == read("gc_pause_ms.closed", run)
    assert read("gc_pause_ms.kv", _run([], lambda tr: [_trace(tr, 1)])) == 0.0


def test_pe_call_and_self_time():
    spans = [HostSpan("pe.kernel:memcpy", 11.0, 11.0004, desc_id=1),
             HostSpan("pe.kernel:memcpy", 11.0005, 11.0011, desc_id=1),
             HostSpan("pe.stack", 12.0, 12.0001, desc_id=2),
             HostSpan("pe.kernel:batch_copy", 12.0002, 12.0005, desc_id=2)]
    run = _run(spans, lambda tr: [_trace(tr, 1, exec0=10.9999, exec1=11.0012),
                                  _trace(tr, 2, exec0=12.0, exec1=12.0008),
                                  _trace(tr, 3)])  # not yet executed
    assert read("pe_call_us.steady", run) == pytest.approx((400 + 600 + 300) / 3)
    # slot 1: 1300 - 1000 us; slot 2: 800 - 300 us (pe.stack is glue)
    assert read("pe_self_us.closed", run) == pytest.approx((300 + 500) / 2)


def test_kvpool_plan_and_commit_are_means_per_swap():
    spans = [HostSpan("kvpool.plan", 11.0, 11.002), HostSpan("kvpool.commit", 11.1, 11.101),
             HostSpan("kvpool.plan", 12.0, 12.004), HostSpan("kvpool.commit", 12.1, 12.103)]
    run = _run(spans, lambda tr: [_trace(tr, 1)])
    assert read("kvpool_plan_us", run) == pytest.approx(3000.0)
    assert read("kvpool_commit_us", run) == pytest.approx(2000.0)


def test_readers_report_nothing_without_their_spans():
    empty = _run([], lambda tr: [_trace(tr, 1, exec0=11.0, exec1=11.1)])
    for name in ("pe_call_us.steady", "pe_self_us.steady", "kvpool_plan_us", "kvpool_commit_us"):
        assert read(name, empty) is None
    # a program whose traces carry no tracer with host spans (before them)
    older = record.Run(spans=[("wait", 10.0, 20.0)],
                       traces=[types.SimpleNamespace(marks={"exec0": 11.0, "exec1": 11.1})])
    for name in ("gc_pause_ms.steady", "pe_call_us.steady", "pe_self_us.steady",
                 "kvpool_plan_us", "kvpool_commit_us"):
        assert read(name, older) is None
        assert read(name, record.Run()) is None


# --------------------------------------------------------------------- gap names
def test_activity_at_follows_the_order_of_precedence():
    queued = DescTrace("q", 1, "memcpy")
    queued.marks.update(accept=0.0, dispatch=10.0)
    pending = DescTrace("p", 2, "memcpy")
    pending.marks.update(exec1=0.0, resolved=5.0, observed=10.0)
    spans = [HostSpan("pe.kernel:memcpy", 1.0, 3.0), HostSpan("gc.gen2", 2.0, 2.5),
             HostSpan("kvpool.plan", 0.5, 4.0), HostSpan("pe.stack", 1.5, 3.5),
             HostSpan("wait.umwait", 0.0, 10.0)]
    acts = explain.activities(spans, [queued, pending])
    assert explain.activity_at(2.2, acts) == "gc.gen2"            # outranks the PE span
    assert explain.activity_at(1.6, acts) == "pe.kernel:memcpy"   # shortest of its rank
    assert explain.activity_at(3.8, acts) == "kvpool.plan"
    assert explain.activity_at(6.0, acts) == "wq_wait"
    assert explain.activity_at(11.0, acts) == "-"
    acts = explain.activities([], [pending])
    assert explain.activity_at(2.0, acts) == "completion_write"
    assert explain.activity_at(7.0, acts) == "host_wait"


def _events(*evs):
    return [types.SimpleNamespace(name=n, start_ns=a, duration_ns=b - a) for n, a, b in evs]


def _profile():
    """One second of window; the device busy 0-100, 300-400 and 700-1000 ms,
    so idle 100-300 and 400-700 ms.  Anchors at perf 1 s and 2 s.  The
    device plane reads 1 ms behind the host plane: the batch_copy program
    starts at 319 ms, and its call at 320 ms."""
    line = types.SimpleNamespace
    host = line(name="/host:CPU", lines=[line(name="python", events=_events(
        ("bench.window", 0, 1000 * MS), ("bench.wait", 50 * MS, 900 * MS),
        ("dsa.clock", 0, 1000), ("dsa.clock", 1000 * MS, 1000 * MS + 1000)))])
    device = line(name="/device:TPU:0", lines=[
        line(name="XLA Modules", events=_events(("jit_batch_copy(1)", 319 * MS, 330 * MS))),
        line(name="XLA Ops", events=_events(("%a = f()", 0, 100 * MS),
                                             ("%b = g()", 300 * MS, 400 * MS),
                                             ("%c = h()", 700 * MS, 1000 * MS)))])
    return line(planes=[host, device])


def test_gaps_are_named_by_bench_span_and_runtime_activity():
    spans = [HostSpan("pe.kernel:memcpy", 1.151, 1.251), HostSpan("gc.gen2", 1.191, 1.211),
             HostSpan("pe.kernel:batch_copy", 1.32, 1.3203, desc_id=9)]
    tracer = _tracer(spans, anchors=(1_000_000_000, 2_000_000_000))
    copy = DescTrace("d9", 9, "batch_copy")
    copy.marks.update(exec0=1.3195, exec1=1.331, resolved=1.411)
    got = explain.explain(_profile(), tracer, [copy])
    assert got["clock"]["rate"] == pytest.approx(1.0)
    assert got["clock"]["device_lead_ms"] == pytest.approx(1.0)
    names = [n for n, _ in got["idle_gaps"]]
    # the longest gap first; a collection outranks the PE call it stops
    assert names == ["bench.wait/-", "bench.wait/gc.gen2"]
    assert [s for _, s in got["idle_gaps"]] == pytest.approx([0.3, 0.2])
    assert got["idle_s"] == pytest.approx(0.5)
    # host spans land on the device plane 1 ms earlier than on the host's
    cover = got["idle_cover"]
    assert cover["gc"] == pytest.approx(4.0)
    assert cover["pe"] == pytest.approx(20.0)
    assert cover["kvpool"] == cover["wq_wait"] == 0.0
    # the copy's completion_write (device plane 330-410 ms) ends 10 ms
    # into a gap
    assert cover["completion"] == pytest.approx(2.0)
    assert cover["-"] == pytest.approx(78.0)
    assert got["stalls"] == []
    # exec0 -> resolved holds the program's start only on the device's clock
    assert got["clock_check"] == {"descriptors": 1, "one_program_pct": 100.0,
                                  "one_program_pct_unshifted": 0.0,
                                  "device_lead_ms": pytest.approx([1.0] * 3)}


def test_clock_check_fits_the_lead_on_one_half_and_checks_the_other():
    """Eight swaps 100 ms apart, each program 1.3 ms ahead of its call on
    the device plane, and each descriptor's exec0 and resolved 0.5 ms
    either side of its call."""
    from repro.obs import ClockMap

    clock = ClockMap(1.0, 0.0)  # perf seconds * 1e9 = profile ns
    starts = [100 * MS * i - 1.3 * MS for i in range(1, 9)]
    calls, copies = [], []
    for i in range(1, 9):
        calls.append(HostSpan("pe.kernel:batch_copy", 0.1 * i, 0.1 * i + 1e-4, desc_id=i))
        copy = DescTrace(f"d{i}", i, "batch_copy")
        copy.marks.update(exec0=0.1 * i - 5e-4, resolved=0.1 * i + 5e-4)
        copies.append(copy)
    got = explain.clock_check(clock, starts, copies, calls)
    assert got["descriptors"] == 8
    assert got["one_program_pct_unshifted"] == 0.0
    assert got["one_program_pct"] == 100.0
    assert got["device_lead_ms"] == pytest.approx([1.3] * 3)
    # a lead that moved between the halves fails the held-out check
    drift = [s - (5 * MS if s > 450 * MS else 0) for s in starts]
    assert explain.clock_check(clock, drift, copies, calls)["one_program_pct"] == 0.0


# --------------------------------------------------------------------- whole runs
@pytest.mark.parametrize("cell", [w["name"] for w in spec.load_benchmark()["workloads"]])
def test_traced_runs_report_the_programs_spans_and_drop_none(cell):
    """A small traced run of each cell on the CPU, through bench/run.py's
    ``run_cell`` and through ``explain_cell``: every span metric the cell
    lists is read, and the tracer's rings dropped nothing."""
    from bench import run
    from bench.tests.test_faults import small

    cfg, mix = small(cell)
    bm = spec.load_benchmark()
    want = {m["name"] for m in spec.metrics(bm, cell, "per_layer")
            if m["source"] == "program_span"}
    result, _ = run.run_cell(cell, 2**31 + 11, 0.4, True, cfg=cfg, mix=mix, require_tpu=False)
    assert result["correct"]
    assert want <= set(result["metrics"])
    got = explain.explain_cell(cell, 2**31 + 11, 0.4, cfg=cfg, mix=mix)
    assert want <= set(got["metrics"])
    assert got["tracer"]["dropped"] == 0
    assert got["clock"]["anchors"] == 2
    assert got["clock"]["rate"] == pytest.approx(1.0, abs=1e-3)
    assert all(c["value"] <= c["limit"] for c in got["checks"].values())


@pytest.mark.parametrize("mode", ["tracer", "off"])
def test_explain_runs_without_the_profiler(mode):
    """The modes that split what tracing costs: the tracer alone reports
    the span metrics; untraced, the end-to-end metrics and the collections."""
    from bench.tests.test_faults import small

    cell = "vhost-64b.closed"
    cfg, mix = small(cell)
    bm = spec.load_benchmark()
    got = explain.explain_cell(cell, 2**31 + 12, 0.4, mode=mode, cfg=cfg, mix=mix)
    assert got["mode"] == mode and "idle_gaps" not in got
    assert {m["name"] for m in spec.metrics(bm, cell, "end_to_end")} <= set(got["metrics"])
    assert len(got["gc"]["collections"]) == 3 and got["gc"]["pause_ms"] >= 0.0
    assert all(c["value"] <= c["limit"] for c in got["checks"].values())
    if mode == "tracer":
        assert {"pe_call_us.closed", "pe_self_us.closed"} <= set(got["metrics"])
        assert got["tracer"]["dropped"] == 0
        assert "pe.zeros" in got["host_spans"]
    else:
        assert "tracer" not in got and "pe_call_us.closed" not in got["metrics"]
