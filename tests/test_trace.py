"""Descriptor-lifecycle tracing: span model, sampling, dependency edges,
critical path, host-free reconciliation, the runtime's host spans and gc
hook, the clock shared with a device profile, and the Perfetto export."""
import gc
import json

import jax.numpy as jnp
import pytest

from repro.core import OpType, QueueFull, WorkDescriptor, make_device
from repro.core.descriptor import BatchDescriptor
from repro.obs import (
    HOST_PHASES,
    PHASES,
    ClockMap,
    DescTrace,
    TraceConfig,
    Tracer,
    TraceRateError,
    critical_path,
    device_lead_ns,
    host_free_fraction,
    make_tracer,
    phase_breakdown,
    slowest,
    to_perfetto,
)


@pytest.fixture
def buf():
    return jnp.zeros((8, 128), jnp.float32)  # 4KB


def _traced_device(**kw):
    kw.setdefault("trace", 1.0)
    return make_device(n_instances=1, **kw)


# --------------------------------------------------------------------- config
def test_trace_rate_error_is_typed_and_coded():
    for bad in (1.5, -0.1, 2, -3.0):
        with pytest.raises(TraceRateError) as ei:
            TraceConfig(rate=bad)
        assert ei.value.code == "DSA105"
        assert ei.value.rate == bad
        assert isinstance(ei.value, ValueError)


def test_make_device_rejects_bad_rate():
    with pytest.raises(TraceRateError):
        make_device(trace=1.5)  # dsalint: disable=DSA105
    with pytest.raises(TraceRateError):
        make_device(trace=-0.5)  # dsalint: disable=DSA105


def test_make_tracer_spec_resolution():
    assert make_tracer(None) is None
    assert make_tracer(False) is None
    assert make_tracer(True).config.rate == 1.0
    assert make_tracer(0.25).config.rate == 0.25
    cfg = TraceConfig(rate=0.5, capacity=16)
    assert make_tracer(cfg).config is cfg
    t = Tracer()
    assert make_tracer(t) is t
    with pytest.raises(TypeError):
        make_tracer("yes")


def test_untraced_device_has_no_tracer(buf):
    device = make_device(n_instances=1)
    assert device.tracer is None
    fut = device.memcpy_async(buf)
    fut.wait()
    assert fut.trace is None
    device.drain()


# --------------------------------------------------------------------- lifecycle
def test_every_phase_present_on_traced_submit(buf):
    device = _traced_device()
    fut = device.memcpy_async(buf)
    fut.wait()
    device.drain()
    dt = fut.trace
    assert dt is not None
    durs = dt.phase_durations()
    assert set(durs) == set(PHASES)
    assert all(d >= 0.0 for d in durs.values())
    # marks are monotonic after cleaning
    marks = dt.clean_marks()
    ts = list(marks.values())
    assert ts == sorted(ts)


def test_batch_trace_starts_at_first_member_allocation(buf):
    device = _traced_device()
    descs = [WorkDescriptor(op=OpType.MEMCPY, src=buf) for _ in range(4)]
    batch = BatchDescriptor(descriptors=descs)
    fut = device.submit(batch)
    fut.wait()
    device.drain()
    dt = fut.trace
    assert dt.attrs["batch"] == 4
    assert dt.marks["create"] == min(d.created_t for d in descs)


def test_then_continuation_gets_child_trace_and_edge(buf):
    device = _traced_device()
    fut = device.memcpy_async(buf)
    chained = fut.then(lambda r: r)
    chained.wait()
    device.drain()
    child = chained.record.trace
    assert child is not None
    assert child.attrs["kind"] == "then"
    assert child.trace_id == fut.trace.trace_id  # same logical request
    assert child.desc_id != fut.trace.desc_id
    kinds = {(p, c): k for p, c, k in device.tracer.edges()}
    assert kinds[(fut.trace.desc_id, child.desc_id)] == "then"
    # then-traces reuse host_wait + callback only
    assert set(child.phase_durations()) == {"host_wait", "callback"}


def test_after_dependency_records_edge(buf):
    device = _traced_device()
    a = device.memcpy_async(buf)
    b = device.memcpy_async(buf, after=[a])
    device.wait_all([a, b])
    device.drain()
    assert (a.trace.desc_id, b.trace.desc_id, "after") in device.tracer.edges()


def test_spans_track_assignment(buf):
    device = _traced_device()
    fut = device.memcpy_async(buf)
    fut.wait()
    device.drain()
    for sp in fut.trace.spans():
        assert sp.track == ("host" if sp.phase in HOST_PHASES else "engine")
        assert sp.dur >= 0.0


# --------------------------------------------------------------------- sampling
def test_fractional_sampling_is_deterministic(buf):
    device = _traced_device(trace=0.25)
    futs = [device.memcpy_async(buf) for _ in range(32)]
    device.wait_all(futs)
    device.drain()
    sampled = [f for f in futs if f.trace is not None]
    assert len(sampled) == 8  # exactly floor/ceil(32 * 0.25), no RNG
    c = device.tracer.counters_snapshot()
    assert c["sampled"] >= 8
    assert c["skipped"] == 24


def test_rate_zero_samples_nothing(buf):
    device = _traced_device(trace=0.0)
    fut = device.memcpy_async(buf)
    fut.wait()
    device.drain()
    assert fut.trace is None
    assert device.tracer.traces() == []


def test_request_context_shares_trace_id_and_verdict(buf):
    device = _traced_device()
    tracer = device.tracer
    with tracer.request("req42"):
        assert tracer.current_trace_id() == "req42"
        a = device.memcpy_async(buf)
        with tracer.request("inner"):
            assert tracer.current_trace_id() == "inner"
        assert tracer.current_trace_id() == "req42"  # re-entrant restore
        b = device.memcpy_async(buf)
    assert tracer.current_trace_id() is None
    device.wait_all([a, b])
    device.drain()
    assert a.trace.trace_id == b.trace.trace_id == "req42"


def test_request_sampling_verdict_is_stable_per_id():
    tracer = Tracer(TraceConfig(rate=0.5))
    verdicts = {rid: tracer._sample_id(rid) for rid in map(str, range(200))}
    assert any(verdicts.values()) and not all(verdicts.values())
    for rid, v in verdicts.items():
        assert tracer._sample_id(rid) == v  # same id -> same answer


def test_ring_capacity_bounds_retention(buf):
    device = _traced_device(trace=TraceConfig(rate=1.0, capacity=8))
    futs = [device.memcpy_async(buf) for _ in range(20)]
    device.wait_all(futs)
    device.drain()
    tracer = device.tracer
    assert len(tracer.traces()) == 8
    # monotonic fold counters survive ring rotation: all 20 folded
    assert tracer.counters_snapshot()["phase.pe_exec_n"] == 20


def test_marks_are_write_once():
    dt = DescTrace("t", 1, "memcpy")
    t0 = dt.mark("create", 10.0)
    assert dt.mark("create", 99.0) == t0
    assert dt.marks["create"] == 10.0


# --------------------------------------------------------------------- analyzers
def _mk(tracer, desc_id, t0, t1, trace_id=None):
    dt = DescTrace(trace_id or f"d{desc_id}", desc_id, "memcpy", tracer=tracer)
    dt.marks["create"] = t0
    dt.marks["submit_enter"] = t1  # gives the trace one derived span
    dt.marks["observed"] = t1
    tracer._ring.append(dt)
    return dt


def test_critical_path_follows_edges_and_clips_overlap():
    tracer = Tracer()
    _mk(tracer, 1, 0.0, 1.0)
    _mk(tracer, 2, 0.5, 3.0)   # overlaps parent by 0.5s
    _mk(tracer, 3, 0.0, 1.5)   # longer standalone than either alone
    tracer.edge(1, 2, "after")
    cp = critical_path(tracer)
    assert cp["chain"] == [1, 2]
    # 1.0 (node 1) + (3.0 - max(0.5, 1.0)) = 3.0, not 1.0 + 2.5
    assert cp["total_s"] == pytest.approx(3.0)
    assert cp["total_s"] <= cp["elapsed_s"] + 1e-9
    assert cp["elapsed_s"] == pytest.approx(3.0)


def test_critical_path_empty_tracer():
    cp = critical_path(Tracer())
    assert cp == {"chain": [], "total_s": 0.0, "elapsed_s": 0.0,
                  "phases": {}, "shares": {}}


def test_phase_breakdown_shares_sum_to_one(buf):
    device = _traced_device()
    futs = [device.memcpy_async(buf) for _ in range(4)]
    device.wait_all(futs)
    device.drain()
    br = phase_breakdown(device.tracer)
    assert set(br) == set(PHASES)
    assert sum(s["share"] for s in br.values()) == pytest.approx(1.0)
    for s in br.values():
        assert s["count"] == 4
        assert s["p95_s"] >= 0.0


def test_slowest_orders_by_extent():
    tracer = Tracer()
    _mk(tracer, 1, 0.0, 1.0)
    _mk(tracer, 2, 0.0, 5.0)
    _mk(tracer, 3, 0.0, 2.0)
    assert [t.desc_id for t in slowest(tracer, k=2)] == [2, 3]


# --------------------------------------------------------------------- host-free
def test_host_free_fraction_matches_waitstats_exactly(buf):
    """ISSUE acceptance: span-derived host-free within 5% of WaitStats —
    by construction they are the SAME numbers, so demand equality."""
    device = _traced_device()
    futs = [device.memcpy_async(buf) for _ in range(8)]
    device.wait_all(futs)
    device.drain()
    spans_frac = host_free_fraction(device.tracer)
    busy = sum(s.busy_s for s in device.wait_stats.values())
    free = sum(s.free_s for s in device.wait_stats.values())
    assert busy + free > 0
    ws_frac = free / (busy + free)
    assert spans_frac == pytest.approx(ws_frac, rel=1e-9)
    assert abs(spans_frac - ws_frac) <= 0.05 * max(ws_frac, 1e-12)
    # the merged span record carries the same split, wait by wait
    waits = device.tracer.host_spans("wait.")
    assert sum(w.attrs["busy_s"] for w in waits) == pytest.approx(busy, rel=1e-9)
    assert sum(w.attrs["free_s"] for w in waits) == pytest.approx(free, rel=1e-9)


def test_wait_spans_recorded_per_wait(buf):
    device = _traced_device()
    fut = device.memcpy_async(buf)
    fut.wait()
    device.drain()
    waits = device.tracer.host_spans("wait.")
    assert waits
    for w in waits:
        assert w.name == "wait.umwait"
        assert w.t1 >= w.t0 and w.thread
        assert w.attrs["busy_s"] >= 0.0 and w.attrs["free_s"] >= 0.0


# --------------------------------------------------------------------- perfetto
def test_perfetto_valid_json_and_monotonic(buf, tmp_path):
    device = _traced_device()
    a = device.memcpy_async(buf)
    b = device.memcpy_async(buf, after=[a])
    c = b.then(lambda r: r)
    device.wait_all([a, b, c])
    device.drain()
    out = tmp_path / "trace.json"
    text = to_perfetto(device.tracer, str(out))
    assert out.read_text() == text
    doc = json.loads(text)  # strict JSON
    events = doc["traceEvents"]
    assert events
    for ev in events:
        if "ts" in ev:
            assert ev["ts"] >= 0
        if ev.get("ph") == "X":
            assert ev["dur"] >= 0
    slices = [ev for ev in events if ev.get("ph") == "X"]
    names = {ev["name"] for ev in slices}
    assert set(PHASES) <= names
    assert any(ev["name"].startswith("wait.") for ev in slices)
    # flow arrows for both edge kinds, start before finish
    flows = {}
    for ev in events:
        if ev.get("ph") in ("s", "f"):
            flows.setdefault(ev["id"], {})[ev["ph"]] = ev
    assert flows
    for pair in flows.values():
        assert set(pair) == {"s", "f"}
        assert pair["f"]["ts"] >= pair["s"]["ts"]
    assert {ev["name"] for ev in events if ev.get("ph") == "s"} == {
        "after", "then"}
    # one metadata process per track, host first
    meta = [ev for ev in events if ev.get("ph") == "M"
            and ev["name"] == "process_name"]
    assert {m["args"]["name"] for m in meta} >= {"dsa-repro/host"}


def test_perfetto_empty_tracer_is_valid():
    doc = json.loads(to_perfetto(Tracer()))
    names = {ev["name"] for ev in doc["traceEvents"]}
    assert names == {"process_name"}  # just the host track metadata


def test_perfetto_nonfinite_attrs_sanitized(tmp_path):
    tracer = Tracer()
    dt = _mk(tracer, 1, 0.0, 1.0)
    dt.attrs["weird"] = float("nan")
    dt.attrs["obj"] = object()
    text = to_perfetto(tracer)
    doc = json.loads(text)  # would raise on bare NaN tokens
    sl = next(ev for ev in doc["traceEvents"] if ev.get("ph") == "X")
    assert sl["args"]["weird"] is None
    assert isinstance(sl["args"]["obj"], str)


# --------------------------------------------------------------------- errors
def test_queuefull_trace_is_terminated_not_leaked(buf):
    device = _traced_device(wq_size=1, max_retries=0)
    futs = []
    saw_full = False
    try:
        for _ in range(64):
            futs.append(device.memcpy_async(buf))  # dsalint: disable=DSA106 — per-descriptor path under test
    except QueueFull:
        saw_full = True
    if futs:
        device.wait_all(futs)
    device.drain()
    if saw_full:
        errored = [dt for dt in device.tracer.traces()
                   if dt.attrs.get("error") == "QueueFull"]
        assert errored
        for dt in errored:
            assert "resolved" in dt.marks  # terminated, not dangling


# --------------------------------------------------------------------- host spans
def test_unfused_batch_records_a_kernel_span_per_member(buf):
    device = _traced_device()
    descs = [WorkDescriptor(op=OpType.MEMCPY, src=jnp.zeros((n, 128), jnp.float32))
             for n in (8, 16, 8)]  # mixed shapes: the unfused path
    fut = device.submit(BatchDescriptor(descriptors=descs))
    fut.wait()
    device.drain()
    dt = fut.trace
    kernels = device.tracer.host_spans("pe.kernel:")
    assert [sp.name for sp in kernels] == ["pe.kernel:memcpy"] * 3
    for sp in kernels:
        assert (sp.desc_id, sp.trace_id) == (dt.desc_id, dt.trace_id)
        assert dt.marks["exec0"] <= sp.t0 <= sp.t1 <= dt.marks["exec1"]
        assert sp.thread.startswith("pe")
    assert not device.tracer.host_spans("pe.stack")


def test_fused_batch_records_its_glue_and_one_kernel_span(buf):
    device = _traced_device()
    descs = [WorkDescriptor(op=OpType.MEMCPY, src=buf) for _ in range(4)]
    fut = device.submit(BatchDescriptor(descriptors=descs))
    fut.wait()
    device.drain()
    pe = [sp for sp in device.tracer.host_spans("pe.")]
    assert [sp.name for sp in pe] == ["pe.stack", "pe.zeros",
                                      "pe.kernel:batch_copy", "pe.unstack"]
    assert all(a.t1 <= b.t0 for a, b in zip(pe, pe[1:]))
    assert {sp.desc_id for sp in pe} == {fut.trace.desc_id}


def test_kv_pool_swaps_record_plan_and_commit():
    from repro.serving.kv_pool import PagedKVPool

    device = _traced_device()
    kv = PagedKVPool(4, 4, 8, 128, device=device)
    assert kv.alloc(0, 3)
    assert kv.swap_out(0) and kv.swap_in(0)
    tracer = device.tracer
    plans, commits = tracer.host_spans("kvpool.plan"), tracer.host_spans("kvpool.commit")
    assert len(plans) == len(commits) == 2
    copies = [dt for dt in tracer.traces() if dt.op == "batch_copy"]
    assert len(copies) == 2
    # plan before its descriptor is created, commit after it is observed
    for plan, dt, commit in zip(plans, copies, commits):
        assert plan.t1 <= dt.marks["submit_enter"]
        assert dt.marks["observed"] <= commit.t0


def test_untraced_device_leaves_gc_callbacks_alone(buf):
    gc.collect()  # earlier tests' dropped devices take their hooks along
    before = list(gc.callbacks)
    device = make_device(n_instances=1)
    fut = device.submit(BatchDescriptor(descriptors=[
        WorkDescriptor(op=OpType.MEMCPY, src=buf) for _ in range(2)]))
    fut.wait()
    gc.collect()
    device.close()
    assert device.tracer is None and fut.trace is None
    assert gc.callbacks == before


def test_gc_hook_records_collections_until_close(buf):
    gc.collect()  # earlier tests' dropped devices take their hooks along
    before = list(gc.callbacks)
    device = _traced_device()
    assert len(gc.callbacks) == len(before) + 1
    gc.collect()
    tracer = device.tracer
    spans = tracer.host_spans("gc.")
    assert any(sp.name == "gc.gen2" and sp.attrs["generation"] == 2 for sp in spans)
    c = tracer.counters_snapshot()
    assert c["gc.collections.gen2"] >= 1
    assert c["gc.pause_s"] == pytest.approx(sum(sp.t1 - sp.t0 for sp in spans))
    device.close()
    device.close()  # idempotent
    assert gc.callbacks == before
    n = len(tracer.host_spans("gc."))
    gc.collect()
    assert len(tracer.host_spans("gc.")) == n


def test_a_dropped_traced_device_takes_its_gc_hook_along():
    gc.collect()  # earlier tests' dropped devices take their hooks along
    before = list(gc.callbacks)
    device = _traced_device()
    del device
    gc.collect()
    assert gc.callbacks == before


def test_rings_count_what_they_drop(buf):
    device = _traced_device()
    futs = [device.memcpy_async(buf) for _ in range(20)]
    device.wait_all(futs)
    device.drain()
    assert device.tracer.counters_snapshot()["dropped"] == 0
    small = _traced_device(trace=TraceConfig(rate=1.0, capacity=8))
    futs = [small.memcpy_async(buf) for _ in range(20)]
    small.wait_all(futs)
    small.drain()
    # 20 traces in a ring of 8, and 8 x 8 host spans hold every kernel
    # call and wait of this run
    assert small.tracer.counters_snapshot()["dropped"] >= 12


# --------------------------------------------------------------------- clock
def test_clock_map_fit():
    cm = ClockMap.fit([1_000, 3_000], [10, 2_010])
    assert (cm.rate, cm.offset_ns) == (1.0, -990.0)
    assert cm.to_profile(2e-6) == pytest.approx(1_010)
    assert cm.to_perf(1_010) == pytest.approx(2e-6)
    # more anchors: the first and the last fix the line
    three = ClockMap.fit([0, 1e9, 2e9], [5, 1e9 + 900, 4e9 + 5])
    assert (three.rate, three.offset_ns) == (2.0, 5.0)
    for perf, prof in (([1], [1]), ([1, 2], [1]), ([4, 4], [1, 2])):
        with pytest.raises(ValueError):
            ClockMap.fit(perf, prof)


def test_anchors_map_marks_onto_the_profile_clock(tmp_path):
    """Marks taken inside known annotations on two threads land within
    50 us of the annotations' starts in the profile."""
    import glob
    import threading
    import time

    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    tracer = Tracer()
    marks = {}

    def work(k):
        for i in range(4):
            with TraceAnnotation(f"probe{k}.{i}"):
                marks[f"probe{k}.{i}"] = time.perf_counter()
            time.sleep(0.005)

    jax.profiler.start_trace(str(tmp_path))
    try:
        tracer.anchor()
        threads = [threading.Thread(target=work, args=(k,)) for k in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        time.sleep(0.05)
        tracer.anchor()
    finally:
        jax.profiler.stop_trace()
    assert not any(t.is_alive() for t in threads)
    profile = ProfileData.from_file(glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")[0])
    starts = {e.name: e.start_ns for plane in profile.planes for line in plane.lines
              for e in line.events if e.name.startswith("probe")}
    assert set(starts) == set(marks)
    clock = ClockMap.between(tracer, profile)
    for name, t in marks.items():
        assert abs(clock.to_profile(t) - starts[name]) < 50_000, name
    # the Perfetto export on that clock: microseconds of the profile
    tracer.record("probe.span", marks["probe0.0"], marks["probe0.1"])
    doc = json.loads(to_perfetto(tracer, clock=clock))
    ev = next(e for e in doc["traceEvents"] if e["name"] == "probe.span")
    assert abs(ev["ts"] - starts["probe0.0"] / 1e3) < 50


def test_device_lead_puts_no_program_before_its_call():
    # the device plane reads 1.4 ms behind; programs start 0 to 0.3 ms
    # after their calls
    delays = [0.0, 0.1e6, 0.3e6, 0.2e6, 0.05e6]
    calls = [100e6 * i for i in range(1, 6)]
    starts = [c - 1.4e6 + d for c, d in zip(calls, delays)]
    assert device_lead_ns(calls, starts) == pytest.approx(1.4e6)
    assert device_lead_ns([], starts) is None
    assert device_lead_ns(calls, []) is None
    cm = ClockMap.fit([0, 1_000], [0, 1_000])
    assert cm.shifted(-1.3e6).to_profile(1e-3) == pytest.approx(1e6 - 1.3e6)


# --------------------------------------------------------------------- memory
def test_host_spans_leave_the_collectors_tracking():
    """A retained span holds only numbers and strings, so the first
    collection it survives stops tracking it: a full ring adds nothing to
    later collections' work."""
    tracer = Tracer()
    tracer.record("pe.kernel:memcpy", 1.0, 2.0, desc_id=3, trace_id="d3")
    tracer.wait_span("umwait", 1.0, 2.0, busy_s=0.5, free_s=0.5, completions=1)
    gc.collect()
    assert len(tracer._spans) == 2
    assert not any(gc.is_tracked(sp) for sp in tracer._spans)
    wait = tracer.host_spans("wait.")[0]
    assert wait.attrs == {"busy_s": 0.5, "free_s": 0.5, "completions": 1}


def test_concurrent_spans_are_all_kept_or_counted():
    """Threads recording into one tracer while its ring rotates: every span
    is either retained or counted in ``dropped``, none lost."""
    import sys
    import threading

    tracer = Tracer(TraceConfig(capacity=16))  # host-span ring of 128
    n_threads, per = 8, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(k):
            for i in range(per):
                tracer.record(f"pe.kernel:t{k}", 0.0, 1.0, desc_id=i)

        threads = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    kept = len(tracer.host_spans())
    assert kept == 128
    assert kept + tracer.counters_snapshot()["dropped"] == n_threads * per
