"""Run one cell with the program's tracer, and say what the runtime was
doing in each idle gap of the device; or measure what tracing costs.

    python bench/explain.py --workload <cell> --seed <n> --seconds <s>
        [--mode profile|tracer|off] [--out <file>]

``--mode profile`` (the default) is ``bench/run.py --trace 1``'s run (the
same profiler options and ``bench.*`` spans), with ``Tracer.anchor()`` at
both ends of the window.  The two ``dsa.clock`` anchors map the program's
lifecycle marks and host spans onto the profile's host plane
(``repro.obs.ClockMap``); the device plane's own offset from it is fitted
from the ``batch_copy`` programs and the ``pe.kernel:batch_copy`` calls
that launched them (``repro.obs.device_lead_ns``).  Prints one JSON line,
also written to ``--out``:

- ``idle_gaps``: the ``TOP`` longest gaps of chip 0, each named
  ``<bench span>/<runtime activity>`` (``activity_at`` the gap's middle,
  ``-`` where nothing covers it), with their seconds;
- ``stalls``: every gap of a second or more, named the same way;
- ``idle_cover``: the share of the device's idle time that each kind of
  runtime activity covers (kinds may overlap), and ``-`` for the rest;
- ``clock_check``: of the window's batch-copy descriptors, the share whose
  mapped ``exec0`` -> ``resolved`` holds the start of exactly one
  ``batch_copy`` program on the device, and the device plane's lead
  (``clock_check``'s doc);
- ``cross_checks``: the runtime's spans against the metrics that time the
  same layers from outside;
- ``metrics``: every end-to-end and per-layer metric of the cell this run
  can read, so that runs of the three modes can be compared;
- ``host_spans``: count and mean microseconds of each host span name in
  the window;
- ``gc``: collections of each generation in the window, their summed
  pause, and the longest gen-2 pauses, from a ``gc.callbacks`` hook of this
  script's own, so that the modes compare alike;
- ``tracer``: the tracer's counters (``dropped``, collections, pause).

``--mode tracer`` runs the tracer without the profiler (no gap names, no
device metrics), and ``--mode off`` runs the cell untraced: with
``profile`` they split what tracing costs between the profiler and the
program's own tracer, and the collections between the program and its
tracer.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Iterable, List, Optional, Sequence, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import readers, record, runtime_spans, spec, trace_reduce, traffic  # noqa: E402
from bench.run import PEAKS, TRACE_DIR, _profile_options  # noqa: E402

STALL_S = 1.0
MODES = ("profile", "tracer", "off")
#: what can name a moment of the runtime, in order of precedence: a
#: collection (it stops every thread), a PE worker's call, the KV pool's
#: host work (host spans, by prefix), then a descriptor queued in its WQ,
#: then one whose completion is being written or observed (phases)
ACTIVITY_ORDER: Tuple[Tuple[str, ...], ...] = (
    ("gc.",), ("pe.",), ("kvpool.",), ("wq_wait",),
    ("completion_write", "host_wait"))
#: labels of ACTIVITY_ORDER's ranks
KINDS = ("gc", "pe", "kvpool", "wq_wait", "completion")
Interval = Tuple[float, float]
Activity = Tuple[int, str, float, float]


def activities(spans: Iterable, traces: Iterable) -> List[Activity]:
    """``(rank, name, t0, t1)`` of every host span and descriptor phase
    that can name a moment, ``rank`` being its place in ACTIVITY_ORDER."""
    items = [(sp.name, sp.t0, sp.t1) for sp in spans]
    items += [(p.phase, p.t0, p.t1) for dt in traces for p in dt.spans()]
    out = []
    for name, t0, t1 in items:
        for rank, prefixes in enumerate(ACTIVITY_ORDER):
            if name.startswith(prefixes):
                out.append((rank, name, t0, t1))
                break
    return out


def activity_at(t: float, acts: Sequence[Activity]) -> str:
    """Name of what the runtime was doing at ``t`` (perf_counter seconds):
    of the ``activities`` covering ``t``, the shortest of the first rank
    that has any; ``-`` where none covers it."""
    covering = [(rank, t1 - t0, name) for rank, name, t0, t1 in acts
                if t0 <= t <= t1]
    return min(covering)[2] if covering else "-"


def device_gaps(profile) -> Tuple[List[Interval], List[Tuple[str, float, float]]]:
    """Idle intervals of chip 0 inside ``bench.window`` (profile ns), the
    way ``trace_reduce`` finds them, and the ``bench.*`` spans."""
    host, planes = [], []
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for line in plane.lines for e in line.events
                     if e.name.startswith("bench.")]
        elif trace_reduce.DEVICE_PLANE.match(plane.name):
            planes.append(plane)
    (win,) = [(a, b) for n, a, b in host if n == trace_reduce.WINDOW]
    if not planes:
        return [], host
    busy = trace_reduce.union([
        (max(a, win[0]), min(b, win[1]))
        for _, a, b in trace_reduce._events(planes[0], "XLA Ops")
        if b > win[0] and a < win[1]])
    edges = [win[0]] + [x for ab in busy for x in ab] + [win[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return gaps, host


def bench_name(mid: float, host) -> str:
    """``trace_reduce``'s name: the innermost bench span open at ``mid``."""
    covering = [(b - a, n) for n, a, b in host
                if n != trace_reduce.WINDOW and a <= mid <= b]
    return min(covering)[1] if covering else trace_reduce.WINDOW


def covered_ns(gaps: List[Interval], spans: List[Interval]) -> float:
    """Nanoseconds of ``gaps`` that the union of ``spans`` covers."""
    out, j = 0.0, 0
    merged = trace_reduce.union(spans)
    for a, b in gaps:
        while j < len(merged) and merged[j][1] <= a:
            j += 1
        k = j
        while k < len(merged) and merged[k][0] < b:
            out += max(min(b, merged[k][1]) - max(a, merged[k][0]), 0.0)
            k += 1
    return out


def program_starts(profile, program: str) -> List[float]:
    """Start (ns, device plane) of every execution of ``program``."""
    return sorted(e.start_ns for plane in profile.planes
                  if trace_reduce.DEVICE_PLANE.match(plane.name)
                  for line in plane.lines if line.name == "XLA Modules"
                  for e in line.events if trace_reduce.program_name(e.name) == program)


def clock_check(clock, starts: List[float], traces, calls) -> Optional[Dict]:
    """Of the batch-copy descriptors, the share whose ``exec0`` ->
    ``resolved``, mapped onto the device plane, holds the start of exactly
    one ``batch_copy`` program.  The device plane's lead is fitted on the
    calls of one half of the window and checked on the descriptors of the
    other half, both ways round (``one_program_pct``; on every call where
    the other half has none); ``device_lead_ms``
    gives the lead's least, median and largest value over every call, and
    ``one_program_pct_unshifted`` the share with no lead taken off."""
    from repro.obs import device_lead_ns

    copies = sorted((t for t in traces if t.op == "batch_copy"
                     and "exec0" in t.marks and "resolved" in t.marks),
                    key=lambda t: t.marks["exec0"])
    if not copies or not starts:
        return None
    ids = {t.desc_id for t in copies}
    call_ns = {sp.desc_id: clock.to_profile(sp.t0) for sp in calls if sp.desc_id in ids}

    def one_program(ts, lead: float) -> int:
        dev = clock.shifted(-lead)
        return sum(1 for t in ts if sum(
            dev.to_profile(t.marks["exec0"]) <= s <= dev.to_profile(t.marks["resolved"])
            for s in starts) == 1)

    halves = (copies[:len(copies) // 2], copies[len(copies) // 2:])
    every = device_lead_ns(list(call_ns.values()), starts) or 0.0
    ok = 0
    for fit, check in (halves, halves[::-1]):
        lead = device_lead_ns([call_ns[t.desc_id] for t in fit if t.desc_id in call_ns], starts)
        ok += one_program(check, every if lead is None else lead)
    leads = sorted(c - min(starts, key=lambda s: abs(s - c)) for c in call_ns.values())
    return {"descriptors": len(copies), "one_program_pct": 100.0 * ok / len(copies),
            "one_program_pct_unshifted": 100.0 * one_program(copies, 0.0) / len(copies),
            "device_lead_ms": ([leads[0] / 1e6, leads[len(leads) // 2] / 1e6, leads[-1] / 1e6]
                               if leads else None)}


def span_means(spans) -> Dict[str, List[float]]:
    """``{name: [count, mean us]}`` of host spans."""
    by: Dict[str, List[float]] = collections.defaultdict(list)
    for sp in spans:
        by[sp.name].append(sp.t1 - sp.t0)
    return {n: [len(v), 1e6 * sum(v) / len(v)] for n, v in sorted(by.items())}


def explain(profile, tracer, traces) -> Dict:
    """Name the idle gaps of ``profile`` by ``tracer``'s host spans and the
    phases of ``traces`` (see the module doc)."""
    from repro.obs import ClockMap, device_lead_ns

    clock = ClockMap.between(tracer, profile)
    starts = program_starts(profile, "batch_copy")
    ids = {t.desc_id for t in traces}
    calls = [sp for sp in tracer.host_spans("pe.kernel:batch_copy") if sp.desc_id in ids]
    lead = device_lead_ns([clock.to_profile(sp.t0) for sp in calls], starts)
    # device-plane ns -> host-plane ns is + lead; perf_counter -> device
    # plane is the anchors' map shifted by - lead
    lead = lead or 0.0
    dev = clock.shifted(-lead)
    gaps, host = device_gaps(profile)
    acts = activities(tracer.host_spans(), traces)

    def name(a: float, b: float) -> str:
        mid = (a + b) / 2
        return f"{bench_name(mid + lead, host)}/{activity_at(dev.to_perf(mid), acts)}"

    idle = sum(b - a for a, b in gaps)
    cover = {}
    for rank, kind in enumerate(KINDS):
        spans = [(dev.to_profile(t0), dev.to_profile(t1)) for r, _, t0, t1 in acts if r == rank]
        cover[kind] = 100.0 * covered_ns(gaps, spans) / idle if idle else 0.0
    every = [(dev.to_profile(t0), dev.to_profile(t1)) for _, _, t0, t1 in acts]
    cover["-"] = 100.0 - (100.0 * covered_ns(gaps, every) / idle if idle else 0.0)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])
    return {
        "clock": {"rate": clock.rate, "anchors": len(tracer.anchors()),
                  "device_lead_ms": lead / 1e6 if calls and starts else None},
        "idle_s": idle / 1e9,
        "idle_gaps": [[name(a, b), (b - a) / 1e9] for a, b in longest[:trace_reduce.TOP]],
        "stalls": [[name(a, b), (b - a) / 1e9] for a, b in longest if b - a >= STALL_S * 1e9],
        "idle_cover": cover,
        "clock_check": clock_check(clock, starts, traces, calls),
    }


class GcWatch:
    """Collections of each generation and their pauses while installed."""

    def __init__(self):
        self.n = [0, 0, 0]
        self.pauses: List[Tuple[int, float]] = []
        self._t0 = 0.0

    def _hook(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.n[info["generation"]] += 1
            self.pauses.append((info["generation"], time.perf_counter() - self._t0))

    def __enter__(self) -> "GcWatch":
        gc.callbacks.append(self._hook)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._hook)

    def summary(self) -> Dict:
        gen2 = sorted((s for g, s in self.pauses if g == 2), reverse=True)
        return {"collections": self.n, "pause_ms": 1e3 * sum(s for _, s in self.pauses),
                "gen2_ms": [1e3 * s for s in gen2[:5]]}


def explain_cell(workload: str, seed: int, seconds: float, *, mode: str = "profile",
                 t_start: Optional[float] = None, cfg: Optional[Dict] = None,
                 mix: Optional[Dict] = None) -> Dict:
    """One run of ``workload`` in ``mode``, explained (see the module doc).
    ``cfg`` and ``mix`` replace the cell's files, as in
    ``bench.run.run_cell`` (tests run small copies on the CPU)."""
    import jax
    from jax.profiler import ProfileData

    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    t_start = time.perf_counter() if t_start is None else t_start
    bm = spec.load_benchmark()
    cell = spec.cell(bm, workload)
    cfg = cfg or spec.config(bm, cell["config"])
    mix = mix or traffic.load(cell["traffic"])
    sut = spec.system(cfg).System(cfg, mix, seed, traced=mode != "off")
    kind = jax.devices()[0].device_kind
    run = record.Run(setup_s=time.perf_counter() - t_start, peaks=PEAKS["devices"].get(kind, {}))
    spans = record.Spans(mode == "profile")
    if mode == "profile":
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=_profile_options())
    try:
        with GcWatch() as watch:
            if mode == "profile":
                sut.tracer.anchor()
            t_window = time.perf_counter()
            with spans.span("window"):
                sut.window(seconds, spans, run)
            if mode == "profile":
                sut.tracer.anchor()
    finally:
        if mode == "profile":
            jax.profiler.stop_trace()
    out = {"workload": workload, "seed": seed, "device": kind, "mode": mode}
    run.spans = [s for s in spans.spans if s[0] != "window"]
    if sut.tracer is not None:
        run.traces = [t for t in sut.tracer.traces() if t.marks.get("submit_enter", 0.0) >= t_window]
    if mode == "profile":
        profile = ProfileData.from_file(str(next(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        run.device = trace_reduce.reduce_profile(profile)
        out.update(explain(profile, sut.tracer, run.traces))
    metrics = {}
    for m in spec.metrics(bm, workload, "end_to_end") + spec.metrics(bm, workload, "per_layer"):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = value
    out["metrics"] = metrics
    out["gc"] = watch.summary()
    tracer = runtime_spans.tracer(run)  # None untraced, or where no host spans are kept
    if tracer is not None:
        out["cross_checks"] = cross_checks(run, tracer)
        t0, t1 = runtime_spans.window(run) or (t_window, time.perf_counter())
        out["host_spans"] = span_means(sp for sp in tracer.host_spans() if t0 <= sp.t0 <= t1)
        out["tracer"] = {k: v for k, v in tracer.counters_snapshot().items()
                         if k == "dropped" or k.startswith("gc.")}
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in sut.check().items()}
    return out


def cross_checks(run, tracer) -> Dict:
    """The runtime's spans against the metrics that time the same layers
    from outside: the KV pool's two spans over ``kvpool_self_us``, and a
    PE slot's kernel calls plus its glue over ``exec0`` -> ``exec1``."""
    r = spec.reader
    plan, commit, own = r("kvpool_plan_us")(run), r("kvpool_commit_us")(run), r("kvpool_self_us")(run)
    call, glue = r("pe_call_us")(run), r("pe_self_us")(run)
    pe_exec = readers.mark_gap_us(run, "exec0", "exec1")
    calls = collections.Counter(sp.desc_id for sp in tracer.host_spans("pe.kernel:"))
    slots = [t.desc_id for t in run.traces if "exec1" in t.marks]
    per_slot = sum(calls[d] for d in slots) / len(slots) if slots else 0.0
    return {
        "kvpool_plan_plus_commit_over_self": (plan + commit) / own if plan and commit and own else None,
        "pe_calls_per_slot": per_slot,
        "pe_calls_plus_self_over_exec": ((per_slot * call + glue) / pe_exec
                                         if call and glue is not None and pe_exec else None),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--mode", choices=MODES, default="profile")
    ap.add_argument("--out", help="also write the JSON line here")
    args = ap.parse_args(argv)
    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    line = json.dumps(explain_cell(args.workload, args.seed, args.seconds, mode=args.mode,
                                   t_start=T0))
    print(line)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
