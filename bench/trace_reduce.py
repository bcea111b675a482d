"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

Layout of a TPU trace as JAX writes it: each chip is a plane named
``/device:TPU:<n>`` with a line ``XLA Modules`` (one event per execution
of a jitted program, named ``jit_<fn>(<fingerprint>)``) and a line
``XLA Ops`` (one event per HLO operation).  The host plane ``/host:CPU``
holds the bench's ``TraceAnnotation`` spans, named ``bench.<layer call>``.
All timestamps share one clock, in nanoseconds.

Busy time is the union of the ``XLA Ops`` intervals inside the window
span ``bench.window``; a program's device time is the summed length of
its module events inside that window, every op of it included.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW = "bench.window"
TOP = 10

Interval = Tuple[float, float]


def program_name(module: str) -> str:
    """``jit_batch_copy(8192054374)`` -> ``batch_copy``."""
    name = re.sub(r"\(\d+\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def op_name(op: str) -> str:
    """``%copy.7 = u32[...] copy(...)`` -> ``copy.7``."""
    return op.split(" = ", 1)[0].lstrip("%").strip()


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _clip(a: float, b: float, w: Interval) -> Interval:
    return max(a, w[0]), min(b, w[1])


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
    return []


def reduce_profile(profile) -> Optional[Dict]:
    """Metrics of a ``jax.profiler.ProfileData`` (see ``reduce``); None
    where the trace holds no TPU plane."""
    host: List[Tuple[str, float, float]] = []
    devices = []
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
        elif DEVICE_PLANE.match(plane.name):
            devices.append(plane)
    windows = [(a, b) for n, a, b in host if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span in the trace, found {len(windows)}")
    win = windows[0]
    if not devices:
        return None
    busy_ns = []
    programs: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    gaps: List[Interval] = []
    for k, plane in enumerate(devices):
        modules = sorted((a, b, program_name(n)) for n, a, b in _events(plane, "XLA Modules")
                         if b > win[0] and a < win[1])
        for a, b, name in modules:
            a, b = _clip(a, b, win)
            programs[name] += (b - a) / 1e9
        spans = []
        m = 0
        for name, a, b in sorted(_events(plane, "XLA Ops"), key=lambda e: e[1]):
            if b <= win[0] or a >= win[1]:
                continue
            a, b = _clip(a, b, win)
            spans.append((a, b))
            while m < len(modules) and modules[m][1] <= a:
                m += 1
            owner = modules[m][2] if m < len(modules) and modules[m][0] <= a else "?"
            ops[f"{owner}/{op_name(name)}"] += (b - a) / 1e9
        merged = union(spans)
        busy_ns.append(sum(b - a for a, b in merged))
        if k == 0:
            edges = [win[0]] + [x for ab in merged for x in ab] + [win[1]]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    inner = [(n, a, b) for n, a, b in host if n != WINDOW]
    named_gaps = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        mid = (a + b) / 2
        covering = [(bb - aa, n) for n, aa, bb in inner if aa <= mid <= bb]
        named_gaps.append([min(covering)[1] if covering else WINDOW, (b - a) / 1e9])
    return {
        "chips": len(devices),
        "window_s": (win[1] - win[0]) / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "programs": dict(programs),
        "device_ops": sorted(([n, s] for n, s in ops.items()), key=lambda x: -x[1])[:TOP],
        "idle_gaps": named_gaps,
    }


def reduce(path: str) -> Optional[Dict]:
    """Reduce the ``.xplane.pb`` at ``path``: window length, device busy
    seconds (mean over chips), device seconds per jitted program, the
    ``TOP`` device ops by time and the ``TOP`` longest idle gaps of chip 0,
    each named by the innermost bench span open at its middle."""
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))
