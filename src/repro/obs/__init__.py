"""Live observability over the streaming-engine fabric (paper §5).

``Telemetry`` (core/telemetry.py) answers "what happened" after a run;
this package answers "what is happening" while it runs — the pcm-accel
analogue.  A ``Sampler`` snapshots every engine / WQ / NUMA node / wait
policy at a fixed interval into bounded ring-buffer ``Series`` (delta
sampling over monotonic counters, O(engines) per tick) with CSV/JSONL
export and windowed percentile summaries; ``tools/pcm_repro.py`` renders
the live terminal view.  See docs/observability.md for the metric
glossary and lifecycle.

Descriptor-lifecycle tracing (docs/tracing.md) rides on the same package:
``make_device(trace=...)`` attaches a ``Tracer`` that records a span tree
per sampled descriptor (create -> validate -> submit -> wq_wait ->
engine_dispatch -> pe_exec -> completion_write -> host_wait -> callback),
dependency edges, and the runtime's host spans (waits, PE kernel calls,
KV-pool steps, garbage collections); ``to_perfetto`` exports the lot as
Chrome/Perfetto trace_event JSON, ``ClockMap`` puts it on a device
profile's clock, and ``critical_path`` / ``phase_breakdown`` /
``host_free_fraction`` are the span analyzers
(``tools/trace_view.py`` is the CLI).
"""
from repro.obs.clock import ClockMap, device_lead_ns
from repro.obs.export import to_csv, to_jsonl, to_perfetto
from repro.obs.sampler import Sampler
from repro.obs.series import Series, percentile
from repro.obs.spans import HOST_PHASES, PHASES, DescTrace, Span
from repro.obs.trace import (
    HostSpan,
    TraceConfig,
    Tracer,
    TraceRateError,
    critical_path,
    host_free_fraction,
    make_tracer,
    phase_breakdown,
    slowest,
)

__all__ = [
    "Sampler", "Series", "percentile",
    "to_csv", "to_jsonl", "to_perfetto",
    "PHASES", "HOST_PHASES", "DescTrace", "Span",
    "Tracer", "TraceConfig", "TraceRateError", "HostSpan", "make_tracer",
    "ClockMap", "device_lead_ns", "critical_path", "phase_breakdown",
    "host_free_fraction", "slowest",
]
