"""What one run leaves for the metric readers: the window's host-clock
samples, the bench's own host spans, the program's lifecycle traces and
the device trace's reduction."""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple


class Spans:
    """Host spans around the bench's calls into each layer.  When the run
    is traced each span is also a ``jax.profiler.TraceAnnotation`` named
    ``bench.<name>``, so the device trace can say what the host was doing
    in each idle gap."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.spans: List[Tuple[str, float, float]] = []
        if traced:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self.traced:
            with self._annotation(f"bench.{name}"):
                yield
        else:
            yield
        self.spans.append((name, t0, time.perf_counter()))


@dataclasses.dataclass
class Run:
    """Inputs of every metric reader (``bench/metrics/<name>.py``)."""

    setup_s: float = 0.0
    # the measured window on the host clock: first due time to the last
    # completion the client observed
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    # open loop: per burst, due time -> completion observed, and due time
    # -> submission returned (the generator's lateness)
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    gen_late_s: List[float] = dataclasses.field(default_factory=list)
    # useful bytes completed in the window (payload only)
    bytes_done: int = 0
    spans: List[Tuple[str, float, float]] = dataclasses.field(default_factory=list)
    # the program's lifecycle traces (repro.obs DescTrace) begun in the window
    traces: List[Any] = dataclasses.field(default_factory=list)
    # trace_reduce.reduce() of the traced window, and the useful bytes each
    # kernel moved inside it
    device: Optional[Dict[str, Any]] = None
    kernel_bytes: Dict[str, int] = dataclasses.field(default_factory=dict)
    peaks: Dict[str, Any] = dataclasses.field(default_factory=dict)
