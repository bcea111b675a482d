"""The runtime's marks on a device profile's clock.

Lifecycle marks and host spans are ``time.perf_counter`` seconds; a JAX
profile (``jax.profiler.ProfileData``) times its host annotations and
device events in nanoseconds on a clock of its own.  ``Tracer.anchor()``
enters the annotation ``dsa.clock`` and keeps the ``perf_counter_ns()``
taken at its entry, so each anchor is one point known on both clocks.
Two anchors, one at each end of a profiled window, fix the offset and the
rate between them:

    tracer.anchor()
    ... profiled window ...
    tracer.anchor()
    clock = ClockMap.between(tracer, ProfileData.from_file(path))
    clock.to_profile(trace.marks["exec0"])   # ns on the profile's clock

No profiler event is emitted per descriptor.

That is the clock of the profile's host plane, where the anchors are.  A
TPU profile's device plane keeps a clock of its own, offset from the
host plane's by about a millisecond (one v5e: each ``batch_copy`` program
starts 1.2-1.4 ms before the host call that launched it).  To place marks
among device events, fit that offset from calls whose programs are known
and shift the map by it:

    lead = device_lead_ns(call_starts_ns, program_starts_ns)
    device_clock = clock.shifted(-lead)
"""
from __future__ import annotations

import dataclasses
import bisect
from typing import Any, List, Optional, Sequence

from repro.obs.trace import ANCHOR


@dataclasses.dataclass(frozen=True)
class ClockMap:
    """profile ns = ``offset_ns`` + ``rate`` x perf_counter ns."""

    rate: float
    offset_ns: float

    @classmethod
    def fit(cls, perf_ns: Sequence[float], profile_ns: Sequence[float]) -> "ClockMap":
        """The line through the first and the last of the paired anchors."""
        if len(perf_ns) != len(profile_ns) or len(perf_ns) < 2:
            raise ValueError(f"need two or more paired anchors, got "
                             f"{len(perf_ns)} perf_counter and "
                             f"{len(profile_ns)} profile times")
        if perf_ns[-1] == perf_ns[0]:
            raise ValueError("anchors taken at one instant fix no rate")
        rate = (profile_ns[-1] - profile_ns[0]) / (perf_ns[-1] - perf_ns[0])
        return cls(rate, profile_ns[0] - rate * perf_ns[0])

    @classmethod
    def between(cls, tracer: Any, profile: Any) -> "ClockMap":
        """Fit from ``tracer``'s anchors and the ``dsa.clock`` annotations of
        ``profile``: the profile holds the tracer's newest anchors."""
        theirs = profile_anchors(profile)
        ours = tracer.anchors()[-len(theirs):] if theirs else []
        return cls.fit(ours, theirs)

    def to_profile(self, t: float) -> float:
        """``perf_counter`` seconds -> profile nanoseconds."""
        return self.offset_ns + self.rate * t * 1e9

    def to_perf(self, ns: float) -> float:
        """Profile nanoseconds -> ``perf_counter`` seconds."""
        return (ns - self.offset_ns) / self.rate / 1e9

    def shifted(self, ns: float) -> "ClockMap":
        """The same map, ``ns`` later on the profile's side."""
        return dataclasses.replace(self, offset_ns=self.offset_ns + ns)


def device_lead_ns(calls_ns: Sequence[float], starts_ns: Sequence[float]
                   ) -> Optional[float]:
    """How far the device plane's clock reads behind the host plane's: the
    largest, over host calls that each launch one program (mapped onto the
    profile, ns), of the call's start minus the nearest program start on
    the device plane.  No program starts before the call that launched
    it, so a shift by less would put some program first; shifted by this
    much, each program lands at or after its call, late by its dispatch
    delay less the least one.  None without calls or programs.  It holds
    where each call launches one program on an idle device, further apart
    than twice the lead: one program left out, or queued behind others,
    is paired with its neighbour's and reads too large a lead."""
    starts = sorted(starts_ns)
    if not starts or not calls_ns:
        return None
    leads = []
    for c in calls_ns:
        i = bisect.bisect_left(starts, c)
        near = [starts[j] for j in (i - 1, i) if 0 <= j < len(starts)]
        leads.append(c - min(near, key=lambda s: abs(s - c)))
    return max(leads)


def profile_anchors(profile: Any) -> List[float]:
    """Start (ns) of every ``dsa.clock`` annotation in ``profile``, in order."""
    return sorted(e.start_ns for plane in profile.planes for line in plane.lines
                  for e in line.events if e.name == ANCHOR)
