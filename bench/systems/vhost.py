"""DPDK vhost-user async enqueue: every burst of guest-bound packets is one
``Device.batch_async`` of one MEMCPY descriptor per packet, and the client
observes its completion through the burst's Future.

Source packets live on the device, ``source_packets_per_size`` distinct
ones per size, made from the seed at set-up.  Packet ``j`` of burst ``i``
copies source slot ``(i * burst + j) % source_packets_per_size`` of its
size.  One burst in ``SAMPLE_EVERY``, at an offset drawn from the seed, is
kept and compared byte for byte with the reference once the window has
closed.

A burst refused with ``QueueFull``, or never completed within
``LATE_LIMIT_S`` of the close, is a failed operation: in the open loop it
is a latency sample at ``LATE_LIMIT_S``, so dropping bursts never reads
as speed, and ``refused_pct`` bounds how many a run may refuse.
"""
from __future__ import annotations

import contextlib
import functools
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, traffic
from repro.core import OpType, QueueFull, Status, WorkDescriptor, make_device
from repro.obs.trace import TraceConfig

SAMPLE_EVERY = 16
# how long after the window's close an outstanding burst is waited for
LATE_LIMIT_S = 60.0
WARMUP_BURSTS = 64
# refused bursts, % of those attempted: sound runs refused up to 2.3% (a
# host stall of about 1 s), a fault that refuses every other burst 50%
REFUSED_LIMIT_PCT = 25.0


class System:
    def __init__(self, cfg: Dict, mix: Dict, seed: int, traced: bool):
        self.mix, self.seed = mix, seed
        self.sw = reference.seed_word(seed)
        self.burst = int(mix["burst"])
        self.pool_n = int(cfg["assumed"]["source_packets_per_size"])
        self.dev = make_device(
            trace=TraceConfig(rate=1.0, capacity=1 << 20) if traced else None)
        self.tracer = self.dev.tracer
        gen = jax.jit(functools.partial(reference.packet_bytes, xp=jnp),
                      static_argnums=(1,))
        self.packets = {
            int(s): list(gen(jnp.uint32(self.sw), int(s), jnp.arange(self.pool_n)))
            for s in mix["sizes"]["values"]
        }
        self.kept: Dict[int, Tuple[np.ndarray, object]] = {}
        self.lost = 0
        self.errors = 0
        warm = traffic.BurstSizes(mix, seed, stream="warmup")
        futs = [self._submit(i, warm.row(i), None) for i in range(WARMUP_BURSTS)]
        self.dev.wait_all([f for f in futs if f is not None])

    def _submit(self, i: int, row: np.ndarray, spans):
        descs = [WorkDescriptor(op=OpType.MEMCPY,
                                src=self.packets[int(s)][(i * self.burst + j) % self.pool_n])
                 for j, s in enumerate(row)]
        try:
            with spans.span("submit") if spans else contextlib.nullcontext():
                return self.dev.batch_async(descs)
        except QueueFull:
            return None

    def _retire(self, i: int, row: np.ndarray, fut, run) -> None:
        if fut.record.status != Status.SUCCESS:
            self.errors += 1
        else:
            run.bytes_done += int(row.sum())
        if i % SAMPLE_EVERY == self.keep_at:
            self.kept[i] = (row, fut.record.result if fut.record.status == Status.SUCCESS else None)

    def window(self, seconds: float, spans, run) -> None:
        sizes = traffic.BurstSizes(self.mix, self.seed)
        self.keep_at = int(traffic.rng(self.seed, "sample").integers(SAMPLE_EVERY))
        if self.mix["loop"] == "open":
            self._open(seconds, sizes, spans, run)
        else:
            self._closed(seconds, int(self.mix["in_flight"]), sizes, spans, run)
        self.refused_pct = 100.0 * run.failed / max(run.attempted, 1)

    def _open(self, seconds, sizes, spans, run) -> None:
        due = traffic.arrivals(self.mix, self.seed, seconds)
        n = len(due)
        rows = [sizes.row(i) for i in range(n)]
        pending: Dict[object, int] = {}
        lat: List[float] = []
        late: List[float] = []
        last = 0.0
        i = 0
        t0 = time.perf_counter()
        while i < n or pending:
            now = time.perf_counter() - t0
            if now > seconds + LATE_LIMIT_S:
                break
            while i < n and due[i] <= now:
                fut = self._submit(i, rows[i], spans)
                now = time.perf_counter() - t0
                late.append(now - due[i])
                if fut is None:
                    run.failed += 1
                    lat.append(LATE_LIMIT_S)
                else:
                    pending[fut] = i
                i += 1
            if pending:
                timeout = max(due[i] - now, 0.0) if i < n else None
                with spans.span("wait"):
                    done, _ = self.dev.wait_any(list(pending), timeout=timeout)
                t = time.perf_counter() - t0
                for f in done:
                    j = pending.pop(f)
                    lat.append(t - due[j])
                    last = t
                    self._retire(j, rows[j], f, run)
            elif i < n:
                with spans.span("idle"):
                    time.sleep(max(due[i] - (time.perf_counter() - t0), 0.0))
        self.lost = len(pending)
        lat.extend([LATE_LIMIT_S] * self.lost)
        run.attempted = n
        run.latencies_s = lat
        run.gen_late_s = late
        run.window_s = max(last, seconds)

    def _closed(self, seconds, in_flight, sizes, spans, run) -> None:
        pending: Dict[object, Tuple[int, np.ndarray]] = {}
        last = 0.0
        i = 0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            while len(pending) < in_flight and now < seconds:
                row = sizes.row(i)
                fut = self._submit(i, row, spans)
                if fut is None:
                    run.failed += 1
                else:
                    pending[fut] = (i, row)
                i += 1
                now = time.perf_counter() - t0
            if not pending or now > seconds + LATE_LIMIT_S:
                break
            with spans.span("wait"):
                done, _ = self.dev.wait_any(list(pending))
            t = time.perf_counter() - t0
            for f in done:
                j, row = pending.pop(f)
                last = t
                self._retire(j, row, f, run)
        self.lost = len(pending)
        run.attempted = i
        run.window_s = last

    def check(self) -> Dict[str, Tuple[float, float]]:
        want = {s: reference.packet_bytes(self.sw, s, np.arange(self.pool_n))
                for s in self.packets}
        bad = 0
        got, exp = [], []
        for i, (row, result) in sorted(self.kept.items()):
            if result is None or len(result) != len(row):
                bad += len(row)
                continue
            for j, (s, out) in enumerate(zip(row, result)):
                got.append(out)
                exp.append(want[int(s)][(i * self.burst + j) % self.pool_n])
        for g, e in zip(jax.device_get(got), exp):
            if g.shape != e.shape or g.dtype != e.dtype or not np.array_equal(g, e):
                bad += 1
        return {"bad_packets": (bad, 0), "lost_bursts": (self.lost + self.errors, 0),
                "refused_pct": (self.refused_pct, REFUSED_LIMIT_PCT)}
