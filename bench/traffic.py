"""The one traffic generator: turns a mix file of ``bench/traffic/`` and a
seed into arrivals, packet sizes and session lengths.

``PoissonArrivals`` and ``ZipfLengths`` are adapted from the program's
``repro.serving.traffic`` classes and kept here, so that no change to the
program can move the yardstick.  Every seed gets the same work in another
order where that matters for the end-to-end metric: session lengths are
fixed quantiles of the Zipf, permuted by the seed.
"""
from __future__ import annotations

import json
import zlib
from pathlib import Path
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> Dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r}: {path} is missing")
    return json.loads(path.read_text())


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, stream): changing one stream's
    draws never perturbs another's.  Any non-negative seed works, also past
    32 bits."""
    return np.random.default_rng([int(seed), zlib.crc32(stream.encode())])


class PoissonArrivals:
    """Constant-rate Poisson process: i.i.d. exponential inter-arrivals."""

    def __init__(self, rate_rps: float, seed: int = 0):
        if rate_rps <= 0:
            raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
        self.rate_rps = float(rate_rps)
        self.seed = int(seed)

    def times(self, horizon_s: float) -> np.ndarray:
        g = rng(self.seed, "arrivals")
        out: List[float] = []
        t = 0.0
        while True:
            t += g.exponential(1.0 / self.rate_rps)
            if t >= horizon_s:
                return np.asarray(out)
            out.append(t)


class ZipfLengths:
    """Bounded Zipf over the integer lengths ``[lo, hi]``: rank 1 (= ``lo``)
    is the most likely, and P(rank k) ~ k**-s."""

    def __init__(self, s: float = 1.1, lo: int = 1, hi: int = 1024):
        if not 1 <= lo <= hi or s <= 0:
            raise ValueError(f"need 1 <= lo <= hi and s > 0, got {lo}, {hi}, {s}")
        ranks = np.arange(1, hi - lo + 2, dtype=np.float64)
        w = ranks ** -float(s)
        self._pmf = w / w.sum()
        self._values = np.arange(lo, hi + 1, dtype=np.int64)

    def quantiles(self, n: int) -> np.ndarray:
        """The lengths at the midpoints of ``n`` equal slices of the
        distribution: a stratified sample that is the same for every seed."""
        q = (np.arange(n) + 0.5) / n
        return self._values[np.searchsorted(np.cumsum(self._pmf), q)]


def arrivals(mix: Dict, seed: int, seconds: float) -> np.ndarray:
    """Due times in ``[0, seconds)`` of an open-loop mix."""
    spec = mix["arrivals"]
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    return PoissonArrivals(spec["rate_per_s"], seed).times(seconds)


class BurstSizes:
    """Item sizes of burst ``i`` (``row(i)``, shape ``[burst]``), drawn per
    item from the mix's weights.  A burst's sizes depend only on (seed,
    stream, burst index); rows are drawn 1024 bursts at a time."""

    CHUNK = 1024

    def __init__(self, mix: Dict, seed: int, stream: str = "sizes"):
        sizes = mix["sizes"]
        self.values = np.asarray(sizes["values"], np.int64)
        p = np.asarray(sizes["weights"], np.float64)
        self.p = p / p.sum()
        self.burst = int(mix["burst"])
        self.seed, self.stream = seed, stream
        self._chunks: Dict[int, np.ndarray] = {}

    def row(self, i: int) -> np.ndarray:
        c = i // self.CHUNK
        block = self._chunks.get(c)
        if block is None:
            block = rng(self.seed, f"{self.stream}.{c}").choice(
                self.values, size=(self.CHUNK, self.burst), p=self.p)
            self._chunks[c] = block
        return block[i - c * self.CHUNK]


def session_tokens(mix: Dict, seed: int) -> np.ndarray:
    """Session lengths in tokens: fixed Zipf quantiles in a seeded order."""
    spec = mix["sessions"]
    z = ZipfLengths(spec["zipf_s"], spec["min_tokens"], spec["max_tokens"])
    toks = z.quantiles(spec["count"])
    return rng(seed, "sessions").permutation(toks)
