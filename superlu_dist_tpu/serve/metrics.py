"""Structured observability for the solve service.

The serving loop needs per-stage latency distributions (queue wait,
batch assembly, device solve, refinement), cache hit rates, batch
occupancy, and hard-failure counters (rejected, deadline-missed) — the
standard inference-server metric surface, kept dependency-free so it
runs under tier-1 CPU tests.

Percentiles are exact over a bounded reservoir: histograms keep up to
`sample_cap` raw samples (deterministic reservoir replacement past the
cap, seeded RNG) plus exact count/sum/min/max, so the small loads
tests drive report true p50/p95/p99 while
memory stays bounded under sustained traffic.  `Metrics.snapshot()`
returns a plain-JSON dict.

A Metrics instance is also an `obs.Registry` provider (it has exactly
the snapshot() contract): `register_obs()` places it in the unified
observability registry, where `obs.snapshot()["serve"]` and the
Prometheus-style `obs.dump_text()` expose the serve counters next to
the phase stats, compile misses and health monitors.  SolveService
does this automatically.
"""

from __future__ import annotations

import random
import threading


def nearest_rank(sorted_samples, p: float) -> float:
    """Nearest-rank percentile of an already-sorted sequence (p in
    0-100) — the ONE percentile definition shared by Histogram and
    the load generator's report."""
    n = len(sorted_samples)
    idx = min(n - 1, max(0, int(round(p / 100.0 * (n - 1)))))
    return float(sorted_samples[idx])


class Counter:
    """Monotonic counter (thread-safe via the owning registry lock)."""

    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value = 0

    def inc(self, n: int = 1) -> None:
        self.value += n


class Histogram:
    """Latency/occupancy distribution with exact bounded-reservoir
    percentiles.  Values are unitless; the convention in this package
    is seconds for latencies and a 0-1 ratio for occupancy."""

    def __init__(self, sample_cap: int = 65536, seed: int = 0) -> None:
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self._cap = sample_cap
        self._samples: list[float] = []
        # deterministic reservoir: same traffic → same snapshot
        self._rng = random.Random(seed)

    def record(self, x: float) -> None:
        self.count += 1
        self.total += x
        if x < self.min:
            self.min = x
        if x > self.max:
            self.max = x
        if len(self._samples) < self._cap:
            self._samples.append(x)
        else:
            j = self._rng.randrange(self.count)
            if j < self._cap:
                self._samples[j] = x

    def percentile(self, p: float) -> float:
        """Exact nearest-rank percentile over the reservoir (p in
        0-100).  0.0 when nothing was recorded."""
        if not self._samples:
            return 0.0
        return nearest_rank(sorted(self._samples), p)

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def summary(self) -> dict:
        if not self.count:
            return {"count": 0}
        s = sorted(self._samples)   # one sort serves all percentiles
        return {
            "count": self.count,
            "mean": self.mean,
            "min": self.min,
            "max": self.max,
            "p50": nearest_rank(s, 50),
            "p95": nearest_rank(s, 95),
            "p99": nearest_rank(s, 99),
        }


class Metrics:
    """Named counters + histograms behind one lock.

    One instance is shared by the factor cache, the micro-batchers and
    the service front door; `snapshot()` is the JSON-ready view."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: dict[str, Counter] = {}
        self._histograms: dict[str, Histogram] = {}

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter()
            c.inc(n)

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram()
            h.record(value)

    def counter(self, name: str) -> int:
        with self._lock:
            c = self._counters.get(name)
            return c.value if c else 0

    def histogram(self, name: str) -> dict:
        with self._lock:
            h = self._histograms.get(name)
            return h.summary() if h else {"count": 0}

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "counters": {k: c.value
                             for k, c in sorted(self._counters.items())},
                "histograms": {k: h.summary()
                               for k, h in sorted(self._histograms.items())},
            }

    def register_obs(self, name: str = "serve") -> "Metrics":
        """Register this instance in the unified observability
        registry (last-wins per name)."""
        from .. import obs
        obs.REGISTRY.register(name, self)
        return self

    def unregister_obs(self, name: str = "serve") -> None:
        """Compare-and-remove: only drops the registration if this
        instance still owns it."""
        from .. import obs
        obs.REGISTRY.unregister(name, self)
