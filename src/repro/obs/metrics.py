"""Metrics registry — named counters / gauges / histograms absorbing the
stack's scattered runtime tallies.

Before this module, each layer grew its own ad-hoc counters:
``Arena.traces`` (scan-body retraces), the arena's device-input cache
hits/misses, ``RolloutReport.meta``'s per-run ``dispatches`` /
``executables_built``, ``SweepService.stats``, and ``NpzChunkStore``
save/load tallies.  They now all write through ONE
:class:`MetricsRegistry` per arena/service (the public attributes —
``Arena.traces``, ``SweepService.stats``, ``NpzChunkStore.saves`` —
remain as *views* over the registry, so every existing assertion keeps
working), which means a single ``snapshot()`` captures the whole
system's runtime shape and ``tools/obs_report.py`` can render it.

Naming scheme (dotted ``layer.noun[.verb]``, pinned in
docs/architecture.md):

* ``arena.traces`` — scan-body (re)traces
* ``arena.dispatches`` / ``arena.executables_built`` — cumulative run
  totals (per-run deltas stay in ``RolloutReport.meta``; the additive
  per-bucket contract is still cross-checked by
  ``RolloutReport.dispatch_accounting``)
* ``arena.input_cache.hits`` / ``arena.input_cache.misses`` —
  device-input caches (lane constants, channels, lr schedules)
* ``service.batches`` / ``service.scenarios`` / ``service.seconds`` /
  ``service.coalesced_lanes`` / ``service.queue_depth``
* ``store.saves`` / ``store.loads``
* ``pool.admits`` / ``pool.evicts`` / ``pool.uploads`` /
  ``pool.traces`` / ``pool.resident`` / ``pool.quant.abs_err`` — the
  streaming :class:`~repro.fl.client_bank.BankPool`'s churn tallies,
  scatter (re)trace count (1 after warmup, forever — the zero-retrace
  contract), resident-count gauge, and per-admit int8 quantization
  error histogram; ``BankPool.admits`` etc. are views over these

Counters are exact ints, gauges hold the last value, histograms keep a
bounded reservoir (newest kept) plus exact running count/sum so
percentiles degrade gracefully while totals never do.
"""

from __future__ import annotations

import math
from collections import deque
from typing import Any, Dict, Iterable, List, Optional

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry"]


class Counter:
    """Monotonic integer counter."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def inc(self, n: int = 1) -> int:
        if n < 0:
            raise ValueError(f"counter {self.name} cannot decrease "
                             f"(inc({n}))")
        self.value += int(n)
        return self.value


class Gauge:
    """Last-value gauge (e.g. cache sizes, queue depth)."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value: float = 0.0

    def set(self, v: float) -> float:
        self.value = float(v)
        return self.value

    def add(self, v: float) -> float:
        self.value = float(self.value) + float(v)
        return self.value


class Histogram:
    """Bounded-reservoir histogram with exact running count/sum.

    The reservoir keeps the newest ``capacity`` observations (a deque,
    not a sampling scheme — the streaming path wants *recent* latency
    percentiles, and the exact count/sum keep long-run totals honest
    regardless of eviction)."""

    __slots__ = ("name", "values", "count", "total")

    def __init__(self, name: str, capacity: int = 2048):
        self.name = name
        self.values: deque = deque(maxlen=capacity)
        self.count = 0
        self.total = 0.0

    def observe(self, v: float) -> None:
        v = float(v)
        self.values.append(v)
        self.count += 1
        self.total += v

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else math.nan

    def percentiles(self, qs: Iterable[float] = (50.0, 90.0, 99.0)
                    ) -> Dict[float, float]:
        """Nearest-rank percentiles over the (recent) reservoir."""
        out: Dict[float, float] = {}
        vals = sorted(self.values)
        for q in qs:
            if not vals:
                out[float(q)] = math.nan
                continue
            rank = max(0, min(len(vals) - 1,
                              int(math.ceil(q / 100.0 * len(vals))) - 1))
            out[float(q)] = vals[rank]
        return out


class MetricsRegistry:
    """One namespace of counters/gauges/histograms for a subsystem tree
    (an arena plus the service and stores built on it share one
    registry).  Accessors create on first use, so instrumented code
    never has to pre-declare."""

    def __init__(self):
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}

    # -- accessors ----------------------------------------------------------

    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            c = self._counters[name] = Counter(name)
        return c

    def gauge(self, name: str) -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            g = self._gauges[name] = Gauge(name)
        return g

    def histogram(self, name: str, capacity: int = 2048) -> Histogram:
        h = self._histograms.get(name)
        if h is None:
            h = self._histograms[name] = Histogram(name, capacity)
        return h

    # -- views --------------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """Flat JSON-shaped view of everything: counters/gauges by name,
        histograms as ``{count, sum, mean, p50, p90, p99}``."""
        out: Dict[str, Any] = {}
        for name, c in sorted(self._counters.items()):
            out[name] = c.value
        for name, g in sorted(self._gauges.items()):
            out[name] = g.value
        for name, h in sorted(self._histograms.items()):
            ps = h.percentiles()
            out[name] = {"count": h.count, "sum": h.total,
                         "mean": h.mean, "p50": ps[50.0],
                         "p90": ps[90.0], "p99": ps[99.0]}
        return out

    def get(self, name: str, default: Optional[float] = 0) -> Any:
        """Read a metric's current value without creating it."""
        if name in self._counters:
            return self._counters[name].value
        if name in self._gauges:
            return self._gauges[name].value
        if name in self._histograms:
            return self._histograms[name]
        return default

    def names(self) -> List[str]:
        return sorted([*self._counters, *self._gauges, *self._histograms])
