"""Process-wide metrics registry: named counters, gauges, and histograms.

The engine's caches and memos each kept private hit/miss integers that only
bench.py knew how to scrape, and only for the caches it knew about. This
registry is the one place every component reports to — `scan_cache`,
`device_cache`, the device memos in `engine/physical`, the decode pool in
`engine/io`, and the optimizer rules — so a
query's cache behavior is answerable from one `snapshot()` (consumed by
`bench_detail.metrics_snapshot` and `explain(analyze=True)`).

Contracts:
- Metric objects are cheap, lock-guarded, and process-wide singletons per
  name: `counter("cache.scan.hits").inc()` from any thread never loses an
  update (pinned by tests/test_tracing.py's pool hammer).
- `snapshot()` is a point-in-time copy (plain dicts, JSON-serializable) and
  includes derived `rates` for every `<base>.hits`/`<base>.misses` counter
  pair, so hit RATES ride the bench artifact without consumer arithmetic.
- Metrics are always on (integer adds; no env gate): unlike spans they cannot
  trigger device work or allocation growth — the registry holds one object
  per metric NAME, never per observation.
"""

from __future__ import annotations

import bisect
import math
import threading
from typing import Dict, List, Optional, Tuple


class Counter:
    """Monotonic counter. `inc` is atomic under the metric's own lock."""

    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += int(n)

    def reset(self) -> None:
        with self._lock:
            self._value = 0

    @property
    def value(self) -> int:
        with self._lock:
            return self._value


class Gauge:
    """Last-write-wins scalar (e.g. bytes currently pinned). `add` supports
    up/down accounting (e.g. the decode pool's in-flight depth), which `set`
    alone cannot do race-free from concurrent workers."""

    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, v) -> None:
        with self._lock:
            self._value = v

    def add(self, n) -> None:
        with self._lock:
            self._value += n

    def inc(self, n=1) -> None:
        self.add(n)

    def dec(self, n=1) -> None:
        self.add(-n)

    def set_max(self, v) -> None:
        """High-water mark: keep the larger of the current and new value."""
        with self._lock:
            if v > self._value:
                self._value = v

    def reset(self) -> None:
        with self._lock:
            self._value = 0.0

    @property
    def value(self):
        with self._lock:
            return self._value


#: Shared log-spaced bucket upper bounds: 4 per decade over 1e-6 … 1e10
#: (microseconds → device-byte counts), Prometheus-style cumulative-`le`
#: semantics, ONE fixed 66-slot array per histogram regardless of observation
#: count. Quantile error is bounded by the bucket width (≤ 10^0.25 ≈ 1.78×
#: relative), which is what a latency p99 needs — the exact extremes still
#: ride `min`/`max`.
BUCKET_BOUNDS: Tuple[float, ...] = tuple(10.0 ** (k / 4.0) for k in range(-24, 41))
_N_BUCKETS = len(BUCKET_BOUNDS) + 1  # + overflow (+Inf)


class Histogram:
    """Quantile histogram: count / total / min / max PLUS bounded log-spaced
    buckets (`BUCKET_BOUNDS`), so `summary()` carries p50/p90/p99. The four
    summary fields keep their exact pre-bucket semantics — every existing
    `bench_detail` consumer reads them unchanged; the quantile keys are
    additive. Fixed memory per metric name, lock-guarded like the counters."""

    __slots__ = ("name", "_lock", "count", "total", "min", "max", "_buckets")

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self.count = 0
        self.total = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self._buckets = [0] * _N_BUCKETS

    def observe(self, v) -> None:
        v = float(v)
        # Non-positive observations (0.0 durations exist) land in the first
        # bucket; bisect_left puts an exact boundary value in its own bucket.
        idx = bisect.bisect_left(BUCKET_BOUNDS, v) if v > 0.0 else 0
        with self._lock:
            self.count += 1
            self.total += v
            self.min = v if self.min is None or v < self.min else self.min
            self.max = v if self.max is None or v > self.max else self.max
            self._buckets[idx] += 1

    def reset(self) -> None:
        with self._lock:
            self.count = 0
            self.total = 0.0
            self.min = None
            self.max = None
            self._buckets = [0] * _N_BUCKETS

    def _quantile_locked(self, q: float) -> Optional[float]:
        if self.count == 0:
            return None
        rank = max(1, math.ceil(q * self.count))
        cum = 0
        for i, n in enumerate(self._buckets):
            if n == 0:
                continue
            if cum + n >= rank:
                lo = BUCKET_BOUNDS[i - 1] if i > 0 else 0.0
                hi = (
                    BUCKET_BOUNDS[i]
                    if i < len(BUCKET_BOUNDS)
                    else (self.max if self.max is not None else lo)
                )
                est = lo + (hi - lo) * ((rank - cum) / n)
                # Clamp to the observed range: an estimate can never claim a
                # latency outside what was actually seen. min/max can be
                # absent with count>0 after merging a checkpoint that
                # carried buckets but no extrema (forward-compat tolerates
                # that) — clamp only on the bounds we have.
                if self.min is not None:
                    est = max(est, self.min)
                if self.max is not None:
                    est = min(est, self.max)
                return est
            cum += n
        return self.max

    def quantile(self, q: float) -> Optional[float]:
        """Estimated q-quantile (0 < q ≤ 1) from the log buckets, clamped to
        the observed [min, max]. None before any observation."""
        with self._lock:
            return self._quantile_locked(q)

    def bucket_counts(self) -> List[Tuple[float, int]]:
        """Cumulative (upper_bound, count≤bound) pairs for the non-empty
        bucket range plus the +Inf total — the Prometheus text-exposition
        shape (`exporter.prometheus_text`). Empty list before any
        observation (no 66-pair noise for untouched metrics)."""
        return self.export_state()[2]

    def summary(self) -> dict:
        with self._lock:
            out = {
                "count": self.count,
                "total": round(self.total, 6),
                "min": self.min,
                "max": self.max,
            }
            if self.count:
                for key, q in (("p50", 0.5), ("p90", 0.9), ("p99", 0.99)):
                    v = self._quantile_locked(q)
                    out[key] = None if v is None else round(v, 6)
            return out

    def dump_state(self) -> dict:
        """JSON-serializable bucket state (sparse: only non-empty slots) —
        what the workload history store checkpoints into its segments so a
        baseline survives process restart and segment compaction without
        keeping every raw observation."""
        with self._lock:
            out = {"count": self.count, "total": round(self.total, 9)}
            if self.min is not None:
                out["min"] = self.min
                out["max"] = self.max
            buckets = {str(i): n for i, n in enumerate(self._buckets) if n}
            if buckets:
                out["buckets"] = buckets
            return out

    def merge_state(self, state: dict) -> None:
        """Fold a `dump_state` payload INTO this histogram (additive: counts
        and bucket slots sum, min/max extend). Unknown keys are ignored and
        malformed fields are skipped — the forward-compat contract of the
        history segment reader. Everything is PARSED before anything is
        mutated: one corrupt checkpoint record must neither raise nor leave
        a half-merged histogram (count without bucket mass)."""
        if not isinstance(state, dict):
            return
        try:
            count = int(state.get("count", 0))
            total = float(state.get("total", 0.0))
        except (TypeError, ValueError):
            return
        def _num(v):
            return float(v) if isinstance(v, (int, float)) and not isinstance(v, bool) else None
        mn, mx = _num(state.get("min")), _num(state.get("max"))
        buckets = []
        raw = state.get("buckets")
        if isinstance(raw, dict):
            for key, n in raw.items():
                try:
                    i, cnt = int(key), int(n)
                except (TypeError, ValueError):
                    continue
                if 0 <= i < _N_BUCKETS:
                    buckets.append((i, cnt))
        with self._lock:
            self.count += count
            self.total += total
            if mn is not None:
                self.min = mn if self.min is None else min(self.min, mn)
            if mx is not None:
                self.max = mx if self.max is None else max(self.max, mx)
            for i, cnt in buckets:
                self._buckets[i] += cnt

    def export_state(self) -> Tuple[int, float, List[Tuple[float, int]]]:
        """(count, total, cumulative buckets) read under ONE lock hold — the
        Prometheus exposition needs `_count` to equal the +Inf bucket, which
        separate `summary()`/`bucket_counts()` reads cannot guarantee under
        concurrent observes."""
        with self._lock:
            out: List[Tuple[float, int]] = []
            if self.count:
                cum = 0
                for i, n in enumerate(self._buckets):
                    cum += n
                    if n and i < len(BUCKET_BOUNDS):
                        out.append((BUCKET_BOUNDS[i], cum))
                out.append((math.inf, self.count))
            return self.count, self.total, out


class MetricsRegistry:
    """Name → metric map. Creation is get-or-create under one registry lock;
    reads/writes of individual metrics take only that metric's lock."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        with self._lock:
            m = self._counters.get(name)
            if m is None:
                m = self._counters[name] = Counter(name)
            return m

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            m = self._gauges.get(name)
            if m is None:
                m = self._gauges[name] = Gauge(name)
            return m

    def histogram(self, name: str) -> Histogram:
        with self._lock:
            m = self._histograms.get(name)
            if m is None:
                m = self._histograms[name] = Histogram(name)
            return m

    def snapshot(self) -> dict:
        """Point-in-time copy of every metric, JSON-serializable. Derived
        `rates` pair up `<base>.hits` / `<base>.misses` counters."""
        with self._lock:
            counters = {n: c.value for n, c in self._counters.items()}
            gauges = {n: g.value for n, g in self._gauges.items()}
            hists = {n: h.summary() for n, h in self._histograms.items()}
        rates = {}
        for name, hits in counters.items():
            # Exact last-segment match: "memo.pairs.peek_hits" must not pair
            # (it has no miss twin — a bogus 1.0 rate would ride the bench).
            base, _, leaf = name.rpartition(".")
            if leaf != "hits" or not base:
                continue
            total = hits + counters.get(base + ".misses", 0)
            if total:
                rates[base] = round(hits / total, 4)
        out = {"counters": counters}
        if gauges:
            out["gauges"] = gauges
        if hists:
            out["histograms"] = hists
        if rates:
            out["rates"] = rates
        return out

    def reset(self) -> None:
        """Zero every metric IN PLACE (tests; the bench never resets —
        lifetime accounting stays monotonic like the cache stats). Metric
        objects stay registered: hot paths bind them once at import
        (`device_cache._HITS`, `physical._MEMO_*`, …), so clearing the maps
        would silently orphan them — their increments would never reach
        `snapshot()` again."""
        with self._lock:
            metrics = (
                list(self._counters.values())
                + list(self._gauges.values())
                + list(self._histograms.values())
            )
        for m in metrics:
            m.reset()


_REGISTRY = MetricsRegistry()


def global_registry() -> MetricsRegistry:
    return _REGISTRY


def counter(name: str) -> Counter:
    return _REGISTRY.counter(name)


def gauge(name: str) -> Gauge:
    return _REGISTRY.gauge(name)


def histogram(name: str) -> Histogram:
    return _REGISTRY.histogram(name)


def snapshot() -> dict:
    return _REGISTRY.snapshot()


def counters_delta(before: dict, after: dict) -> dict:
    """Counter names whose value changed between two `snapshot()`s — the
    per-query attribution `explain(analyze=True)` prints."""
    b = before.get("counters", {})
    out = {}
    for name, v in after.get("counters", {}).items():
        d = v - b.get(name, 0)
        if d:
            out[name] = d
    return out
