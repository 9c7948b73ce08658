"""Metrics registry: counters, gauges, histograms, structured events
(DESIGN.md §10.2).

Also the canonical home of the percentile/summary math — the one
nearest-rank :func:`percentile` the serving metrics, the benchmark
timers, and the tests all share (previously each carried its own copy).

A :class:`MetricsRegistry` is plain host-side bookkeeping: integer adds
and list appends, never anything traced — it is always on (the serving
metrics have always been) and costs nanoseconds per update.  The default
process registry is what the runtime/serving/autotune instrumentation
writes to; tests swap a fresh one in with :func:`use_registry`.

Metric naming: dot-separated ``subsystem.metric`` with units in the
suffix (``_s`` seconds, ``_ms`` milliseconds, ``_bytes`` bytes); the
full catalogue lives in DESIGN.md §10.2.
"""

from __future__ import annotations

import contextlib
import math
from collections import deque
from typing import Callable, Iterable, Sequence

from repro.obs.trace import clock as _clock


# ---- canonical percentile / summary math ----------------------------------

def percentile(sorted_vals: Sequence[float], p: float) -> float | None:
    """Nearest-rank percentile of an ascending sequence (None when
    empty): the smallest value with at least ``p`` of the sample at or
    below it, i.e. index ``ceil(p*n) - 1``."""
    n = len(sorted_vals)
    if not n:
        return None
    return sorted_vals[max(0, min(n - 1, math.ceil(p * n) - 1))]


def summarize(samples: Iterable[float]) -> dict:
    """count/min/max/mean/p50/p95 of a sample (the one summary shape)."""
    vals = sorted(samples)
    if not vals:
        return {"count": 0, "min": None, "max": None, "mean": None,
                "p50": None, "p95": None}
    return {"count": len(vals), "min": vals[0], "max": vals[-1],
            "mean": sum(vals) / len(vals),
            "p50": percentile(vals, 0.50), "p95": percentile(vals, 0.95)}


# ---- primitives ------------------------------------------------------------

class Counter:
    """Monotonic event count."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def inc(self, n: int = 1) -> None:
        self.value += n


class Gauge:
    """Last-written value (e.g. a plan's ``peak_bytes``)."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = None

    def set(self, v) -> None:
        self.value = v


class Histogram:
    """Sample accumulator summarized via the canonical percentile math."""

    __slots__ = ("name", "samples")

    def __init__(self, name: str):
        self.name = name
        self.samples: list[float] = []

    def observe(self, v: float) -> None:
        self.samples.append(v)

    def observe_many(self, vals: Iterable[float]) -> None:
        self.samples.extend(vals)

    @property
    def count(self) -> int:
        return len(self.samples)

    def summary(self) -> dict:
        return summarize(self.samples)


class MetricsRegistry:
    """Named counters/gauges/histograms plus a bounded structured-event
    ring (``event()`` — what the autotuner's hit/miss audit trail uses)."""

    def __init__(self, max_events: int = 4096):
        self._metrics: dict[str, Counter | Gauge | Histogram] = {}
        self._events: deque[dict] = deque(maxlen=max_events)

    def _get(self, name: str, cls):
        m = self._metrics.get(name)
        if m is None:
            m = self._metrics[name] = cls(name)
        elif not isinstance(m, cls):
            raise TypeError(f"metric {name!r} already registered as "
                            f"{type(m).__name__}, not {cls.__name__}")
        return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    # ---- structured events ------------------------------------------------
    def event(self, name: str, **fields) -> dict:
        ev = dict(event=name, **fields)
        self._events.append(ev)
        return ev

    def events(self, name: str | None = None) -> list[dict]:
        return [e for e in self._events
                if name is None or e["event"] == name]

    # ---- reporting --------------------------------------------------------
    def snapshot(self) -> dict:
        """name -> value (counters/gauges) or summary dict (histograms)."""
        out: dict = {}
        for name, m in sorted(self._metrics.items()):
            out[name] = m.summary() if isinstance(m, Histogram) else m.value
        return out

    def reset(self) -> None:
        self._metrics.clear()
        self._events.clear()


_REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The default process registry (what instrumentation writes to)."""
    return _REGISTRY


def set_registry(registry: MetricsRegistry) -> MetricsRegistry:
    global _REGISTRY
    prev, _REGISTRY = _REGISTRY, registry
    return prev


@contextlib.contextmanager
def use_registry(registry: MetricsRegistry | None = None):
    """Swap in a registry (default: a fresh one) for a scope — how tests
    isolate their counts from process-global state."""
    reg = registry if registry is not None else MetricsRegistry()
    prev = set_registry(reg)
    try:
        yield reg
    finally:
        set_registry(prev)


# ---- serving metrics (shared by both servers) ------------------------------

class ServingMetrics:
    """Latency/throughput bookkeeping shared by both servers (DESIGN.md
    §7.4) — now a thin view over registry primitives: the latency
    histogram, served/dropped counters, and the busy window, emitting the
    same ``metrics()`` dict shape as ever.  The busy window uses the
    owner's (injectable) clock — under a fake clock, throughput reports
    simulated time, the same domain as the latency percentiles.

    ``registry`` defaults to a **private** :class:`MetricsRegistry` per
    instance — two servers in one process must not sum each other's
    ``served`` — exposed as ``.registry`` so callers can read the series
    (``serve.latency_s``, ``serve.bucket_size``, ...) directly.  The
    process registry keeps the runtime-wide series (autotune, retraces,
    arena bytes) that *are* shared."""

    def __init__(self, clock: Callable[[], float] = _clock,
                 registry: MetricsRegistry | None = None,
                 prefix: str = "serve"):
        self._clock = clock
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self._lat = self.registry.histogram(f"{prefix}.latency_s")
        self._served = self.registry.counter(f"{prefix}.served")
        self._dropped = self.registry.counter(f"{prefix}.dropped")
        self._buckets = self.registry.histogram(f"{prefix}.bucket_size")
        # Resilience series (DESIGN.md §11): retries, terminal errors,
        # admission rejections, and backend demotions.
        self._retries = self.registry.counter(f"{prefix}.retries")
        self._errors = self.registry.counter(f"{prefix}.errors")
        self._rejected = self.registry.counter(f"{prefix}.rejected")
        self._degraded = self.registry.counter(f"{prefix}.degraded")
        self._t_first: float | None = None
        self._t_last: float | None = None

    @property
    def latencies(self) -> list[float]:
        return self._lat.samples

    @property
    def served(self) -> int:
        return self._served.value

    def mark_dispatch(self, bucket: int | None = None) -> None:
        """First device work entered flight: the busy window opens.
        ``bucket`` (when known) feeds the per-bucket dispatch histogram."""
        if bucket is not None:
            self._buckets.observe(bucket)
        if self._t_first is None:
            self._t_first = self._clock()

    def record(self, latencies: list[float]) -> None:
        """A batch of requests completed with these submit→done times."""
        self._lat.observe_many(latencies)
        self._served.inc(len(latencies))
        self._t_last = self._clock()

    def record_dropped(self, n: int = 1) -> None:
        self._dropped.inc(n)

    def record_retry(self, n: int = 1) -> None:
        self._retries.inc(n)

    def record_error(self, n: int = 1) -> None:
        self._errors.inc(n)

    def record_rejected(self, n: int = 1) -> None:
        self._rejected.inc(n)

    def record_degraded(self, n: int = 1) -> None:
        self._degraded.inc(n)

    def snapshot(self, *, dropped: int, queue_depth: int,
                 **extra) -> dict:
        lat = sorted(self.latencies)
        busy = (self._t_last - self._t_first
                if self._t_first is not None and self._t_last is not None
                else None)
        return {
            "served": self.served,
            "dropped": dropped,
            "retries": self._retries.value,
            "errors": self._errors.value,
            "rejected": self._rejected.value,
            "degraded": self._degraded.value,
            "queue_depth": queue_depth,
            "p50_ms": None if not lat else percentile(lat, 0.50) * 1e3,
            "p95_ms": None if not lat else percentile(lat, 0.95) * 1e3,
            "throughput": (self.served / busy if busy else None),
            **extra,
        }
