"""Streaming metrics: mergeable fixed-bucket histograms, counters,
gauges, and the exporters that turn one serving/sweep run into a
Prometheus textfile plus a JSON snapshot.

Design constraints (docs/algorithms.md Sec. 13):

  * **Fixed buckets, mergeable.** A :class:`Histogram` owns an immutable
    tuple of upper bucket edges chosen at construction.  Observations
    only increment integer bucket counts (plus ``n``/``sum``/min/max
    accumulators), so merging two histograms with the same edges is
    element-wise integer addition — associative and commutative by
    construction, which is what lets per-run registries from a bench
    grid be folded together in any order (property-tested in
    ``tests/test_metrics.py``).
  * **No jax at module load.** Like the rest of ``repro.obs`` this
    module imports only stdlib + numpy; :func:`memory_snapshot` talks to
    jax solely through ``sys.modules`` so importing the metrics layer
    never initializes a device backend.
  * **Decision-inert taps.** Nothing here is called from inside a jitted
    computation; adapters (:func:`observe_queue_sim`,
    :func:`observe_online_diag`) read results that already exist, so
    enabling metrics cannot perturb cache/routing decisions.

Exposition: :meth:`MetricsRegistry.export_prometheus` writes the
Prometheus textfile format (cumulative ``_bucket{le=...}`` lines,
``_sum``/``_count``, counter/gauge samples) validated by
``scripts/check_metrics.py``; :meth:`MetricsRegistry.export_json` writes
the full mergeable state for offline analysis.
"""
from __future__ import annotations

import bisect
import json
import sys
from dataclasses import dataclass, field

import numpy as np

#: Default latency bucket upper edges (seconds) — log-ish spaced from
#: 1 ms to 60 s, matching the QueueSim latency scales in BENCH_serving.
DEFAULT_LATENCY_EDGES = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                         0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 60.0)
#: Edges for unit-interval quantities (hit rates, fractions).
UNIT_EDGES = (0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0)
#: Edges for small nonnegative counts (downloads in flight, evictions).
COUNT_EDGES = (0.0, 1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
               500.0, 1000.0)


class Histogram:
    """Fixed-bucket streaming histogram.

    ``counts[i]`` counts observations ``v <= edges[i]`` (first matching
    bucket); ``counts[-1]`` is the overflow bucket ``v > edges[-1]``.
    ``merge`` requires identical edges and adds counts — order never
    matters.
    """

    def __init__(self, name: str, edges=DEFAULT_LATENCY_EDGES):
        if not edges or list(edges) != sorted(edges):
            raise ValueError(f"edges must be sorted and non-empty: {edges}")
        self.name = name
        self.edges = tuple(float(e) for e in edges)
        self.counts = [0] * (len(self.edges) + 1)
        self.n = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")

    def observe(self, value: float, count: int = 1):
        v = float(value)
        self.counts[bisect.bisect_left(self.edges, v)] += count
        self.n += count
        self.total += v * count
        self.vmin = min(self.vmin, v)
        self.vmax = max(self.vmax, v)

    def observe_many(self, values):
        for v in np.asarray(values, float).ravel():
            self.observe(float(v))

    def merge(self, other: "Histogram") -> "Histogram":
        """In-place merge; returns self.  Requires identical edges."""
        if other.edges != self.edges:
            raise ValueError(f"bucket mismatch: {self.name} has "
                             f"{len(self.edges)} edges, merge source has "
                             f"{len(other.edges)}")
        self.counts = [a + b for a, b in zip(self.counts, other.counts)]
        self.n += other.n
        self.total += other.total
        self.vmin = min(self.vmin, other.vmin)
        self.vmax = max(self.vmax, other.vmax)
        return self

    @property
    def mean(self) -> float:
        return self.total / self.n if self.n else 0.0

    def percentile(self, q: float) -> float:
        """Approximate percentile by linear interpolation inside the
        containing bucket, clamped to the observed [vmin, vmax]."""
        if self.n == 0:
            return 0.0
        target = (q / 100.0) * self.n
        cum = 0
        for i, c in enumerate(self.counts):
            if c and cum + c >= target:
                lo = self.edges[i - 1] if i > 0 else self.vmin
                hi = self.edges[i] if i < len(self.edges) else self.vmax
                lo = min(max(lo, self.vmin), self.vmax)
                hi = min(max(hi, self.vmin), self.vmax)
                frac = (target - cum) / c
                return lo + frac * (hi - lo)
            cum += c
        return self.vmax

    def to_dict(self) -> dict:
        return {"name": self.name, "edges": list(self.edges),
                "counts": list(self.counts), "n": self.n,
                "sum": self.total,
                "min": self.vmin if self.n else None,
                "max": self.vmax if self.n else None}

    @classmethod
    def from_dict(cls, d: dict) -> "Histogram":
        h = cls(d["name"], d["edges"])
        h.counts = [int(c) for c in d["counts"]]
        h.n = int(d["n"])
        h.total = float(d["sum"])
        h.vmin = float("inf") if d.get("min") is None else float(d["min"])
        h.vmax = float("-inf") if d.get("max") is None else float(d["max"])
        return h


@dataclass
class Counter:
    name: str
    value: float = 0.0

    def inc(self, amount: float = 1.0):
        if amount < 0:
            raise ValueError("counters only go up")
        self.value += amount


@dataclass
class Gauge:
    """Last-value gauge with a high-water mark (merge takes the max, the
    right fold for memory watermarks)."""
    name: str
    value: float = 0.0
    hwm: float = float("-inf")

    def set(self, value: float):
        self.value = float(value)
        self.hwm = max(self.hwm, self.value)


class MetricsRegistry:
    """Named histograms/counters/gauges with get-or-create accessors,
    registry-level merge, and Prometheus/JSON exporters.

    Metric names use Prometheus conventions (``snake_case``, unit
    suffix); the exporters prepend ``repro_``.
    """

    def __init__(self):
        self.histograms: dict = {}
        self.counters: dict = {}
        self.gauges: dict = {}

    def histogram(self, name: str, edges=DEFAULT_LATENCY_EDGES) -> Histogram:
        h = self.histograms.get(name)
        if h is None:
            h = self.histograms[name] = Histogram(name, edges)
        elif h.edges != tuple(float(e) for e in edges):
            raise ValueError(f"histogram {name} re-declared with "
                             "different edges")
        return h

    def counter(self, name: str) -> Counter:
        return self.counters.setdefault(name, Counter(name))

    def gauge(self, name: str) -> Gauge:
        return self.gauges.setdefault(name, Gauge(name))

    def merge(self, other: "MetricsRegistry") -> "MetricsRegistry":
        """Fold another registry into this one (histogram counts add,
        counters add, gauges keep the high-water mark).  Returns self."""
        for name, h in other.histograms.items():
            self.histogram(name, h.edges).merge(h)
        for name, c in other.counters.items():
            self.counter(name).inc(c.value)
        for name, g in other.gauges.items():
            mine = self.gauge(name)
            mine.set(max(mine.value, g.value) if mine.hwm > float("-inf")
                     else g.value)
            mine.hwm = max(mine.hwm, g.hwm)
        return self

    def to_dict(self) -> dict:
        return {
            "histograms": {k: h.to_dict()
                           for k, h in sorted(self.histograms.items())},
            "counters": {k: c.value
                         for k, c in sorted(self.counters.items())},
            "gauges": {k: {"value": g.value,
                           "max": None if g.hwm == float("-inf") else g.hwm}
                       for k, g in sorted(self.gauges.items())},
        }

    @classmethod
    def from_dict(cls, d: dict) -> "MetricsRegistry":
        reg = cls()
        for k, hd in d.get("histograms", {}).items():
            reg.histograms[k] = Histogram.from_dict(hd)
        for k, v in d.get("counters", {}).items():
            reg.counters[k] = Counter(k, float(v))
        for k, gd in d.get("gauges", {}).items():
            g = reg.gauge(k)
            g.value = float(gd["value"])
            g.hwm = (float("-inf") if gd.get("max") is None
                     else float(gd["max"]))
        return reg

    # -- exporters --------------------------------------------------

    def render_prometheus(self, prefix: str = "repro_") -> str:
        lines = []
        for name, h in sorted(self.histograms.items()):
            full = prefix + name
            lines.append(f"# HELP {full} repro streaming histogram")
            lines.append(f"# TYPE {full} histogram")
            cum = 0
            for e, c in zip(h.edges, h.counts):
                cum += c
                lines.append(f'{full}_bucket{{le="{format(e, "g")}"}} {cum}')
            lines.append(f'{full}_bucket{{le="+Inf"}} {h.n}')
            lines.append(f"{full}_sum {format(h.total, '.17g')}")
            lines.append(f"{full}_count {h.n}")
        for name, c in sorted(self.counters.items()):
            full = prefix + name
            lines.append(f"# HELP {full} repro counter")
            lines.append(f"# TYPE {full} counter")
            lines.append(f"{full} {format(c.value, '.17g')}")
        for name, g in sorted(self.gauges.items()):
            full = prefix + name
            lines.append(f"# HELP {full} repro gauge")
            lines.append(f"# TYPE {full} gauge")
            lines.append(f"{full} {format(g.value, '.17g')}")
        return "\n".join(lines) + "\n"

    def export_prometheus(self, path, prefix: str = "repro_"):
        with open(path, "w") as f:
            f.write(self.render_prometheus(prefix))
        return path

    def export_json(self, path):
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)
        return path


# -- stack adapters: one metrics schema for serving + online runs ------

def observe_queue_sim(registry: MetricsRegistry, sim) -> MetricsRegistry:
    """Fold one finished ``QueueSim`` run into the shared schema:
    per-request latency + its exact attribution phases (queue wait,
    loading stall, service) as histograms, outcome counters.  Reads the
    simulator after the fact — cannot perturb its decisions."""
    lat = registry.histogram("request_latency_seconds")
    que = registry.histogram("request_queue_seconds")
    stl = registry.histogram("request_stall_seconds")
    svc = registry.histogram("request_service_seconds")
    for r in sim.done:
        lat.observe(r.latency)
        que.observe(r.queue_s)
        stl.observe(r.stall_s)
        svc.observe(r.service_s)
    registry.counter("requests_served_total").inc(len(sim.done))
    registry.counter("requests_dropped_total").inc(sim.dropped)
    registry.counter("deadline_misses_total").inc(
        sim.dropped + sum(not r.met_slo for r in sim.done))
    return registry


def observe_online_diag(registry: MetricsRegistry, diag: dict
                        ) -> MetricsRegistry:
    """Fold one online run's per-slot telemetry (the ``diagnostics=True``
    curves from ``repro.traces.engine``: hit_rate, dl_in_flight,
    evictions, cache_mb) into the same histogram types the serving plane
    uses, so one textfile carries both planes."""
    if "hit_rate" in diag:
        registry.histogram("online_hit_rate", UNIT_EDGES).observe_many(
            diag["hit_rate"])
    if "dl_in_flight" in diag:
        registry.histogram("online_dl_in_flight", COUNT_EDGES
                           ).observe_many(diag["dl_in_flight"])
    if "evictions" in diag:
        ev = np.asarray(diag["evictions"], float).ravel()
        registry.histogram("online_evictions", COUNT_EDGES
                           ).observe_many(ev)
        registry.counter("online_evictions_total").inc(float(ev.sum()))
    if "cache_mb" in diag:
        cm = np.asarray(diag["cache_mb"], float).ravel()
        if cm.size:
            g = registry.gauge("online_cache_mb")
            g.set(float(cm[-1]))
            g.hwm = max(g.hwm, float(cm.max()))
    return registry


# -- memory watermarks -------------------------------------------------

def _host_rss_kb() -> float:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1])
    except OSError:
        pass
    return _host_maxrss_kb()


def _host_maxrss_kb() -> float:
    try:
        import resource
        return float(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
    except Exception:  # pragma: no cover - non-POSIX fallback
        return 0.0


def memory_snapshot() -> dict:
    """Host RSS (current + peak, kB) and — when jax is already imported
    by the caller — live device-array bytes via ``jax.live_arrays()``
    (falling back to the backend's ``live_buffers``).  Importing this
    module never pulls in jax; a process that never touched jax gets
    host numbers only."""
    snap = {"host_rss_kb": _host_rss_kb(),
            "host_maxrss_kb": _host_maxrss_kb()}
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            arrs = jax.live_arrays()
        except Exception:
            try:
                arrs = jax.devices()[0].client.live_buffers()
            except Exception:
                arrs = None
        if arrs is not None:
            snap["device_live_bytes"] = int(
                sum(int(getattr(a, "nbytes", 0)) for a in arrs))
            snap["device_live_arrays"] = len(arrs)
    return snap
