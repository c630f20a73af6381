"""``repro.obs`` — the observability layer: host-side span tracing,
retrace accounting, run manifests, streaming metrics, per-request
events, and jit-safe solver/engine diagnostics summaries.

Five parts (docs/algorithms.md Sec. 10 and 13):

  * :mod:`repro.obs.tracing` — ``Span``/``Tracer`` built on the
    monotonic ``time.perf_counter``, with thread CPU time per span, the
    spans named in any ``jax.profiler`` trace, ``Tracer.since`` and
    JSONL export as the in-process reads, and a registry of jitted
    entry points whose compile-cache sizes turn into per-span retrace
    counts;
  * :mod:`repro.obs.manifest` — ``run_manifest``/``write_manifest``:
    git SHA, jax/device info, x64 flags, seeds, and a config hash next
    to every emitted results file;
  * :mod:`repro.obs.diagnostics` — host-side summaries of the jit-safe
    diagnostics pytrees the kernels emit (``diagnostics=True`` through
    ``repro.core.lp``, ``repro.traces.engine`` and the ``repro.scale``
    executor);
  * :mod:`repro.obs.metrics` — mergeable fixed-bucket streaming
    histograms, counters, gauges; Prometheus-textfile + JSON exporters;
    adapters folding QueueSim runs and online engine telemetry into one
    shared schema; :func:`memory_snapshot` device/host watermarks;
  * :mod:`repro.obs.events` — the structured per-request event log the
    queue simulator emits (arrival/route/queue/stall/service +
    finish|miss|drop terminals, with a conservation check).

This package imports neither jax nor any ``repro`` sibling at module
load, so every dispatch site can depend on it without import cycles or
early device initialization.
"""
from repro.obs.diagnostics import (DEFAULT_TOL, convergence_table,
                                   lp_diag_summary)
from repro.obs.events import PHASE_KINDS, TERMINAL_KINDS, Event, EventLog
from repro.obs.manifest import config_hash, run_manifest, write_manifest
from repro.obs.metrics import (COUNT_EDGES, DEFAULT_LATENCY_EDGES,
                               UNIT_EDGES, Counter, Gauge, Histogram,
                               MetricsRegistry, memory_snapshot,
                               observe_online_diag, observe_queue_sim)
from repro.obs.tracing import (TRACER, Span, Tracer, jit_cache_sizes,
                               register_jit, retrace_snapshot,
                               retraces_since, span, total_retraces_since)
