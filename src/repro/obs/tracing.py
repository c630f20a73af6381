"""Host-side tracing: nested spans on the monotonic clock + retrace
accounting for jitted entry points.

``time.time()`` is not monotonic (NTP can step it backwards mid-run),
so every duration here comes from ``time.perf_counter``.  A ``Tracer``
records a tree of :class:`Span`\\ s — one per ``with tracer.span(...)``
block — with the block's wall time and the CPU time its thread spent
in it (``time.thread_time``).  It keeps the newest :data:`MAX_SPANS`;
:meth:`Tracer.since` reads the spans that started after a point in
time, and :meth:`Tracer.export_jsonl` writes them out, one per line.

Each span also opens a ``jax.profiler.TraceAnnotation`` of its name,
so under ``jax.profiler.trace`` (or ``start_trace``) the program's
spans appear as named host events on the profiler's own clock, next to
the device's operations.

Retrace accounting: dispatch sites register their jitted callables
under stable names (:func:`register_jit`); a span snapshots the
per-entry-point compile-cache sizes (``fn._cache_size()``) on entry and
records the delta on exit as ``Span.retraces``.  A warm dispatch spans
``retraces == 0``; a span that compiled records how many new
executables it cost — which is how the report separates compile time
from execute time, and how ``tests/test_obs.py`` turns "repeat sweeps
retrace nothing" into an enforced invariant.  Fine-grained spans pass
``count_retraces=False`` and skip the snapshot.

No jax import happens at module load: jax is imported at the first
span — safe to import from anywhere, including before ``XLA_FLAGS`` is
set.
"""
from __future__ import annotations

import json
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field

# ---------------------------------------------------------------------------
# jitted entry point registry (retrace accounting)
# ---------------------------------------------------------------------------

_JIT_REGISTRY: dict = {}


def register_jit(name: str, fn):
    """Register a jitted callable under a stable name so its compile
    cache can be watched for retraces.  Idempotent; returns ``fn``."""
    _JIT_REGISTRY[str(name)] = fn
    return fn


def jit_cache_sizes() -> dict:
    """{registered name: current compile-cache size}.  Entries whose
    callable does not expose ``_cache_size`` report -1."""
    out = {}
    for name, fn in _JIT_REGISTRY.items():
        size = fn._cache_size() if hasattr(fn, "_cache_size") else -1
        out[name] = int(size)
    return out


def retrace_snapshot() -> dict:
    """A point-in-time copy of :func:`jit_cache_sizes` — pass it to
    :func:`retraces_since` after the work you want to account."""
    return jit_cache_sizes()


def retraces_since(snapshot: dict) -> dict:
    """{name: newly compiled executables since ``snapshot``} — only
    positive deltas; entry points registered after the snapshot count
    their full cache size."""
    now = jit_cache_sizes()
    out = {}
    for name, size in now.items():
        delta = size - snapshot.get(name, 0)
        if delta > 0 and size >= 0:
            out[name] = delta
    return out


def total_retraces_since(snapshot: dict) -> int:
    return sum(retraces_since(snapshot).values())


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

#: spans a :class:`Tracer` keeps; older ones are dropped
MAX_SPANS = 4096
#: the prefix of the benchmark harness's own profiler annotations
#: (``chipbench``), which no program span may take
RESERVED_PREFIX = "bench:"

_TraceAnnotation = None


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` of ``name``: a host event in
    the profiler's trace while one is being taken, next to nothing
    otherwise."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    return _TraceAnnotation(name)


@dataclass
class Span:
    """One timed block: name, perf_counter start, wall and thread CPU
    seconds, nesting (depth + the parent's ``index``; ``index`` counts
    every span the tracer has opened, so it stays valid after older
    spans are dropped), free-form attrs, and the retrace count its work
    caused."""
    name: str
    t0: float
    seconds: float = 0.0
    cpu_seconds: float = 0.0
    depth: int = 0
    index: int = 0
    parent: int = -1
    attrs: dict = field(default_factory=dict)
    retraces: int = 0

    def to_dict(self) -> dict:
        return {"name": self.name, "t0": self.t0,
                "seconds": self.seconds, "cpu_seconds": self.cpu_seconds,
                "depth": self.depth, "index": self.index,
                "parent": self.parent, "retraces": self.retraces,
                "attrs": self.attrs}


class Tracer:
    """Records a tree of spans, the newest :data:`MAX_SPANS` of them;
    export as JSONL."""

    def __init__(self):
        self._spans: deque = deque(maxlen=MAX_SPANS)
        self._stack: list[int] = []
        self._opened = 0

    @contextmanager
    def span(self, name: str, *, count_retraces: bool = True, **attrs):
        """Time a block, and name it in the profiler's trace.  Yields the
        (open) :class:`Span`; its ``seconds``, ``cpu_seconds`` and
        ``retraces`` (counted only with ``count_retraces``) are filled
        when the block exits."""
        if str(name).startswith(RESERVED_PREFIX):
            raise ValueError(f"span names may not start with "
                             f"{RESERVED_PREFIX!r}: {name!r}")
        sp = Span(name=str(name), t0=time.perf_counter(),
                  depth=len(self._stack), index=self._opened,
                  parent=self._stack[-1] if self._stack else -1,
                  attrs=dict(attrs))
        self._opened += 1
        self._spans.append(sp)
        self._stack.append(sp.index)
        snap = retrace_snapshot() if count_retraces else None
        cpu0 = time.thread_time()
        try:
            with _annotation(sp.name):
                yield sp
        finally:
            sp.cpu_seconds = time.thread_time() - cpu0
            sp.seconds = time.perf_counter() - sp.t0
            if snap is not None:
                sp.retraces = total_retraces_since(snap)
            self._stack.pop()

    @property
    def spans(self) -> list:
        """The spans kept, in start order."""
        return list(self._spans)

    def since(self, t: float) -> list:
        """The kept spans that started at or after perf_counter time
        ``t``, in start order."""
        out = []
        for sp in reversed(self._spans):
            if sp.t0 < t:
                break
            out.append(sp)
        return out[::-1]

    def reset(self):
        self._spans.clear()
        self._stack.clear()

    def export_jsonl(self, path):
        """One span per line, in start order."""
        lines = [json.dumps(sp.to_dict()) for sp in self._spans]
        with open(path, "w") as f:
            f.write("\n".join(lines) + ("\n" if lines else ""))
        return path


#: The process-wide default tracer every dispatch site records into.
TRACER = Tracer()


@contextmanager
def span(name: str, **attrs):
    """``with obs.span("solve"): ...`` — sugar for ``TRACER.span``."""
    with TRACER.span(name, **attrs) as sp:
        yield sp
