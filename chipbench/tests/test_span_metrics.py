"""The readers of the program's spans (``grid_host_ms``,
``device_wait_ms``, ``fetch_ms``, ``grid_cpu_ms``) on spans planted in
a tracer of their own, around a window of known bounds."""
import time
from types import SimpleNamespace

import pytest

from chipbench import harness as H
from repro.obs import Tracer, tracing

#: per stage span: (wall s, thread CPU s) planted in each window step
PLANTED = {"run_grid": (0.600, 0.020), "grid.prepare": (0.003, 0.003),
           "chunk": (0.590, 0.010), "grid.put": (0.001, 0.001),
           "grid.dispatch": (0.002, 0.002), "grid.wait": (0.580, 0.0001),
           "grid.fetch": (0.0005, 0.0004), "grid.unstack": (0.004, 0.004)}


def _step(tr):
    """One decision's spans, nested as the grid executor opens them,
    with the planted times."""
    spans = []

    def span(name):
        return tr.span(name, count_retraces=False)

    with span("run_grid") as rg:
        spans.append(rg)
        with span("grid.prepare") as sp:
            spans.append(sp)
        with span("chunk") as c:
            spans.append(c)
            for name in ("grid.put", "grid.dispatch", "grid.wait",
                         "grid.fetch"):
                with span(name) as sp:
                    spans.append(sp)
        with span("grid.unstack") as sp:
            spans.append(sp)
    for sp in spans:
        sp.seconds, sp.cpu_seconds = PLANTED[sp.name]


@pytest.fixture
def window(monkeypatch):
    """A tracer holding one step before the window, three inside it and
    one after; the harness's context for that window."""
    tr = Tracer()
    monkeypatch.setattr(tracing, "TRACER", tr)
    _step(tr)
    ctx = SimpleNamespace(window_t0=time.perf_counter(), counters={})
    for _ in range(3):
        _step(tr)
    ctx.window_t1 = time.perf_counter()
    _step(tr)
    ctx.counters["decisions"] = 3
    return ctx


@pytest.mark.parametrize("metric,expected_ms", [
    ("grid_host_ms", 1e3 * (0.003 + 0.001 + 0.002 + 0.004)),
    ("device_wait_ms", 580.0),
    ("fetch_ms", 0.5),
    ("grid_cpu_ms", 20.0),
])
def test_reader_mean_over_window(window, metric, expected_ms):
    value = H.load_module("metrics", metric).read(window, None)
    assert value == pytest.approx(expected_ms, rel=1e-12)


@pytest.mark.parametrize("metric", ["grid_host_ms", "device_wait_ms",
                                    "fetch_ms", "grid_cpu_ms"])
def test_reader_without_decisions_or_spans(window, monkeypatch, metric):
    mod = H.load_module("metrics", metric)
    window.counters["decisions"] = 0
    assert mod.read(window, None) is None
    # a program whose tracer has no ``since`` gives no reading
    window.counters["decisions"] = 3
    monkeypatch.setattr(tracing, "TRACER", SimpleNamespace(spans=[]))
    assert mod.read(window, None) is None
    # nor does a window in which no span of the metric started
    monkeypatch.setattr(tracing, "TRACER", Tracer())
    assert mod.read(window, None) is None


@pytest.mark.parametrize("metric", ["grid_host_ms", "device_wait_ms",
                                    "fetch_ms", "grid_cpu_ms"])
def test_reader_after_ring_wrap(monkeypatch, metric):
    """A window that opened more spans than the tracer keeps gives no
    reading, not a sum of the spans left; one that fits reads whole."""
    mod = H.load_module("metrics", metric)
    monkeypatch.setattr(tracing, "MAX_SPANS", 3 * len(PLANTED))
    for steps, wraps in ((4, True), (2, False)):
        tr = Tracer()
        monkeypatch.setattr(tracing, "TRACER", tr)
        _step(tr)
        ctx = SimpleNamespace(window_t0=time.perf_counter(),
                              counters={"decisions": steps})
        for _ in range(steps):
            _step(tr)
        ctx.window_t1 = time.perf_counter()
        assert (mod.read(ctx, None) is None) == wraps
