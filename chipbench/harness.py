"""What every cell shares: finding a cell's files by name, seeds, the
measured window, host spans, compile counting, percentiles, the device
block and the result line.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``.  Its
configuration is ``configs/<config>.json``, its traffic
``traffic/<traffic>.json``; the traffic file names the driver
(``drivers/<driver>.py``) that feeds it to the program, and each
per-layer metric is read by ``metrics/<metric>.py``.  Adding a cell,
a mix or a metric adds files; none of this code changes.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import time
import zlib
from contextlib import contextmanager

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: the persistent compile cache: a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

#: JAX's compile events: a trace to a jaxpr, and a backend compile
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(SystemExit):
    """No TPU, or fewer chips than the cell asks for."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(root, "BENCHMARK.json")


def resolve(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic and metrics:
    ``{"cell", "config", "traffic", "end_to_end", "per_layer"}``."""
    spec = benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; one of {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}

    def listed(m):
        return "workloads" not in m or name in m["workloads"]

    return {"cell": cell,
            "config": load_json(root, configs[cell["config"]]["file"]),
            "traffic": load_json(HERE, "traffic", cell["traffic"] + ".json"),
            "end_to_end": [m for m in spec["end_to_end"] if listed(m)],
            "per_layer": [m for m in spec["per_layer"] if listed(m)]}


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    mod_name = f"chipbench.{kind}.{name.replace('.', '_').replace('-', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def sub_seed(seed: int, what: str) -> int:
    """A 31-bit seed for one use of the run's ``--seed`` (any size)."""
    import numpy as np

    ss = np.random.SeedSequence([int(seed) % (1 << 64), zlib.crc32(
        what.encode())])
    return int(ss.generate_state(1, dtype=np.uint32)[0] & 0x7FFFFFFF)


def quantile(values, q: float) -> float:
    """Exact order-statistic quantile with linear interpolation between
    the two nearest ranks (numpy's default, method "linear")."""
    v = sorted(float(x) for x in values)
    if not v:
        raise ValueError("quantile of no values")
    pos = (len(v) - 1) * q
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


class Context:
    """One run: its arguments, host spans, counters and the window."""

    def __init__(self, workload: str, seed: int, seconds: float,
                 trace: bool, resolved: dict, t_start: float):
        self.workload, self.seed = workload, int(seed)
        self.seconds, self.trace = float(seconds), bool(trace)
        self.cell = resolved["cell"]
        self.config = resolved["config"]
        self.traffic = resolved["traffic"]
        self.t_start = t_start
        self.spans: dict = {}
        self.counters: dict = {}
        self.info: dict = {}
        self.window_t0 = self.window_t1 = None
        self.compiles = 0
        self._listener = None
        self._trace_dir = None

    def sub_seed(self, what: str) -> int:
        return sub_seed(self.seed, what)

    @contextmanager
    def span(self, name: str):
        """Time a block on the host clock, and name it in the profiler's
        trace so that idle gaps can be attributed to it."""
        import jax

        t, c = time.perf_counter(), time.thread_time()
        with jax.profiler.TraceAnnotation(f"bench:{name}"):
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t)
        self._span_at.setdefault(name, []).append(
            (t, time.thread_time() - c))

    def phase(self, name: str, seconds: float):
        """Record one part of set-up, printed under ``setup_phases_s``."""
        self.info.setdefault("setup_phases_s", {})[name] = seconds

    def count(self, name: str, n=1):
        self.counters[name] = self.counters.get(name, 0) + n

    # -- the measured window ------------------------------------------------
    def start_window(self):
        import jax

        from repro.obs.tracing import retrace_snapshot

        self.spans.clear()
        self._span_at = {}
        self.counters.clear()
        self.compiles = 0

        def on_event(event, secs, **kw):
            if event in COMPILE_EVENTS:
                self.compiles += 1

        self._listener = on_event
        jax.monitoring.register_event_duration_secs_listener(on_event)
        self._gc = {"n": 0, "s": 0.0, "t": None}
        gc.callbacks.append(self._on_gc)
        self._retrace_snap = retrace_snapshot()
        if self.trace:
            import tempfile

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            self._trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self.window_t0 = time.perf_counter()
        self.deadline = self.window_t0 + self.window_length

    @property
    def window_length(self) -> float:
        """Seconds the window measures: ``--seconds``, or in a traced run
        at most the mix's ``trace_seconds``, so that the trace stays small
        enough to read within the run."""
        if not self.trace:
            return self.seconds
        return min(self.seconds, float(self.traffic.get("trace_seconds",
                                                          self.seconds)))

    def _on_gc(self, phase, info):
        """Counts the interpreter's full collections in the window and
        the time they take."""
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._gc["t"] = time.perf_counter()
        elif self._gc["t"] is not None:
            self._gc["n"] += 1
            self._gc["s"] += time.perf_counter() - self._gc["t"]

    def now(self) -> float:
        """Seconds since the window opened."""
        return time.perf_counter() - self.window_t0

    def end_window(self):
        import jax

        from repro.obs.tracing import total_retraces_since

        self.window_t1 = time.perf_counter()
        if self.trace:
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(self._listener)
        gc.callbacks.remove(self._on_gc)
        self.info["full_gc_in_window"] = {"count": self._gc["n"],
                                          "seconds": self._gc["s"]}
        self.info["slowest_spans_s"] = {
            k: sorted(v)[-3:] for k, v in self.spans.items()}
        # where in the window the slowest spans fell, and how much of
        # each the calling thread spent on its own CPU
        self.info["slowest_spans_at"] = {
            k: [{"at_s": self._span_at[k][i][0] - self.window_t0,
                 "wall_s": v[i], "thread_cpu_s": self._span_at[k][i][1]}
                for i in sorted(range(len(v)), key=v.__getitem__)[-3:]]
            for k, v in self.spans.items()}
        self.info["compiles_in_window"] = self.compiles
        self.info["obs_retraces_in_window"] = total_retraces_since(
            self._retrace_snap)

    @property
    def window_seconds(self) -> float:
        return self.window_t1 - self.window_t0

    def read_trace(self):
        """(busy_s, window_s, breakdown) of the traced window; the trace
        directory is removed once read."""
        import shutil

        from chipbench import trace as TR

        try:
            return TR.reduce_dir(self._trace_dir, self.window_seconds)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)


def require_chips(n: int):
    """The TPU devices of this process, at least ``n`` of them; a run
    without them ends here, before any result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"chipbench: no TPU (JAX found {devs[0].platform})")
    if len(devs) < n:
        raise NoChip(f"chipbench: the cell needs {n} chips, JAX found "
                     f"{len(devs)}")
    return devs


def device_block(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def enable_cache():
    """Point JAX's persistent compile cache at the checkout's fixed
    directory, and cache every program, however quick its compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR


def peak(device_kind: str, what: str) -> float:
    """A published peak of ``device_kind`` from ``peaks.json``; a device
    the table does not name is an error, not a default."""
    table = load_json(HERE, "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json")
    return float(table[device_kind][what])
