"""Reduce a profiler trace (``*.xplane.pb``) to the device's busy time,
the traced window, and the breakdown a result line carries.

* Busy time is the union of the intervals in which an operation ran on
  a device, taken per device plane (``/device:TPU:<i>``) from its ``XLA
  Ops`` line, and averaged over the devices.
* The breakdown's ``device_ops`` are the ten operations with the most
  summed device time, named by their HLO text without layouts; its
  ``idle_gaps`` sum the gaps between device operations by what the host
  thread that carries the harness's annotations was doing: each gap is
  cut where an annotation opens or closes, and each piece is named by
  the innermost ``bench:<name>`` annotation open over it (``none`` where
  none was), then ``/`` and the innermost other event open on that
  thread at its middle, if any (such as ``PjitFunction(<name>)``); ten
  largest first.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench:"


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float):
    """The idle (start, end) gaps of [lo, hi] between the intervals."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_name(hlo: str) -> str:
    """An operation's HLO text without its layouts, at most 120
    characters."""
    return _LAYOUT.sub("", hlo)[:120]


def read_events(planes):
    """{"devices": {plane: [(start_s, end_s, name)]}, "bench":
    [(start_s, end_s, name)], "host": [(start_s, end_s, name)]} from the
    planes of one trace: the harness's annotations without their
    ``bench:`` prefix, and every other event of the host threads that
    carry them."""
    devices, bench, host = {}, [], []
    span = lambda ev: (ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9)
    for plane in planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            chosen = [lines[OPS_LINE]] if OPS_LINE in lines else \
                list(lines.values())
            devices[name] = [span(ev) + (op_name(ev.name),)
                             for ln in chosen for ev in ln.events]
        elif name.startswith("/host:"):
            for ln in plane.lines:
                evs = list(ln.events)
                if not any(ev.name.startswith(HOST_PREFIX) for ev in evs):
                    continue
                for ev in evs:
                    if ev.name.startswith(HOST_PREFIX):
                        bench.append(span(ev) + (ev.name[len(HOST_PREFIX):],))
                    else:
                        host.append(span(ev) + (ev.name,))
    return {"devices": devices, "bench": bench, "host": host}


class _Open:
    """The innermost of a set of nested (start, end, name) events open at
    a time."""

    def __init__(self, events):
        self.events = sorted(events)
        self.starts = [e[0] for e in self.events]

    def at(self, t: float):
        i = bisect.bisect_right(self.starts, t) - 1
        for j in range(i, max(i - 256, -1), -1):
            if self.events[j][1] >= t:
                return self.events[j][2]
        return None


def reduce_events(events, window_s: float):
    """(busy_s, window_s, breakdown) from :func:`read_events` output.
    ``window_s`` is the traced window as the harness timed it; busy time
    is averaged over the device planes."""
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane")
    bench, host = _Open(events["bench"]), _Open(events["host"])
    every = events["bench"] + events["host"]
    cuts = sorted({t for s, e, _ in events["bench"] for t in (s, e)})
    busy = []
    op_time = defaultdict(float)
    idle = defaultdict(float)
    for evs in devices.values():
        iv = [(s, e) for s, e, _ in evs]
        busy.append(union_length(iv))
        for s, e, n in evs:
            op_time[n] += e - s
        if not iv:
            continue
        lo = min(s for s, _ in iv)
        hi = max(e for _, e in iv)
        if every:
            lo = min(lo, min(s for s, _, _ in every))
            hi = max(hi, max(e for _, e, _ in every))
        for gs, ge in gaps(iv, lo, hi):
            i, j = bisect.bisect_right(cuts, gs), bisect.bisect_left(cuts, ge)
            edges = [gs] + cuts[i:j] + [ge]
            for a, b in zip(edges, edges[1:]):
                mid = 0.5 * (a + b)
                label = bench.at(mid) or "none"
                inner = host.at(mid)
                if inner:
                    label += "/" + inner
                idle[label] += (b - a) / len(devices)
    busy_s = sum(busy) / len(busy)
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                               key=lambda kv: -kv[1])[:10]]
    ops = {k: v / len(devices) for k, v in op_time.items()}
    return busy_s, float(window_s), {"device_ops": top(ops),
                                     "idle_gaps": top(idle)}


def reduce_dir(trace_dir: str, window_s: float):
    """:func:`reduce_events` of the one ``.xplane.pb`` under a directory
    that ``jax.profiler.start_trace`` wrote."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    return reduce_events(read_events(data.planes), window_s)
