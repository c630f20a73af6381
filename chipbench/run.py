"""The benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine: set-up
(inputs and weights from ``--seed``, every shape the cell uses warmed),
a measured window of ``--seconds``, then the check of what the window
produced against the plain reference.  The last line on standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics), ``device``, ``breakdown`` in a traced run, and ``checks``:
each number compared, with its limit.  The same numbers are the last
lines on standard error.  Without a TPU, or with fewer chips than the
cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import harness as H  # noqa: E402

T_IMPORTED = time.perf_counter()


def _finite(x) -> bool:
    return x is not None and x == x and abs(x) != float("inf")


def run_cell(ctx: H.Context, resolved: dict, devs) -> dict:
    """Set-up, window, metrics and check of one cell; returns the result
    object (without printing it)."""
    driver = H.load_module("drivers", ctx.traffic["driver"])
    t = time.perf_counter()
    state = ctx.state = driver.setup(ctx)
    ctx.phase("driver_setup", time.perf_counter() - t)
    # what set-up made lives for the whole run: keep the interpreter's
    # full collections in the window from walking it again
    t = time.perf_counter()
    gc.collect()
    gc.freeze()
    ctx.phase("gc_freeze", time.perf_counter() - t)
    setup_s = time.perf_counter() - ctx.t_start
    ctx.start_window()
    driver.window(ctx, state)
    ctx.end_window()
    device = H.device_block(devs)
    result = {"attempted": int(state.attempted), "failed": int(state.failed)}
    if ctx.trace:
        t = time.perf_counter()
        busy_s, window_s, breakdown = ctx.read_trace()
        ctx.info["trace_read_s"] = time.perf_counter() - t
        ctx.info["busy_s"], ctx.info["window_s"] = busy_s, window_s
        device["busy_s"], device["window_s"] = busy_s, window_s
        metrics = {}
        for m in resolved["per_layer"]:
            value = H.load_module("metrics", m["name"]).read(ctx, state)
            if _finite(value):
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        result["breakdown"] = breakdown
    else:
        e2e = driver.end_to_end(ctx, state)
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in resolved["end_to_end"]}
    driver.free(state)
    gc.unfreeze()
    gc.collect()
    t = time.perf_counter()
    checks = driver.check(ctx, state)
    ctx.info["check_s"] = time.perf_counter() - t
    correct = bool(checks) and all(
        _finite(c["value"]) and c["value"] <= c["limit"] for c in checks)
    ctx.info["setup_s"] = setup_s
    return {"correct": correct, **result, "metrics": metrics,
            "device": device, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    resolved = H.resolve(args.workload)
    t = time.perf_counter()
    H.enable_cache()
    devs = H.require_chips(int(resolved["cell"]["chips"]))
    devs = devs[: int(resolved["cell"]["chips"])]
    ctx = H.Context(args.workload, args.seed, args.seconds,
                    bool(args.trace), resolved, T_START)
    ctx.phase("imports", T_IMPORTED - T_START)
    ctx.phase("jax_devices", time.perf_counter() - t)
    ctx.device_kind = devs[0].device_kind
    result = run_cell(ctx, resolved, devs)
    print(json.dumps({"info": ctx.info}, default=float), flush=True)
    for c in result["checks"]:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
