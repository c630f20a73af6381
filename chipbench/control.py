"""Readings that a cell's limits and load are set from, on the chip.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--control-seeds <k>]
    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] --rates <req/s> [<req/s> ...]

For every seed, one whole run of the cell at its own size and load with
a window of ``--seconds`` (the program's readings, as ``run.py`` would
check them); for the first ``--control-seeds`` seeds also the control's
readings on the same requests (the reference computed in the precision
next below the configuration's; a driver may add planted faults, each
reading named ``<control>:<number>``).  All in one process, so that set-up
compiles once.  Prints one JSON line per seed and, last, each number's
lower reading (the largest the program gave), upper reading (the
smallest the control gave) and their ratio.

With ``--rates``, the knee of a serving cell instead: for each seed one
set-up, then one window per rate, the cell's traffic at that rate with
no drain after the window.  A rate is kept up with on a seed when every
request that arrived more than ``MARGIN_S`` (2 s) before the window's end
was served within it.  Prints one JSON line per window and, last, the
rates kept up with on every seed and the knee: the highest rate below
which every rate tried was kept up with.
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

import numpy as np  # noqa: E402

from chipbench import harness as H  # noqa: E402
from chipbench import run as RUN  # noqa: E402

#: requests that arrive this close to the window's end may be served
#: after it, even where the system keeps up
MARGIN_S = 2.0


def _number(key: str) -> str:
    """The number a control reading is of: ``<control>:<number>`` or
    ``<number>``."""
    return key.split(":")[-1]


def window_line(ctx, st, rate: float) -> dict:
    """What one window at ``rate`` served, and whether it kept up."""
    T = ctx.window_length
    at, end = st.arrivals.at, st.end
    served_in = ~np.isnan(end) & (end <= T)
    due = at <= T - MARGIN_S
    lat = end[served_in] - at[served_in]
    return {"seed": ctx.seed, "rate": rate, "offered": int(len(at)),
            "served_in_window": int(served_in.sum()),
            "due": int(due.sum()), "due_served": int((served_in & due).sum()),
            "kept_up": bool(np.all(served_in[due])),
            "p50_ms": 1e3 * H.quantile(lat, 0.5) if len(lat) else None,
            "p90_ms": 1e3 * H.quantile(lat, 0.9) if len(lat) else None,
            "mean_batch": float(np.mean([len(b["ids"])
                                         for b in st.batches] or [0])),
            "compiles_in_window": ctx.info["compiles_in_window"]}


def knee(args, resolved, devs, driver):
    kept = {r: True for r in args.rates}
    for seed in args.seeds:
        ctx = H.Context(args.workload, seed, args.seconds, False, resolved,
                        time.perf_counter())
        ctx.device_kind = devs[0].device_kind
        st = driver.setup(ctx)
        for rate in args.rates:
            ctx.traffic = dict(resolved["traffic"], rate_per_s=rate,
                               drain_limit_s=0.0)
            st.batches = []
            ctx.start_window()
            driver.window(ctx, st)
            ctx.end_window()
            line = window_line(ctx, st, rate)
            kept[rate] &= line["kept_up"]
            print(json.dumps(line), flush=True)
        del st, ctx
        gc.collect()
    best = None
    for r in sorted(args.rates):
        if not kept[r]:
            break
        best = r
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "seeds": args.seeds, "kept_up": kept, "knee": best,
                      "device": H.device_block(devs)}), flush=True)


def readings(args, resolved, devs, driver):
    lower, upper = {}, {}
    for i, seed in enumerate(args.seeds):
        ctx = H.Context(args.workload, seed, args.seconds, False, resolved,
                        time.perf_counter())
        ctx.device_kind = devs[0].device_kind
        result = RUN.run_cell(ctx, resolved, devs)
        line = {"seed": seed, "correct": result["correct"],
                "program": {c["name"]: c["value"]
                            for c in result["checks"]},
                "metrics": {k: v["value"]
                            for k, v in result["metrics"].items()}}
        if "decision_diffs" in ctx.info:
            line["decision_diffs"] = ctx.info["decision_diffs"]
        for k, v in line["program"].items():
            lower[k] = max(lower.get(k, v), v)
        if i < args.control_seeds:
            line["control"] = driver.control(ctx, ctx.state)
            for k, v in line["control"].items():
                upper[k] = min(upper.get(k, v), v)
        print(json.dumps(line, default=float), flush=True)
        del ctx
        gc.collect()
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper,
                      "ratio": {k: upper[k] / lower[_number(k)]
                                for k in upper if lower.get(_number(k))}},
                     default=float),
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--rates", type=float, nargs="+")
    args = ap.parse_args(argv)

    resolved = H.resolve(args.workload)
    H.enable_cache()
    chips = int(resolved["cell"]["chips"])
    devs = H.require_chips(chips)[:chips]
    driver = H.load_module("drivers", resolved["traffic"]["driver"])
    (knee if args.rates else readings)(args, resolved, devs, driver)
    return 0


if __name__ == "__main__":
    sys.exit(main())
