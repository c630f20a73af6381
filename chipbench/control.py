"""Readings that a cell's limits are set from, on the chip.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--control-seeds <k>]

For every seed, one whole run of the cell at its own size and load with
a window of ``--seconds`` (the program's readings, as ``run.py`` would
check them); for the first ``--control-seeds`` seeds also the control's
readings on the same requests (the reference computed in the precision
next below the configuration's).  All in one process, so that set-up
compiles once.  Prints one JSON line per seed and, last, each number's
lower reading (the largest the program gave), upper reading (the
smallest the control gave) and their ratio.
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
from chipbench import run as RUN  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    resolved = H.resolve(args.workload)
    H.enable_cache()
    chips = int(resolved["cell"]["chips"])
    devs = H.require_chips(chips)[:chips]
    driver = H.load_module("drivers", resolved["traffic"]["driver"])
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
                      "ratio": {k: upper[k] / lower[k] for k in upper
                                if lower.get(k)}}, default=float),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
