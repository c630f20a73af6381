#!/usr/bin/env python
"""Bench regression gate: compare freshly produced ``results/bench/
BENCH_*.json`` against the committed baselines.

Three kinds of checks, driven by the manifest below:

  * **perf ratios** (speedups, higher is better): machine-portable because
    both sides of each ratio ran on the same box; fail when a fresh ratio
    drops below ``(1 - RATIO_TOL)`` of the baseline (>25% slowdown);
  * **correctness gaps** (lower is better) and **flags** (must stay
    truthy): fail on ANY growth beyond the absolute floor — an
    equivalence gap that widens is a correctness regression, not noise;
  * **drifts** (must stay put, either direction): reproduced paper
    quantities like the CoCaR-vs-best-baseline improvement ratio; fail
    when a fresh value moves more than the per-key relative tolerance
    from the baseline — in either direction, since a quality *jump* is as
    suspicious as a drop when the algorithms did not change.

Perf ratios are only compared when the fresh run used the same scale
knobs (scale fields below) as the baseline; a CI smoke run at a smaller
scale skips them with a notice instead of failing spuriously.

Usage:
    python scripts/check_bench.py [--baseline DIR] [--fresh DIR]

Defaults: baseline = the committed copy (via ``git show HEAD:...``),
fresh = ``results/bench``.  Exit code 1 on any failure.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
RATIO_TOL = 0.25          # fail on >25% slowdown of a perf ratio
GAP_FLOOR = 1e-9          # correctness gaps may float below this freely

#: per-file manifest: dotted paths into the JSON payload
MANIFEST = {
    "BENCH_online.json": {
        "scale": ["throughput.scenarios", "throughput.n_slots",
                  "throughput.n_users"],
        "ratios": ["throughput.speedup"],
        "gaps": ["throughput.max_avg_qoe_gap",
                 "equivalence.cocar-ol.max_slot_qoe_relgap",
                 "equivalence.lfu.max_slot_qoe_relgap",
                 "equivalence.lfu-mad.max_slot_qoe_relgap",
                 "equivalence.random.max_slot_qoe_relgap"],
        "flags": ["equivalence.cocar-ol.final_state_equal",
                  "equivalence.lfu.final_state_equal",
                  "equivalence.lfu-mad.final_state_equal",
                  "equivalence.random.final_state_equal"],
    },
    "BENCH_users.json": {
        "scale": ["scale.users_per_slot", "scale.n_slots",
                  "scale.chunk_slots"],
        "ratios": [],
        "gaps": ["identity.max_slot_qoe_relgap",
                 "identity.numpy_max_slot_qoe_relgap"],
        # the Workload API's contract: the aggregated count-tensor engine
        # makes the SAME cache decisions as the per-user replay at small
        # U, chunk streaming changes nothing (a scan is a strict fold),
        # and the U=1e6 stream never materializes a dense (T, U) tensor
        # (peak host memory bounded and << the dense-equivalent bytes)
        "flags": ["identity.decisions_identical",
                  "identity.numpy_state_equal",
                  "identity.chunked_identical",
                  "scale.memory_bounded", "scale.no_dense_tensor"],
    },
    "BENCH_offline.json": {
        "scale": ["throughput.variants", "throughput.n_seeds",
                  "throughput.n_users", "throughput.pdhg_iters"],
        "ratios": ["throughput.speedup_vs_host_loop",
                   "throughput.speedup_vs_host_rr"],
        "gaps": ["equivalence.max_obj_gap", "equivalence.max_metric_gap",
                 "throughput.avg_precision_gap"],
        "flags": ["equivalence.decisions_identical"],
    },
    "BENCH_baselines.json": {
        "scale": ["throughput.variants", "throughput.n_seeds",
                  "throughput.n_users", "throughput.pdhg_iters"],
        "ratios": ["throughput.speedup_vs_host_loop"],
        "gaps": ["equivalence.max_obj_gap", "equivalence.max_metric_gap"]
        + [f"equivalence.per_policy.{p}.metric_gap"
           for p in ("cocar", "spr3", "greedy", "random", "gatmarl")],
        "flags": ["equivalence.decisions_identical"],
        # the reproduced Sec. VII-B headline: CoCaR over the best
        # baseline.  Scale-keyed on the comparison block itself (the
        # equivalence grid), which every CI path runs at the same config
        # — so this gate engages on smoke, full, and nightly runs alike.
        "drifts": [("comparison.improvement_ratio", 0.15)],
        "drift_scale": ["comparison.variants", "comparison.n_seeds",
                        "comparison.n_users", "comparison.best_of",
                        "comparison.pdhg_iters", "comparison.episodes"],
    },
    "BENCH_serving.json": {
        # closed-loop serving runs at ONE fixed scale on every CI path
        # (smoke == full), so all gates engage everywhere
        "scale": ["offline.n_pods", "offline.n_models", "offline.n_users",
                  "offline.n_windows", "offline.pdhg_iters",
                  "offline.duration_s"],
        "ratios": [],
        # catalog D_m seconds vs the loader's actual transfer seconds:
        # the same byte math, so the gap must stay at zero
        "gaps": ["agreement.max_transfer_gap_s"],
        # the decision bridge's contract: residencies come from the
        # control plane (never hand-constructed), the measured catalog's
        # bandwidth sits in the Table III band, CoCaR's ranking survives
        # simulated loading delay, Eq. 37's mid-download invariant holds
        # non-vacuously with numpy/scan state parity, and a plan reaches
        # real running weights in the cluster
        "flags": ["offline.decisions_from_control_plane",
                  "offline.ranking_preserved",
                  "offline.catalog.crosscheck.ok",
                  "offline.attribution_exact",
                  "events_conserved",
                  "online.states_equal_numpy_scan",
                  "online.mid_download_never_serves",
                  "online.in_flight_nonvacuous",
                  "cluster.real_generation"],
        # the headline margin (CoCaR's delivered precision under loading
        # delay over the best baseline's) plus the request-level latency
        # attribution: phase fractions and percentiles of CoCaR's
        # delayed runs — deterministic simulation at a fixed scale, so a
        # move beyond tolerance means the serving behaviour changed
        "drifts": [("offline.cocar_over_best_baseline", 0.2),
                   ("offline.per_policy.cocar.attribution.stall.frac",
                    0.25),
                   ("offline.per_policy.cocar.attribution.queue.frac",
                    0.25),
                   ("offline.per_policy.cocar.attribution.service.frac",
                    0.25),
                   ("offline.per_policy.cocar.attribution.stall.p95",
                    0.25),
                   ("offline.per_policy.cocar.attribution.service.p95",
                    0.25),
                   ("offline.per_policy.cocar.delayed.p95_latency", 0.25),
                   ("offline.per_policy.cocar.delayed.p99_latency",
                    0.25)],
        "drift_scale": ["offline.n_pods", "offline.n_models",
                        "offline.n_users", "offline.n_windows",
                        "offline.pdhg_iters", "offline.duration_s"],
    },
    "BENCH_scale.json": {
        "scale": ["throughput.variants", "throughput.n_seeds",
                  "throughput.n_users", "throughput.pdhg_iters",
                  "throughput.devices"],
        "ratios": ["throughput.sharded_speedup"],
        "gaps": ["equivalence.max_obj_gap", "equivalence.max_metric_gap",
                 "throughput.decision_obj_gap",
                 "throughput.decision_metric_gap"],
        # the executor's contract: sharded/bucketed/chunked dispatch makes
        # the SAME decisions as the one-device vmap path, and chunked
        # streaming keeps peak live input bytes under half the one-shot
        # grid's (the CI smoke produces the equivalence flags; the
        # throughput flags exist on full-scale runs)
        "flags": ["equivalence.decisions_identical",
                  "equivalence.bucketed_identical",
                  "equivalence.online_identical",
                  "throughput.decisions_identical",
                  "throughput.memory_bounded"],
    },
}


def _get(payload, dotted):
    cur = payload
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def _load(root, name, git_ref=None):
    if git_ref is not None:
        try:
            out = subprocess.run(
                ["git", "show", f"{git_ref}:results/bench/{name}"],
                cwd=REPO, capture_output=True, text=True, check=True)
        except subprocess.CalledProcessError:
            return None
        return json.loads(out.stdout)
    path = pathlib.Path(root) / name
    if not path.exists():
        return None
    return json.loads(path.read_text())


def check_file(name, spec, base, fresh):
    """Returns a list of (level, message); level in {fail, warn, ok}.

    A fresh field that was not produced at this scale (e.g. a CI smoke run
    writes only the equivalence block) is skipped with a notice — the
    full-scale local/bench runs are where every field exists.  A file where
    *nothing* could be compared fails: that is a schema break, not a
    smaller scale.
    """
    msgs = []
    same_scale = all(_get(base, k) == _get(fresh, k) for k in spec["scale"])
    for key in spec["ratios"]:
        b, f = _get(base, key), _get(fresh, key)
        if f is None:
            msgs.append(("warn", f"{name}:{key} not produced by this run"))
        elif b is None:
            msgs.append(("warn", f"{name}:{key} has no baseline yet"))
        elif not same_scale:
            msgs.append(("warn", f"{name}:{key} perf check skipped "
                         "(scale mismatch vs baseline)"))
        elif f < b * (1.0 - RATIO_TOL):
            msgs.append(("fail", f"{name}:{key} regressed: "
                         f"{f:.2f} < {b:.2f} - {RATIO_TOL:.0%}"))
        else:
            msgs.append(("ok", f"{name}:{key} {f:.2f} (baseline {b:.2f})"))
    for key in spec["gaps"]:
        b, f = _get(base, key), _get(fresh, key)
        if f is None:
            msgs.append(("warn", f"{name}:{key} not produced by this run"))
        elif b is None:
            msgs.append(("warn", f"{name}:{key} has no baseline yet"))
        elif f > max(b, GAP_FLOOR):
            msgs.append(("fail", f"{name}:{key} correctness gap grew: "
                         f"{f:.3e} > {max(b, GAP_FLOOR):.3e}"))
        else:
            msgs.append(("ok", f"{name}:{key} {f:.2e} "
                         f"(baseline {b:.2e})"))
    for key in spec["flags"]:
        f = _get(fresh, key)
        if f is None:
            msgs.append(("warn", f"{name}:{key} not produced by this run"))
        elif not f:
            msgs.append(("fail", f"{name}:{key} is {f!r}, must be true"))
        else:
            msgs.append(("ok", f"{name}:{key} true"))
    drift_scale_keys = spec.get("drift_scale", spec["scale"])
    drift_same_scale = all(_get(base, k) == _get(fresh, k)
                           for k in drift_scale_keys)
    for key, rtol in spec.get("drifts", ()):
        b, f = _get(base, key), _get(fresh, key)
        if f is None:
            msgs.append(("warn", f"{name}:{key} not produced by this run"))
        elif b is None:
            msgs.append(("warn", f"{name}:{key} has no baseline yet"))
        elif not drift_same_scale:
            msgs.append(("warn", f"{name}:{key} drift check skipped "
                         "(scale mismatch vs baseline)"))
        elif abs(f - b) > rtol * abs(b):
            msgs.append(("fail", f"{name}:{key} drifted beyond {rtol:.0%}: "
                         f"{f:.3f} vs baseline {b:.3f}"))
        else:
            msgs.append(("ok", f"{name}:{key} {f:.3f} "
                         f"(baseline {b:.3f}, tol {rtol:.0%})"))
    if not any(level == "ok" for level, _ in msgs):
        msgs.append(("fail", f"{name}: nothing comparable was produced "
                     "(schema break?)"))
    return msgs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default=None,
                    help="directory with baseline BENCH_*.json "
                         "(default: committed copy at --git-ref)")
    ap.add_argument("--fresh", default=str(REPO / "results" / "bench"),
                    help="directory with freshly produced BENCH_*.json")
    ap.add_argument("--git-ref", default="HEAD",
                    help="ref for the committed baseline (default HEAD)")
    args = ap.parse_args(argv)

    failures = 0
    checked = 0
    for name, spec in MANIFEST.items():
        base = _load(args.baseline, name,
                     git_ref=None if args.baseline else args.git_ref)
        fresh = _load(args.fresh, name)
        if base is None:
            print(f"[skip] {name}: no committed baseline")
            continue
        if fresh is None:
            print(f"[FAIL] {name}: baseline exists but no fresh result "
                  f"under {args.fresh}")
            failures += 1
            continue
        checked += 1
        for level, msg in check_file(name, spec, base, fresh):
            tag = {"fail": "[FAIL]", "warn": "[skip]", "ok": "[ ok ]"}[level]
            print(f"{tag} {msg}")
            failures += level == "fail"
    if checked == 0:
        print("[FAIL] no bench files checked — baselines missing?")
        failures += 1
    print(f"check_bench: {failures} failure(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
