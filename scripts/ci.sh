#!/usr/bin/env bash
# CI / newcomer entry point: install deps, lint, run the tier-1 suite,
# then the engine-equivalence bench smokes + the bench regression gate.
set -euo pipefail
cd "$(dirname "$0")/.."

if [ "${CI_SKIP_INSTALL:-0}" != "1" ]; then
    python -m pip install -r requirements.txt
fi

# lint (rules live in pyproject.toml); skipped quietly where ruff is not
# installed — the CI workflow always installs and runs it
if command -v ruff >/dev/null 2>&1; then
    ruff check .
else
    echo "ci.sh: ruff not installed, skipping lint"
fi

# --durations surfaces the slowest tests in the job log; REPRO_TEST_TIMEOUT
# (set by the CI workflow, see tests/conftest.py) hard-kills a hung device
# dispatch after N seconds instead of eating the whole job budget
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m pytest -x -q --durations=15 "$@"

# bench smokes: NumPy OnlineSim == scan engine on every policy, the
# NumPy round+repair == fused offline pipeline, and every offline
# baseline's device kernel == its NumPy oracle, all on small grids.
# Fresh results land in the results/bench/ci/ scratch dir — never over
# the committed baselines — and check_bench compares the two (correctness
# gaps always; perf ratios and drift checks only for same-scale runs).
# JAX_ENABLE_X64 is scoped to these steps: the equivalence engines want
# f64 defaults, while the Pallas kernel tests above pin float32.
JAX_ENABLE_X64=1 PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.bench_online --smoke
JAX_ENABLE_X64=1 PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.bench_offline --smoke
# the Workload API's aggregation certificate: count-tensor engines make
# the per-user replay's decisions bit-exactly at small U, and a U=1e6/slot
# poisson_zipf stream runs chunk-by-chunk at bounded host memory (the
# smoke keeps U at 1e6 — per-slot cost is U-independent, that is the
# point — and only shortens the horizon)
JAX_ENABLE_X64=1 PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.bench_users --smoke
JAX_ENABLE_X64=1 PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.bench_baselines --smoke
# the sharded grid executor under a forced 8-device host mesh: shard_map
# + bucketed batching + chunk streaming must reproduce the one-device
# dispatch's decisions exactly (the flag is also set inside bench_scale
# before its first jax import; exporting it here keeps the subprocess
# honest even if that import order ever changes)
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_ENABLE_X64=1 PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.bench_scale --smoke
# closed-loop serving smoke: control-plane decisions -> ServingPlan ->
# queue simulator under measured loading times, with the request-level
# telemetry always on (event log + streaming metrics).  Runs at the
# SAME fixed scale as the committed baseline, so check_bench's flags
# (ranking survives loading delay, exact latency attribution, event
# conservation, Eq. 37 mid-download invariant, Table III cross-check)
# and the attribution/percentile drifts all engage here
JAX_ENABLE_X64=1 PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.bench_serving --smoke
# the Prometheus textfile the smoke just exported must parse and carry
# the serving schema (cumulative buckets, _sum/_count consistency)
python scripts/check_metrics.py results/bench/ci/BENCH_serving.metrics.prom
# observability smoke (repro.obs): a tiny sharded offline sweep with the
# jit-safe diagnostics taps ON, then report.py over its artifacts —
# manifests, span traces, and the one uniform gate: PDHG convergence
# (every smoke window must clear DEFAULT_TOL) plus the deadline-miss
# regression check against the committed BENCH_serving baseline (the
# serving smoke above writes the fresh copy into results/bench/ci)
XLA_FLAGS=--xla_force_host_platform_device_count=2 \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.experiments.sweep --smoke --devices 2
python scripts/report.py results/sweep/ci results/bench/ci \
    --check-converged | tee /tmp/obs_report.txt
grep -q "== Convergence" /tmp/obs_report.txt \
    || { echo "ci.sh: report.py produced no convergence section"; exit 1; }
grep -q "== Deadline misses" /tmp/obs_report.txt \
    || { echo "ci.sh: report.py produced no deadline-miss section"; exit 1; }
python scripts/check_bench.py --fresh results/bench/ci
