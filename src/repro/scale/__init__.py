"""Scale layer: the sharded, bucketed, chunk-streaming grid executor.

``run_grid(GridSpec(...))`` is the single entry point every grid in the
repo routes through — ``repro.core.cocar.cocar_grid``,
``repro.traces.engine.run_online_grid``, the sweep harness
(``repro.experiments.sweep``), and ``benchmarks/bench_scale.py``.  See
``repro.scale.executor`` for the architecture and
``docs/algorithms.md`` Sec. 9 for the grid-axes → mesh-axes → bucket
mapping.
"""
from repro.scale.buckets import Bucket, BucketPlan, plan_buckets
from repro.scale.executor import (GridResult, GridSpec,
                                  compiled_cache_stats, run_grid)

__all__ = ["Bucket", "BucketPlan", "plan_buckets", "GridResult",
           "GridSpec", "compiled_cache_stats", "run_grid"]
