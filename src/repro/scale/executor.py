"""Sharded grid executor: one entry point for every scenario grid.

``run_grid(spec)`` takes a :class:`GridSpec` describing a grid of
independent work items — offline CoCaR windows, the five-policy
comparison, or online (scenario × workload × policy) scan jobs — and runs
it through three composable layers:

  1. **bucketed batching** (``repro.scale.buckets``): heterogeneous
     (N, U) windows are grouped into a small set of padded shapes
     instead of one global max-pad, bounding both compile count and
     padding waste;
  2. **mesh partitioning**: each bucket's batch axis is partitioned
     across a ``("data", "model")`` host-device mesh with
     ``jax.shard_map`` (``launch/mesh.py`` plumbing: the chips of one
     TPU host, or K virtual CPU devices under
     ``XLA_FLAGS=--xla_force_host_platform_device_count=K``) — the grid
     axes (variants × seeds × policies / windows / workload families)
     all live on the stacked batch axis, so "data" is the only mesh axis
     the executor shards;
  3. **chunked streaming**: the batch is dispatched in fixed-size chunks
     whose device buffers are donated (``donate_argnums``), so peak live
     memory is O(chunk), not O(grid), as grids grow to thousands of
     scenarios.

The layout is chosen by ``GridSpec.devices`` alone: ``None`` (the
default) vmaps each chunk on one device with no mesh; ``k`` runs the
same vmapped kernel under ``shard_map`` over a k-device mesh (``k=1``
included).

Decision identity across layouts is engineered, not hoped for.  Padded
rows are exactly inert in every kernel, and the rounding/baseline
randomness is drawn ONCE at the grid's global max shape — exactly the
tensors the single-dispatch path consumes — and *sliced* per bucket, so
the executor is bit-compatible with the legacy one-device dispatch.
Any (bucketing × chunking × mesh) combination therefore reproduces the
same cache/routing arrays and winning trials bit-identically (asserted
in ``tests/test_scale.py`` and gated by ``benchmarks/bench_scale.py`` →
``scripts/check_bench.py``).

Compiled executables are cached module-level, keyed on (kind, mesh,
static knobs); chunk shapes are padded to full chunks, so a whole
sweep compiles once per (bucket shape, chunk) and repeated sweeps with
the same :class:`~repro.scale.buckets.BucketPlan` key retrace nothing.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field

import numpy as np

from repro.obs import tracing as OT
from repro.scale.buckets import plan_buckets

GRID_KINDS = ("offline", "policy", "online")


@dataclass
class GridSpec:
    """One grid run: what to execute, and how to lay it out.

    ``kind`` selects the kernel family: ``"offline"`` (fused LP → round
    → repair → metrics over ``insts``), ``"policy"`` (all five offline
    policies over ``insts``), ``"online"`` (the scan engine over
    ``jobs`` + ``ocfg``).  ``devices=None`` runs the bucketed/chunked
    schedule vmapped on one device with no mesh; ``devices=k``
    partitions each chunk across a k-device mesh with ``shard_map``
    (decision-identical to the no-mesh run).
    """
    kind: str
    insts: list = None           # offline / policy kinds
    jobs: list = None            # online kind
    ocfg: object = None          # online kind
    seed: int = 0
    n_seeds: int = 1             # offline/policy: rounding seeds
    best_of: int = 8
    pdhg_iters: int = 4000
    episodes: int = 150          # policy: GatMARL training budget
    devices: int = None          # mesh size; None = one device, no mesh
    chunk_size: int = 0          # batch per dispatch; 0 = one chunk/bucket
    max_buckets: int = 4
    round_users_to: int = 1
    progress: object = None      # callable(dict) per finished chunk
    diagnostics: bool = False    # jit-safe solver/engine telemetry tap


@dataclass
class GridResult:
    """``results`` in the kind's host shape (see ``run_grid``), plus
    scheduler stats: bucket plan key, chunk count, peak per-chunk input
    bytes vs the whole-grid bytes a one-shot dispatch would pin."""
    results: object
    stats: dict = field(default_factory=dict)


# ---------------------------------------------------------------------------
# mesh + compiled-executable cache
# ---------------------------------------------------------------------------

_COMPILED = {}


def _mesh_key(mesh):
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), mesh.devices.shape,
            tuple(d.id for d in mesh.devices.flat))


def compiled_cache_stats():
    """{cache key: jit-cache size} — exposed so tests can assert that
    repeated sweeps with the same bucket plan retrace nothing."""
    out = {}
    for k, fn in _COMPILED.items():
        size = fn._cache_size() if hasattr(fn, "_cache_size") else -1
        out[k] = size
    return out


def _compile(kind, mesh, n_args, make_inner, *statics):
    """Wrap ``make_inner()`` (a vmapped kernel over the batch axis) in
    shard_map over the mesh's "data" axis (identity when ``mesh`` is
    None), jit it with every array argument donated, and cache it.
    Every compiled entry point is registered with ``repro.obs`` so chunk
    spans count its retraces."""
    key = (kind, _mesh_key(mesh)) + tuple(statics)
    if key not in _COMPILED:
        import jax

        fn = make_inner()
        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            s = P("data")
            fn = jax.shard_map(fn, mesh=mesh, in_specs=(s,) * n_args,
                               out_specs=s, check_vma=False)
        _COMPILED[key] = OT.register_jit(
            f"scale:{key}", jax.jit(fn, donate_argnums=tuple(range(n_args))))
    return _COMPILED[key]


# ---------------------------------------------------------------------------
# chunked streaming
# ---------------------------------------------------------------------------

def _nbytes(tree):
    import jax

    return sum(np.asarray(leaf).nbytes for leaf in jax.tree.leaves(tree))


def _take_rows(tree, take):
    import jax

    return jax.tree.map(lambda a: np.take(np.asarray(a), take, axis=0),
                        tree)


def _run_chunks(spec: GridSpec, mesh, fn, args, B: int, stats: dict,
                bucket_key=None):
    """Stream ``args`` through ``fn`` in fixed-size chunks; returns
    outputs concatenated back to batch size B (as host numpy).  ``args``
    is a tuple of pytrees with a leading batch axis of size B.

    Every chunk is padded to the full chunk size by repeating element 0
    (one compiled shape per bucket; the pad rows are sliced off), its
    inputs are laid out on the mesh with ``device_put`` before the call,
    and the compiled function donates them — the chunk's buffers die
    with its dispatch, so peak live memory tracks the chunk, not the
    grid.

    With ``spec.diagnostics`` each chunk span additionally records
    memory watermarks (``repro.obs.metrics.memory_snapshot``): live
    device-array bytes after the chunk's outputs land on the host, plus
    host RSS — and ``stats`` carries the grid-wide peaks.  The tap runs
    strictly after the dispatch, so it cannot perturb results; when
    diagnostics are off it is never called."""
    import jax

    D = 1 if mesh is None else int(mesh.devices.size)
    chunk = int(spec.chunk_size) if spec.chunk_size else B
    chunk = -(-max(chunk, 1) // D) * D            # round up to mesh multiple
    sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(mesh, P("data"))

    outs = []
    n_chunks = -(-B // chunk)
    for ci, start in enumerate(range(0, B, chunk)):
        take = np.arange(start, min(start + chunk, B))
        if len(take) < chunk:                     # pad the tail chunk
            take = np.concatenate(
                [take, np.zeros(chunk - len(take), dtype=int)])
        if chunk == B:
            chunk_args = args                     # whole grid, one chunk:
        else:                                     # no identity row-copy
            with OT.TRACER.span("grid.prepare", count_retraces=False):
                chunk_args = tuple(_take_rows(a, take) for a in args)
        in_bytes = sum(_nbytes(a) for a in chunk_args)
        pad_rows = int(chunk - (min(start + chunk, B) - start))
        with OT.TRACER.span("chunk", kind=spec.kind,
                            bucket=str(bucket_key), chunk=ci,
                            n_chunks=n_chunks, batch=int(len(take)),
                            pad_rows=pad_rows, in_bytes=in_bytes) as sp:
            with jax.enable_x64(True):
                with OT.TRACER.span("grid.put", count_retraces=False):
                    chunk_args = tuple(jax.device_put(a, sharding)
                                       for a in chunk_args)
                with OT.TRACER.span("grid.dispatch", count_retraces=False), \
                        warnings.catch_warnings():
                    # donation is best-effort: only inputs whose shape/
                    # layout matches an output can be reused (the online
                    # state is; most static tensors are not) — the
                    # mismatches are expected, not a bug
                    warnings.filterwarnings(
                        "ignore",
                        message="Some donated buffers were not usable")
                    out = fn(*chunk_args)
                # which devices the chunk ran on, read from the arrays
                sp.attrs["devices"] = sorted(
                    {d.id for a in jax.tree.leaves((chunk_args, out))
                     for d in a.sharding.device_set})
                # the device's work the host does not overlap, then the
                # copy of the finished outputs to the host
                with OT.TRACER.span("grid.wait", count_retraces=False):
                    jax.block_until_ready(out)
                with OT.TRACER.span("grid.fetch", count_retraces=False):
                    out = jax.tree.map(np.asarray, out)
            if spec.diagnostics:
                from repro.obs.metrics import memory_snapshot

                mem = memory_snapshot()
                sp.attrs.update(mem)
                for k in ("device_live_bytes", "host_rss_kb",
                          "host_maxrss_kb"):
                    if k in mem:
                        stats[f"peak_{k}"] = max(
                            stats.get(f"peak_{k}", 0), mem[k])
        dt = sp.seconds
        outs.append(out)
        stats["chunks"] = stats.get("chunks", 0) + 1
        stats["peak_chunk_in_bytes"] = max(
            stats.get("peak_chunk_in_bytes", 0), in_bytes)
        stats["grid_in_bytes"] = stats.get("grid_in_bytes", 0) + in_bytes
        if spec.progress is not None:
            spec.progress({"bucket": bucket_key, "chunk": ci,
                           "n_chunks": n_chunks, "batch": int(len(take)),
                           "in_bytes": in_bytes, "seconds": dt,
                           "retraces": sp.retraces})
    if len(outs) == 1:
        out = outs[0]
    else:
        out = jax.tree.map(lambda *xs: np.concatenate(xs, axis=0), *outs)
    return jax.tree.map(lambda a: a[:B], out)


def _fit_axes(arr, *dims):
    """Slice (or zero-pad) trailing axes of a globally-drawn tensor down
    to a bucket's padded sizes.  Real rows are always a prefix, so the
    values real rows consume are exactly the global draw's — the
    load-bearing fact behind bucket-invariant decisions."""
    arr = np.asarray(arr)
    for ax, size in dims:
        cur = arr.shape[ax]
        if size < cur:
            arr = np.take(arr, np.arange(size), axis=ax)
        elif size > cur:
            pad = [(0, 0)] * arr.ndim
            pad[ax] = (0, size - cur)
            arr = np.pad(arr, pad)
    return arr


def _mesh_of(spec: GridSpec):
    """No mesh for ``devices=None``; else a ``devices``-wide mesh, whose
    width ``make_host_mesh`` validates."""
    if spec.devices is None:
        return None
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh(data=int(spec.devices), model=1)


# ---------------------------------------------------------------------------
# kind: offline  (fused LP -> round -> repair -> argmax -> metrics)
# ---------------------------------------------------------------------------

def _run_offline(spec: GridSpec, mesh, stats):
    from repro.core import cocar as CC
    from repro.core.rounding import draw_rounding_uniforms
    from repro.mec.scenario import stack_instances

    insts = list(spec.insts)
    B = len(insts)
    M, H = insts[0].M, insts[0].H
    N_g = max(i.N for i in insts)
    U_g = max(i.U for i in insts)
    with OT.TRACER.span("grid.prepare", count_retraces=False):
        plan = plan_buckets([(i.N, i.U) for i in insts], spec.max_buckets,
                            round_users_to=spec.round_users_to)
        stats["plan"] = plan.key
        S, T = int(spec.n_seeds), max(int(spec.best_of), 1)
        # the tensors offline_uniforms draws for the max-padded stack
        u_cat, u_phi = draw_rounding_uniforms(spec.seed, S * T, N_g, M,
                                              U_g, H, batch=B)

    results = [None] * B
    for bucket in plan.buckets:
        idx = np.asarray(bucket.indices)
        Nb, Ub = bucket.n_bs, bucket.n_users
        with OT.TRACER.span("grid.prepare", count_retraces=False):
            stacked = stack_instances([insts[i] for i in idx],
                                      pad_to=(Nb, Ub))
            args = (stacked.data,
                    _fit_axes(u_cat[idx], (2, Nb)),
                    _fit_axes(u_phi[idx], (2, Nb), (3, Ub)))
        fn = _compile("offline", mesh, 3, _offline_inner(spec),
                      int(spec.pdhg_iters), S, bool(spec.diagnostics))
        out = _run_chunks(spec, mesh, fn, args,
                          len(idx), stats, bucket_key=bucket.key)
        with OT.TRACER.span("grid.unstack", count_retraces=False):
            per = CC._unstack_device(stacked, out, S)
        for j, i in enumerate(idx):
            results[int(i)] = per[j]
    return results


def _offline_inner(spec: GridSpec):
    def make():
        import jax

        from repro.core.cocar import _pipeline_kernel

        iters, n_seeds = int(spec.pdhg_iters), int(spec.n_seeds)
        diagnostics = bool(spec.diagnostics)
        def cocar_offline_pipeline(d, uc, up):
            return _pipeline_kernel(d, uc, up, iters, n_seeds,
                                    diagnostics=diagnostics)

        # a named function, so that the compiled executable is named
        # after it in the profiler's trace
        return jax.vmap(cocar_offline_pipeline)
    return make


# ---------------------------------------------------------------------------
# kind: policy  (CoCaR + the four Sec. VII-B baselines)
# ---------------------------------------------------------------------------

def _run_policy(spec: GridSpec, mesh, stats):
    from repro.core import cocar as CC
    from repro.mec.scenario import stack_instances

    insts = list(spec.insts)
    B = len(insts)
    M, H = insts[0].M, insts[0].H
    N_g = max(i.N for i in insts)
    U_g = max(i.U for i in insts)
    plan = plan_buckets([(i.N, i.U) for i in insts], spec.max_buckets,
                        round_users_to=spec.round_users_to)
    stats["plan"] = plan.key
    S = int(spec.n_seeds)
    uniforms = CC.policy_uniforms_dims((B, N_g, M, U_g, H), spec.seed,
                                       S, spec.best_of)

    #: (axis slices to a bucket's padded sizes) per uniform tensor, in
    #: ``policy_uniforms`` order, axis 0 being the batch axis
    _CUTS = (((2, "N"),), ((2, "N"), (3, "U")), ((2, "N"),),
             ((2, "N"), (3, "U")), ((2, "N"),), ((2, "N"),), ((2, "U"),))

    results = {p: [None] * B for p in CC.OFFLINE_POLICIES}
    lp_obj = [None] * B
    lp_diag = [None] * B if spec.diagnostics else None
    for bucket in plan.buckets:
        idx = np.asarray(bucket.indices)
        Nb, Ub = bucket.n_bs, bucket.n_users
        stacked = stack_instances([insts[i] for i in idx],
                                  pad_to=(Nb, Ub))
        gat = CC.gat_grid_policies(stacked, spec.seed, spec.episodes)

        sizes = {"N": Nb, "U": Ub}
        args = ((stacked.data,)
                + tuple(_fit_axes(u[idx], *((ax, sizes[d]) for ax, d in dims))
                        for u, dims in zip(uniforms, _CUTS))
                + (gat[0], gat[1], gat[2]))
        fn = _compile("policy", mesh, 11, _policy_inner(spec),
                      int(spec.pdhg_iters), S, bool(spec.diagnostics))
        out = _run_chunks(spec, mesh, fn, args, len(idx), stats,
                          bucket_key=bucket.key)
        for j, i in enumerate(idx):
            inst = insts[int(i)]
            lp_obj[int(i)] = float(out["lp_obj"][j])
            if lp_diag is not None:
                from repro.obs.diagnostics import lp_diag_summary

                curves = {k: np.asarray(v[j])
                          for k, v in out["lp_diag"].items()}
                lp_diag[int(i)] = lp_diag_summary(curves)
            for p in CC.OFFLINE_POLICIES:
                results[p][int(i)] = [
                    (out[p]["x"][j, s, :inst.N],
                     out[p]["A"][j, s, :inst.N, :inst.U],
                     {k: float(v[j, s])
                      for k, v in out[p]["metrics"].items()})
                    for s in range(S)]
    stats["lp_obj"] = lp_obj
    if lp_diag is not None:
        # JSON-safe per-window convergence summaries (curves stay on the
        # offline kind, which returns them per window in full)
        stats["lp_diag"] = lp_diag
    return results


def _policy_inner(spec: GridSpec):
    def make():
        import jax

        from repro.core.cocar import _policy_kernel

        iters, n_seeds = int(spec.pdhg_iters), int(spec.n_seeds)
        diagnostics = bool(spec.diagnostics)
        return jax.vmap(
            lambda *a: _policy_kernel(*a, iters, n_seeds,
                                      diagnostics=diagnostics))
    return make


# ---------------------------------------------------------------------------
# kind: online  (the scan engine over (scenario x workload x policy) jobs;
# jobs carry aggregated-demand Workloads — grid_payloads materializes each
# job's (T, N, M) count tensor, so no per-user tensor reaches the mesh)
# ---------------------------------------------------------------------------

def _run_online(spec: GridSpec, mesh, stats):
    from repro.traces import engine as TE

    jobs = list(spec.jobs)
    payloads = TE.grid_payloads(jobs, spec.ocfg)
    B = len(payloads)

    # bucket online jobs by their exact array shapes — no padding needed,
    # so heterogeneous (n_bs, n_models, n_slots) grids just become
    # separate buckets
    groups = {}
    for i, pl in enumerate(payloads):
        key = (pl["counts"].shape, pl["stream"].adjust_ns.shape,
               pl["stream"].perms.shape)
        groups.setdefault(key, []).append(i)
    stats["plan"] = tuple(
        (key[0], len(idx)) for key, idx in sorted(groups.items()))

    results = [None] * B
    for key, idx in sorted(groups.items()):
        pls = [payloads[i] for i in idx]
        params = TE.OnlineParams(*(
            np.stack([np.asarray(getattr(pl["params"], f)) for pl in pls])
            for f in TE.OnlineParams._fields))
        st0 = TE.init_state(pls[0]["params"], spec.ocfg.dT_past)
        st0 = TE.OnlineState(*(
            np.broadcast_to(x, (len(idx),) + x.shape) for x in st0))
        args = (params, st0,
                np.stack([pl["counts"] for pl in pls]),
                np.stack([pl["stream"].adjust_ns for pl in pls]),
                np.stack([pl["stream"].u_model for pl in pls]),
                np.stack([pl["stream"].perms for pl in pls]),
                np.stack([pl["stream"].u_shrink for pl in pls]),
                np.asarray([pl["policy"] for pl in pls]))
        fn = _compile("online", mesh, 8,
                      _online_inner(bool(spec.diagnostics)),
                      bool(spec.diagnostics))
        stF, qoe, hits, diag, _ = _run_chunks(spec, mesh, fn, args, len(idx),
                                              stats, bucket_key=key[0])
        for j, i in enumerate(idx):
            tot = max(pls[j]["total"], 1.0)
            results[int(i)] = {
                "avg_qoe": float(qoe[j].sum()) / tot,
                "hit_rate": float(hits[j].sum()) / tot,
                "slot_qoe": qoe[j],
                "slot_hits": hits[j],
                "final_state": TE.OnlineState(*(x[j] for x in stF)),
            }
            if spec.diagnostics:
                results[int(i)]["diagnostics"] = {
                    k: np.asarray(v[j]) for k, v in diag.items()}
    return results


def _online_inner(diagnostics: bool = False):
    def make():
        import functools

        import jax

        from repro.traces.engine import _scan_run

        return jax.vmap(functools.partial(_scan_run,
                                          diagnostics=diagnostics))
    return make


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def run_grid(spec: GridSpec) -> GridResult:
    """Execute one grid.  Result shapes by kind (all at true, unpadded
    instance shapes, in the caller's original order):

      offline: ``results[b][s] = (x, A, info)`` — the ``cocar_grid``
               contract;
      policy:  ``results[policy][b][s] = (x, A, metrics)`` — the
               ``policy_grid_host`` contract (per-window LP objectives
               land in ``stats["lp_obj"]``);
      online:  ``results[job]`` summary dicts — the ``run_online_grid``
               contract.
    """
    if spec.kind not in GRID_KINDS:
        raise ValueError(f"unknown grid kind {spec.kind!r}; "
                         f"one of {GRID_KINDS}")
    if spec.kind == "online":
        if spec.jobs is None or spec.ocfg is None:
            raise ValueError("online grids need spec.jobs and spec.ocfg")
        if not spec.jobs:
            return GridResult(results=[], stats={})
    elif not spec.insts:
        raise ValueError(f"{spec.kind} grids need spec.insts")

    mesh = _mesh_of(spec)
    stats = {"kind": spec.kind,
             "devices": 1 if mesh is None else int(mesh.devices.size)}
    runner = {"offline": _run_offline, "policy": _run_policy,
              "online": _run_online}[spec.kind]
    with OT.TRACER.span("run_grid", kind=spec.kind, mesh=mesh is not None,
                        devices=stats["devices"],
                        diagnostics=bool(spec.diagnostics)) as sp:
        results = runner(spec, mesh, stats)
    stats["seconds"] = sp.seconds
    stats["retraces"] = sp.retraces
    return GridResult(results=results, stats=stats)
