"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
mesh and extract roofline inputs (FLOPs, bytes, collective bytes, memory).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all                 # 16x16
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod     # 2x16x16

Results are cached as JSON under results/dryrun/.
"""
# The very first lines — before ANY other import, jax locks the device count
# on first init.  512 placeholder host devices back the production meshes.
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse          # noqa: E402
import json              # noqa: E402
import pathlib           # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs                                   # noqa: E402
from repro.distribution import sharding as shd              # noqa: E402
from repro.launch import specs as SP                        # noqa: E402
from repro.launch.hlo_analysis import analyse_hlo           # noqa: E402
from repro.launch.mesh import make_production_mesh          # noqa: E402
from repro.launch.steps import (init_train_state,           # noqa: E402
                                make_decode_step,
                                make_prefill_step, make_train_step)
from repro.models import model as M                         # noqa: E402
from repro.models.config import build_plan                  # noqa: E402

def named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, mesh, exit_idx: int = -1):
    """Returns the lowered computation for one (arch, shape, mesh) cell."""
    cfg = configs.get_config(arch)
    seq, batch, mode = SP.SHAPES[shape_name]
    plan = build_plan(cfg)
    pshapes = jax.eval_shape(lambda: M.init(cfg, jax.random.key(0)))
    # sharding regime per workload (§Perf iteration): weight-stationary TP
    # only pays off when activations are tiny (decode); train AND prefill
    # (1M-token batches) want FSDP×TP — serve-mode MoE sharding at prefill
    # made GSPMD replicate the dispatch einsums 16x (measured, reverted)
    pspec = shd.param_specs(cfg, mesh, pshapes,
                            mode="serve" if mode == "decode" else "train")
    psh = named(mesh, pspec)
    bd = shd.batch_dim_spec(mesh, batch)
    ins = SP.input_specs(cfg, shape_name)

    if mode == "train":
        state_shapes = jax.eval_shape(
            lambda: init_train_state(cfg, jax.random.key(0)))
        opt_sh = {"master": psh, "m": psh, "v": psh,
                  "step": NamedSharding(mesh, P())}
        state_sh = {"params": psh, "opt": opt_sh}
        batch_sh = named(mesh, shd.batch_specs(cfg, mesh, batch, mode))
        fn = make_train_step(cfg, plan=plan)
        jfn = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                      donate_argnums=(0,))
        return jfn.lower(state_shapes, ins["batch"])

    csh = named(mesh, shd.cache_specs(cfg, mesh, batch, plan))
    if mode == "prefill":
        batch_sh = named(mesh, shd.batch_specs(cfg, mesh, batch, mode))
        fn = make_prefill_step(cfg, exit_idx=exit_idx, plan=plan)
        jfn = jax.jit(fn, in_shardings=(psh, batch_sh, csh),
                      donate_argnums=(2,))
        return jfn.lower(pshapes, ins["batch"], ins["cache"])

    # decode
    tok_sh = NamedSharding(mesh, P(bd, None))
    pos_sh = NamedSharding(mesh, P())
    fn = make_decode_step(cfg, exit_idx=exit_idx, plan=plan)
    jfn = jax.jit(fn, in_shardings=(psh, tok_sh, pos_sh, csh),
                  donate_argnums=(3,))
    return jfn.lower(pshapes, ins["tokens"], ins["pos"], ins["cache"])


def analyse(lowered, dump_hlo: str = None):
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    res = {"compile_s": round(compile_s, 1)}

    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        # NOTE: XLA counts while bodies once -> raw values under-count scans;
        # the loop-aware numbers below are the roofline inputs.
        res["flops_per_device_raw"] = float(ca.get("flops", -1.0))
        res["bytes_per_device_raw"] = float(ca.get("bytes accessed", -1.0))
    except Exception as e:   # pragma: no cover
        res["cost_analysis_error"] = str(e)

    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                res[k] = int(v)
        if "argument_size_in_bytes" in res:
            res["peak_bytes_per_device"] = (
                res.get("argument_size_in_bytes", 0)
                + res.get("temp_size_in_bytes", 0)
                + res.get("output_size_in_bytes", 0))
    except Exception as e:   # pragma: no cover
        res["memory_analysis_error"] = str(e)

    hlo = compiled.as_text()
    la = analyse_hlo(hlo)
    res["flops_per_device"] = la.get("flops")
    res["hbm_bytes_per_device"] = la.get("hbm_bytes")
    res["collectives"] = la.get("collectives", {})
    res["collective_bytes_per_device"] = la.get("collective_bytes", 0)
    if dump_hlo:
        pathlib.Path(dump_hlo).write_text(hlo)
        res["hlo_path"] = dump_hlo
    return res


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: pathlib.Path,
             force: bool = False, dump_hlo: bool = False):
    mesh_name = "2x16x16" if multi_pod else "16x16"
    out = out_dir / mesh_name / f"{arch}__{shape_name}.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    if out.exists() and not force:
        rec = json.loads(out.read_text())
        print(f"[cached] {mesh_name} {arch} {shape_name}: ok={rec.get('ok')}")
        return rec

    cfg = configs.get_config(arch)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if not SP.supports_cell(cfg, shape_name):
        rec.update(ok=None, skipped=SP.skip_reason(cfg, shape_name))
        out.write_text(json.dumps(rec, indent=1))
        print(f"[skip]   {mesh_name} {arch} {shape_name}: {rec['skipped']}")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        with jax.set_mesh(mesh):
            t0 = time.time()
            lowered = lower_cell(arch, shape_name, mesh)
            rec["lower_s"] = round(time.time() - t0, 1)
            hlo_path = (str(out)[:-5] + ".hlo") if dump_hlo else None
            rec.update(analyse(lowered, dump_hlo=hlo_path))
            rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    out.write_text(json.dumps(rec, indent=1))
    status = "ok" if rec["ok"] else "FAIL"
    print(f"[{status}]   {mesh_name} {arch} {shape_name} "
          f"lower={rec.get('lower_s')}s compile={rec.get('compile_s')}s "
          f"coll={rec.get('collective_bytes_per_device', 0)/1e6:.0f}MB"
          + ("" if rec["ok"] else f"  {rec.get('error', '')[:200]}"))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--dump-hlo", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    out_dir = pathlib.Path(args.out)

    cells = []
    archs = configs.ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SP.SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            cells.append((a, s))

    n_fail = 0
    for a, s in cells:
        rec = run_cell(a, s, args.multi_pod, out_dir, force=args.force,
                       dump_hlo=args.dump_hlo)
        if rec.get("ok") is False:
            n_fail += 1
    print(f"done: {len(cells)} cells, {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
