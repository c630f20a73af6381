"""Benchmark entrypoint: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Default scale finishes on a
laptop-class CPU; set REPRO_BENCH_FULL=1 for the paper-scale settings
(N=5, U=600, 10 windows / 100 slots).
"""
from __future__ import annotations

import time

from benchmarks import (bench_baselines, bench_kernels, bench_offline,
                        bench_online, bench_serving, common,
                        motivating_example, roofline, tables)


def _emit_offline(name, res):
    for a, r in res.items():
        extra = f"prec={r.get('avg_precision', r.get('lr_bound', 0)):.3f}"
        if "hit_rate" in r:
            extra += f";hr={r['hit_rate']:.3f};mem={r.get('mem_util', 0):.3f}"
        common.csv_row(f"{name}_{a}", r.get("seconds", 0) * 1e6, extra)


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    t0 = time.time()
    print("name,us_per_call,derived")

    st, dy = motivating_example.run_example()
    common.csv_row("motivating_static", 0,
                   f"prec={st['avg_precision']:.3f};hr={st['hit_rate']:.3f}")
    common.csv_row("motivating_dynamic", 0,
                   f"prec={dy['avg_precision']:.3f};hr={dy['hit_rate']:.3f}")

    res4 = tables.table4_offline()
    _emit_offline("table4", res4)

    res5 = tables.table5_online()
    for key, block in res5.items():
        for a, r in block.items():
            common.csv_row(f"table5_{key}_{a}", r.get("seconds", 0) * 1e6,
                           f"qoe={r['avg_qoe']:.3f};hr={r['hit_rate']:.3f}")

    for fn, name in ((tables.fig6_memory, "fig6"),
                     (tables.fig8_zipf, "fig8")):
        res = fn()
        for xval, algos in res.items():
            for a, r in algos.items():
                common.csv_row(f"{name}_{xval}_{a}",
                               r.get("seconds", 0) * 1e6,
                               f"prec={r['avg_precision']:.3f};"
                               f"hr={r['hit_rate']:.3f}")

    res = tables.fig12_memory_online(caps=(100, 500, 900))
    for cap, algos in res.items():
        for a, r in algos.items():
            common.csv_row(f"fig12_{cap}_{a}", r.get("seconds", 0) * 1e6,
                           f"qoe={r['avg_qoe']:.3f};hr={r['hit_rate']:.3f}")

    sw = tables.sweep_table()
    common.csv_row("sweep_grid", sw["seconds"] / len(sw["rows"]) * 1e6,
                   f"variants={len(sw['rows'])};"
                   f"total_s={sw['seconds']:.2f}")

    bench_serving.main()
    bench_online.main()
    bench_offline.main()
    bench_baselines.main()
    bench_kernels.main()

    for mesh in ("16x16", "2x16x16"):
        rows = roofline.load_cells(mesh)
        ok = [r for r in rows if "skipped" not in r]
        if ok:
            worst = min(ok, key=lambda r: r["roofline_fraction"] or 1)
            best = max(ok, key=lambda r: r["roofline_fraction"] or 0)
            common.csv_row(
                f"roofline_{mesh}", 0,
                f"cells={len(ok)};best={best['arch']}/{best['shape']}="
                f"{best['roofline_fraction']};worst={worst['arch']}/"
                f"{worst['shape']}={worst['roofline_fraction']}")

    common.csv_row("total_bench", (time.time() - t0) * 1e6, "done")


if __name__ == "__main__":
    main()
