"""Cells of ``BENCHMARK.json`` cut to sizes a CPU test run holds, and a
driver for them that skips the harness's look for a chip."""
from __future__ import annotations

import copy
import time

#: per cell: its configuration and traffic files, and the keys replaced
#: in them for the CPU
SMALL = {
    "sec7a-offline": {"files": ("sec7a", "zipf-windows"),
                      "config": {"n_users": 200, "pdhg_iters": 300},
                      "traffic": {"check_windows": 3}},
    "edge4-qwen-poisson": {
        "files": ("edge4-qwen1.5-0.5b", "poisson-serve"),
        "config": {"hidden_size": 64, "intermediate_size": 128,
                   "num_attention_heads": 4, "num_key_value_heads": 4,
                   "num_hidden_layers": 4, "vocab_size": 500,
                   "exit_layers": [2, 3, 4], "torch_dtype": "float32"},
        "traffic": {"prompt_tokens": 16, "new_tokens": 4,
                    "rate_per_s": 16.0, "batch_sizes": [1, 2],
                    "check_batches": 3}},
}


def resolved(cell: str) -> dict:
    """The cell as ``harness.resolve`` gives it, read from its files so
    that a cell not (yet) in ``BENCHMARK.json`` runs too."""
    from chipbench import harness as H

    config, traffic = SMALL[cell]["files"]
    spec = H.benchmark()
    listed = lambda m: "workloads" not in m or cell in m["workloads"]
    res = {"cell": {"name": cell, "config": config, "traffic": traffic,
                    "chips": 1},
           "config": H.load_json(H.HERE, "configs", config + ".json"),
           "traffic": H.load_json(H.HERE, "traffic", traffic + ".json"),
           "end_to_end": [m for m in spec["end_to_end"] if listed(m)],
           "per_layer": [m for m in spec["per_layer"] if listed(m)]}
    res["config"].update(copy.deepcopy(SMALL[cell]["config"]))
    res["traffic"].update(SMALL[cell]["traffic"])
    return res


def run(cell: str, seed: int = 12345678901, seconds: float = 0.5,
        **overrides):
    """One whole run of ``cell`` at its small size on the CPU; returns
    (result, context), the driver's state as ``context.state``.
    ``overrides`` replace traffic keys."""
    import jax

    from chipbench import harness as H
    from chipbench import run as RUN

    res = resolved(cell)
    res["traffic"].update(overrides)
    ctx = H.Context(cell, seed, seconds, False, res, time.perf_counter())
    ctx.device_kind = "TPU v5 lite"
    return RUN.run_cell(ctx, res, jax.devices()[:1]), ctx
