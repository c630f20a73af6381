"""Production mesh construction.

Single pod:  (16, 16) = 256 chips, axes ("data", "model")   — TPU v5e pod.
Multi-pod:   (2, 16, 16) = 512 chips, axes ("pod", "data", "model");
             the "pod" axis is pure data-parallel (DCN-friendly: only the
             gradient all-reduce crosses pods).

Defined as functions so importing this module never touches jax device state.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax (launch/dryrun.py does this)")
    import numpy as np
    dev = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(dev, axes)


def make_host_mesh(*, data: int = 1, model: int = 1):
    """Small ("data", "model") mesh for host-device runs (CPU tests, the
    ``repro.scale`` grid executor).  Validates the device count up front:
    a short mesh would otherwise surface as an inscrutable reshape or
    shard_map error far from the cause."""
    import numpy as np
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got ({data}, {model})")
    n = data * model
    devices = jax.devices()
    if len(devices) < n:
        platform = devices[0].platform
        hint = (f"set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
                "before the first jax import for virtual CPU devices"
                if platform == "cpu" else
                f"run on a host with {n} {platform} devices")
        raise RuntimeError(
            f"mesh ({data}, {model}) needs {n} devices, but only "
            f"{len(devices)} {platform} device(s) exist — {hint}, or "
            "shrink the mesh")
    dev = np.asarray(devices[:n]).reshape((data, model))
    return jax.sharding.Mesh(dev, ("data", "model"))


# TPU v5e per-chip hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link
