"""Run manifests: provenance JSON written next to every results file.

A manifest answers "what produced this JSON?" without re-running
anything: git SHA + dirty flag, jax version / backend / device kind /
devices / x64 flag (only if jax is already imported — building a
manifest never triggers device initialization), python/numpy/platform,
the argv that launched the run, seeds, and the run config with a
canonical sha256 hash so two runs can be compared by a single string.
"""
from __future__ import annotations

import hashlib
import json
import os
import platform
import subprocess
import sys
import time


def _git(*args):
    try:
        out = subprocess.run(("git",) + args, capture_output=True,
                             text=True, timeout=10,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() if out.returncode == 0 else None
    except (OSError, subprocess.SubprocessError):
        return None


def _git_info() -> dict:
    status = _git("status", "--porcelain")
    return {"sha": _git("rev-parse", "HEAD"),
            "branch": _git("rev-parse", "--abbrev-ref", "HEAD"),
            "dirty": bool(status) if status is not None else None}


def _jax_info() -> dict:
    # read-only: report on jax only when the run already imported it,
    # so writing a manifest never initializes a backend itself
    if "jax" not in sys.modules:
        return {"imported": False}
    jax = sys.modules["jax"]
    try:
        devices = jax.devices()
        return {"imported": True,
                "version": jax.__version__,
                "backend": devices[0].platform if devices else None,
                "device_kind": devices[0].device_kind if devices else None,
                "device_count": len(devices),
                "devices": [str(d) for d in devices],
                "x64": bool(jax.config.jax_enable_x64)}
    except Exception as e:  # backend init can fail in odd environments
        return {"imported": True, "version": getattr(jax, "__version__", None),
                "error": repr(e)}


def config_hash(config) -> str:
    """sha256 of the canonical (sorted-keys, default=str) JSON encoding
    — a stable fingerprint for "same run config"."""
    blob = json.dumps(config, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()


def run_manifest(config=None, seeds=None, extra=None) -> dict:
    """Build the provenance record for one run."""
    try:
        import numpy as np
        np_version = np.__version__
    except ImportError:  # pragma: no cover
        np_version = None
    man = {
        "schema": "repro.obs.manifest/v1",
        "created_unix": time.time(),
        "created": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "git": _git_info(),
        "jax": _jax_info(),
        "python": sys.version.split()[0],
        "numpy": np_version,
        "platform": platform.platform(),
        "argv": list(sys.argv),
        "env": {k: os.environ[k]
                for k in ("JAX_ENABLE_X64", "XLA_FLAGS", "REPRO_BENCH_FULL")
                if k in os.environ},
        "seeds": seeds,
        "config": config,
        "config_hash": config_hash(config) if config is not None else None,
    }
    if extra:
        man["extra"] = dict(extra)
    return man


def write_manifest(results_path, config=None, seeds=None, extra=None) -> str:
    """Write ``<results stem>.manifest.json`` next to ``results_path``
    and return the manifest path."""
    results_path = os.fspath(results_path)
    stem, _ = os.path.splitext(results_path)
    path = stem + ".manifest.json"
    with open(path, "w") as f:
        json.dump(run_manifest(config=config, seeds=seeds, extra=extra),
                  f, indent=2, default=str)
        f.write("\n")
    return path
