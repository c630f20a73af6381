"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and
each phase's path and checks run at a tiny size on the CPU, so the
script cannot rot between chip runs."""
import json
import os
import subprocess
import sys

import pytest

from repro import configs
from repro.mec.scenario import MECConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as CS  # noqa: E402


def _phase_lines(out):
    return [json.loads(line) for line in out.splitlines()
            if line.startswith("{")]


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not _phase_lines(out.stdout)


@pytest.mark.parametrize("phase", ["offline", "online", "serving",
                                   "sharded"])
def test_phase_runs_at_tiny_size(phase, capsys):
    if phase == "offline":
        CS.phase_offline(0, cfg=MECConfig(n_users=30), pdhg_iters=300,
                         best_of=3)
    elif phase == "online":
        CS.phase_online(0, cfg=MECConfig(n_users=60), n_slots=4)
    elif phase == "serving":
        CS.phase_serving(0, model_cfg=configs.get_smoke("qwen1.5-0.5b"),
                         prompt=12, new_tokens=3)
    else:
        CS.phase_sharded(0, 1, base=MECConfig(n_users=30), pdhg_iters=300,
                         best_of=3)
    lines = _phase_lines(capsys.readouterr().out)
    assert lines and all(_well_formed(line) for line in lines)


def _well_formed(line):
    """Every printed phase line names its phase and reports finite
    set-up and warm seconds."""
    return line["setup_s"] >= 0 and line["warm_s"] >= 0
