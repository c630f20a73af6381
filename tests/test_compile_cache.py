"""The persistent compile cache of the command-line entry points
(``repro.compile_cache``): placed from outside by
``JAX_COMPILATION_CACHE_DIR``, otherwise at a fixed, git-ignored path in
the checkout.  Each case runs in its own process, so this worker's JAX
configuration is never touched."""
import os
import subprocess
import sys

from repro.compile_cache import DEFAULT_DIR

REPO = DEFAULT_DIR.parent

_PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((32, 32))).block_until_ready()
"""


def _probe(cache_env, compile_min_s):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=compile_min_s,
               PYTHONPATH=str(REPO / "src"))
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_env)
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=300,
                         check=True)
    return out.stdout.split()


def test_env_dir_holds_every_compiled_program(tmp_path):
    returned, configured = _probe(tmp_path, "0")
    assert returned == configured == str(tmp_path)
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_default_dir_is_fixed_and_git_ignored():
    # a compile threshold no program reaches: nothing is written
    returned, configured = _probe(None, "1e9")
    assert returned == configured == str(REPO / ".jax_cache")
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()
