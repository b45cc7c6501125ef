"""Where the entry points keep JAX's persistent compilation cache.

Each case runs in a fresh interpreter: the cache directory is process-wide
JAX configuration, and setting it here would reach every later test of
this worker.
"""
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
PROBE = """
import jax, repro.core, repro.launch.compressd
before = jax.config.jax_compilation_cache_dir
from repro.launch.jaxcache import enable_compile_cache
print(before)
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_cache_dir_from_env_else_repo(tmp_path, env_dir):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO / "src")
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, capture_output=True,
                         text=True, timeout=120, check=True).stdout.split("\n")
    before, chosen, configured = out[:3]
    if env_dir:
        # JAX reads the variable itself; the helper sets nothing over it
        assert before == chosen == configured == str(tmp_path / env_dir)
    else:
        # importing the library leaves the cache alone; the helper picks
        # the fixed, gitignored path
        assert before == "None"
        assert chosen == configured == str(REPO / ".jax_cache")
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
