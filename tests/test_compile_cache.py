"""Where the entry points put JAX's persistent compilation cache.

Each case runs in a fresh process: turning the cache on is process-global
JAX configuration, which the test workers must not inherit.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

PROBE = """
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from repro.launch.compile_cache import use_compile_cache
print("CACHE", use_compile_cache(), jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_directory(tmp_path, from_env):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, compiled programs land there
    and the helper configures nothing; without it the cache is the fixed
    ``.jax_cache/`` at the repo root."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("CACHE "))
    returned, configured = line.split()[1:]
    want = str(tmp_path) if from_env else str(ROOT / ".jax_cache")
    assert returned == configured == want
    if from_env:
        assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())
