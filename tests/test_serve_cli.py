"""The k-NN serving CLI, ``python -m repro.launch.serve knn``, end to end.

Both run in a child process: the import check needs a fresh interpreter, and
the CLI is run the way a user runs it.
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _env(tmp_path):
    # the child keeps its compile cache out of the checkout
    return dict(os.environ, PYTHONPATH=SRC,
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))


def test_serve_imports_no_lm_stack(tmp_path):
    """Importing the k-NN CLI loads none of the LM library."""
    code = (
        "import sys, repro.launch.serve\n"
        "print(sorted(m for m in sys.modules if m.startswith(\n"
        "    ('repro.models', 'repro.configs', 'repro.train'))))\n"
    )
    r = subprocess.run([sys.executable, "-c", code], env=_env(tmp_path),
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip() == "[]"


@pytest.mark.parametrize("tenants", [1, 2])
def test_serve_knn_runs_end_to_end(tmp_path, tenants):
    """Two ticks at 2,000 objects through a session (1 tenant) or through a
    KnnServer (2 tenants), and the steady-state line at the end."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "knn",
         "--objects", "2000", "--ticks", "2", "--k", "8", "--chunk", "512",
         "--tenants", str(tenants)],
        env=_env(tmp_path), capture_output=True, text=True,
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    lines = r.stdout.splitlines()
    assert [ln.split(":")[0] for ln in lines if " tick " in ln] == [
        "[knn] tick 0", "[knn] tick 1"]
    want = (f"[knn] {tenants} tenants steady-state" if tenants > 1
            else "[knn] steady-state throughput")
    assert lines[-1].startswith(want), lines[-1]
