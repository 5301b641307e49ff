"""The comparison that decides ``correct`` refuses what it must.

Each fault is planted under the window of a rehearsal (the look for a chip
skipped, the rest of the run driven as on the chip) and must turn
``correct`` false; the control, the reference in bfloat16 put in the
program's place, must fail too, while the same reference in float32, a
second witness, agrees with the host brute force.
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

TESTS = Path(__file__).resolve().parent
BENCH = TESTS.parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src"), str(TESTS)):
    if p not in sys.path:
        sys.path.insert(0, p)

import fault_plant  # noqa: E402
import x4_root  # noqa: E402
from knnbench import checks, harness  # noqa: E402

SEED = 3_000_000_019


@pytest.mark.parametrize("cell", ["gaussian_join"])
@pytest.mark.parametrize("fault", ["stale", "half", "altered"])
def test_a_planted_fault_is_not_correct(cell, fault):
    out = harness.rehearse(cell, SEED, 1.0,
                           around_window=fault_plant.FAULTS[fault])
    assert not out["correct"], out["verdict"]
    assert out["verdict"]["checks"]["pairs_wrong"][0] > 0


@pytest.mark.parametrize("cell", ["gaussian_join"])
def test_the_control_fails_and_the_witness_agrees(cell):
    c = harness.load_cell(cell).rehearsal()
    drv = c.driver()
    import jax

    st = drv.setup(c, SEED, jax.devices()[:1], lambda m: None)
    drv.window(st, 1.0, lambda n: __import__("contextlib").nullcontext())
    drv.release(st)
    k = int(c.config["service"]["k"])
    sound, _ = checks.compare(drv.answers(st, SEED), k)
    assert sound == {"rows_wrong": (0, 0), "pairs_wrong": (0, 0)}
    witness = checks.control(drv.answers(st, SEED), k, "float32")
    assert witness == {"rows_wrong": (0, 0), "pairs_wrong": (0, 0)}
    ctrl = checks.control(drv.answers(st, SEED), k, "bfloat16")
    assert ctrl["rows_wrong"][0] > ctrl["rows_wrong"][1]


FOUR_DEVICES = r"""
import sys
from pathlib import Path
for p in ({bench!r}, {src!r}, {tests!r}):
    sys.path.insert(0, p)
import jax
assert jax.device_count() == 4
import fault_plant
from knnbench import harness
root = Path({root!r})
print("sound", harness.rehearse("gaussian_join_x4", {seed}, 1.0,
                                root=root)["correct"])
for name, fault in fault_plant.FAULTS.items():
    out = harness.rehearse("gaussian_join_x4", {seed}, 1.0, root=root,
                           around_window=fault)
    print(name, out["correct"], out["verdict"]["checks"])
"""


def test_planted_faults_on_four_devices(tmp_path):
    code = FOUR_DEVICES.format(bench=str(BENCH), src=str(ROOT / "src"),
                               tests=str(TESTS), seed=SEED,
                               root=str(x4_root.make(tmp_path)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = dict(line.split(" ", 1) for line in r.stdout.splitlines())
    assert lines["sound"].startswith("True"), r.stdout
    for name in fault_plant.FAULTS:
        assert lines[name].startswith("False"), r.stdout
