"""Each cell end to end at its rehearsal size, and how cells are found.

The rehearsals run on the CPU with the look for a chip skipped: they check
the control flow, the warm-up (no compile inside the window) and that the
comparison with the reference passes on a sound program.  They give no
device number.
"""
from __future__ import annotations

import contextlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from knnbench import harness  # noqa: E402

sys.path.insert(0, str(BENCH / "tests"))
import x4_root  # noqa: E402

SEED = 2_147_483_659  # more than 32 signed bits hold


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


@pytest.mark.parametrize("cell", ["gaussian_join"])
def test_rehearsal(cell):
    out = harness.rehearse(cell, SEED, 1.0)
    run, verdict = out["run"], out["verdict"]
    assert out["correct"], verdict
    assert verdict["attempted"] > 0 and verdict["failed"] == 0
    assert run["compiles_in_window"] == 0
    assert run["ticks"] and run["window_s"] > 0.5
    c = harness.load_cell(cell)
    names = {m["name"] for m in c.metrics("end_to_end")}
    got = harness.read_metrics(c, "end_to_end", run)
    # the CPU reports no device memory, so only peak_hbm_mb stays silent
    assert set(got) == names - {"peak_hbm_mb"}
    assert all(v["value"] > 0 for v in got.values())
    layer = harness.read_metrics(c, "per_layer", run)
    trace_only = {"device_idle_share"}
    expect = {m["name"] for m in c.metrics("per_layer")} - trace_only
    assert set(layer) == expect


FOUR_DEVICES = r"""
import sys
from pathlib import Path
sys.path.insert(0, {bench!r}); sys.path.insert(0, {src!r})
import jax
assert jax.device_count() == 4
from knnbench import harness
root = Path({root!r})
out = harness.rehearse("gaussian_join_x4", {seed}, 1.0, root=root)
run = out["run"]
assert out["correct"], out["verdict"]
assert run["compiles_in_window"] == 0
assert all(len(t["shard_candidates"]) == 4 for t in run["ticks"])
cell = harness.load_cell("gaussian_join_x4", root)
gap = harness.read_metrics(cell, "per_layer", run)["plan_straggler_gap"]
assert gap["value"] >= 1.0
print("X4_OK")
"""


def test_rehearsal_on_four_devices(tmp_path):
    code = FOUR_DEVICES.format(bench=str(BENCH), src=str(ROOT / "src"),
                               seed=SEED, root=str(x4_root.make(tmp_path)))
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        timeout=600)
    assert r.returncode == 0 and "X4_OK" in r.stdout, r.stderr[-3000:]


def test_a_new_cell_is_found_by_its_files_alone(tmp_path):
    """A configuration, a mix, a metric and a cell added as new files (and
    entries), with no edit to a file of the harness, drivers or metrics."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = json.loads((BENCH / "configs" / "sowell_gaussian_1m.json")
                     .read_text())
    cfg["world"]["distribution"] = "uniform"
    (tmp_path / "bench" / "configs" / "dummy_uniform.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "dummy_mix.json").write_text(
        json.dumps({"driver": "closed_join", "frames": 3, "warm_ticks": 1,
                    "check_rows": 16}))
    (tmp_path / "bench" / "metrics" / "dummy_ticks.py").write_text(
        "def read(run):\n    return len(run['ticks']) or None\n")
    bench["configs"].append({"name": "dummy_uniform", "source": "test",
                             "file": "bench/configs/dummy_uniform.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy_cell", "config": "dummy_uniform",
                               "traffic": "dummy_mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "dummy_ticks", "unit": "ticks",
                               "better": "higher", "source": "host_clock",
                               "layer": "session", "moves": "queries_per_s",
                               "workloads": ["dummy_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("dummy_cell", tmp_path)
    assert cell.config["world"]["distribution"] == "uniform"
    assert cell.traffic["frames"] == 3
    assert [m["name"] for m in cell.metrics("per_layer")] == ["dummy_ticks"]
    out = harness.rehearse("dummy_cell", 7, 0.5, root=tmp_path)
    assert out["correct"]
    got = harness.read_metrics(cell, "per_layer", out["run"])
    assert got["dummy_ticks"]["value"] == len(out["run"]["ticks"]) > 0


def test_run_refuses_without_a_tpu():
    r = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "gaussian_join",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_env(), cwd=ROOT, timeout=300)
    assert r.returncode == 2
    assert r.stdout.strip() == ""
    assert "no result" in r.stderr


def test_run_fails_without_the_program(tmp_path):
    """Only BENCHMARK.json and the benchmark's files: no result line."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gaussian_join",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_env(PYTHONPATH=""),
        cwd=tmp_path, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_every_seed_offers_the_same_work():
    """--seed draws the objects' ids, the issuers' order and the rows
    checked; the world and the issuing objects are one draw of the
    deployment, the same in every run."""
    import jax
    import numpy as np

    cell = harness.load_cell("gaussian_join").rehearsal()
    drv = cell.driver()
    a, b = (drv.setup(cell, seed, jax.devices()[:1], lambda m: None)
            for seed in (5, SEED))
    assert not np.array_equal(a.qid, b.qid)
    assert not np.array_equal(a.ring.frames, b.ring.frames)

    def places(frame):
        return frame[np.lexsort(frame.T)]

    for fa, fb in zip(a.ring.frames, b.ring.frames):
        assert np.array_equal(places(fa), places(fb))
        assert np.array_equal(places(fa[a.qid]), places(fb[b.qid]))
    # and the sweep does the same work under either labelling
    work = []
    for st in (a, b):
        drv._loop(st, lambda name: contextlib.nullcontext(), ticks=3,
                  keep=True)
        work.append([(t["iterations"], t["candidates"])
                     for t in drv.record(st)["ticks"]])
    assert work[0] == work[1]
