"""The delta-reporting cell, ``gaussian_churn``, at its rehearsal size.

On the CPU with the look for a chip skipped: the loop of object reports
spliced into the index runs end to end with nothing compiled inside the
window and passes the comparison with the reference; every seed offers the
same work; and a session that loses one tick's reports is refused, so the
``fresh`` guarantee is checked, not assumed.  No number here is a device
number.
"""
from __future__ import annotations

import contextlib
import itertools
import sys
from pathlib import Path
from unittest import mock

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from knnbench import harness  # noqa: E402

CELL = "gaussian_churn"
SEED = 2_147_483_677  # more than 32 signed bits hold


def test_rehearsal_is_correct_and_reads_every_metric():
    out = harness.rehearse(CELL, SEED, 1.0)
    run, verdict = out["run"], out["verdict"]
    assert out["correct"], verdict
    assert verdict["attempted"] > 0 and verdict["failed"] == 0
    assert run["compiles_in_window"] == 0
    assert run["ticks"] and run["window_s"] > 0.5
    cell = harness.load_cell(CELL)
    reports = cell.rehearsal().config["reports_per_tick"]
    for t in run["ticks"]:
        assert t["delta_rows"] == {"incremental": reports, "skip": 0}[
            t["maintenance"]]
    e2e = harness.read_metrics(cell, "end_to_end", run)
    # the CPU reports no device memory, so only peak_hbm_mb stays silent
    assert set(e2e) == {m["name"] for m in cell.metrics("end_to_end")} - {
        "peak_hbm_mb"}
    layer = harness.read_metrics(cell, "per_layer", run)
    assert set(layer) == {m["name"] for m in cell.metrics("per_layer")}
    assert layer["incremental_tick_share"]["value"] > 0
    assert layer["delta_ingest_ms"]["value"] > 0


def test_every_seed_offers_the_same_work():
    """--seed draws the objects' ids, the issuers' order and the rows
    checked; the world, the issuers and the order of the reports are one
    draw of the deployment, so the sweep and the splice do the same work
    under either labelling."""
    import jax

    cell = harness.load_cell(CELL).rehearsal()
    drv = cell.driver()
    work = []
    for seed in (5, SEED):
        st = drv.setup(cell, seed, jax.devices()[:1], lambda m: None)
        drv._loop(st, lambda name: contextlib.nullcontext(), ticks=4,
                  keep=True)
        work.append([(t["iterations"], t["candidates"], t["maintenance"],
                      t["delta_rows"], t["moved"])
                     for t in drv.record(st)["ticks"]])
    assert work[0] == work[1]
    assert {m for _, _, m, _, _ in work[0]} == {"incremental"}


@contextlib.contextmanager
def one_tick_of_reports_lost():
    """The session drops the reports of the window's second tick."""
    from repro.api.session import KnnSession

    orig = KnnSession.update_objects
    calls = itertools.count()

    def update_objects(self, ids, positions):
        if next(calls) != 1:
            orig(self, ids, positions)

    with mock.patch.object(KnnSession, "update_objects", update_objects):
        yield


def test_a_lost_tick_of_reports_is_not_correct():
    out = harness.rehearse(CELL, SEED, 1.0,
                           around_window=one_tick_of_reports_lost)
    assert not out["correct"], out["verdict"]
    assert out["verdict"]["checks"]["pairs_wrong"][0] > 0
    assert np.any([t["maintenance"] == "skip" for t in out["run"]["ticks"]])
