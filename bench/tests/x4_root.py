"""A copy of the benchmark whose ``BENCHMARK.json`` lists the four-chip cell.

``gaussian_join_x4`` (the gaussian join on the ``sharded`` plan over a v5e
2x2 host) has its configuration and its straggler metric in place but is
not yet a cell of the benchmark: it has not been measured on four chips.
The four-device tests drive it from such a copy.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

CONFIG = {"name": "sowell_gaussian_1m_x4",
          "source": json.loads((BENCH / "configs" / "sowell_gaussian_1m_x4"
                                ".json").read_text())["source"],
          "file": "bench/configs/sowell_gaussian_1m_x4.json",
          "reduced": ["query_rate"],
          "why": "the Gaussian-hotspot join with its query axis over 4 chips"}
CELL = {"name": "gaussian_join_x4", "config": "sowell_gaussian_1m_x4",
        "traffic": "iterated_join", "chips": 4,
        "why": "the gaussian join with 65,536 queries split over 4 chips"}
STRAGGLER = {"name": "plan_straggler_gap", "unit": "ratio",
             "better": "lower", "source": "program_counter", "layer": "plan",
             "moves": "queries_per_s", "workloads": ["gaussian_join_x4"]}


def make(dest: Path) -> Path:
    """Copy the benchmark under ``dest`` with the cell listed; its root."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench["configs"].append(CONFIG)
    bench["workloads"].append(CELL)
    bench["per_layer"].append(STRAGGLER)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gaussian_join" in m.get("workloads", []):
            m["workloads"].append(CELL["name"])
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest
