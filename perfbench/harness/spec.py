"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here, in ``run.py`` or elsewhere under ``harness/`` names a
cell, a configuration, a traffic mix or a metric: a later PR adds
entries to ``BENCHMARK.json`` and files under ``configs/``,
``traffic/``, ``metrics/``, ``deployments/``, ``generators/`` and
``readers/``, and edits nothing that is there.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, name: str):
        bench = load_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(
                f"perfbench: no workload {name!r} in BENCHMARK.json "
                f"(known: {sorted(cells)})")
        self.bench = bench
        self.workload = cells[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == self.workload["config"])
        self.config = load_json(ROOT, cfg_entry["file"])
        self.traffic = load_json(BENCH_DIR, "traffic",
                                 self.workload["traffic"] + ".json")
        self.peaks = load_json(BENCH_DIR, "peaks.json")

    def _mine(self, m: dict, e2e_names: set) -> bool:
        if "workloads" in m:
            return self.name in m["workloads"]
        # a per-layer metric without the key is due in every cell that
        # reports the end-to-end metric it moves
        return "moves" not in m or m["moves"] in e2e_names

    def end_to_end(self) -> List[dict]:
        return [m for m in self.bench["end_to_end"]
                if self._mine(m, set())]

    def per_layer(self) -> List[dict]:
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"] if self._mine(m, mine)]

    @staticmethod
    def metric_spec(name: str) -> dict:
        return load_json(BENCH_DIR, "metrics", name + ".json")


def component(kind_dir: str, kind: str):
    """``perfbench/<kind_dir>/<kind>.py``, found by name: a new kind is
    a new file."""
    return importlib.import_module(f"perfbench.{kind_dir}.{kind}")
