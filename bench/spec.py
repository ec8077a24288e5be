"""Reads ``BENCHMARK.json`` and finds a cell's files by name.

A configuration is ``bench/configs/<config>.json`` (the entry's
``file``), a traffic mix ``bench/traffic/<traffic>.json``, and a
per-layer metric's reader ``bench/metrics/<metric>.py`` with a function
``read(run)``. Configurations and mixes are data: they name the kinds
of data, updates, arrivals and queries of the one generator
(``bench/stream.py``) and set their parameters. A later change adds a
cell, a configuration, a mix or a metric by adding files and entries;
nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

LOOPS = ("closed", "open")


class Cell:
    """One workload entry with its configuration, mix and metrics."""

    def __init__(self, root: Path, spec: dict, name: str):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; one of {sorted(cells)}")
        w = cells[name]
        self.name, self.chips = name, int(w["chips"])
        configs = {c["name"]: c for c in spec["configs"]}
        self.config = json.loads((root / configs[w["config"]]["file"])
                                 .read_text())
        self.mix = json.loads((root / "bench" / "traffic"
                               / f"{w['traffic']}.json").read_text())
        if self.mix.get("loop") not in LOOPS:
            raise ValueError(f"traffic {w['traffic']!r}: loop must be one "
                             f"of {LOOPS}")

        def mine(m):
            return name in m.get("workloads", [name])

        self.end_to_end = [m for m in spec["end_to_end"] if mine(m)]
        self.per_layer = [m for m in spec["per_layer"] if mine(m)]
        self.root = root

    def reader(self, metric: str):
        """The ``read(run)`` function of a per-layer metric's file."""
        path = self.root / "bench" / "metrics" / f"{metric}.py"
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read


def load(root: Path, name: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    return Cell(root, spec, name)
