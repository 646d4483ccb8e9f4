"""Finds every file of a cell by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a later PR adds entries to ``BENCHMARK.json`` and files beside the
existing ones, and edits no file that is there.

    configs/<config>.json     sizes as run, ``family``, ``source``, ``changed``
    traffic/<traffic>.json    parameters of one traffic mix
    cells/<workload>.json     limits of the cell's ``correct`` comparison
    metrics/<metric>.py       one per-layer reader, ``read(ctx) -> float|None``
    families/<family>.py      runner, required-FLOP function, reference adapter
"""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with the files its names point at."""

    def __init__(self, manifest: dict, name: str):
        rows = [w for w in manifest["workloads"] if w["name"] == name]
        if not rows:
            have = sorted(w["name"] for w in manifest["workloads"])
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {have}")
        self.name = name
        self.entry = rows[0]
        self.chips = int(self.entry["chips"])
        cfg_row = next(c for c in manifest["configs"]
                       if c["name"] == self.entry["config"])
        self.config = load_json(os.path.join(ROOT, cfg_row["file"]))
        self.traffic = load_json(os.path.join(
            BENCH_DIR, "traffic", self.entry["traffic"] + ".json"))
        self.limits = load_json(os.path.join(
            BENCH_DIR, "cells", name + ".json"))["limits"]
        self.family = importlib.import_module(
            "benchmark.families." + self.config["family"])
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in manifest["per_layer"]
                          if name in m.get("workloads", [name])]

    def reader(self, metric: str):
        """The ``read(ctx)`` of ``metrics/<metric>.py``."""
        return importlib.import_module("benchmark.metrics." + metric).read
