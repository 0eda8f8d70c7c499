"""BENCHMARK.json and the files it names, looked up by name.

The harness keeps no list of its own.  A cell names a configuration and
a traffic mix; the mix's file names its ``kind``; the kind is a module
in ``drivers/``; a per-layer metric is a module in ``layer_metrics/``
with the metric's name; a configuration's ``family`` is a module in
``families/`` (its plain reference and its byte floor).  Adding any of
them is adding files and an entry, never editing one.
"""

from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def module_name(name: str) -> str:
    """A metric or kind name as a module name: ``.`` and ``-`` cannot be
    in one, so they are written ``_``."""
    return name.replace(".", "_").replace("-", "_")


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(kind_dir: str, name: str) -> dict:
    if not NAME_RE.match(name):
        raise ValueError(f"{name!r} is not a permitted name")
    path = os.path.join(HERE, kind_dir, f"{name}.json")
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, bench: dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(
                f"no workload {name!r}; BENCHMARK.json has {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == self.entry["config"])
        with open(os.path.join(ROOT, cfg_entry["file"])) as f:
            self.config = json.load(f)
        self.traffic = load_json("traffic", self.entry["traffic"])
        self.driver = importlib.import_module(
            f"chipbench.drivers.{module_name(self.traffic['kind'])}")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def layer_reader(self, metric_name: str):
        return importlib.import_module(
            f"chipbench.layer_metrics.{module_name(metric_name)}").read
