"""``BENCHMARK.json`` and the files it names, found by name.

A cell is a configuration file (``configs`` entry ``file``) under a traffic
mix (``<dir>/traffic/<traffic>.json``), checked against the limits in
``<dir>/limits/<cell>.json``.  A per-layer metric is read by
``<dir>/metrics/<metric>.py``, a module with ``read(run) -> float | None``;
where there is none, by the module named for the part of the metric's name
before its first ``.`` (``device_idle_share.ptt`` and
``device_idle_share.backlog`` share ``device_idle_share.py``).
A configuration's ``reference`` names its model family: the harness builds
it with ``<dir>/models/<reference>.py`` (``make_params``, ``input_bank``,
``build_loop``) and checks it against ``<dir>/reference/<reference>.py``
(``logits``, and ``compare`` where the family judges its outputs otherwise
than ``check.compare`` does).  ``<dir>`` is each of the benchmark's ``paths`` in turn, so a
later change adds a cell, a mix, a metric or a model family by adding files.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list  # the BENCHMARK.json entries this cell reports
    per_layer: list


class Bench:
    def __init__(self, root: str):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)

    def _find(self, *parts: str) -> str:
        for d in self.doc["paths"]:
            path = os.path.join(self.root, d, *parts)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(f"no {os.path.join(*parts)} under "
                                f"{self.doc['paths']}")

    @staticmethod
    def _load(path: str) -> dict:
        with open(path) as f:
            return json.load(f)

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.doc["workloads"]}
        if name not in cells:
            raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
        w = cells[name]
        conf = {c["name"]: c for c in self.doc["configs"]}[w["config"]]

        def mine(m):
            return name in m.get("workloads", [name])

        return Cell(
            name=name, chips=int(w["chips"]),
            config=self._load(os.path.join(self.root, conf["file"])),
            traffic=self._load(self._find("traffic", w["traffic"] + ".json")),
            limits=self._load(self._find("limits", name + ".json")),
            end_to_end=[m for m in self.doc["end_to_end"] if mine(m)],
            per_layer=[m for m in self.doc["per_layer"] if mine(m)])

    def reader(self, metric: str):
        """The ``read`` function of a per-layer metric's module."""
        try:
            path = self._find("metrics", metric + ".py")
        except FileNotFoundError:
            path = self._find("metrics", metric.split(".")[0] + ".py")
        return load_module(path).read

    def family(self, name: str):
        """The model family module ``models/<name>.py``."""
        return load_module(self._find("models", name + ".py"))

    def reference(self, name: str):
        """The plain reference module ``reference/<name>.py``."""
        return load_module(self._find("reference", name + ".py"))


def load_module(path: str):
    """The module at ``path``, loaded once per process: a module loaded
    again would bring new jitted functions, which compile again."""
    path = os.path.realpath(path)
    name = "bench_file_" + "".join(c if c.isalnum() else "_" for c in path)
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return mod
