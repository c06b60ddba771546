"""Everything the harness runs is found by a name in ``BENCHMARK.json``.

A configuration, a traffic mix, a builder, a runner, a per-layer metric and
its reader each sit in a file of their own under one of the benchmark's
``paths``; a later PR adds files and one entry, and edits none that is here:

    configs/<config>.json          published keys, cuts, ``builder``
    builders/<builder>.py          build(config, traffic, seed, devices) -> system
    traffic/<traffic>.json         parameters of the mix, ``runner``
    runners/<runner>.py            run(system, traffic, ctx) -> Outcome
    layer_metrics/<metric>.json    layer, unit, moves, ``reader``
    readers/<reader>.py            read(ctx) -> float or None"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class Registry:
    def __init__(self, root: Optional[str] = None):
        self.root = os.path.abspath(root or ROOT)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.benchmark = json.load(f)
        self.dirs: List[str] = [os.path.join(self.root, p)
                                for p in self.benchmark["paths"]]

    # -- entries of BENCHMARK.json ----------------------------------------
    def _entry(self, key: str, name: str) -> dict:
        for e in self.benchmark[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"BENCHMARK.json has no {key} entry named {name!r}; "
                       f"it has {[e['name'] for e in self.benchmark[key]]}")

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def metrics_of(self, workload: str, key: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.benchmark[key]
                if "workloads" not in m or workload in m["workloads"]]

    # -- files -----------------------------------------------------------
    def find(self, kind: str, name: str, ext: str) -> str:
        for d in self.dirs:
            path = os.path.join(d, kind, name + ext)
            if os.path.isfile(path):
                return path
        raise FileNotFoundError(
            f"no {kind}/{name}{ext} under {self.benchmark['paths']}")

    def _json(self, path: str) -> dict:
        with open(path) as f:
            return json.load(f)

    def config(self, name: str) -> dict:
        entry = self._entry("configs", name)
        return self._json(os.path.join(self.root, entry["file"]))

    def traffic(self, name: str) -> dict:
        return self._json(self.find("traffic", name, ".json"))

    def layer_metric(self, name: str) -> dict:
        return self._json(self.find("layer_metrics", name, ".json"))

    def module(self, kind: str, name: str):
        """The Python file ``<kind>/<name>.py``, loaded by its path so that
        a directory added to ``paths`` needs no package of its own."""
        path = self.find(kind, name, ".py")
        key = f"_benchmark_{kind}_{name}_{abs(hash(path))}"
        if key in sys.modules:
            return sys.modules[key]
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
        return mod
