"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix, one kind
of traffic, one model family or one per-layer metric sits in a file of
its own, found here by its name. Adding one means adding files and
entries; no file that is there needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Layout:
    """A checkout: ``<root>/BENCHMARK.json`` and ``<root>/benchmark/``."""

    def __init__(self, root: str = ROOT):
        self.root = os.path.abspath(root)
        self.base = os.path.join(self.root, "benchmark")
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)

    def _module(self, directory: str, name: str):
        path = os.path.join(self.base, directory, name + ".py")
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no benchmark/{directory}/{name}.py "
                                    f"in {self.root}")
        ident = f"{directory}_{name}".replace(".", "_").replace("-", "_")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_plugin_{ident}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def kind(self, name: str):
        """``kinds/<name>.py``: runs one kind of traffic, ``run(ctx)``."""
        return self._module("kinds", name)

    def family(self, name: str):
        """``families/<name>.py``: builds the program's graph from a
        configuration, names its reference, and answers what the shared
        readers ask of a family (its counts of a step's bytes, a chunk's
        operations, ...)."""
        return self._module("families", name)

    def reference(self, name: str):
        return self._module("reference", name)

    def reader(self, metric: str):
        """``layer_metrics/<metric>.py``: ``read(run) -> number | None``,
        one reader a quantity, for every cell its entry lists. What
        differs by family it asks of ``run["family"]``, by the name its
        ``ASKS`` states. An entry that has to stand apart from its
        quantity's, because it moves another end-to-end metric or belongs
        to another layer there, is named ``<quantity>.<what sets it
        apart>`` and read by the quantity's file, unless it has one of
        its own."""
        return self._module("layer_metrics", self.quantity(metric))

    def quantity(self, metric: str) -> str:
        """The quantity whose file reads the entry ``metric``."""
        own = os.path.join(self.base, "layer_metrics", metric + ".py")
        if not os.path.isfile(own) and "." in metric:
            return metric.rpartition(".")[0]
        return metric

    def mix(self, name: str) -> Dict:
        path = os.path.join(self.base, "traffic", name + ".json")
        with open(path) as f:
            mix = json.load(f)
        if "kind" not in mix:
            raise ValueError(f"{path}: a mix names its kind")
        return mix

    def cell(self, workload: str) -> Dict:
        """The cell ``workload``: its entry, its configuration (entry and
        file), its mix, and the metrics it reports."""
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(known: {sorted(cells)})")
        w = cells[workload]
        entry = {c["name"]: c for c in self.bench["configs"]}[w["config"]]
        with open(os.path.join(self.root, entry["file"])) as f:
            config = json.load(f)
        e2e = [m for m in self.bench["end_to_end"]
               if workload in m.get("workloads", [workload])]
        names = {m["name"] for m in e2e}
        per_layer = [m for m in self.bench["per_layer"]
                     if (workload in m["workloads"] if "workloads" in m
                         else m["moves"] in names)]
        return {"workload": w, "config_entry": entry, "config": config,
                "mix": self.mix(w["traffic"]), "end_to_end": e2e,
                "per_layer": per_layer}
