"""Find a cell's files by the names that BENCHMARK.json gives.

    <bench>/configs/<config>.json     sizes of the model, as run
    <bench>/traffic/<traffic>.json    parameters of the traffic mix
    <bench>/limits/<workload>.json    limits of the comparison that decides
                                      `correct`
    <bench>/metrics/<metric>.py       reader of one per-layer metric
    <bench>/reference/<reference>.py  one family, as the configuration's
                                      `reference` names it: its plain
                                      forward pass, the weights made from
                                      the seed, its FLOPs

A later cell, mix, metric or family is added as new files and entries;
nothing here names one.  A cell's `chips` are the devices its step and
its reference run over.
"""

import importlib.util
import json
import os


def _load_json(path):
    with open(path) as f:
        return json.load(f)


class Bench:
    def __init__(self, root: str):
        self.root = root
        self.spec = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.dir = os.path.join(root, self.spec["paths"][0])

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return _load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _load_json(os.path.join(self.dir, "traffic", name + ".json"))

    def limits(self, workload: str) -> dict:
        return _load_json(os.path.join(self.dir, "limits", workload + ".json"))

    def end_to_end(self, workload: str) -> list:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.spec["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> list:
        """The per-layer metrics this cell reports: those that list it, and
        those without a list whose end-to-end metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(workload)}
        return [m for m in self.spec["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reader(self, metric: str):
        """`read(ctx)` of metrics/<metric>.py."""
        path = os.path.join(self.dir, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
