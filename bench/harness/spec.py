"""Find what `BENCHMARK.json` names: cells, configurations, mixes, metrics.

Everything is looked up by name under the benchmark's own directory, so a
later change adds a configuration, a traffic mix or a metric by adding a
file and an entry, never by editing one:

    bench/configs/<file named in BENCHMARK.json>   a deployment (JSON)
    bench/generators/<config["generator"]>.py      its edge-list generator
    bench/traffic/<traffic>.json                   a traffic mix (data only)
    bench/metrics/<metric name>.py                 a metric reader
    bench/peaks.json                               peaks by device_kind
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench = _json(os.path.join(root, "BENCHMARK.json"))

    def workload(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.bench["workloads"]]
        raise KeyError(f"unknown workload {name!r}; known: {known}")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return _json(os.path.join(self.root, c["file"]))
        raise KeyError(f"unknown config {name!r}")

    def traffic(self, name: str) -> dict:
        return _json(os.path.join(BENCH, "traffic", f"{name}.json"))

    def generator(self, name: str):
        return _module(os.path.join(BENCH, "generators", f"{name}.py"),
                       f"bench_generator_{name}")

    def metrics(self, cell: str, traced: bool) -> list:
        """The metrics a run of `cell` reports: its end-to-end ones when
        untraced, its per-layer ones when traced."""
        group = self.bench["per_layer" if traced else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    @staticmethod
    def reader(name: str):
        return _module(os.path.join(BENCH, "metrics", f"{name}.py"),
                       "bench_metric_" + name.replace(".", "_"))

    @staticmethod
    def peaks(device_kind: str) -> dict:
        table = _json(os.path.join(BENCH, "peaks.json"))["devices"]
        if device_kind not in table:
            raise KeyError(f"device_kind {device_kind!r} is not in "
                           f"bench/peaks.json ({sorted(table)})")
        return table[device_kind]
