"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix; the configuration's
file is the one ``BENCHMARK.json`` gives, its reference is the ``.py``
beside it, the mix is ``bench/traffic/<traffic>.json``, and every metric
is read by ``bench/metrics/<metric name>.py``.  Adding any of them means
adding files and entries, never editing one that is there.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib


class Spec:
    """``BENCHMARK.json`` of the checkout at ``root``; the benchmark's
    files are under ``root/bench``."""

    def __init__(self, root: pathlib.Path):
        self.root = pathlib.Path(root)
        self.bench_dir = self.root / "bench"
        with open(self.root / "BENCHMARK.json", encoding="utf-8") as fh:
            self.data = json.load(fh)

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def config_entry(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def config(self, cell: dict) -> dict:
        with open(self.root / self.config_entry(cell["config"])["file"],
                  encoding="utf-8") as fh:
            return json.load(fh)

    def reference(self, cell: dict):
        """The configuration's reference module (the ``.py`` beside its
        file)."""
        path = (self.root / self.config_entry(cell["config"])["file"]) \
            .with_suffix(".py")
        return _load_module(path, f"bench_ref_{cell['config']}")

    def traffic(self, cell: dict) -> dict:
        path = self.bench_dir / "traffic" / f"{cell['traffic']}.json"
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)

    def metrics(self, cell: dict, trace: bool) -> list:
        """The metric entries this cell reports: its end-to-end metrics
        in a plain run, its per-layer metrics in a traced one."""
        group = self.data["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or cell["name"] in m["workloads"]]

    def reader(self, metric: dict):
        """``read(run)`` of ``bench/metrics/<name>.py``."""
        path = self.bench_dir / "metrics" / f"{metric['name']}.py"
        return _load_module(path, "bench_metric_"
                            + metric["name"].replace(".", "_")).read


def _load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
