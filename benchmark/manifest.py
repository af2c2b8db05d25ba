"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a later PR adds entries to ``BENCHMARK.json`` and files under
``benchmark/`` and this module finds them.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file by path: names such as ``gpt2-small.py`` are file
    names the manifest dictates, not importable module names."""
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, HERE))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: str = ROOT, bench_dir: str | None = None):
        self.root = root
        self.bench_dir = bench_dir or os.path.join(root, "benchmark")
        self.doc = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(known: {sorted(self.cells)})")
        return self.cells[name]

    def config(self, cell: dict) -> dict:
        entry = self.configs[cell["config"]]
        cfg = _load_json(os.path.join(self.root, entry["file"]))
        cfg["name"] = entry["name"]
        return cfg

    def traffic(self, cell: dict) -> dict:
        t = _load_json(os.path.join(self.bench_dir, "traffic",
                                    cell["traffic"] + ".json"))
        t["name"] = cell["traffic"]
        return t

    def kind(self, traffic: dict):
        return load_module(os.path.join(self.bench_dir, "kinds",
                                        traffic["kind"] + ".py"))

    def limits(self, cell_name: str) -> dict:
        """The limits of the cell's output check, each set from readings
        PERF.md records."""
        return _load_json(os.path.join(self.bench_dir, "limits",
                                       cell_name + ".json"))

    def reference(self, config: dict):
        return load_module(os.path.join(self.bench_dir, "reference",
                                        config["name"] + ".py"))

    def _reports(self, metric: dict, cell_name: str,
                 e2e_of_cell: set[str] | None = None) -> bool:
        if "workloads" in metric:
            return cell_name in metric["workloads"]
        if e2e_of_cell is None:          # an end-to-end metric: every cell
            return True
        return metric["moves"] in e2e_of_cell

    def end_to_end(self, cell_name: str) -> list[dict]:
        return [m for m in self.doc["end_to_end"]
                if self._reports(m, cell_name)]

    def per_layer(self, cell_name: str) -> list[dict]:
        e2e = {m["name"] for m in self.end_to_end(cell_name)}
        return [m for m in self.doc["per_layer"]
                if self._reports(m, cell_name, e2e)]

    def read_metric(self, metric: dict, ctx: dict):
        """One per-layer metric through its reader: ``metrics/<name>.json``
        names the reader and its arguments, ``readers/<reader>.py`` holds
        ``read(ctx, **args)``. ``None`` = nothing to read here."""
        spec = _load_json(os.path.join(self.bench_dir, "metrics",
                                       metric["name"] + ".json"))
        reader = load_module(os.path.join(self.bench_dir, "readers",
                                          spec["reader"] + ".py"))
        return reader.read(ctx, **spec.get("args", {}))

    def peak(self, device_kind: str) -> dict:
        peaks = _load_json(os.path.join(self.bench_dir, "peaks.json"))
        if device_kind not in peaks["devices"]:
            raise KeyError(
                f"device_kind {device_kind!r} is not in benchmark/peaks.json"
                f" (known: {sorted(peaks['devices'])}); add it with its "
                "source, there is no default")
        return peaks["devices"][device_kind]
