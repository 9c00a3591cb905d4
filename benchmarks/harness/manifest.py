"""BENCHMARK.json and the files it names.  Whatever belongs to one
configuration, one traffic mix or one metric is a file of its own, found
here by the name in the manifest; nothing in the harness lists them."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class ManifestError(ValueError):
    pass


def _load_json(path: str) -> dict:
    with open(path, "rb") as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise ManifestError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.data = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, self.data["paths"][0])

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(
            f"no workload {name!r}; have "
            f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, cell: dict) -> dict:
        for c in self.data["configs"]:
            if c["name"] == cell["config"]:
                return _load_json(os.path.join(self.root, c["file"]))
        raise ManifestError(f"no config {cell['config']!r}")

    def traffic(self, cell: dict) -> dict:
        return _load_json(os.path.join(
            self.bench_dir, "traffic", cell["traffic"] + ".json"))

    def topology(self, kind: str):
        return load_module(os.path.join(
            self.bench_dir, "topologies", kind + ".py"), f"topology_{kind}")

    def shape_path(self, traffic: dict) -> str:
        """The file of the traffic mix's shape: what a row is, which
        rows are corrupted, in which order they are offered."""
        return os.path.join(self.bench_dir, "shapes",
                            traffic.get("shape", "transfer") + ".py")

    def shape(self, traffic: dict):
        path = self.shape_path(traffic)
        return load_module(path, "shape_" + os.path.basename(path)[:-3])

    def arrivals(self, traffic: dict):
        """A paced mix's arrivals: `due_ns(traffic, n, seed)`."""
        name = traffic.get("arrivals", "poisson")
        return load_module(os.path.join(
            self.bench_dir, "arrivals", name + ".py"), f"arrivals_{name}")

    def metrics(self, group: str, cell_name: str) -> list[dict]:
        """The manifest's `end_to_end` or `per_layer` entries that this
        cell reports: those without a `workloads` key, or that list it."""
        return [m for m in self.data[group]
                if "workloads" not in m or cell_name in m["workloads"]]

    def reader(self, group: str, name: str):
        """The metric's reader: `read(run) -> number | None` in
        <group dir>/<name>.py."""
        d = {"end_to_end": "e2e_metrics", "per_layer": "layer_metrics"}[group]
        return load_module(os.path.join(self.bench_dir, d, name + ".py"),
                            f"metric_{name.replace('.', '_')}").read

    def peaks(self, device_kind: str) -> dict:
        table = _load_json(os.path.join(self.bench_dir, "peaks.json"))
        if device_kind not in table["devices"]:
            raise ManifestError(
                f"device kind {device_kind!r} is not in peaks.json: add it "
                f"with its source, there is no default")
        return table["devices"][device_kind]
