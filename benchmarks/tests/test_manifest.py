"""BENCHMARK.json against the contract's naming rules, and every file a
cell needs resolving by name."""

import json
import os
import re

import pytest

from harness.manifest import ROOT, Manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return Manifest()


def test_keys_names_units(man):
    d = man.data
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in d[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(set(names)) == len(names)
    for w in d["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    e2e = {m["name"]: m for m in d["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    cells = {w["name"] for w in d["workloads"]}
    for m in d["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in d["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        # each listed cell reports the end-to-end metric this one moves
        moved = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in moved.get("workloads", cells), (m["name"], w)
    for m in d["end_to_end"] + d["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert len(json.dumps(d)) < 64 * 1024


def test_every_cell_resolves_by_name(man):
    used = set()
    for w in man.data["workloads"]:
        cfg = man.config(w)
        used.add(w["config"])
        assert cfg["name"] == w["config"]
        assert {"source", "assumed", "reduced", "guarantees",
                "topology"} <= set(cfg)
        tr = man.traffic(w)
        assert tr["kind"] in ("flood", "paced")
        assert ("rate_per_s" in tr) == (tr["kind"] == "paced")
        assert ("pool_txns" in tr) != ("pool_txn_per_s" in tr)
        shape = man.shape(tr)
        assert all(callable(getattr(shape, f))
                   for f in ("build", "corrupt", "order", "genesis"))
        if tr["kind"] == "paced":
            assert callable(man.arrivals(tr).due_ns)
        assert os.path.exists(os.path.join(
            man.bench_dir, "topologies", cfg["topology"] + ".py"))
        e2e = man.metrics("end_to_end", w["name"])
        assert {"setup_s"} < {m["name"] for m in e2e}
        assert man.metrics("per_layer", w["name"])
        for group in ("end_to_end", "per_layer"):
            for m in man.metrics(group, w["name"]):
                assert callable(man.reader(group, m["name"]))
    assert used == {c["name"] for c in man.data["configs"]}
    files = [c["file"] for c in man.data["configs"]]
    assert len(set(files)) == len(files)
    assert all(f.startswith("benchmarks/") for f in files)


def test_files_under_paths_use_name_characters(man):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(man.bench_dir):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert ok.match(rel), rel


def test_unknown_device_is_an_error(man):
    from harness.manifest import ManifestError

    assert man.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ManifestError):
        man.peaks("TPU v9 imaginary")
