"""The four-chip cell rehearsed on four virtual CPU devices: the run
and its check with the verify stage's all-pass mask (corrupted
transactions land, so `correct` is false; a rehearsal prints no
metric; every chip is dealt its share), the cell's entries in the
manifest, the two per-shard readers' arithmetic, and the refusal of a
program whose verify stage takes no mesh."""

import json
import os
import subprocess
import sys

import pytest

from harness import mesh_readers
from harness.manifest import Manifest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "verify-fanout-4chip"


def test_allpass_mask_reads_incorrect_on_the_fanout_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 26), "--seconds", "1", "--trace", "0",
         "--cpu", "--control", "allpass", "--set", "verify.batch=16",
         "--set", "mesh.lanes_per_device=4"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()]
    out = lines[-1]
    assert out["correct"] is False and out["attempted"] > 1000
    assert out["failed"] == 0
    assert out["metrics"] == {} and out["rehearsal"] is True
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 4
    setup = next(ln for ln in lines if "setup" in ln)["setup"]
    assert setup["armed"] == {"verify": True, "rings": True}
    chk = next(ln for ln in lines if "check" in ln)
    assert chk["corrupted_landed"] == chk["corrupted_offered"] > 0
    assert chk["check"]["landed_but_not_due"]["value"] \
        == chk["corrupted_landed"]
    # everything else the run checks still held
    for k in ("landed_bytes_matching_nothing_offered", "missing_and_uncounted",
              "native_lanes_not_armed", "pool_exhausted",
              "compiles_in_window", "chips_dealt_no_signature"):
        assert chk["check"][k]["value"] == 0, k
    # dealt round-robin: every chip got its quarter, to within one
    # signature a batch
    shards = chk["shard_elems"]
    assert len(shards) == 4 and sum(shards) > 1000
    assert max(shards) - min(shards) <= sum(chk["batch_closes"].values())


def test_the_cell_reports_what_the_tile_cells_report_and_two_more():
    man = Manifest()
    cell = man.cell(CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "spam-flood"
    cfg = man.config(cell)
    tile = man.config(man.cell("verify-spam-flood"))
    assert cfg["guarantees"] == tile["guarantees"]
    assert cfg["verify"] == dict(tile["verify"], batch=4096)
    assert cfg["mesh"] == {"devices": 4, "lanes_per_device": 1024}
    assert {m["name"] for m in man.metrics("end_to_end", CELL)} \
        == {"verify_per_s", "setup_s"}
    # per layer: the flood tile cell's metrics under this cell's own
    # names (`.fanout`: entries of their own, the tile's lists left as
    # they were), each over the tile's reader, and the two shard fills
    layer = {m["name"]: m for m in man.metrics("per_layer", CELL)}
    flood = {m["name"]: m
             for m in man.metrics("per_layer", "verify-spam-flood")}
    assert not set(layer) & set(flood)
    assert {n.rsplit(".", 1)[1] for n in layer} == {"fanout"}
    by_stem = {n.rsplit(".", 1)[0]: m for n, m in flood.items()}
    shard = {"verify.shard_fill_max_pct", "verify.shard_fill_min_pct"}
    assert {n.rsplit(".", 1)[0] for n in layer} == set(by_stem) | shard
    for name, m in layer.items():
        assert m["workloads"] == [CELL] and m["moves"] == "verify_per_s"
        twin = by_stem.get(name.rsplit(".", 1)[0])
        if twin is None:
            continue
        assert {k: m[k] for k in ("unit", "better", "source", "layer")} \
            == {k: twin[k] for k in ("unit", "better", "source", "layer")}
        assert man.reader("per_layer", name).__code__ \
            is man.reader("per_layer", twin["name"]).__code__


def _run(shards, batches=10, batch=4096):
    v = {f"shard_elems_s{i}": n for i, n in enumerate(shards)}
    v["batches"] = batches
    return {"counters": {"verify0": v}, "batch": batch}


def test_shard_fill_is_each_chips_share_of_its_lane_range():
    run = _run([10240, 5120, 1024, 0])
    assert mesh_readers.shard_fill_pcts(run) == [100.0, 50.0, 10.0, 0.0]
    assert mesh_readers.shard_fill_max_pct(run) == 100.0
    assert mesh_readers.shard_fill_min_pct(run) == 0.0
    man = Manifest()
    for which, want in (("max", 100.0), ("min", 0.0)):
        read = man.reader("per_layer", f"verify.shard_fill_{which}_pct.fanout")
        assert read(run) == want
    # counters s10, s11 sort by number, not by name
    many = _run(list(range(12, 0, -1)), batches=1, batch=12)
    assert mesh_readers.shard_fill_pcts(many) \
        == [100.0 * n for n in range(12, 0, -1)]


@pytest.mark.parametrize("run", [
    {"counters": {"verify0": {"batches": 5, "batch_elems": 9}}, "batch": 16},
    {"counters": {}, "batch": 16},
    _run([0, 0], batches=0),
])
def test_a_program_without_the_counters_gives_nothing(run):
    assert mesh_readers.shard_fill_max_pct(run) is None
    assert mesh_readers.shard_fill_min_pct(run) is None


def test_a_program_whose_stage_takes_no_mesh_is_refused_by_name(
        monkeypatch, capsys):
    from firedancer_tpu.runtime.verify import VerifyStage

    man = Manifest()
    cfg = man.config(man.cell(CELL))
    assert callable(man.topology(cfg["topology"]).prewarm)
    monkeypatch.delattr(VerifyStage, "metrics_schema_n")
    with pytest.raises(SystemExit) as e:
        man.topology(cfg["topology"])
    assert e.value.code == 2
    assert "takes no mesh" in capsys.readouterr().err
