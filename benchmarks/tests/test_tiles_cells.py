"""The two cells of PR 39 as files and as runs on the CPU: the on/off
arrivals keep their mean rate and their shape; `leader-transfer-burst`
and `fddev-bench-flood` resolve by name; the process-per-tile topology
drives a rehearsal under the all-pass mask to `correct` false with
every check of its own at 0, every tile's native lanes armed in its own
process, a tile table on the check line, and no process or /dev/shm
segment left behind."""

import json
import os
import subprocess
import sys

import numpy as np

from harness.manifest import Manifest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TILES = ["benchg", "verify0", "pack", "bank0", "poh", "shred", "store"]


def _run(*argv):
    """-> (the run's stdout lines as JSON, its pid): in a session of its
    own, so that what it leaves running can be found by that."""
    p = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "run.py"), *argv, "--trace",
         "0", "--cpu", "--control", "allpass", "--set",
         "program_config.verify.batch=16"], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        start_new_session=True)
    out, err = p.communicate(timeout=300)
    assert p.returncode == 0, err[-3000:]
    return [json.loads(ln) for ln in out.splitlines()], p.pid


def test_onoff_keeps_the_mean_rate_and_the_on_off_shape():
    man = Manifest()
    traffic = man.traffic(man.cell("leader-transfer-burst"))
    assert (traffic["rate_per_s"], traffic["on_ms"], traffic["off_ms"],
            traffic["pool_txn_per_s"], traffic["corrupt_one_in"],
            traffic["warmup_s"], traffic["kind"], traffic["arrivals"]) \
        == (8000, 50, 150, 8800, 128, 2.0, "paced", "onoff")
    n = 8000 * 24
    due = man.arrivals(traffic).due_ns(traffic, n, 2**31 + 5)
    assert (np.diff(due) >= 0).all()
    assert abs(n / (due[-1] / 1e9) - 8000) < 0.01 * 8000    # the mean
    period = 200_000_000
    phase = due % period
    assert (phase < 50_000_000).all()           # nothing is due while off
    per_burst = np.bincount(due // period)
    assert 100 <= len(per_burst) <= 122         # 100 bursts in 20 s
    assert abs(per_burst[:-1].mean() - 1600) < 16
    # while on: 32,000 a second, Poisson
    on = np.diff(due)[np.diff(due // period) == 0]
    assert abs(1e9 / on.mean() - 32000) < 0.02 * 32000
    # another seed, another schedule; the same seed, the same
    assert (man.arrivals(traffic).due_ns(traffic, n, 2**31 + 5) == due).all()
    assert (man.arrivals(traffic).due_ns(traffic, n, 6) != due).any()


def test_the_promoted_arrivals_module_is_the_seam_tests_byte_for_byte():
    a = os.path.join(BENCH, "arrivals", "onoff.py")
    b = os.path.join(BENCH, "tests", "data", "seam", "arrivals", "onoff.py")
    assert open(a, "rb").read() == open(b, "rb").read()


def test_both_cells_resolve_and_report_what_the_issue_lists():
    man = Manifest()
    burst, tiles = (man.cell("leader-transfer-burst"),
                    man.cell("fddev-bench-flood"))
    assert (burst["config"], burst["traffic"], burst["chips"]) \
        == ("leader-v5e", "transfer-burst", 1)
    assert (tiles["config"], tiles["traffic"], tiles["chips"]) \
        == ("fddev-bench-v5e", "transfer-flood-tiles", 1)
    # the flood of leader-transfer-flood with a pool for the tiles' rate
    flood, own = (man.traffic(man.cell("leader-transfer-flood")),
                  man.traffic(tiles))
    assert {k: v for k, v in own.items() if k not in ("note",
                                                      "pool_txn_per_s")} \
        == {k: v for k, v in flood.items() if k not in ("note",
                                                        "pool_txn_per_s")}
    assert own["pool_txn_per_s"] == 90000 > flood["pool_txn_per_s"]
    cfg = man.config(tiles)
    assert cfg["topology"] == "leader_tiles" and len(cfg["source"]) <= 200
    assert {"source", "reduced", "assumed", "guarantees"} <= set(cfg)
    assert cfg["program_config"]["layout"]["bank_stage_count"] == 1
    assert len(man.data["per_layer"]) == 128    # none added: it is full
    for cell in (burst, tiles):
        e2e = {m["name"] for m in man.metrics("end_to_end", cell["name"])}
        assert e2e == {"landed_per_s", "setup_s"}
        for group in ("end_to_end", "per_layer"):
            for m in man.metrics(group, cell["name"]):
                assert callable(man.reader(group, m["name"]))
    layer = lambda c: {m["name"] for m in man.metrics("per_layer", c)}  # noqa: E731
    flood = layer("leader-transfer-flood")
    assert layer("leader-transfer-burst") == flood
    # what reads one thread's timers or sweeps means nothing across
    # processes
    assert flood - layer("fddev-bench-flood") == {
        "thread.accounted_pct.leader", "host.us_per_txn.leader",
        "verify.stage_ms_per_batch.leader", "host.empty_sweep_pct.leader",
        # its publish phase spans calls held by backpressure: "work less
        # the stamped phases" is no remainder there (it reads negative)
        "verify.offcall_ms_per_batch.leader"}


def test_the_burst_cell_rehearses_and_closes_batches_at_the_deadline():
    lines, _pid = _run("--workload", "leader-transfer-burst", "--seed",
                       str(2**31 + 39), "--seconds", "2")
    out = lines[-1]
    assert out["correct"] is False and out["metrics"] == {}
    win = next(ln for ln in lines if "window" in ln)["window"]
    assert abs(win["offered"] - 16000) < 1600   # 10 bursts of ~1,600
    assert win["generator_late"]["n"] == win["offered"]
    assert win["verify"]["batch_close_deadline"] > 0
    chk = next(ln for ln in lines if "check" in ln)
    assert chk["check"]["missing_and_uncounted"]["value"] == 0


def test_a_process_per_tile_rehearses_to_incorrect_and_leaves_nothing():
    lines, pid = _run("--workload", "fddev-bench-flood", "--seed",
                      str(2**31 + 40), "--seconds", "1")
    out = lines[-1]
    assert out["correct"] is False and out["attempted"] > 1000
    assert out["failed"] == 0 and out["metrics"] == {}
    setup = next(ln for ln in lines if "setup" in ln)["setup"]
    assert setup["armed"] == {t: True for t in TILES}
    chk = next(ln for ln in lines if "check" in ln)
    assert chk["drained"] is True
    # the all-pass mask lets the corrupted rows land; everything else
    # the run checks still holds, its own three among them (but for
    # pool_exhausted: at batch 16 with no device the tiles can outrun
    # the traffic file's pool, which is sized for the chip)
    assert chk["corrupted_landed"] == chk["corrupted_offered"] > 0
    assert chk["check"]["landed_but_not_due"]["value"] \
        == chk["corrupted_landed"]
    for k in ("landed_bytes_matching_nothing_offered", "missing_and_uncounted",
              "fec_sets_not_stored", "tap_txn_minus_bank_txn_exec",
              "tap_overrun", "native_lanes_not_armed",
              "account_store_off_ledger_replay", "tile_deaths",
              "children_or_segments_left"):
        assert chk["check"][k] == {"value": 0, "limit": 0}, k
    assert chk["accounts_replayed"] > 64 and chk["dead_tiles"] == []
    assert set(chk["tiles"]) == set(TILES)
    for t, row in chk["tiles"].items():
        assert set(row) == {"busy_pct", "backp_pct", "poll_pct"}, t
        assert 0 <= sum(row.values()) <= 100.01, t
    assert chk["busiest_tile"] in TILES
    assert chk["slots_sealed"] >= 2
    # a run's names carry its uid, <pid>_<n>; its tiles its session
    assert not [n for n in os.listdir("/dev/shm") if f"_{pid}_" in n]
    assert subprocess.run(["pgrep", "-s", str(pid)],
                          capture_output=True).stdout == b""
