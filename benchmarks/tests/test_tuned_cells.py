"""The cell of PR 45 as files and as a run on the CPU:
`fddev-bench-tuned-flood` resolves by name to the configuration
`fddev-bench-tuned-v5e` (four bank tiles, a process each, over one funk
segment; the bench profile's two keys on) and the traffic file
`transfer-flood-tuned`; no width of the configuration differs from
`fddev-bench-v5e`'s; the cell reports what `fddev-bench-flood` reports;
and the process-per-tile topology with four banks drives a rehearsal
under the all-pass mask to `correct` false, with every check of its own
at 0, every tile's native lanes armed in its own process, every bank
tile executing and taking accounts from the others, and nothing left
behind.  (With the program's own verdicts the CPU verifies ~16
signatures a second at batch 16: too few for four banks to all get
work, which the cell's `banks_that_executed_nothing` would rightly
call incorrect; tests/test_leader_banks.py holds the four-bank
topology to the plain replay at toy size.)"""

import json
import os
import subprocess
import sys

from harness.manifest import Manifest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
BANKS = ["bank0", "bank1", "bank2", "bank3"]
TILES = ["benchg", "verify0", "pack", *BANKS, "poh", "shred", "store"]
CELL = "fddev-bench-tuned-flood"


def _run(*argv):
    """-> (the run's stdout lines as JSON, its pid): in a session of its
    own, so that what it leaves running can be found by that."""
    p = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--trace", "0", "--cpu", "--set", "program_config.verify.batch=16",
         *argv], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        text=True, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        start_new_session=True)
    out, err = p.communicate(timeout=600)
    assert p.returncode == 0, err[-3000:]
    return [json.loads(ln) for ln in out.splitlines()], p.pid


def test_the_cell_resolves_and_differs_from_the_stock_one_where_it_says():
    man = Manifest()
    cell, stock = man.cell(CELL), man.cell("fddev-bench-flood")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("fddev-bench-tuned-v5e", "transfer-flood-tuned", 1)
    cfg, base = man.config(cell), man.config(stock)
    assert cfg["topology"] == "leader_tiles_banks"
    assert len(cfg["source"]) <= 200 and "bench-zen3-32core.toml" \
        in cfg["source"] and "[development.bench]" in cfg["source"]
    assert {"source", "reduced", "assumed", "guarantees"} <= set(cfg)
    # every width is the stock configuration's: the verify batch and
    # row, the deadline, the seven ring depths, the slot, pack's rule
    pc, bc = cfg["program_config"], base["program_config"]
    for group in ("verify", "pack", "links"):
        assert pc[group] == bc[group], group
    assert cfg["slot_clock"] == base["slot_clock"]
    # what differs: four bank tiles, the profile's two keys, the payers
    assert pc["layout"] == {"verify_stage_count": 1, "bank_stage_count": 4}
    assert pc["development"] == {"bench": {
        "larger_max_cost_per_block": True, "disable_status_cache": True}}
    assert "development" not in bc
    assert cfg["traffic_accounts"] == {"n_payers": 1024, "n_dests": 1024}
    assert not any("bank_stage_count: 1" in r for r in cfg["reduced"])
    assert any("4 bank tiles" in r for r in cfg["reduced"])
    assert len(cfg["guarantees"]) == len(base["guarantees"]) + 2
    entry = next(c for c in man.data["configs"]
                 if c["name"] == "fddev-bench-tuned-v5e")
    assert entry["source"] == cfg["source"]
    assert "bank_stage_count" not in entry["reduced"]
    # the traffic: the stock cell's flood with a pool for 160K txn/s
    own, flood = man.traffic(cell), man.traffic(stock)
    assert {k: v for k, v in own.items()
            if k not in ("note", "pool_txn_per_s")} \
        == {k: v for k, v in flood.items()
            if k not in ("note", "pool_txn_per_s")}
    assert own["pool_txn_per_s"] == 160000
    # it reports what the stock cell reports, by the same readers
    assert len(man.data["per_layer"]) == 128    # none added: it is full
    for group in ("end_to_end", "per_layer"):
        names = {m["name"] for m in man.metrics(group, CELL)}
        assert names == {m["name"] for m in
                         man.metrics(group, "fddev-bench-flood")}
        for name in names:
            assert callable(man.reader(group, name))
    assert len(man.data["workloads"]) == 12
    assert sum(w["chips"] == 4 for w in man.data["workloads"]) == 1


def test_four_bank_tiles_rehearse_to_incorrect_under_the_allpass_mask():
    lines, pid = _run("--seed", str(2**31 + 45), "--seconds", "2",
                      "--control", "allpass")
    out = lines[-1]
    assert out["control"] == "allpass" and out["correct"] is False
    assert out["attempted"] > 1000 and out["failed"] == 0
    assert out["metrics"] == {} and out["rehearsal"] is True
    setup = next(ln for ln in lines if "setup" in ln)["setup"]
    assert setup["armed"] == {t: True for t in TILES}
    chk = next(ln for ln in lines if "check" in ln)
    assert chk["drained"] is True
    # the all-pass mask lets the corrupted rows land: that, and nothing
    # else, is what the run's check finds
    assert chk["corrupted_landed"] == chk["corrupted_offered"] > 0
    wrong = {k for k, v in chk["check"].items() if v["value"] > v["limit"]}
    assert wrong == {"landed_but_not_due",
                     "verify_fail_minus_corrupted_offered",
                     "reference_sample_disagreements"}
    for k in ("account_store_off_ledger_replay",
              "banks_that_executed_nothing", "tap_txn_minus_bank_txn_exec",
              "tile_deaths", "children_or_segments_left", "pool_exhausted",
              "fec_sets_not_stored", "native_lanes_not_armed"):
        assert chk["check"][k] == {"value": 0, "limit": 0}, k
    assert chk["accounts_replayed"] > 1024 and chk["dead_tiles"] == []
    assert set(chk["tiles"]) == set(TILES)
    assert chk["busiest_tile"] in TILES
    # a block a bank: what it executed, what its session took from the
    # segment, its use of the store's lock
    assert set(chk["banks_per_s"]) == set(BANKS)
    for b, row in chk["banks_per_s"].items():
        assert row["txn_exec"] > 0 and row["session_refreshed"] > 0, b
        assert row["funk_lock_acquires"] > 0, b
    per_bank = [chk["pack_per_s"][f"mb_scheduled_b{k}"] for k in range(4)]
    assert all(v > 0 for v in per_bank)
    assert "bank_idle_polls" in chk["pack_per_s"]
    win = next(ln for ln in lines if "window" in ln)["window"]
    assert abs(win["served"] - win["offered"]) < 0.2 * win["offered"]
    # a run's names carry its uid, <pid>_<n>; its tiles its session
    assert not [n for n in os.listdir("/dev/shm") if f"_{pid}_" in n]
    assert subprocess.run(["pgrep", "-s", str(pid)],
                          capture_output=True).stdout == b""

