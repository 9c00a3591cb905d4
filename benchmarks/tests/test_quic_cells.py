"""The two cells of PR 41 as files and as runs on the CPU: they resolve
by name; the `transfer-mtu` shape makes 1,232-byte rows that the
program's parser accepts with one signature; both cells, rehearsed with
the real program at batch 16, end `correct` true with the front's
checks at 0, every tile's lanes armed, and nothing left behind."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness.manifest import Manifest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TILES = ["benchg", "benchs0", "benchs1", "benchs2", "benchs3", "quic",
         "verify0", "out"]
ACCOUNTS = {"n_payers": 4, "n_dests": 8}


def test_both_cells_resolve_and_report_what_the_issue_lists():
    man = Manifest()
    mtu, flood = man.cell("verify-spam-mtu"), man.cell("verify-quic-flood")
    assert (mtu["config"], mtu["traffic"], mtu["chips"]) \
        == ("verify-quic-v5e", "spam-mtu-flood", 1)
    assert (flood["config"], flood["traffic"], flood["chips"]) \
        == ("verify-quic-v5e", "spam-flood", 1)
    t = man.traffic(mtu)
    assert (t["kind"], t["shape"], t["pool_txns"], t["corrupt_one_in"],
            t["warmup_s"]) == ("flood", "transfer-mtu", 8192, 128, 2.0)
    config = man.config(mtu)
    assert config["topology"] == "verify_quic"
    assert config["program_config"]["verify"]["max_msg_len"] == 1232
    for cell in (mtu, flood):
        e2e = [m["name"] for m in man.metrics("end_to_end", cell["name"])]
        assert e2e == ["verify_per_s", "setup_s"]
        layer = {m["name"] for m in man.metrics("per_layer", cell["name"])}
        assert {"kernel.ms_per_batch.tile", "verify.h2d_ms_per_batch.tile",
                "verify.fill_pct.tile", "chip.empty_pct.tile",
                "device.idle_pct.tile", "verify_ms_p95.flood",
                "path.to_verify_ms.tile"} <= layer
        # one thread's timers are not this cell's
        assert not {"thread.accounted_pct.tile",
                    "verify.stage_ms_per_batch.tile",
                    "gen.late_ms_p95.tile"} & layer
    assert len(man.data["per_layer"]) == 128
    assert len(man.data["workloads"]) == 11


def test_the_mtu_shape_makes_rows_at_the_bound_that_the_parser_accepts():
    from firedancer_tpu.protocol.base58 import b58_encode
    from firedancer_tpu.protocol.txn import TXN_MTU, txn_parse

    man = Manifest()
    traffic = man.traffic(man.cell("verify-spam-mtu"))
    shape = man.shape(traffic)
    assert b58_encode(shape.MEMO_PROGRAM) \
        == "MemoSq4gqABAXKb96qnH8TysNcWxMyWCqXgDLGmfcHr"
    pool = shape.build(2**31 + 9, 24, ACCOUNTS, traffic)
    again = shape.build(2**31 + 9, 24, ACCOUNTS, traffic, 8, 16)
    assert (pool.len == TXN_MTU).all() and (pool.sigs == 1).all()
    assert len({pool.row(i) for i in range(pool.n)}) == pool.n
    assert [again.row(i) for i in range(8)] \
        == [pool.row(8 + i) for i in range(8)]
    from harness import reference

    for i in range(pool.n):
        row = pool.row(i)
        d = txn_parse(row)
        assert d is not None and d.signature_cnt == 1
        assert len(d.instrs) == 2 and d.acct_addr_cnt == 4
        assert set(row[-shape.MEMO_SZ:]) <= set(b"0123456789abcdef")
    assert all(reference.verdicts(pool, range(pool.n)).values())
    bad = shape.corrupt(pool, 8, 5)
    assert len(bad) == 3
    assert not any(reference.verdicts(pool, bad).values())
    assert (shape.order(pool, 5, traffic) == np.arange(24)).all()


def _run(*argv):
    """-> (the run's stdout lines as JSON, its pid): in a session of its
    own, so that what it leaves running can be found by that."""
    p = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "run.py"), *argv, "--trace",
         "0", "--cpu", "--set", "program_config.verify.batch=16",
         # rings the CPU's verify can drain in the harness's 20 s
         "--set", "program_config.verify.receive_buffer_depth=64",
         "--set", "program_config.quic.stream_window=8"], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        start_new_session=True)
    out, err = p.communicate(timeout=900)
    assert p.returncode == 0, err[-3000:]
    return [json.loads(ln) for ln in out.splitlines()], p.pid


@pytest.mark.parametrize("cell, two_chunks", [
    ("verify-spam-mtu", True), ("verify-quic-flood", False)])
def test_a_rehearsal_with_the_real_program_ends_correct(cell, two_chunks):
    """The whole run on the CPU at batch 16 (the 16 x 1,332 program
    compiles once, ~2 min, then loads from the cache): `correct` true,
    every check of the front at 0, and `--control allpass` false."""
    lines, pid = _run("--workload", cell, "--seed", str(2**31 + 41),
                      "--seconds", "3")
    setup, check, window, last = lines[-4:]
    assert last["correct"] is True and last["failed"] == 0, check
    assert last["rehearsal"] is True and last["metrics"] == {}
    assert set(setup["setup"]["armed"]) == set(TILES)
    assert all(setup["setup"]["armed"].values())
    for name in ("quic_published_off_plain_reference",
                 "acked_minus_published_minus_drops", "quic_drop_counters",
                 "tile_deaths", "children_or_segments_left",
                 "compiles_in_window", "native_lanes_not_armed"):
        assert check["check"][name] == {"value": 0, "limit": 0}, name
    assert check["drained"] is True
    assert set(check["tiles"]) == set(TILES)
    assert check["busiest_tile"] in TILES
    q = check["quic"]
    assert q["reasm_published"] > 0 and q["reasm_evicted"] == 0
    share = q["reasm_multi_chunk_over_published"]
    assert (share >= 0.99) if two_chunks else (share == 0)
    assert q["net_punts_over_dgram_rx"] < 0.01
    assert all(b["send_blocked_credit"] > 0
               for b in check["benchs"].values())
    assert check["reference"]["captures"] == 4
    assert check["reference"]["reassembled"] > 0
    assert window["window"]["verify"]["verify_fail"] > 0
    # nothing of the run is left
    assert not [n for n in os.listdir("/dev/shm") if f"_{pid}_" in n]
    left = subprocess.run(["pgrep", "-s", str(pid)], capture_output=True,
                          text=True).stdout.split()
    assert not left


def test_the_allpass_control_reads_incorrect():
    lines, _pid = _run("--workload", "verify-quic-flood", "--seed", "7",
                       "--seconds", "2", "--control", "allpass")
    check, last = lines[-3], lines[-1]
    assert last["correct"] is False
    assert check["check"]["landed_but_not_due"]["value"] > 0
    assert check["check"]["quic_published_off_plain_reference"]["value"] == 0
