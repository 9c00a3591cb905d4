"""The cell `replay-blocks-flood` and the files it is made of: the
issue's parameters, the blocks' layout, the blocker's bytes against the
check's (`slot_frames`), the reference's copy, a program without the
replay topology refused by name, and the cell's rehearsal — the all-pass
mask has to read `correct` false by the dead slot alone."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness.manifest import Manifest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
MAN = Manifest()
CELL = MAN.cell("replay-blocks-flood")
CONFIG = MAN.config(CELL)
TRAFFIC = MAN.traffic(CELL)


def test_the_files_carry_the_issues_parameters():
    assert CELL == {"name": "replay-blocks-flood",
                    "config": "replay-verify-v5e", "traffic": "blocks-flood",
                    "chips": 1, "why": CELL["why"]}
    assert TRAFFIC["kind"] == "flood" and TRAFFIC["shape"] == "transfer"
    assert TRAFFIC["pool_txns"] == 639840 == 16 * 39990
    assert TRAFFIC["corrupt_one_in"] == 639840 and TRAFFIC["warmup_s"] == 2.0
    pc = CONFIG["program_config"]
    assert pc["verify"] == {"batch": 16384, "max_msg_len": 256,
                            "batch_deadline_ms": 2.0,
                            "receive_buffer_depth": 1024}
    assert pc["replay"] == {"frag_mtu": 65536, "out_depth": 1024,
                            "txns_per_entry": 31, "entries_per_batch": 2,
                            "slot_txns": 39990, "dead_one_in_slots": 16}
    assert pc["poh"] == {"hashes_per_tick": 64, "ticks_per_slot": 8}
    assert CONFIG["topology"] == "replay_verify"
    entry = next(c for c in MAN.data["configs"]
                 if c["name"] == "replay-verify-v5e")
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) == 196
    assert [r.split(":")[0] for r in CONFIG["reduced"]] == entry["reduced"]
    assert len(CONFIG["guarantees"]) == 5 and len(CONFIG["assumed"]) >= 7
    lists = [m for m in MAN.data["end_to_end"] + MAN.data["per_layer"]
             if CELL["name"] in m.get("workloads", ())]
    assert [m["name"] for m in lists if "bound" in m] == ["verify_per_s"]
    assert len(lists) == 21 and all(
        m["name"].endswith((".tile", ".flood")) for m in lists[1:])
    assert all(m["workloads"][-1] == CELL["name"] for m in lists)


def test_the_reference_copy_is_the_programs_file_byte_for_byte():
    a = os.path.join(ROOT, "firedancer_tpu/ops/ref/replay_verify_plain.py")
    b = os.path.join(BENCH, "harness/replay_reference.py")
    assert open(a, "rb").read() == open(b, "rb").read()


@pytest.fixture(scope="module")
def topo():
    return MAN.topology("replay_verify")


def test_a_slot_is_1290_entries_8_ticks_649_batches(topo):
    pc = CONFIG["program_config"]
    lay = topo.Layout(pc["replay"], pc["poh"])
    assert (lay.entry_txns > 0).sum() == 1290
    assert (lay.entry_txns == 0).sum() == 8 and lay.entry_txns[-1] == 0
    assert lay.n_batches == 649 == CONFIG["widths"]["slot"]["entry_batches"]
    assert lay.batch_txns.sum() == 39990 and lay.batch_txns.max() == 62
    assert lay.batch_of(0) == 0 and lay.batch_of(39989) == 648
    for k in (0, 61, 62, 5021, 20000, 39989):
        j = lay.batch_of(k)
        assert lay.batch_txn0[j] <= k < lay.batch_txn0[j] + lay.batch_txns[j]
    # an entry batch of two full entries is what the shred tile's
    # batch_target_sz holds
    assert 2 * (4 + 38 + 31 * 217) == CONFIG["widths"]["entry_batch_bytes"] \
        <= pc["shred"]["batch_target_sz"]


def test_the_blocker_cuts_the_bytes_the_check_makes_again(topo):
    """The blocker (numpy lane and transaction-a-step lane, whatever
    the sweeps' sizes) makes, frame for frame, what `slot_frames` makes
    of the same transactions: whole slots, and a run that ends inside
    an entry, at an entry's end before a tick, and at a slot's end."""
    from firedancer_tpu.runtime.stage import Stage

    lay = topo.Layout({"slot_txns": 50, "txns_per_entry": 7,
                       "entries_per_batch": 2},
                      {"ticks_per_slot": 3, "hashes_per_tick": 4})
    rng = np.random.default_rng(5)
    gseed = b"bench5"
    for n_txns, sizes in ((137, (215,)), (100, (215,)), (21, (215,)),
                          (150, (215, 180)), (64, (215,))):
        txns = [bytes(rng.integers(0, 256, size=sizes[k % len(sizes)],
                                   dtype=np.uint8)) for k in range(n_txns)]
        b = topo.Blocker.__new__(topo.Blocker)
        Stage.__init__(b, "b")
        b.lay, b.gseed = lay, gseed
        b.n_txn = b.frames_made = 0
        from collections import deque

        b._q, b._pend, b._ents = deque(), [], []
        b._pend_ts = b._ents_ts = 0
        b._slot = b._entry = b._batch = 0
        b._h = b._seed = topo.slot_seed(gseed, 0)
        b._carry, b._carry_ts = None, []
        at = 0
        while at < n_txns:
            take = int(rng.integers(1, 40))
            part = txns[at:at + take]
            buf = b"".join(part)
            offs = np.cumsum([0] + [len(p) for p in part[:-1]])
            rows = [[0, 0, int(o), len(p), 0, 7, 0, 0]
                    for o, p in zip(offs, part)]
            b.sweep_frags(rows, buf)
            at += len(part)
        b.flush_tail()
        got = [f for f, _sig, _ts in b._q]
        want = []
        for s in range(-(-n_txns // 50)):
            want += topo.slot_frames(lay, gseed, s, txns[50 * s:50 * s + 50])
        assert got == want, (n_txns, sizes)
        assert b.n_txn == n_txns


def test_a_program_without_the_replay_topology_is_refused_by_name():
    code = (
        "import sys; sys.path.insert(0, %r); sys.path.insert(0, %r)\n"
        "from firedancer_tpu.models import leader_topo\n"
        "del leader_topo.build_replay_topology_from_config\n"
        "from harness.manifest import Manifest\n"
        "Manifest().topology('replay_verify')\n" % (ROOT, BENCH))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2 and p.stdout == ""
    assert "build_replay_topology_from_config" in p.stderr \
        and "replay_verify" in p.stderr


def test_allpass_mask_reads_incorrect_by_the_dead_slot_alone():
    """The cell's rehearsal (the verify skill's line): the all-pass mask
    lets the dead slot through, so the run reads `correct` false — by
    the checks the dead slot moves and by no other."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "replay-blocks-flood", "--seed", str(2**31 + 149), "--seconds", "10",
         "--trace", "0", "--cpu", "--control", "allpass", "--set",
         "program_config.verify.batch=16"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()]
    out = lines[-1]
    chk = next(ln for ln in lines if "check" in ln)
    assert out["correct"] is False and out["rehearsal"] is True
    assert out["metrics"] == {} and out["failed"] == 0
    # a lap and more, on a loaded machine too: the corrupted row offered
    assert out["attempted"] + 2 * out["attempted"] // 10 > 640_000
    moved = {"landed_but_not_due", "verify_fail_minus_corrupted_offered",
             "reference_sample_disagreements", "slots_dead_minus_due",
             "dead_at_batch_off_reference",
             "entry_batches_off_plain_reference"}
    c = chk["check"]
    assert all(c[k]["value"] > 0 for k in moved)
    assert {k for k, v in c.items() if v["value"] > v["limit"]} == moved
    assert chk["corrupted_rows_landed"] == 1 and chk["drained"] is True
    r = chk["replay"]
    assert r["slots_dead_sig"] == 0 and r["slots_live"] >= 16
    assert r["entry_txn_out"] == r["txn_in"] == out["attempted"] \
        + (r["txn_in"] - out["attempted"])
    assert r["poh_check_ns"] > 0 and r["entry_unpack_ns"] > 0
    assert r["batch_close_full"] > 0.9 * r["batches"]
