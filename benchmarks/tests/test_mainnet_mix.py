"""The `mainnet-mix` shape, its files and the cell made of them: rows
that the program and the reference parse alike, the shares the traffic
file promises, pure functions of the seed, the two copies of the ledger
reference, the eight counter readers, and a rehearsal of the cell."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import ledger_reference as ledger
from harness import mix_readers as mr
from harness import reference
from harness.manifest import Manifest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
MAN = Manifest()
CELL = MAN.cell("leader-mainnet-mix")
CONFIG = MAN.config(CELL)
TRAFFIC = MAN.traffic(CELL)
ACCOUNTS = CONFIG["traffic_accounts"]
SHAPE = MAN.shape(TRAFFIC)
SEED = 2**31 + 31
N = 6000


@pytest.fixture(scope="module")
def pool():
    return SHAPE.build(SEED, N, ACCOUNTS, TRAFFIC)


def test_the_files_carry_the_issues_parameters():
    assert TRAFFIC["kind"] == "flood" and TRAFFIC["shape"] == "mainnet-mix"
    assert TRAFFIC["corrupt_one_in"] == 128 and TRAFFIC["warmup_s"] == 2.0
    assert TRAFFIC["pool_txn_per_s"] % 5000 == 0
    assert ACCOUNTS == {"n_voters": 2048, "n_payers": 4096, "n_dests": 4096,
                        "slot_hashes": 512}
    v = CONFIG["program_config"]["verify"]
    assert (v["batch"], v["max_msg_len"]) == (1024, 384)
    assert CONFIG["topology"] == "leader_votes" and CELL["chips"] == 1
    assert CONFIG["slot_clock"]["slot_ms"] == 400.0
    assert (SHAPE.VOTE_SHARE, SHAPE.ZIPF_THETA, SHAPE.COSIGNED_ONE_IN,
            SHAPE.PRICED_ONE_IN, SHAPE.REPEAT_SHARE, SHAPE.CU_LIMIT) \
        == (0.70, 0.99, 5, 2, 0.10, 20_000)
    assert (SHAPE.NEAR, SHAPE.FAR) == ((1, 8), (1024, 32768))
    assert len(CONFIG["guarantees"]) == 7 and len(CONFIG["assumed"]) >= 10
    # the program's TOML is the same deployment
    from firedancer_tpu.utils.config import load_config

    cfg = load_config(os.path.join(ROOT, "config", "leader-mainnet-v5e.toml"))
    assert (cfg.verify.batch, cfg.verify.max_msg_len) == (1024, 384)
    assert (cfg.genesis.n_voters, cfg.genesis.slot_hashes) == (2048, 512)
    assert (cfg.layout.verify_stage_count, cfg.layout.bank_stage_count) \
        == (1, 2)


def test_the_shapes_constants_are_the_programs():
    from firedancer_tpu.flamenco.solcompat import SYSVAR_NAMES
    from firedancer_tpu.pack.cost import COMPUTE_BUDGET_PROGRAM
    from firedancer_tpu.protocol.txn import SYSTEM_PROGRAM, VOTE_PROGRAM

    by_name = {n: a for a, n in SYSVAR_NAMES.items()}
    assert SHAPE.SYSVAR_CLOCK == by_name["clock"]
    assert SHAPE.SYSVAR_SLOT_HASHES == by_name["slot_hashes"]
    assert SHAPE.VOTE_PROGRAM == VOTE_PROGRAM == ledger.VOTE_PROGRAM
    assert SHAPE.SYSTEM_PROGRAM == SYSTEM_PROGRAM == ledger.SYSTEM_PROGRAM
    assert SHAPE.COMPUTE_BUDGET_PROGRAM == COMPUTE_BUDGET_PROGRAM \
        == ledger.COMPUTE_BUDGET_PROGRAM


def test_rows_parse_as_the_program_and_the_reference_parse_them(pool):
    from firedancer_tpu.flamenco.exec_native import eligible_packed
    from firedancer_tpu.pack import cost as fc
    from firedancer_tpu.protocol import txn as ft

    assert pool.classes == ("vote", "transfer") and pool.n == N
    votes = pool.cls == SHAPE.VOTE
    assert (pool.len[votes] == SHAPE.VOTE_TXN_SZ).all()
    assert (pool.sigs[votes] == 1).all()
    assert int(pool.len.max()) - 1 - 64 * int(pool.sigs[pool.len.argmax()]) \
        <= CONFIG["program_config"]["verify"]["max_msg_len"]
    assert len({pool.row(i) for i in range(N)}) == N          # all distinct
    for i in range(0, N, 7):
        row = pool.row(i)
        sigs, pks, msg = reference.split(row)
        desc = ft.txn_parse(row)
        assert desc is not None and desc.signature_cnt == len(sigs) \
            == pool.sigs[i] == len(pks)
        cost = fc.compute_cost(row, desc)
        assert cost.is_simple_vote == bool(votes[i])
        assert eligible_packed(row, ft.txn_pack(desc))        # native subset
        n_sig, keys, instrs = ledger.parse(row)
        assert (n_sig, keys) == (len(sigs), desc.acct_addrs(row))
        assert ledger.fee(n_sig, instrs) \
            == cost.rewards(desc.signature_cnt)               # pack's order
        if votes[i]:
            assert len(msg) == 265 and len(keys) == 5
            assert keys[2:] == [SHAPE.SYSVAR_SLOT_HASHES, SHAPE.SYSVAR_CLOCK,
                                SHAPE.VOTE_PROGRAM]
            assert instrs[0][1] == [1, 2, 3, 0]
    assert all(reference.verdicts(pool, range(0, N, 5)).values())


def test_class_signature_fee_and_zipf_shares():
    pl = SHAPE.plan(SEED, 200_000, ACCOUNTS)
    votes = pl["cls"] == SHAPE.VOTE
    tr = ~votes
    assert abs(votes.mean() - 0.70) < 0.01
    assert abs((pl["cosigner"][tr] >= 0).mean() - 0.20) < 0.01
    assert (pl["cosigner"][tr] != pl["payer"][tr]).all()
    priced = pl["price"][tr] > 0
    assert abs(priced.mean() - 0.50) < 0.01
    p = pl["price"][tr][priced]
    assert p.min() >= 1 and p.max() <= 10**6
    # log-uniform: a third of the prices in each two decades
    assert abs(((p >= 100) & (p < 10_000)).mean() - 1 / 3) < 0.02
    # Zipf 0.99 over 4,096: the hottest ~11 %, the hottest eight ~30 %
    hits = np.bincount(pl["dest"][tr], minlength=ACCOUNTS["n_dests"])
    assert abs(hits[0] / tr.sum() - 0.108) < 0.01
    assert abs(hits[:8].sum() / tr.sum() - 0.296) < 0.015
    assert abs(np.bincount(pl["payer"][tr],
                           minlength=4096).max() / tr.sum()) < 0.002
    # one vote a validator a round, the j-th for slot BASE_SLOT + j
    k = np.flatnonzero(votes)
    full = len(k) // 2048 * 2048
    per_round = pl["voter"][k[:full]].reshape(-1, 2048)
    assert (np.sort(per_round, axis=1) == np.arange(2048)).all()
    assert (per_round[0] != per_round[1]).any()               # reshuffled
    assert (pl["vote_no"][k[:full]].reshape(-1, 2048)
            == np.arange(full // 2048)[:, None]).all()


def test_a_pool_that_outlasts_slot_hashes_is_refused():
    with pytest.raises(ValueError, match="SlotHashes"):
        SHAPE.plan(SEED, 1_500_000, ACCOUNTS)
    SHAPE.plan(SEED, 1_200_000, ACCOUNTS)          # 410 votes a voter: fine


def test_a_row_range_equals_the_same_rows_of_the_whole_pool(pool):
    part = SHAPE.build(SEED, N, ACCOUNTS, TRAFFIC, 4096, 4150)
    assert [part.row(k) for k in range(54)] \
        == [pool.row(4096 + k) for k in range(54)]
    assert (part.cls == pool.cls[4096:4150]).all()
    assert (part.sigs == pool.sigs[4096:4150]).all()
    other = SHAPE.build(SEED + 1, N, ACCOUNTS, TRAFFIC, 0, 8)
    assert [other.row(k) for k in range(8)] != [pool.row(k) for k in range(8)]


def test_corruption_fails_a_row_whole_from_any_signature():
    pool = SHAPE.build(SEED, 1280, ACCOUNTS, TRAFFIC)
    clean = pool.buf.copy()
    bad = SHAPE.corrupt(pool, 128, SEED)
    assert len(bad) == 10 and (np.diff(bad // 128) == 1).all()
    at = np.flatnonzero(pool.buf != clean)
    assert len(at) == 10                                      # one bit a row
    sig = (at - pool.off[bad] - 1) // 64
    assert ((0 <= sig) & (sig < pool.sigs[bad])).all()
    got = reference.verdicts(pool, range(1280))
    assert {i for i, ok in got.items() if not ok} == set(bad.tolist())


def test_order_repeats_a_tenth_at_the_two_distances():
    class P:
        n = 400_000
    order = SHAPE.order(P, SEED, TRAFFIC)
    assert (SHAPE.order(P, SEED, TRAFFIC) == order).all()
    n_rep = len(order) - P.n
    assert abs(n_rep / len(order) - 0.10) < 0.001
    count = np.bincount(order, minlength=P.n)
    assert (count >= 1).all()                                 # nothing skipped
    # a repeat is an offer of a row that was offered before; the first
    # offer of every row keeps the pool's order
    first_at = np.full(P.n, -1)
    pos = np.arange(len(order))
    first_at[order[::-1]] = pos[::-1]
    assert (np.diff(first_at) > 0).all()
    rep = np.flatnonzero(first_at[order] != pos)
    assert len(rep) == n_rep
    # distance to the row's first offer, in offers
    gap = rep - first_at[order[rep]]
    settled = rep > SHAPE.FAR[1] + 16          # past the order's first offers
    near = gap <= 16
    assert abs(near[settled].mean() - 0.5) < 0.01
    close = gap[near & settled]
    assert close.min() >= 1 and np.quantile(close, 0.99) <= 9 \
        and close.max() <= 12
    far = gap[~near & settled]
    assert far.min() >= 1024 and far.max() <= 32768 + 8


def test_genesis_names_what_traffic_touches():
    g = SHAPE.genesis({"n_voters": 8, "n_payers": 4, "n_dests": 5,
                       "slot_hashes": 512}, SEED)
    assert g["n_payers"] == 0 and len(g["payers"]) == 4
    assert len(g["voters"]) == 8 and g["slot"] == SHAPE.BASE_SLOT + 512
    slots = [s for s, _h in g["slot_hashes"]]
    assert slots == list(range(SHAPE.BASE_SLOT + 511, SHAPE.BASE_SLOT - 1, -1))
    assert len(set(g["preload"])) == len(g["preload"]) == 4 + 8 + 8 + 5 + 2
    # the program's own seed derivations give the same validator set
    from firedancer_tpu.runtime.bank import seeded_validators

    mine = seeded_validators(g["seed"], n_voters=8, n_slot_hashes=512,
                             first_slot=SHAPE.BASE_SLOT)
    assert mine == {k: g[k] for k in ("voters", "slot_hashes", "slot")}


def test_the_two_copies_of_the_ledger_reference_are_equal():
    with open(os.path.join(BENCH, "harness", "ledger_reference.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "firedancer_tpu", "ops", "ref",
                           "ledger_replay.py"), "rb") as f:
        assert f.read() == mine
    assert b"import flamenco" not in mine and b"firedancer_tpu." not in \
        mine.split(b'"""', 2)[2]


def _run(**counters):
    return {"counters": counters, "offered": 1000, "served": 800}


def test_mix_readers_arithmetic():
    run = _run(
        pack={"txn_scheduled": 800, "txn_scheduled_votes": 560,
              "microblocks": 40, "conflict_skips": 1200, "txn_dropped": 9,
              "txn_in": 900, "dedup_dup": 60},
        verify0={"dedup_dup": 40},
        bank0={"txn_exec": 500, "bank_txn_native": 450, "native_punt": 5,
               "txn_exec_votes": 300, "txn_exec_failed_votes": 3},
        bank1={"txn_exec": 300, "bank_txn_native": 270, "native_punt": 3,
               "txn_exec_votes": 260, "txn_exec_failed_votes": 4})
    assert mr.vote_share_pct(run) == pytest.approx(70.0)
    assert mr.mb_fill_txn(run) == pytest.approx(20.0)
    assert mr.conflict_skips_per_txn(run) == pytest.approx(1.5)
    assert mr.dropped_pct(run) == pytest.approx(1.0)
    assert mr.native_txn_pct(run) == pytest.approx(90.0)
    assert mr.punt_per_100_txn(run) == pytest.approx(1.0)
    assert mr.vote_failed_pct(run) == pytest.approx(100 * 7 / 560)
    assert mr.dup_pct(run) == pytest.approx(10.0)
    # a counter lists itself only once it has counted: nothing dropped,
    # punted, failed or skipped reads 0, not silence
    for stage, gone in (("pack", ("txn_dropped", "conflict_skips")),
                        ("bank0", ("native_punt", "txn_exec_failed_votes")),
                        ("bank1", ("native_punt", "txn_exec_failed_votes"))):
        for k in gone:
            del run["counters"][stage][k]
    assert mr.dropped_pct(run) == mr.conflict_skips_per_txn(run) \
        == mr.punt_per_100_txn(run) == mr.vote_failed_pct(run) == 0.0


NEW = {"pack.vote_share_pct": mr.vote_share_pct,
       "pack.mb_fill_txn": mr.mb_fill_txn,
       "pack.conflict_skips_per_txn": mr.conflict_skips_per_txn,
       "pack.dropped_pct": mr.dropped_pct,
       "bank.native_txn_pct": mr.native_txn_pct,
       "bank.punt_per_100_txn": mr.punt_per_100_txn,
       "bank.vote_failed_pct": mr.vote_failed_pct,
       "dedup.dup_pct": mr.dup_pct}


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_reader_entry_and_silence_where_counters_are_absent(name):
    entry = next(m for m in MAN.data["per_layer"]
                 if m["name"] == name + ".mainnet")
    assert entry["workloads"] == ["leader-mainnet-mix"]
    assert entry["moves"] == "landed_per_s"
    assert entry["source"] == "program_counter"
    read = MAN.reader("per_layer", name + ".mainnet")
    assert read.__module__ == "harness.mix_readers" and read is NEW[name]
    # a program without the counters (the parent), a tile, an idle window
    assert read(_run(pack={"txn_in": 5}, verify0={}, bank0={})) is None
    assert read(_run(verify0={"batches": 3}, sink={})) is None
    assert read({"counters": {}, "offered": 0, "served": 0}) is None


def test_the_cell_reports_25_mainnet_metrics_over_files_that_exist():
    names = [m["name"] for m in MAN.metrics("per_layer", "leader-mainnet-mix")]
    assert len(names) == 25 and all(n.endswith(".mainnet") for n in names)
    for n in names:
        assert callable(MAN.reader("per_layer", n))
    e2e = [m["name"] for m in MAN.metrics("end_to_end", "leader-mainnet-mix")]
    assert e2e == ["landed_per_s", "setup_s"]


def test_allpass_mask_reads_incorrect_on_the_mainnet_cell():
    """The run the check has to fail: every corrupted row lands, and the
    ledger replay still agrees with the account store (the replay takes
    the block as it is stored)."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "leader-mainnet-mix", "--seed", str(2**31 + 131), "--seconds", "1",
         "--trace", "0", "--cpu", "--control", "allpass", "--set",
         "program_config.verify.batch=16", "--set",
         "traffic_accounts.n_voters=256", "--set",
         "traffic_accounts.n_payers=256"], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()]
    out = lines[-1]
    assert out["correct"] is False and out["attempted"] > 1000
    assert out["metrics"] == {} and out["rehearsal"] is True
    chk = next(ln for ln in lines if "check" in ln)
    assert chk["corrupted_landed"] > 0
    assert chk["check"]["landed_but_not_due"]["value"] \
        == chk["corrupted_landed"]
    for k in ("account_store_off_ledger_replay", "votes_accepted_minus_replay",
              "votes_dropped_while_regular_pending", "msg_too_long",
              "landed_bytes_matching_nothing_offered", "missing_and_uncounted",
              "fec_sets_not_stored", "tap_txn_minus_bank_txn_exec",
              "pool_exhausted", "compiles_in_window",
              "native_lanes_not_armed"):
        assert chk["check"][k]["value"] == 0, k
    assert chk["votes_landed"] > 500 and chk["dedup_counted"] > 50
    assert chk["pack"]["txn_scheduled_votes"] == chk["votes_landed"]
