"""The leader under mainnet-shaped ingress, at a small size on the CPU:
the bank's genesis function, and the whole pipeline (two banks, a hot
account, votes, priced transfers, repeats) held to the plain ledger
replay of its own stored block (ops/ref/ledger_replay.py)."""

import os
import sys

import pytest

from firedancer_tpu.flamenco import agave_state as ast
from firedancer_tpu.flamenco.runtime import acct_build, acct_decode
from firedancer_tpu.flamenco.solcompat import SYSVAR_NAMES, SYSVAR_OWNER
from firedancer_tpu.models.leader import build_leader_pipeline
from firedancer_tpu.ops.ref import ledger_replay as ledger
from firedancer_tpu.protocol.txn import SYSTEM_PROGRAM, VOTE_PROGRAM
from firedancer_tpu.runtime import bank
from firedancer_tpu.runtime.benchg import pool_payers
from firedancer_tpu.runtime.poh_stage import parse_entry
from firedancer_tpu.runtime.shred_stage import deshred_entry_batch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
ACCOUNTS = {"n_voters": 64, "n_payers": 32, "n_dests": 16,
            "slot_hashes": 512}
SEED = 2**31 + 531


def _records(ctx) -> dict:
    return {k: ctx.funk.rec_query(None, k) for k in ctx.funk.rec_keys(None)}


@pytest.mark.parametrize("kw", [
    {}, {"n_payers": 5, "seed": b"other"},
    {"n_payers": 3, "payer_lamports": 77, "with_status_cache": False,
     "slot": 9}])
def test_default_bank_ctx_is_the_genesis_functions_payer_only_case(kw):
    """Byte for byte what `default_bank_ctx` made before it became a
    case of `genesis_bank_ctx`: the seed's payers, funded, and nothing
    else; the pool's blockhash current; the same slot."""
    a, b = bank.default_bank_ctx(**kw), bank.genesis_bank_ctx(**kw)
    want = {pub: acct_build(kw.get("payer_lamports", 10**12))
            for _, pub in pool_payers(kw.get("seed", b"benchg"),
                                      kw.get("n_payers", 8))}
    assert _records(a) == _records(b) == want
    assert a.slot == b.slot == kw.get("slot", 1)
    assert (a.status_cache is None) == (not kw.get("with_status_cache", True))
    assert a.sx.sysvars["slot_hashes"] == b"\x00" * 8     # empty: votes reject


def test_genesis_makes_vote_accounts_sysvars_and_listed_payers():
    ids = [bytes([7, k]) * 16 for k in range(3)]
    vas = [bytes([9, k]) * 16 for k in range(3)]
    sh = [(12 - k, bytes([k]) * 32) for k in range(4)]
    payers = [bytes([5, k]) * 16 for k in range(2)]
    ctx = bank.genesis_bank_ctx(slot=13, payers=payers, n_payers=0,
                                voters=zip(ids, vas), slot_hashes=sh,
                                preload=ids + vas)
    recs = _records(ctx)
    by_name = {n: a for a, n in SYSVAR_NAMES.items()}
    assert set(recs) == set(payers) | set(ids) | set(vas) \
        | {by_name["clock"], by_name["slot_hashes"]}
    for k in payers + ids:
        assert recs[k] == acct_build(10**12)
    for ident, va in zip(ids, vas):
        lam, owner, _ex, data = acct_decode(recs[va])
        assert (lam, owner, len(data)) == (bank.VOTE_ACCOUNT_LAMPORTS,
                                           VOTE_PROGRAM, 3762)
        vs = ast.vote_state_decode(data)
        assert vs.node_pubkey == vs.authorized_withdrawer == ident
        assert vs.authorized_voter_for(0) == ident and not vs.votes
    sx = ctx.sx
    for name in ("clock", "slot_hashes"):
        lam, owner, _ex, data = acct_decode(recs[by_name[name]])
        assert owner == SYSVAR_OWNER and data == sx.sysvars[name]
    assert len(sx.sysvars["slot_hashes"]) == 8 + 4 * 40 and sx.slot == 13
    # and the program's seed derivations give a whole validator set
    g = bank.seeded_validators(b"s", n_voters=2, n_slot_hashes=3)
    assert g["slot"] == 4 and [s for s, _ in g["slot_hashes"]] == [3, 2, 1]
    assert len(g["voters"]) == 2 and g["voters"][0][0] != g["voters"][1][0]


def _counters_in_the_tracing(pipe) -> dict:
    """The six vote and conflict counters where an operator reads them:
    the registry a scraper reads (schema -> Prometheus), the monitor's
    lines under its table, slotreport's stage block.  -> vote_row by
    stage."""
    from firedancer_tpu.runtime import monitor as mon
    from firedancer_tpu.runtime import slot_report
    from firedancer_tpu.utils import metrics as fm

    stages = {s.name: s for s in (pipe.pack, *pipe.banks)}
    for s in stages.values():
        s.during_housekeeping()
        if s.metrics.registry is None:    # cooperative: none attached
            s.metrics.attach(fm.MetricsRegistry(s.metrics.schema))
        s.metrics.flush()
    regs = {n: s.metrics.registry for n, s in stages.items()}
    text = fm.render_prometheus(regs)
    for k in ("txn_scheduled_votes", "txn_dropped_votes",
              "votes_dropped_while_regular_pending", "conflict_skips"):
        assert f'{k}{{stage="pack"}} {stages["pack"].metrics.get(k)}' in text
    for k in ("txn_exec_votes", "txn_exec_failed_votes"):
        assert f'{k}{{stage="bank1"}} ' \
            f'{stages["bank1"].metrics.get(k)}' in text
    rows = {n: fm.vote_row(r) for n, r in regs.items()}
    assert list(rows["pack"]) == ["scheduled", "dropped",
                                  "dropped_while_regular_pending",
                                  "conflict_skips"]
    assert list(rows["bank0"]) == ["exec", "exec_failed"]
    assert fm.vote_row(pipe.poh.metrics.registry) is None
    rendered = mon.MonitorSession.render(
        [{"stage": n, "signal": 1, "heartbeat_age_ms": 1.0, "in": 0,
          "out": 0, "overrun": 0, "backpressure": 0, "iters": 1,
          "votes": row} for n, row in rows.items()], None, 1.0)
    assert f"pack: votes scheduled={rows['pack']['scheduled']:,} dropped=0 " \
           f"dropped_while_regular_pending=0 conflict_skips=" in rendered
    assert f"bank0: votes exec={rows['bank0']['exec']:,} exec_failed=" \
        in rendered
    dump = fm.flight_dump_obj("t", {n: (regs[n], s.recorder)
                                    for n, s in stages.items()})
    report = slot_report.build_report(dump)["stages"]
    assert {n: report[n]["votes"] for n in rows} == rows
    assert report["pack"]["counters"]["conflict_skips"] \
        == rows["pack"]["conflict_skips"]
    return rows


@pytest.fixture(scope="module")
def mix():
    """A 2,000-row mainnet-mix pool, its order with repeats and its
    genesis, from the benchmark's own shape file."""
    sys.path.insert(0, BENCH)
    try:
        from harness.manifest import load_module

        shape = load_module(os.path.join(BENCH, "shapes", "mainnet-mix.py"),
                            "shape_mainnet_mix_t1")
        pool = shape.build(SEED, 2000, ACCOUNTS, {})
        return (shape, pool, shape.order(pool, SEED, {}),
                shape.genesis(ACCOUNTS, SEED))
    finally:
        sys.path.remove(BENCH)


@pytest.mark.parametrize("lanes", ["native", "python"])
def test_leader_pipeline_equals_the_ledger_replay_of_its_block(
        mix, lanes, monkeypatch):
    """Batch 16, two banks, 16 Zipf-hot destinations: the account store
    after the drain is the plain replay of the stored block — every
    landed transaction executed once, in block order, whichever bank
    ran it; repeats land once; votes go first and none is dropped."""
    shape, pool, order, g = mix
    if lanes == "python":
        for k in ("PACK", "EXEC", "BANK"):
            monkeypatch.setenv(f"FDTPU_NATIVE_{k}", "0")
    pipe = build_leader_pipeline(
        n_verify=1, n_bank=2, pool_size=1, gen_limit=0, batch=16,
        max_msg_len=384, verify_precomputed=True, n_payers=1,
        bank_ctx=bank.genesis_bank_ctx(**g))
    try:
        pipe.benchg.pool = [pool.row(int(i)) for i in order]
        pipe.benchg.limit = len(order)
        pipe.run(max_iters=400)
        for _ in range(20):
            if pipe.benchg._i >= len(order) \
                    and not pipe.pack.pack.pending_cnt():
                break
            pipe.run(max_iters=200)
        report = pipe.report()
        block = [t for slot in sorted(pipe.store.sets_by_slot)
                 for e in deshred_entry_batch(
                     pipe.store.entry_batch_bytes(slot))
                 for t in parse_entry(e)[2]]
        sx = pipe.bank_ctx.sx
        lamports = {k: 10**12 for k in g["payers"]}
        lamports.update({i: 10**12 for i, _ in g["voters"]})
        lamports.update({v: bank.VOTE_ACCOUNT_LAMPORTS
                         for _, v in g["voters"]})
        ref = ledger.replay(
            lamports, {v: ledger.VoteAccount(i) for i, v in g["voters"]},
            dict(g["slot_hashes"]), g["slot"], block)
        for key, want in ref["lamports"].items():
            val = sx.funk.rec_query(sx.xid, key)
            assert (acct_decode(val)[0] if val else 0) == want, key.hex()
        for key, want in ref["vote_accounts"].items():
            vs = ast.vote_state_decode(
                acct_decode(sx.funk.rec_query(sx.xid, key))[3])
            assert (vs.votes[-1].lockout.slot if vs.votes else None,
                    vs.root_slot, len(vs.votes), vs.credits()) == (
                want.last_voted_slot, want.root, len(want.tower),
                want.credits)
        shown = _counters_in_the_tracing(pipe)
    finally:
        pipe.close()
    assert shown["pack"]["scheduled"] == ref["votes"]
    assert shown["pack"]["dropped_while_regular_pending"] == 0
    assert shown["bank0"]["exec"] + shown["bank1"]["exec"] == ref["votes"]
    # every distinct row landed once (nothing corrupted, nothing shed)
    assert sorted(block) == sorted({pool.row(i) for i in range(pool.n)})
    banks = [report["bank0"], report["bank1"]]
    n_votes = int((pool.cls == shape.VOTE).sum())
    assert sum(b["txn_exec"] for b in banks) == pool.n
    assert all(b["txn_exec"] > 0 for b in banks)              # both ran
    assert sum(b.get("txn_exec_votes", 0) for b in banks) == n_votes \
        == ref["votes"] == report["pack"]["txn_scheduled_votes"]
    assert sum(b.get("txn_exec_failed_votes", 0) for b in banks) \
        == ref["votes_failed"]
    assert ref["transfers_failed"] == 0
    p = report["pack"]
    assert p.get("votes_dropped_while_regular_pending", 0) == 0
    assert p.get("txn_dropped_votes", 0) == 0 and p["conflict_skips"] > 0
    dups = p.get("dedup_dup", 0) + report["verify0"].get("dedup_dup", 0) \
        + report.get("dedup", {}).get("frags_in", 0) \
        - report.get("dedup", {}).get("frags_out", 0)
    assert dups == len(order) - pool.n
    # a priority fee was charged, and a hot account was hit from both banks
    fees = sum(ledger.fee(*ledger.parse(t)[::2]) for t in block)
    assert fees > 5000 * sum(int(s) for s in pool.sigs)
    if lanes == "native":
        assert sum(b.get("native_punt", 0) for b in banks) == 0
        assert sum(b.get("bank_txn_native", 0) for b in banks) > 0
