"""B bank tiles, a process each, over ONE account store (models/leader_topo
.build_bank; NativeFunk.attach; native/fd_bank.cpp's read-through): the
process topology at toy size with B = 2 and 4 stores the block the
cooperative form of the same configuration stores, and its one funk
segment, read through attach_readonly, equals the plain replay of that
block — a stale read between two tiles would be a balance off the
replay; every tile executes, every tile takes its accounts from the
segment (`session_refreshed`), and the tiles' `txn_exec` sum to what
was offered.  One payer's transfers forced to hop between two tiles
every time read the replay's balance too.  The bench profile's two keys reach where they act: the
block's cost limit in both pack lanes, the status cache in the bank
tiles.  A SIGKILLed bank1 is named and nothing is left; what the
topology cannot build it refuses by name.

Batch 16, the all-pass mask (the verify program is not under test).
"""

import hashlib
import json
import os
import signal

import pytest

from firedancer_tpu.funk import funk_native
from firedancer_tpu.models import leader_topo as lt
from firedancer_tpu.models.leader import (
    block_limits_of, build_leader_pipeline_from_config,
)
from firedancer_tpu.ops.ref import ed25519_ref as ref
from firedancer_tpu.ops.ref import ledger_replay
from firedancer_tpu.pack import cost as fc
from firedancer_tpu.pack import scheduler_native
from firedancer_tpu.protocol import txn as ft_txn
from firedancer_tpu.runtime import topo as ft
from firedancer_tpu.runtime.benchg import pool_payers
from firedancer_tpu.runtime.slot_clock import SlotClockCfg
from firedancer_tpu.runtime.store import StoredSlots
from firedancer_tpu.tango import shm
from firedancer_tpu.utils.config import load_config

from test_leader_tiles import (
    HELD, _block, _drive, _no_trace_of, _release,
)

pytestmark = pytest.mark.skipif(
    not (shm.native_ring_enabled() and scheduler_native.available()
         and funk_native.available()),
    reason="the native ring, pack and funk lanes are not available")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 3072
# pack puts one transaction a payer into a microblock of at most 31: 96
# payers keep three to four microblocks in flight, so a payer's
# consecutive transfers land on different bank tiles (with 4 payers one
# microblock holds them all and one tile does all the work)
N_PAYERS = 96
SLOT_MS = 100.0
BENCH = {"larger_max_cost_per_block": True, "disable_status_cache": True}


def _cfg(n_bank: int, bench: dict = BENCH):
    return load_config(None, overrides={
        "layout": {"bank_stage_count": n_bank},
        "verify": {"batch": 16, "max_msg_len": 256},
        "pack": {"hold_when_full": True},
        "poh": {"slot_ms": SLOT_MS},
        "development": {"bench": bench}})


def _launch(n_bank: int, n: int = N):
    topo = lt.build_leader_topology_from_config(
        _cfg(n_bank), n_txns=n, pool_size=n, n_payers=N_PAYERS,
        verify_precomputed=True)
    h = ft.launch(topo, held=HELD)
    try:
        held = [h.build_held(name) for name in HELD]
        h.wait_running(120)
    except BaseException:
        h.close()
        raise
    return h, held


def _banks(n_bank: int) -> list[str]:
    return [f"bank{b}" for b in range(n_bank)]


@pytest.mark.parametrize("n_bank", [2, 4])
def test_b_bank_processes_store_the_cooperative_block_and_one_replayable_store(
        n_bank):
    pipe = build_leader_pipeline_from_config(
        _cfg(n_bank), pool_size=N, gen_limit=N, n_payers=N_PAYERS,
        verify_precomputed=True, slot_clock=SlotClockCfg(slot_ms=SLOT_MS))
    try:
        assert pipe.bank_ctx.status_cache is None       # the profile's key
        pipe.run(until_txns=N, max_iters=400_000)
        coop = _block(pipe.store)
    finally:
        pipe.close()
    assert len(coop) == len(set(coop)) == N

    h, held = _launch(n_bank)
    banks = _banks(n_bank)
    try:
        assert sorted(h.procs) == sorted(
            ["pack", "poh", "shred", "store"] + banks)

        def settled(c, mark=[None]):
            slots = c["poh"]["slots_sealed"] + c["poh"]["slot_missed"]
            if sum(c[b]["txn_exec"] for b in banks) < N:
                return False
            if mark[0] is None:
                mark[0] = slots
            return slots - mark[0] >= 2 \
                and c["shred"]["fec_sets"] == c["store"]["sets_stored"]

        c = _drive(h, held, settled)
        # every tile executed, natively, on accounts read through the
        # segment; together they executed what
        # was offered, each transaction once
        assert sum(c[b]["txn_exec"] for b in banks) == N
        for b in banks:
            assert c[b]["txn_exec"] > 0, b
            assert c[b]["session_refreshed"] > 0, b
            assert c[b]["native_exec"] == c[b]["txn_exec"], b
            assert c[b]["native_punt"] == 0 and c[b]["bank_funk_falls"] == 0
            assert c[b]["funk_lock_acquires"] >= 2 * c[b]["microblocks"], b
            assert c[b]["native_lanes"] == 3        # rings, sweep, funk
            assert c[b]["native_lanes_off"] == 0, b
        assert c["pack"]["microblocks"] == sum(
            c["pack"][f"mb_scheduled_b{k}"] for k in range(n_bank))
        assert all(c["pack"][f"mb_scheduled_b{k}"] == c[b]["microblocks"]
                   for k, b in enumerate(banks))
        tiles = _block(StoredSlots(lt.store_dir(h)))
        assert sorted(tiles) == sorted(coop)            # each once
        # the ONE store every tile wrote, from this process
        funded = 10**12
        want = ledger_replay.replay(
            {pub: funded for _s, pub in pool_payers(b"benchg", N_PAYERS)},
            {}, {}, 1, tiles)
        from firedancer_tpu.flamenco.runtime import acct_decode

        ro = funk_native.NativeFunk.attach_readonly(lt.bank_funk_shm(h))
        try:
            assert ro.writers() == n_bank
            got = {k: acct_decode(v)[0] if v else 0 for k in want["lamports"]
                   for v in [ro.rec_query(lt.BANK_FORK_XID, k)]}
        finally:
            ro.close()
        assert got == want["lamports"] and len(got) > N_PAYERS
        table = h.format_monitor()
        assert all(b in table for b in banks) and "funk" in table
    finally:
        _release(held)
        h.halt()
        h.close()
    _no_trace_of(h)


def test_one_payers_transfers_hop_between_two_banks_every_time():
    """The hand-over forced, not left to the traffic: ONE payer, a
    transaction a microblock, and a pack (held here) that gives each
    microblock to the bank after the last one's.  The payer's lock lets
    one transfer run at a time, so every transfer executes on the tile
    that did NOT execute the one before: each starts from a balance the
    other tile left in the segment.  A session that trusted its own copy
    would read the payer one transfer short of the replay (192
    transfers over the generator's 64 destinations: each is touched
    three times, so the sessions hold every account they are tested
    on)."""
    n = 192
    cfg = load_config(None, overrides={
        "layout": {"bank_stage_count": 2},
        "verify": {"batch": 16, "max_msg_len": 256},
        "pack": {"hold_when_full": True, "max_txn_per_microblock": 1},
        "poh": {"slot_ms": SLOT_MS},
        "development": {"bench": BENCH}})
    topo = lt.build_leader_topology_from_config(
        cfg, n_txns=n, pool_size=n, n_payers=1, verify_precomputed=True)
    h = ft.launch(topo, held=HELD + ("pack",))
    try:
        held = [h.build_held(name) for name in HELD + ("pack",)]
        pack = held[-1]
        turn = [0]

        def after_credit():
            # PackStage.after_credit, the bank chosen by turn
            pack._flush_intake()
            b = turn[0]
            if pack._ready_to_schedule() and not pack._bank_busy[b] \
                    and pack.outs[b].cr_avail > 0 and pack._try_emit(b):
                turn[0] = 1 - b

        pack.after_credit = after_credit
        h.wait_running(120)

        def settled(c, mark=[None]):
            slots = c["poh"]["slots_sealed"] + c["poh"]["slot_missed"]
            if c["bank0"]["txn_exec"] + c["bank1"]["txn_exec"] < n:
                return False
            if mark[0] is None:
                mark[0] = slots
            return slots - mark[0] >= 2 \
                and c["shred"]["fec_sets"] == c["store"]["sets_stored"]

        c = _drive(h, held, settled)
        tiles = _block(StoredSlots(lt.store_dir(h)))
        assert len(tiles) == n
        (_sec, payer), = pool_payers(b"benchg", 1)
        want = ledger_replay.replay({payer: 10**12}, {}, {}, 1, tiles)
        from firedancer_tpu.flamenco.runtime import acct_decode

        ro = funk_native.NativeFunk.attach_readonly(lt.bank_funk_shm(h))
        try:
            got = {k: acct_decode(v)[0] if v else 0 for k in want["lamports"]
                   for v in [ro.rec_query(lt.BANK_FORK_XID, k)]}
        finally:
            ro.close()
        assert got == want["lamports"]
        assert got[payer] < 10**12 - n * 5000       # n fees, n transfers
        # strictly by turns, each in the sweep lane, each account of
        # each transfer (payer, destination, program) from the segment
        for b in _banks(2):
            assert c[b]["txn_exec"] == c[b]["microblocks"] == n // 2, b
            assert c[b]["bank_txn_native"] == n // 2, b
            assert c[b]["session_refreshed"] == 3 * (n // 2), b
    finally:
        _release(held)
        h.halt()
        h.close()
    _no_trace_of(h)


def test_one_bank_tile_takes_nothing_from_the_segment():
    """The stock deployment: one writer, so the read-through does not
    run (`session_refreshed` 0) and the lock is never contended."""
    topo = lt.build_leader_topology_from_config(
        _cfg(1, {}), n_txns=192, pool_size=192, n_payers=8,
        verify_precomputed=True)
    h = ft.launch(topo, held=HELD)
    try:
        held = [h.build_held(name) for name in HELD]
        h.wait_running(120)
        c = _drive(h, held, lambda c: c["bank0"]["txn_exec"] == 192)
        c = h.counters()
        assert c["bank0"]["session_refreshed"] == 0
        assert c["bank0"]["funk_lock_contended"] == 0
        assert c["bank0"]["funk_lock_acquires"] > 0
    finally:
        _release(held)
        h.halt()
        h.close()
    _no_trace_of(h)


def test_a_sigkilled_bank1_is_named_and_nothing_is_left():
    h, held = _launch(2, n=512)
    try:
        _drive(h, held, lambda c: c["bank0"]["txn_exec"] > 0)
        seg = os.path.join("/dev/shm", lt.bank_funk_shm(h))
        assert os.path.exists(seg)
        os.kill(h.procs["bank1"].pid, signal.SIGKILL)
        h.procs["bank1"].join(10)
        assert h.dead() == ["bank1"]
        assert h.counters()["bank0"]["txn_exec"] > 0    # the others read
        assert os.path.exists(seg)
    finally:
        _release(held)
        h.close()
    _no_trace_of(h)


# -- the bench profile's keys ----------------------------------------------------

_BH = hashlib.sha256(b"banks-bh").digest()


def _heavy_txn(i: int) -> bytes:
    """A transaction that asks for 1.4M compute units of a program that
    is no builtin: 35 of them pass the stock block's 48M cost units
    (pack costs them, nothing here executes them)."""
    sec = hashlib.sha256(b"hv%d" % i).digest()
    accts = [ref.public_key(sec), hashlib.sha256(b"hd%d" % i).digest(),
             hashlib.sha256(b"some program").digest(),
             fc.COMPUTE_BUDGET_PROGRAM]
    instrs = [
        ft_txn.InstrSpec(program_id=3, accounts=b"", data=b"\x02"
                         + (1_400_000).to_bytes(4, "little")),
        ft_txn.InstrSpec(program_id=2, accounts=bytes([0, 1]), data=b"\x01")]
    msg = ft_txn.message_build(
        version=ft_txn.VLEGACY, signature_cnt=1, readonly_signed_cnt=0,
        readonly_unsigned_cnt=2, acct_addrs=accts, recent_blockhash=_BH,
        instrs=instrs)
    return ft_txn.txn_assemble([ref.sign(sec, msg)], msg)


@pytest.mark.parametrize("lane", ["native", "python"])
@pytest.mark.parametrize("larger", [False, True],
                         ids=["stock_limit", "larger_max_cost_per_block"])
def test_the_block_cost_limit_is_the_configs_in_both_pack_lanes(lane, larger):
    """40 transactions of ~1.4M cost units each: without the key the block
    is full at 48M (34 scheduled, the rest wait for the next block),
    with it all 40 are scheduled."""
    from firedancer_tpu.runtime.pack_stage import NativePackStage, PackStage
    from firedancer_tpu.runtime.verify import encode_verified, sig_tag

    limits = block_limits_of(_cfg(1, {"larger_max_cost_per_block": larger}))
    assert (limits is None) == (not larger)
    if larger:
        assert limits.max_cost_per_block == 18 * fc.MAX_COST_PER_BLOCK \
            == fc.LARGER_MAX_COST_PER_BLOCK
    uid = shm.fresh_uid()
    links = [shm.ShmLink.create(f"fdtpu_{name}_{uid}", depth=256, mtu=mtu)
             for name, mtu in (("vd", 4096), ("bd", 64), ("pb", 65536))]
    vd, bd, pb = links
    cls = NativePackStage if lane == "native" else PackStage
    pack = cls("pack", ins=[shm.make_consumer(vd), shm.make_consumer(bd)],
               outs=[shm.make_producer(pb)], bank_cnt=1, min_pending=1,
               mb_deadline_s=0.0, limits=limits)
    feeder, done, bank = (shm.make_producer(vd), shm.make_producer(bd),
                          shm.make_consumer(pb))
    try:
        cost = None
        for i in range(40):
            p = _heavy_txn(i)
            t = ft_txn.txn_parse(p)
            cost = cost or fc.compute_cost(p, t).total
            assert feeder.try_publish(encode_verified(p, t),
                                      sig=sig_tag(t.signatures(p)[0]),
                                      tsorig=1)
        assert 34 * cost <= fc.MAX_COST_PER_BLOCK < 35 * cost
        for _ in range(600):
            if bank.poll() not in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
                assert done.try_publish(b"d", sig=0, tsorig=1)
            pack.run_once()
        m = pack.metrics
        assert m.get("txn_in") == 40
        assert m.get("txn_scheduled") == (40 if larger else 34)
        assert m.get("mb_scheduled_b0") == m.get("microblocks") > 0
    finally:
        pack.ins, pack.outs = [], []
        pack.drop_native_views()
        del feeder, done, bank
        import gc

        gc.collect()
        for link in links:
            link.close()
            link.unlink()


@pytest.mark.parametrize("lane", ["native", "python"])
def test_a_slot_boundary_keeps_the_locks_of_a_microblock_in_flight(lane):
    """Two transfers from different payers into ONE destination: bank 0
    holds the first, so the second waits for its account.  A slot
    boundary passes while bank 0 is still executing: the block's
    accounting closes only once bank 0 is done (`end_block` gives every
    lock back), so bank 1 is never handed the account under bank 0 —
    with bank tiles in processes of their own both would start from its
    old value and one transfer would be lost."""
    from firedancer_tpu.runtime.pack_stage import NativePackStage, PackStage
    from firedancer_tpu.runtime.slot_clock import SlotClockCfg
    from firedancer_tpu.runtime.verify import encode_verified, sig_tag

    now = [0]
    clock = SlotClockCfg(slot_ms=100.0, t0_ns=0).build(now_fn=lambda: now[0])
    uid = shm.fresh_uid()
    links = [shm.ShmLink.create(f"fdtpu_{name}_{uid}", depth=64, mtu=mtu)
             for name, mtu in (("vd", 4096), ("bd0", 64), ("bd1", 64),
                               ("pb0", 65536), ("pb1", 65536))]
    vd, bd0, bd1, pb0, pb1 = links
    cls = NativePackStage if lane == "native" else PackStage
    pack = cls("pack", ins=[shm.make_consumer(l) for l in (vd, bd0, bd1)],
               outs=[shm.make_producer(pb0), shm.make_producer(pb1)],
               bank_cnt=2, min_pending=1, mb_deadline_s=0.0, clock=clock)
    feeder, done0 = shm.make_producer(vd), shm.make_producer(bd0)
    bank0, bank1 = shm.make_consumer(pb0), shm.make_consumer(pb1)
    dest = hashlib.sha256(b"one destination").digest()
    try:
        for i in range(2):
            sec = hashlib.sha256(b"lk%d" % i).digest()
            p = ft_txn.transfer_txn(sec, dest, 1 + i, _BH,
                                    from_pubkey=ref.public_key(sec))
            t = ft_txn.txn_parse(p)
            assert feeder.try_publish(encode_verified(p, t),
                                      sig=sig_tag(t.signatures(p)[0]),
                                      tsorig=1)
            for _ in range(20):
                pack.run_once()
        m = pack.metrics
        assert m.get("txn_in") == 2 and m.get("txn_scheduled") == 1
        assert bank0.has_pending() and not bank1.has_pending()
        now[0] = 150_000_000                    # the boundary passes
        for _ in range(50):
            pack.run_once()
        assert m.get("blocks_closed") == 1
        assert m.get("txn_scheduled") == 1 and not bank1.has_pending()
        assert done0.try_publish(b"d", sig=0, tsorig=1)   # bank 0 is done
        for _ in range(50):
            pack.run_once()
        assert m.get("txn_scheduled") == 2
    finally:
        pack.ins, pack.outs = [], []
        pack.drop_native_views()
        del feeder, done0, bank0, bank1
        import gc

        gc.collect()
        for link in links:
            link.close()
            link.unlink()


def test_slotreport_and_the_monitor_show_the_store_the_banks_share():
    """`slotreport`: a `funk` block a bank tile (its lock counters,
    what it took from the segment, each wait over 100 us with its
    holder), pack's microblocks a bank, and the run's `funk` block
    summed over the tiles; the monitor's `funk:` line over the same
    rows."""
    from firedancer_tpu.runtime import slot_report as sr
    from firedancer_tpu.utils import metrics as fm

    def bank(refreshed, holds, contended, wait_ns):
        return {"bank_funk_writes": 10, "bank_funk_falls": 0,
                "session_refreshed": refreshed, "funk_lock_acquires": holds,
                "funk_lock_contended": contended,
                "funk_lock_wait_ns": wait_ns}

    dump = {"uid": "u", "stages": {
        "bank0": {"metrics": bank(7, 100, 3, 5000), "records": [
            (11, fm.EV_FUNK_LOCK_WAIT, fm.funk_lock_wait_arg(2, 250_000))]},
        "bank1": {"metrics": bank(9, 80, 1, 1000), "records": []},
        "pack": {"metrics": {"mb_scheduled_b0": 50, "mb_scheduled_b1": 40,
                             "bank_idle_polls": 6}, "records": []}}}
    rep = sr.build_report(dump)["stages"]
    assert rep["bank0"]["funk"] == {
        "lock_holds": 100, "contended": 3, "wait_ns": 5000,
        "session_refreshed": 7,
        "long_waits": [{"ts": 11, "holder": 2, "us": 250}]}
    assert rep["bank1"]["funk"]["session_refreshed"] == 9
    assert rep["pack"]["funk"] == {"mb_b0": 50, "mb_b1": 40,
                                   "bank_idle_polls": 6}
    assert rep["funk"]["counters"] == {
        "bank_funk_writes": 20, "bank_funk_falls": 0, "bank_tiles": 2,
        "funk_lock_acquires": 180, "funk_lock_contended": 4,
        "funk_lock_wait_ns": 6000, "session_refreshed": 16}
    line = fm.format_funk({n: fm.funk_row(st["metrics"])
                           for n, st in dump["stages"].items()})
    assert line.startswith("funk: 2 bank tile(s) over one store")
    assert "holds=180 contended=4 (2.22%)" in line
    assert "session_refreshed bank0=7 bank1=9" in line
    assert "pack: microblocks b0=50 b1=40 bank_idle_polls=6" in line
    assert fm.format_funk({"poh": None, "pack": None}) is None
    assert fm.funk_lock_wait_fields(
        fm.funk_lock_wait_arg(3, 1_500_000)) == {"holder": 3, "us": 1500}
    assert fm.EVENT_NAMES[fm.EV_FUNK_LOCK_WAIT] == "funk_lock_wait"


def test_the_keys_reach_the_tiles_that_act_on_them():
    topo = lt.build_leader_topology_from_config(_cfg(4))
    spec = {s.name: s for s in topo.stages}
    assert spec["pack"].kwargs["limits"].max_cost_per_block \
        == fc.LARGER_MAX_COST_PER_BLOCK
    assert spec["pack"].kwargs["n_bank"] == 4
    for b in range(4):
        kw = spec[f"bank{b}"].kwargs
        assert kw["status_cache"] is False and kw["bank_idx"] == b
        # one segment, the run's, for every tile; the supervisor's to
        # take away (Topology.own)
        assert kw["funk_shm"] == spec["bank0"].kwargs["funk_shm"]
    assert spec["bank0"].kwargs["funk_shm"] in topo.owned
    stock = {s.name: s for s in
             lt.build_leader_topology_from_config(_cfg(1, {})).stages}
    assert stock["pack"].kwargs["limits"] is None
    assert stock["bank0"].kwargs["status_cache"] is True


def test_the_toml_is_the_benchmarks_deployment():
    cfg = load_config(os.path.join(ROOT, "config",
                                   "fddev-bench-tuned-v5e.toml"))
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "fddev-bench-tuned-v5e.json")) as f:
        bench = json.load(f)
    want = load_config(None, overrides=dict(
        bench["program_config"],
        poh={"slot_ms": bench["slot_clock"]["slot_ms"]}))
    assert cfg == want
    assert cfg.layout.bank_stage_count == 4
    assert cfg.development.bench.larger_max_cost_per_block
    assert cfg.development.bench.disable_status_cache
    # and the stock file's, which keeps the profile's keys off
    stock = load_config(os.path.join(ROOT, "config", "fddev-bench-v5e.toml"))
    assert stock.development == type(stock.development)()
    assert stock.layout.bank_stage_count == 1


def test_what_the_process_topology_cannot_build_it_refuses_by_name(
        monkeypatch):
    with pytest.raises(ValueError, match="disable_status_cache"):
        lt.build_leader_topology_from_config(
            _cfg(2, {"larger_max_cost_per_block": True}))
    with pytest.raises(ValueError, match="MAX_BANK_TILES"):
        lt.build_leader_topology(n_bank=fc.MAX_BANK_TILES + 1,
                                 status_cache=False)
    monkeypatch.setenv(funk_native.ENV_SWITCH, "0")
    with pytest.raises(ValueError, match="native funk"):
        lt.build_leader_topology_from_config(_cfg(2))
    # one bank tile still runs over the Python funk, as before
    lt.build_leader_topology_from_config(_cfg(1, {}))


@pytest.mark.parametrize("config,n_bank", [
    (None, 1),                                # the defaults ask for two
    ("config/leader-v5e.toml", 1),            # the documented command's
    ("config/leader-mainnet-v5e.toml", 1),
    ("config/fddev-bench-v5e.toml", 1),
    ("config/fddev-bench-tuned-v5e.toml", 4),
])
def test_run_processes_builds_every_committed_config(
        config, n_bank, monkeypatch, capsys):
    """`run --processes` up to the launch: a config that asks for more
    than one bank tile without the profile's disable_status_cache runs
    one and says so (it does not die in the builder's refusal); the
    tuned file runs its four."""
    from firedancer_tpu.__main__ import main

    class Built(Exception):
        pass

    def launch(topo, **kw):
        raise Built(topo)

    monkeypatch.setattr(ft, "launch", launch)
    argv = ["run", "--processes", "--cpu", "--txns", "64"]
    if config is not None:
        argv += ["--config", os.path.join(ROOT, config)]
    with pytest.raises(Built) as built:
        main(argv)
    names = [s.name for s in built.value.args[0].stages]
    assert [n for n in names if n.startswith("bank")] == _banks(n_bank)
    asked = load_config(
        os.path.join(ROOT, config) if config else None
    ).layout.bank_stage_count
    note = "the process topology runs 1 bank stage"
    assert (note in capsys.readouterr().err) == (asked != n_bank)
