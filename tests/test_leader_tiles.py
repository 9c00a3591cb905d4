"""A process per tile with stages held in the launching process
(runtime/topo.launch(held=), models/leader_topo
.build_leader_topology_from_config): the caller's generator and verify
stage exchange frags with spawned pack, bank, poh, shred and store tiles
over the topology's shm rings; every tile's counters are read from its
shm segment; the stored block is read back from the store tile's files
and the account store through NativeFunk.attach_readonly, and both are
what the cooperative form of the same configuration makes; and nothing
of a run — no child, no /dev/shm segment, no file — outlives close(),
also after a tile was SIGKILLed.

Batch 16; the verify program's arithmetic is conftest's toy (some rows
fail, as corrupted ones would) or the all-pass mask.
"""

import os
import signal
import time

import pytest

from firedancer_tpu.funk import funk_native
from firedancer_tpu.models import leader_topo as lt
from firedancer_tpu.models.leader import build_leader_pipeline_from_config
from firedancer_tpu.ops.ref import ledger_replay
from firedancer_tpu.pack import scheduler_native
from firedancer_tpu.protocol import txn as ft_txn
from firedancer_tpu.runtime import topo as ft
from firedancer_tpu.runtime.benchg import gen_transfer_pool, pool_payers
from firedancer_tpu.runtime.poh_stage import parse_entry
from firedancer_tpu.runtime.shred_stage import deshred_entry_batch
from firedancer_tpu.runtime.slot_clock import SlotClockCfg
from firedancer_tpu.runtime.store import StoredSlots
from firedancer_tpu.tango import shm
from firedancer_tpu.utils.config import load_config

pytestmark = pytest.mark.skipif(
    not (shm.native_ring_enabled() and scheduler_native.available()
         and funk_native.available()),
    reason="the native ring, pack and funk lanes are not available")

N = 192
N_PAYERS = 8
HELD = ("benchg", "verify0")
SPAWNED = ["pack", "bank0", "poh", "shred", "store"]
SLOT_MS = 100.0


def _cfg():
    return load_config(None, overrides={
        "layout": {"bank_stage_count": 1},
        "verify": {"batch": 16, "max_msg_len": 256},
        "pack": {"hold_when_full": True},
        "poh": {"slot_ms": SLOT_MS}})


def _block(store) -> list[bytes]:
    """The stored block's transactions, slot by slot in block order."""
    return [txn for slot in sorted(store.sets_by_slot)
            for entry in deshred_entry_batch(store.entry_batch_bytes(slot))
            for txn in parse_entry(entry)[2]]


def _launch(**kw):
    topo = lt.build_leader_topology_from_config(
        _cfg(), n_txns=N, pool_size=N, n_payers=N_PAYERS, **kw)
    h = ft.launch(topo, held=HELD)
    try:
        held = [h.build_held(name) for name in HELD]
        h.wait_running(120)
    except BaseException:
        h.close()
        raise
    return h, held


def _drive(h, held, until, limit_s: float = 60.0) -> dict:
    """Run the held stages until `until(counters)`; -> the counters."""
    t_end = time.monotonic() + limit_s
    while time.monotonic() < t_end:
        for _ in range(200):
            for s in held:
                s.run_once()
        c = h.counters()
        if until(c):
            return c
        assert not h.dead(), h.format_monitor()
    raise AssertionError(f"not reached in {limit_s} s:\n{h.format_monitor()}")


def _release(held) -> None:
    for s in held:
        s.ins, s.outs = [], []
        s.drop_native_views()


def _no_trace_of(h) -> None:
    assert h.left_behind() == []
    assert all(not p.is_alive() for p in h.procs.values())
    assert not [n for n in os.listdir("/dev/shm")
                if n.endswith("_" + h.uid) or f"_{h.uid}_" in n]
    assert not os.path.exists(lt.store_dir(h))


def _toy_ok(t: bytes, toy_lane_ok) -> bool:
    d = ft_txn.txn_parse(t)
    msg = d.message(t)
    return all(toy_lane_ok(len(msg), msg[0], sig[0], sig[63], pk[0], pk[31])
               for sig, pk in zip(d.signatures(t), d.signers(t)))


def test_the_stored_block_and_the_accounts_are_the_cooperative_forms(
        toy_verify_ok):
    """The same configuration and the same offers in both forms: the
    process form's stored block holds exactly the rows the cooperative
    form's does — the same set, every byte, each once, the rows the
    verify program fails absent — and the bank tile's balances, read
    through attach_readonly, equal the plain replay of that block."""
    pool = gen_transfer_pool(N, n_payers=N_PAYERS)
    want = sorted(t for t in pool if _toy_ok(t, toy_verify_ok))
    assert 0 < len(want) < N                    # some rows fail

    pipe = build_leader_pipeline_from_config(
        _cfg(), pool_size=N, gen_limit=N, n_payers=N_PAYERS,
        verify_precomputed=False, slot_clock=SlotClockCfg(slot_ms=SLOT_MS))
    try:
        pipe.run(until_txns=len(want), max_iters=400_000)
        coop = _block(pipe.store)
        assert pipe.verifies[0].metrics.get("verify_fail") == N - len(want)
    finally:
        pipe.close()
    assert sorted(coop) == want

    h, held = _launch(verify_cpu=True)
    try:
        def settled(c, mark=[None, 0]):
            # every passing row executed, and two slot boundaries since
            # (the shred tile flushes a slot's tail at its end)
            slots = c["poh"]["slots_sealed"] + c["poh"]["slot_missed"]
            if c["bank0"]["txn_exec"] < len(want):
                return False
            if mark[0] is None:
                mark[0] = slots
            return slots - mark[0] >= 2 \
                and c["shred"]["fec_sets"] == c["store"]["sets_stored"]

        c = _drive(h, held, settled)
        assert c["verify0"]["verify_fail"] == N - len(want)
        assert c["bank0"]["txn_exec"] == len(want)
        tiles = _block(StoredSlots(lt.store_dir(h)))
        assert sorted(tiles) == want == sorted(coop)    # each once
        # the account store, from this process
        funded = 10**12
        ref = ledger_replay.replay(
            {pub: funded for _s, pub in pool_payers(b"benchg", N_PAYERS)},
            {}, {}, 1, tiles)
        from firedancer_tpu.flamenco.runtime import acct_decode

        ro = funk_native.NativeFunk.attach_readonly(lt.bank_funk_shm(h))
        try:
            got = {k: acct_decode(v)[0] if v else 0 for k in ref["lamports"]
                   for v in [ro.rec_query(lt.BANK_FORK_XID, k)]}
        finally:
            ro.close()
        assert got == ref["lamports"] and len(got) > N_PAYERS
        assert any(v != funded for v in got.values())
    finally:
        _release(held)
        h.halt()
        h.close()
    _no_trace_of(h)


def test_held_and_spawned_stages_are_one_topology_and_leave_nothing():
    """The caller's generator and verify stage and five spawned tiles
    exchange frags; `counters()` reads every stage, each with the
    ledger and its native lanes armed in its own process; close()
    leaves no process, segment or file."""
    h, held = _launch(verify_precomputed=True)
    try:
        assert sorted(h.procs) == sorted(SPAWNED)
        assert sorted(h.held) == sorted(HELD)
        _drive(h, held, lambda c: c["bank0"]["txn_exec"] == N
               and c["poh"]["mixins"] == c["bank0"]["microblocks"]
               and c["store"]["sets_stored"] > 0)
        # the tiles answer a read one after another, each at its own
        # instant: once all N have landed, a second read is of a
        # settled pipeline
        c = h.counters()
        assert set(c) == set(HELD) | set(SPAWNED)
        assert c["benchg"]["frags_out"] == c["verify0"]["frags_in"] == N
        assert c["pack"]["txn_in"] == c["pack"]["txn_scheduled"] == N
        assert c["poh"]["mixins"] == c["bank0"]["microblocks"] > 0
        assert c["shred"]["fec_sets"] > 0 and c["store"]["shreds_in"] > 0
        for name, k in c.items():
            assert k["loop_work_ns"] > 0 and k["loop_work_n"] > 0, name
            assert k["native_lanes"] > 0 and k["native_lanes_off"] == 0, name
        assert c["bank0"]["native_lanes"] == 3      # rings, sweep, funk
        assert c["bank0"]["sweep_crossings"] > 0    # C's words, from shm
        table = h.format_monitor()
        assert "busy%" in table and "backp%" in table
        assert all(n in table for n in HELD + tuple(SPAWNED))
    finally:
        _release(held)
        h.halt()
        h.close()
    _no_trace_of(h)
    h.close()                                       # twice is once


def test_a_sigkilled_tile_is_named_and_nothing_is_left():
    """A tile that dies is not a hang: `dead()` names it, the counters
    of the others still read, and close() takes away what the dead
    tile made too (its funk segment: it never got to unlink it)."""
    h, held = _launch(verify_precomputed=True)
    try:
        _drive(h, held, lambda c: c["bank0"]["txn_exec"] > 0)
        funk_seg = os.path.join("/dev/shm", lt.bank_funk_shm(h))
        assert os.path.exists(funk_seg)
        os.kill(h.procs["bank0"].pid, signal.SIGKILL)
        h.procs["bank0"].join(10)
        assert h.dead() == ["bank0"]
        c = h.counters()                # the dead tile: as last flushed
        assert c["bank0"]["txn_exec"] > 0 and c["pack"]["txn_in"] > 0
        assert os.path.exists(funk_seg)
    finally:
        _release(held)
        h.close()
    _no_trace_of(h)


def test_a_launch_that_fails_leaves_nothing():
    """A held name the topology does not have: refused, and the links
    and segments made before the refusal are gone."""
    before = set(os.listdir("/dev/shm"))
    topo = lt.build_leader_topology_from_config(
        _cfg(), n_txns=N, pool_size=N, verify_precomputed=True)
    with pytest.raises(ValueError, match="not in the topology"):
        ft.launch(topo, held=("verify9",))
    assert set(os.listdir("/dev/shm")) - before == set()


@pytest.mark.parametrize("ring", ["native", "python"])
def test_a_full_pool_holds_its_intake_and_still_takes_done_frames(
        ring, monkeypatch):
    """`hold_when_full`: a pool without room for one more burst leaves
    the txn ring unpolled — nothing is evicted, the calls it then makes
    are charged to backpressure — while the bank's done frames still
    come in past the held intake, so the pool drains and the intake
    opens again."""
    from firedancer_tpu.runtime.pack_stage import NativePackStage
    from firedancer_tpu.runtime.verify import encode_verified, sig_tag

    monkeypatch.setenv("FDTPU_NATIVE_RING", "1" if ring == "native" else "0")
    depth, n = 128, 256
    uid = shm.fresh_uid()
    links = [shm.ShmLink.create(f"fdtpu_{name}_{uid}", depth=512, mtu=mtu)
             for name, mtu in (("vd", 4096), ("bd", 64), ("pb", 65536))]
    vd, bd, pb = links
    pack = NativePackStage(
        "pack", ins=[shm.make_consumer(vd), shm.make_consumer(bd)],
        outs=[shm.make_producer(pb)], bank_cnt=1, depth=depth,
        min_pending=1, mb_deadline_s=0.0, hold_when_full=True)
    feeder, done, bank = (shm.make_producer(vd), shm.make_producer(bd),
                          shm.make_consumer(pb))
    try:
        for p in gen_transfer_pool(n, n_payers=64):
            t = ft_txn.txn_parse(p)
            assert feeder.try_publish(encode_verified(p, t),
                                      sig=sig_tag(t.signatures(p)[0]),
                                      tsorig=1)
        for _ in range(50):
            pack.run_once()
        m = pack.metrics
        # one microblock is out, the bank holds it; the pool filled up
        # to a burst under its depth and the rest waits in the ring
        assert m.get("microblocks") == 1 and bank.has_pending()
        held_at = m.get("txn_in")
        assert depth - pack.burst <= held_at - m.get("txn_scheduled") <= depth
        assert m.get("txn_dropped") == 0 and pack.intake_room == 0
        assert pack.ins[0].has_pending()
        backp = m.get("loop_backp_n")
        pack.run_once()
        assert m.get("loop_backp_n") == backp + 1 and m.get("txn_in") == held_at
        # the bank works through what it is given: every done frame
        # comes in past the held intake, and all n land, none dropped
        for _ in range(2000):
            if bank.poll() not in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
                assert done.try_publish(b"d", sig=0, tsorig=1)
            pack.run_once()
            if m.get("txn_scheduled") == n:
                break
        assert m.get("txn_scheduled") == m.get("txn_in") == n
        assert m.get("txn_dropped") == 0
        assert m.get("microblock_done") >= m.get("microblocks") - 1
    finally:
        pack.ins, pack.outs = [], []
        pack.drop_native_views()
        del feeder, done, bank
        import gc

        gc.collect()
        for link in links:
            link.close()
            link.unlink()


def test_a_store_with_a_directory_writes_what_another_process_reads(
        tmp_path):
    """`persist_dir`: the resolved sets go to a file a slot and not
    into the stage's memory; StoredSlots reads back the same sets and
    entry batches the in-memory store holds, after a housekeeping
    flush."""
    from firedancer_tpu.runtime.store import StoreStage

    pipe = build_leader_pipeline_from_config(
        load_config(None, overrides={"layout": {"bank_stage_count": 1},
                                     "verify": {"batch": 16,
                                                "max_msg_len": 256}}),
        pool_size=48, gen_limit=48, n_payers=N_PAYERS,
        verify_precomputed=True)
    twin = StoreStage("twin", persist_dir=str(tmp_path),
                      trust_membership=True)
    try:
        seen = []
        keep = pipe.store.after_frag
        pipe.store.after_frag = lambda i, meta, payload: (
            seen.append(bytes(payload)), keep(i, meta, payload))[1]
        pipe.run(until_txns=48)
        for payload in seen:
            twin.after_frag(0, None, payload)
        assert twin.sets_by_slot == {} and twin.metrics.get("sets_stored") \
            == pipe.store.metrics.get("sets_stored") > 0
        twin.during_housekeeping()
        back = StoredSlots(str(tmp_path))
        assert sorted(back.sets_by_slot) == sorted(pipe.store.sets_by_slot)
        for slot, sets in pipe.store.sets_by_slot.items():
            assert [(s.fec_set_idx, s.data_shreds) for s in sets] == \
                [(s.fec_set_idx, s.data_shreds)
                 for s in back.sets_by_slot[slot]]
            assert back.entry_batch_bytes(slot) \
                == pipe.store.entry_batch_bytes(slot)
        assert len(_block(back)) == 48
    finally:
        pipe.close()
