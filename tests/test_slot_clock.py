"""Slot-clock plane: deadline geometry, paced PoH sealing, the missed-
slot outcome, pack's deadline block close + carryover + load shedding,
and the compressed-cadence cooperative pipeline run (the acceptance
surface of ISSUE 14: every slot seals at its deadline with bounded
jitter, the unscheduled tail carries over with zero loss, an induced
overrun yields slot_missed + clean continuation)."""

import time

import pytest

from firedancer_tpu.runtime.slot_clock import (
    SlotClock,
    SlotClockCfg,
    resolve_clock,
)
from firedancer_tpu.tango import shm
from firedancer_tpu.tango.rings import MCache
from firedancer_tpu.utils import metrics as fm

MS = 1_000_000  # ns


def vclock(t, **kw):
    """A SlotClock over fully virtual time: t is a 1-element list of ns."""
    kw.setdefault("slot_ms", 100.0)
    kw.setdefault("slot0", 1)
    kw.setdefault("ticks_per_slot", 4)
    kw.setdefault("miss_grace_frac", 0.25)
    cfg = SlotClockCfg(t0_ns=0, **kw)
    return SlotClock(cfg, now_fn=lambda: t[0])


# -- geometry -----------------------------------------------------------------


def test_slot_clock_geometry():
    t = [0]
    c = vclock(t, n_slots=5)
    assert c.slot_at(0) == 1
    assert c.slot_at(99 * MS) == 1
    assert c.slot_at(100 * MS) == 2
    assert c.slot_at(450 * MS) == 5
    assert c.start_of(3) == 200 * MS
    assert c.deadline_of(3) == 300 * MS
    assert c.remaining_ns(1, 40 * MS) == 60 * MS
    # ticks 1..4 of slot 1 due at 25/50/75/100ms
    assert c.ticks_due(1, 0) == 0
    assert c.ticks_due(1, 24 * MS) == 0
    assert c.ticks_due(1, 25 * MS) == 1
    assert c.ticks_due(1, 99 * MS) == 3
    assert c.ticks_due(1, 500 * MS) == 4  # clamped
    assert c.tick_deadline(2, 1) == 125 * MS
    # grace: missed only past deadline + 25ms
    assert not c.missed(1, 100 * MS)
    assert not c.missed(1, 125 * MS)
    assert c.missed(1, 126 * MS)
    # window: 5 slots -> handoff at 500ms
    assert c.last_slot() == 5
    assert c.window_end_ns() == 500 * MS
    assert c.in_window(5) and not c.in_window(6)
    assert not c.window_done(499 * MS) and c.window_done(500 * MS)


def test_slot_clock_pre_anchor_clamps_to_slot0():
    t = [0]
    cfg = SlotClockCfg(slot_ms=100.0, t0_ns=50 * MS)
    c = SlotClock(cfg, now_fn=lambda: t[0])
    # the boot-grace period belongs to the first slot
    assert c.slot_at(0) == cfg.slot0
    assert c.ticks_due(cfg.slot0, 0) == 0


def test_cfg_anchoring_idempotent_and_picklable():
    import pickle

    cfg = SlotClockCfg(slot_ms=50.0, n_slots=3)
    a = cfg.anchored(1.0, now_ns=1000)
    assert a.t0_ns == 1000 + int(1e9)
    assert a.anchored(5.0) is a  # already anchored: no re-anchor
    assert pickle.loads(pickle.dumps(a)) == a
    with pytest.raises(TypeError):
        resolve_clock(object())
    assert resolve_clock(None) is None


def test_slot_clock_rejects_degenerate_geometry():
    with pytest.raises(ValueError):
        SlotClock(SlotClockCfg(slot_ms=0.0, t0_ns=0))
    with pytest.raises(ValueError):
        SlotClock(SlotClockCfg(ticks_per_slot=0, t0_ns=0))


# -- paced poh ----------------------------------------------------------------


def make_poh(t, **kw):
    from firedancer_tpu.runtime.poh_stage import PohStage

    clock = vclock(t, **kw)
    uid = shm.fresh_uid("tsc")
    link = shm.ShmLink.create(f"fdtpu_ps_{uid}", depth=256, mtu=65536)
    poh = PohStage("poh", outs=[shm.Producer(link)], clock=clock)
    poh.require_credit = True
    return poh, link, clock


def drive(poh, t, upto_ms, step_ms=5, iters=30):
    for ms in range(int(t[0] / MS), upto_ms + 1, step_ms):
        t[0] = ms * MS
        for _ in range(iters):
            poh.run_once()


def test_poh_ticks_paced_to_the_deadline():
    t = [0]
    poh, link, clock = make_poh(t, n_slots=2)
    sink = shm.Consumer(link, lazy=4)
    try:
        # halfway through slot 1 exactly 2 of 4 ticks may have landed
        drive(poh, t, 50)
        assert poh.metrics.get("ticks") == 2
        # a stalled wall clock emits nothing no matter how hot the loop
        for _ in range(2000):
            poh.run_once()
        assert poh.metrics.get("ticks") == 2
        drive(poh, t, 99)
        assert poh.metrics.get("ticks") == 3  # final tick seals AT 100ms
        drive(poh, t, 100)
        assert poh.metrics.get("ticks") == 4
        assert poh.metrics.get("slots_sealed") == 1
        assert poh.slot == 2
    finally:
        del sink
        link.close()
        link.unlink()


def test_poh_seal_regardless_of_pending_load_and_window_close():
    t = [0]
    poh, link, clock = make_poh(t, n_slots=2)
    try:
        # jump straight to the deadline: every tick of slot 1 must land
        # NOW (sealed at the boundary regardless of how it was paced)
        t[0] = 100 * MS
        for _ in range(50):
            poh.run_once()
        assert poh.metrics.get("slots_sealed") == 1
        assert poh.metrics.get("ticks") == 4
        # slot 2 seals at its own deadline and the window closes: the
        # handoff fires on the schedule, not on drain
        drive(poh, t, 200)
        assert poh.metrics.get("slots_sealed") == 2
        assert poh.window_closed
        assert poh.slots_done() == 2
        # past the window nothing ever ticks again
        drive(poh, t, 400)
        assert poh.metrics.get("ticks") == 8
    finally:
        link.close()
        link.unlink()


def test_poh_missed_slot_is_a_value_not_a_hang():
    t = [0]
    poh, link, clock = make_poh(t, n_slots=6)
    try:
        drive(poh, t, 100)  # slot 1 seals clean
        assert poh.metrics.get("slots_sealed") == 1
        # freeze across the boundaries of slots 2 and 3 (plus grace)
        t[0] = 330 * MS
        for _ in range(50):
            poh.run_once()
        assert poh.metrics.get("slot_missed") == 2
        assert poh.metrics.get("slot_skipped_ticks") == 8
        assert poh.slot == 4  # clean continuation at the scheduled slot
        # the flight ring carries one slot_missed record per slot
        missed_evs = [r for r in poh.recorder.records()
                      if r[1] == fm.EV_SLOT_MISSED]
        assert [r[2] for r in missed_evs] == [2, 3]
        # the rest of the window seals normally
        drive(poh, t, 600)
        assert poh.metrics.get("slots_sealed") == 4
        assert poh.window_closed
        assert poh.slots_done() == 6
    finally:
        link.close()
        link.unlink()


def test_poh_backpressure_past_grace_becomes_a_miss():
    """Credit starvation at the boundary: the consumer never drains, the
    ring fills, poh cannot land the final ticks — past the grace that is
    a MISSED slot and the stage moves on (never a hang, never a drop of
    the chain's continuity)."""
    from firedancer_tpu.runtime.poh_stage import PohStage

    t = [0]
    clock = vclock(t, n_slots=3)
    uid = shm.fresh_uid("tbp")
    link = shm.ShmLink.create(f"fdtpu_ps_{uid}", depth=4, mtu=65536)
    poh = PohStage("poh", outs=[shm.Producer(link)], clock=clock)
    poh.require_credit = True
    try:
        # nobody consumes: 4 credits total, slot 1's 4 ticks exhaust them
        drive(poh, t, 100)
        assert poh.metrics.get("slots_sealed") == 1
        # slot 2's ticks cannot publish (ring full); past grace -> miss
        drive(poh, t, 230)
        assert poh.metrics.get("slot_missed") >= 1
        hashcnt_at_miss = poh.chain.hashcnt
        # a consumer appears; the NEXT slot proceeds from the live chain
        sink = shm.Consumer(link, lazy=1)
        while isinstance(sink.poll(), tuple):
            pass
        for p in poh.outs:
            p.refresh_credits()
        drive(poh, t, 300)
        assert poh.slots_done() == 3
        assert poh.chain.hashcnt > hashcnt_at_miss
    finally:
        link.close()
        link.unlink()



def test_poh_entries_name_their_slot_and_the_last_tick_closes_it():
    """Under the clock every entry frag's sig is poh_sig: the slot it
    belongs to, and block-complete on a sealed slot's last tick only — a
    missed slot never gets one."""
    from firedancer_tpu.runtime.poh_stage import poh_sig_fields

    t = [0]
    poh, link, clock = make_poh(t, n_slots=None)
    sink = shm.Consumer(link, lazy=4)
    try:
        drive(poh, t, 130)              # slot 1 sealed, into slot 2
        t[0] = 380 * MS                 # 2 and 3 pass unsealed: missed
        for _ in range(30):
            poh.run_once()
        drive(poh, t, 560)              # 4 and 5 sealed, into slot 6
        got = []
        while True:
            r = sink.poll()
            if r in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
                break
            got.append(poh_sig_fields(int(r[0][MCache.COL_SIG])))
        assert poh.metrics.get("slots_sealed") == 3
        assert poh.metrics.get("slot_missed") == 2
        slots = [sl for sl, _ in got]
        assert slots == sorted(slots)
        by_slot: dict = {}
        for sl, last in got:
            by_slot.setdefault(sl, []).append(last)
        assert sorted(by_slot) == [1, 2, 4, 5, 6]
        # four ticks a slot; only the sealed slots' fourth says complete
        for sealed in (1, 4, 5):
            assert by_slot[sealed] == [False, False, False, True]
        assert not any(by_slot[2]) and len(by_slot[2]) < 4
        assert by_slot[6] == [False, False]
    finally:
        poh.outs = []
        link.close()
        link.unlink()


# -- pack: deadline close, carryover, shedding --------------------------------


def _mk_pack_stage(t, clock_kw=None, **kw):
    from firedancer_tpu.runtime.pack_stage import PackStage

    clock = vclock(t, **(clock_kw or {}))
    uid = shm.fresh_uid("tpk")
    l_in = shm.ShmLink.create(f"fdtpu_pi_{uid}", depth=256, mtu=4096)
    l_out = shm.ShmLink.create(f"fdtpu_po_{uid}", depth=64, mtu=65536)
    l_done = shm.ShmLink.create(f"fdtpu_pd_{uid}", depth=64, mtu=64)
    stage = PackStage(
        "pack",
        ins=[shm.Consumer(l_in, lazy=8), shm.Consumer(l_done, lazy=8)],
        outs=[shm.Producer(l_out)],
        bank_cnt=1,
        clock=clock,
        **kw,
    )
    return stage, (l_in, l_out, l_done), clock


def _feed_txns(stage, l_in, n, seed=b"carry"):
    from firedancer_tpu.protocol import txn as ft
    from firedancer_tpu.runtime.benchg import gen_transfer_pool
    from firedancer_tpu.runtime.verify import encode_verified

    prod = shm.Producer(l_in)
    pool = gen_transfer_pool(n, seed=seed)
    for i, payload in enumerate(pool):
        desc = ft.txn_parse(payload)
        assert prod.try_publish(encode_verified(payload, desc), sig=i)
    for _ in range(n + 16):
        stage.run_once()


def test_pack_deadline_close_carries_tail_across_slots():
    t = [0]
    stage, links, clock = _mk_pack_stage(
        t, clock_kw={"slot_ms": 100.0},
        min_pending=10**9, mb_deadline_s=10**9, adaptive=False,
    )
    l_in, l_out, l_done = links
    try:
        _feed_txns(stage, l_in, 24)
        assert stage._pending_cnt() == 24
        # mid-slot: the absurd min_pending blocks scheduling entirely
        t[0] = 50 * MS
        for _ in range(20):
            stage.run_once()
        assert stage.metrics.get("microblocks") == 0
        # the slot's final stretch (last 25%): deadline-aware close
        # schedules aggressively — no accumulation games at the boundary
        t[0] = 80 * MS
        for _ in range(20):
            stage.run_once()
        assert stage.metrics.get("microblocks") >= 1
        first_slot_scheduled = stage.metrics.get("txn_scheduled")
        assert first_slot_scheduled > 0
        # cross the boundary: block accounting resets, NOTHING is lost —
        # the unscheduled tail is simply still pooled
        t[0] = 101 * MS
        for _ in range(5):
            stage.run_once()
        assert stage.metrics.get("blocks_closed") == 1
        assert stage.metrics.get("txn_dropped") == 0
        assert (stage._pending_cnt() + first_slot_scheduled) == 24
    finally:
        for link in links:
            link.close()
            link.unlink()


def test_pack_load_shed_at_the_deadline_python_lane():
    t = [0]
    stage, links, clock = _mk_pack_stage(
        t, clock_kw={"slot_ms": 100.0},
        min_pending=10**9, mb_deadline_s=10**9, adaptive=False,
        shed_keep=8,
    )
    l_in, l_out, l_done = links
    try:
        _feed_txns(stage, l_in, 24)
        assert stage._pending_cnt() == 24
        t[0] = 50 * MS  # mid-slot: no shedding yet
        for _ in range(5):
            stage.run_once()
        assert stage.metrics.get("txn_shed") == 0
        t[0] = 80 * MS  # the clock says the slot can't drain 24: shed
        stage.run_once()
        assert stage.metrics.get("txn_shed") == 16
        # the 8 survivors are either still pooled or already scheduled
        # by the same deadline-close posture — never lost
        assert (stage._pending_cnt()
                + stage.metrics.get("txn_scheduled")) == 8
        # shed events ride the flight ring
        assert any(r[1] == fm.EV_SLOT_SHED
                   for r in stage.recorder.records())
    finally:
        for link in links:
            link.close()
            link.unlink()


def test_pack_shed_drops_lowest_priority_first_and_spares_votes():
    from firedancer_tpu.pack.scheduler import Pack
    from firedancer_tpu.protocol import txn as ft
    from firedancer_tpu.runtime.benchg import gen_transfer_pool

    pack = Pack(bank_cnt=1, depth=64)
    pool = gen_transfer_pool(12, seed=b"shed")
    descs = []
    for payload in pool:
        d = ft.txn_parse(payload)
        assert pack.insert(payload, d)
        descs.append((payload, d))
    before = pack.pending_cnt()
    # the shed order is the pool tail: capture it, then shed
    tail = [o.first_sig() for o in pack._pending[-4:]]
    assert pack.shed_lowest(4) == 4
    assert pack.pending_cnt() == before - 4
    for sig in tail:
        assert sig not in pack._sigs
    # over-shedding is clamped, never an error
    assert pack.shed_lowest(10**6) == before - 4
    assert pack.pending_cnt() == 0


def test_native_pack_shed_parity():
    from firedancer_tpu.pack import scheduler_native as sn

    if not sn.available():
        pytest.skip("native pack .so unavailable")
    from firedancer_tpu.pack.scheduler import Pack
    from firedancer_tpu.protocol import txn as ft
    from firedancer_tpu.runtime.benchg import gen_transfer_pool

    py = Pack(bank_cnt=1, depth=64)
    nat = sn.NativePack(bank_cnt=1, depth=64)
    pool = gen_transfer_pool(16, seed=b"shednat")
    from firedancer_tpu.runtime.verify import encode_verified

    entries = []
    for i, payload in enumerate(pool):
        d = ft.txn_parse(payload)
        assert py.insert(payload, d)
        entries.append((encode_verified(payload, d), i + 1, 0))
    codes = nat.insert_burst(entries)
    assert codes == bytes([sn.INS_OK]) * len(entries)
    assert nat.pending_cnt() == py.pending_cnt() == 16
    assert nat.shed_lowest(5) == py.shed_lowest(5) == 5
    assert nat.pending_cnt() == py.pending_cnt() == 11
    # the survivors schedule identically: shed trimmed the same tail
    mb_py = py.schedule_next_microblock(0)
    res_nat = nat.schedule(0, mb_seq=0)
    assert (res_nat is None) == (not mb_py)
    if mb_py:
        assert res_nat[1] == len(mb_py)
    nat.close()


# -- the compressed-cadence pipeline run (acceptance) -------------------------


@pytest.mark.slow  # ~24 s wall (real compressed clock + pipeline build);
# the cadence invariants each have focused tier-1 tests above, and the
# fused-stage clock run (test_poh_shred_fused) keeps an e2e clock test
# in tier-1
def test_leader_pipeline_under_compressed_cadence_zero_loss():
    """The cooperative leader pipeline against a real (compressed) wall
    clock: every slot seals at its deadline with bounded jitter, txns
    keep landing across the boundaries (the carryover contract — zero
    loss, regression-diffed against the clock-off run), and the window
    closes on the schedule."""
    from firedancer_tpu.models.leader import build_leader_pipeline

    N = 96
    n_slots = 4
    cfg = SlotClockCfg(slot_ms=150.0, slot0=1, ticks_per_slot=4,
                       n_slots=n_slots, miss_grace_frac=0.3)

    def run(clocked: bool):
        pipe = build_leader_pipeline(
            n_verify=1, n_bank=2, pool_size=N, gen_limit=N, batch=32,
            verify_precomputed=True,
            slot_clock=cfg if clocked else None,
        )
        try:
            if clocked:
                deadline = time.monotonic() + 30
                while (not pipe.poh.window_closed
                       and time.monotonic() < deadline):
                    for s in pipe.stages:
                        s.run_once()
                # drain the committed tail through shred/store
                pipe.finish()
            else:
                pipe.run(until_txns=N, max_iters=400_000)
            report = {
                "landed": sum(b.metrics.get("txn_exec")
                              for b in pipe.banks),
                "rejected": sum(b.metrics.get("txn_rejected")
                                for b in pipe.banks),
                "dropped": pipe.pack.metrics.get("txn_dropped"),
                "shed": pipe.pack.metrics.get("txn_shed"),
            }
            poh_m = pipe.poh.metrics
            stats = {
                "sealed": poh_m.get("slots_sealed"),
                "missed": poh_m.get("slot_missed"),
                "seal_p99_ns": poh_m.quantile("slot_seal_lag_ns", 0.99),
                "blocks_closed": pipe.pack.metrics.get("blocks_closed"),
            }
            return report, stats
        finally:
            pipe.close()

    clocked, cstats = run(clocked=True)
    # cadence: every slot sealed AT its deadline, jitter inside grace
    assert cstats["sealed"] == n_slots, cstats
    assert cstats["missed"] == 0, cstats
    grace_ns = cfg.miss_grace_frac * cfg.slot_ms * 1e6
    assert 0 < cstats["seal_p99_ns"] <= grace_ns, cstats
    assert cstats["blocks_closed"] >= 1, cstats  # tail carried >= once
    # zero loss under the clock
    assert clocked["dropped"] == 0 and clocked["shed"] == 0
    # regression diff vs the clock-off stream: same landed/rejected split
    free, _ = run(clocked=False)
    assert clocked["landed"] == free["landed"] == N
    assert clocked["rejected"] == free["rejected"] == 0


# -- the shred stage follows poh's slot through the whole pipeline (ISSUE 25) --


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_leader_pipeline_stores_each_slot_under_its_own_slot(fused):
    """The cooperative leader pipeline on scripted time, 8 slots with one
    jump that misses slot 3: what the banks commit in a slot is stored as
    that slot's block — its shred index from 0, block-complete on its
    last data shred, its parent the block before — and every stored slot
    reassembles."""
    from firedancer_tpu.models.leader import build_leader_pipeline
    from firedancer_tpu.protocol import shred as fs
    from firedancer_tpu.runtime.shred_stage import deshred_entry_batch

    N = 400
    t = [0]
    clock = SlotClockCfg(slot_ms=100.0, slot0=1, ticks_per_slot=4,
                         n_slots=8, t0_ns=0).build(now_fn=lambda: t[0])
    pipe = build_leader_pipeline(
        n_verify=1, n_bank=2, pool_size=N, gen_limit=N, batch=32,
        verify_precomputed=True, slot_clock=clock, keep_sets=False,
        fuse_poh_shred=fused)
    try:
        for it in range(10_000):
            t[0] += 154 * MS if it == 40 else 4 * MS
            for s in pipe.stages:
                s.run_once()
            if pipe.poh.window_closed:
                break
        assert pipe.poh.window_closed
        pipe.finish()
        assert pipe.poh.metrics.get("slots_sealed") >= 5
        assert pipe.poh.metrics.get("slot_missed") >= 1
        assert sum(b.metrics.get("txn_exec") for b in pipe.banks) == N
        store = pipe.store
        assert store.metrics.get("sets_stored") \
            == pipe.shred.metrics.get("fec_sets") > 0
        stored = sorted(store.sets_by_slot)
        assert 3 not in stored and len(stored) >= 6
        assert pipe.shred.slot == stored[-1] == 8
        n_txn = {}
        parent = 0
        for slot in stored:
            frames = deshred_entry_batch(store.entry_batch_bytes(slot))
            n_txn[slot] = sum(int.from_bytes(f[36:38], "little")
                              for f in frames)
            sets = sorted(store.sets_by_slot[slot],
                          key=lambda st: st.fec_set_idx)
            data = [fs.parse(b) for st in sets for b in st.data_shreds]
            assert all(d.slot == slot for d in data)
            assert [d.idx for d in data] == list(range(len(data)))
            done = [bool(d.flags & fs.DATA_FLAG_SLOT_COMPLETE) for d in data]
            assert done == [False] * (len(data) - 1) + [True]
            assert {d.parent_off for d in data} == {slot - parent}
            parent = slot
        assert sum(n_txn.values()) == N
        assert sum(1 for v in n_txn.values() if v) >= 3   # not one block
    finally:
        pipe.close()
