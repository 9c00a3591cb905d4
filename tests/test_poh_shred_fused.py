"""Differential suite for the fused poh+shred crash domain (ISSUE 16,
runtime/shred_stage.FusedPohShredStage).

The fusion collapses the poh->shred ring hop: entries feed the shredder
in-process, inside the same run_once sweep that mixed them into the
chain.  The contract is byte-identity — the wire-shred stream of the
fused stage must equal the unfused PohStage -> ring -> ShredStage
topology frame for frame, under free-running PoH and under the slot
clock (sealed slots, missed-slot accounting and window close included),
because fusion is a crash-domain/latency change, NOT a protocol change.
"""

from __future__ import annotations

import hashlib

import pytest

from firedancer_tpu.ops.ref import ed25519_ref as ref
from firedancer_tpu.runtime.poh_stage import PohStage
from firedancer_tpu.runtime.shred_stage import FusedPohShredStage, ShredStage
from firedancer_tpu.runtime.slot_clock import SlotClockCfg
from firedancer_tpu.tango import shm

MS = 1_000_000
_SECRET = hashlib.sha256(b"fused-leader").digest()


def _mb(i: int, n_txn: int = 5) -> bytes:
    """An executed-microblock frame (bank->poh wire format)."""
    out = bytearray()
    out += hashlib.sha256(b"mixin%d" % i).digest()
    out += n_txn.to_bytes(2, "little")
    for k in range(n_txn):
        p = hashlib.sha256(b"txn%d.%d" % (i, k)).digest() * 6  # 192B
        out += len(p).to_bytes(2, "little")
        out += p
    return bytes(out)


class _Topo:
    """Either topology behind one drive interface."""

    def __init__(self, *, fused: bool, clock=None, uid=None):
        uid = uid or shm.fresh_uid()
        tag = "f" if fused else "u"
        self.links = [shm.ShmLink.create(f"tpf_{tag}i_{uid}", depth=256,
                                         mtu=65536, n_fseq=1)]
        lss = shm.ShmLink.create(f"tpf_{tag}s_{uid}", depth=4096, mtu=1232,
                                 n_fseq=1)
        self.links.append(lss)
        self.prod = shm.make_producer(self.links[0])
        signer = lambda root: ref.sign(_SECRET, root)  # noqa: E731
        if fused:
            self.poh = FusedPohShredStage(
                "poh_shred", ins=[shm.make_consumer(self.links[0], lazy=8)],
                outs=[shm.make_producer(lss)], clock=clock,
                signer=signer, secret=_SECRET, shred_slot=1)
            self.shred = self.poh.shred_half
            self.stages = [self.poh]
        else:
            lps = shm.ShmLink.create(f"tpf_up_{uid}", depth=1024, mtu=65536,
                                     n_fseq=1)
            self.links.append(lps)
            self.poh = PohStage(
                "poh", ins=[shm.make_consumer(self.links[0], lazy=8)],
                outs=[shm.make_producer(lps)], clock=clock)
            self.shred = ShredStage(
                "shred", ins=[shm.make_consumer(lps, lazy=8)],
                outs=[shm.make_producer(lss)], signer=signer,
                secret=_SECRET, slot=1)
            self.stages = [self.poh, self.shred]
        self.poh.require_credit = True
        self.poh.entries = []
        self.sink = shm.make_consumer(lss, lazy=4)
        self.shreds: list[tuple[bytes, int]] = []

    def step(self) -> None:
        for s in self.stages:
            s.run_once()

    def drain(self) -> None:
        while True:
            r = self.sink.poll()
            if r in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
                break
            meta, payload = r
            self.shreds.append((bytes(payload), int(meta[1])))

    def finish(self) -> None:
        self.poh.hashes_per_iter = 0  # stop the free-running clock
        for _ in range(50):
            self.step()
        self.shred.flush(block_complete=True)
        for _ in range(10):
            self.step()
        self.drain()

    def close(self) -> None:
        for s in self.stages + [self.shred]:
            s.ins = []
            s.outs = []
        self.prod = None
        self.sink = None
        import gc

        gc.collect()
        for link in self.links:
            link.close()
            link.unlink()


def _run_free(fused: bool):
    topo = _Topo(fused=fused)
    try:
        mbs = [_mb(i) for i in range(40)]
        fed = 0
        for it in range(400):
            # two microblocks per sweep: mixins interleave with ticks
            for _ in range(2):
                if fed < len(mbs) and topo.prod.try_publish(
                        mbs[fed], sig=fed, tsorig=1000 + fed):
                    fed += 1
            topo.step()
            topo.drain()
        assert fed == len(mbs)
        topo.finish()
        rep = {k: topo.poh.metrics.get(k) for k in ("ticks", "mixins")}
        rep.update({k: topo.shred.metrics.get(k) for k in
                    ("entry_batches", "fec_sets", "data_shreds_out",
                     "parity_shreds_out")})
        return topo.shreds, list(topo.poh.entries), rep
    finally:
        topo.close()


def test_free_running_stream_byte_identical():
    s_u, e_u, rep_u = _run_free(fused=False)
    s_f, e_f, rep_f = _run_free(fused=True)
    assert rep_u == rep_f
    assert rep_u["mixins"] == 40
    assert rep_u["data_shreds_out"] > 0
    assert e_u == e_f          # entry triples incl. chain hashes
    assert s_u == s_f          # wire shreds byte-for-byte, same order


def _run_clocked(fused: bool):
    """Scripted virtual time: paced ticks, one forced miss (an abrupt
    2.6-slot jump past the grace), window close at n_slots."""
    t = [0]
    clock = SlotClockCfg(
        slot_ms=100.0, slot0=1, ticks_per_slot=4, n_slots=6, t0_ns=0,
    ).build(now_fn=lambda: t[0])
    topo = _Topo(fused=fused, clock=clock)
    try:
        mbs = [_mb(i, n_txn=3) for i in range(30)]
        fed = 0
        step_ns = 2 * MS
        for it in range(200):
            if it == 80:
                t[0] += 260 * MS  # freeze across 2 boundaries + grace
            else:
                t[0] += step_ns
            if it % 3 == 0 and fed < len(mbs):
                if topo.prod.try_publish(mbs[fed], sig=fed,
                                         tsorig=1000 + fed):
                    fed += 1
            topo.step()
            topo.drain()
        assert fed == len(mbs)
        assert topo.poh.window_closed
        topo.shred.flush(block_complete=True)
        for _ in range(10):
            topo.step()
        topo.drain()
        rep = {k: topo.poh.metrics.get(k) for k in (
            "ticks", "mixins", "slots_sealed", "slot_missed",
            "slot_skipped_ticks")}
        rep["slots_done"] = topo.poh.slots_done()
        return topo.shreds, list(topo.poh.entries), rep
    finally:
        topo.close()


def test_slot_clock_stream_byte_identical_with_miss_accounting():
    s_u, e_u, rep_u = _run_clocked(fused=False)
    s_f, e_f, rep_f = _run_clocked(fused=True)
    assert rep_u == rep_f      # seals, misses, skipped ticks — identical
    assert rep_u["slot_missed"] >= 1       # the forced jump missed slots
    assert rep_u["slots_sealed"] >= 1
    assert rep_u["slots_done"] == 6        # window fully accounted
    assert e_u == e_f
    assert s_u == s_f


def test_fused_leader_pipeline_end_to_end():
    """The fused topology as a whole pipeline: txns land, shreds arrive
    at the store, the block seals — and the fused stage is ONE crash
    domain in the stage list (no poh->shred link exists)."""
    from firedancer_tpu.models.leader import build_leader_pipeline

    pipe = build_leader_pipeline(
        n_verify=1, n_bank=1, pool_size=128, gen_limit=96,
        verify_precomputed=True, fuse_poh_shred=True, keep_sets=True,
    )
    try:
        pipe.run(until_txns=96, max_iters=40_000)
        assert pipe.poh is pipe.stages[-2]  # fused stage, then store
        assert pipe.shred is pipe.poh.shred_half
        assert not any(s.name == "shred" for s in pipe.stages)
        assert pipe.pack.metrics.get("txn_in") >= 96
        assert pipe.banks[0].metrics.get("txn_exec") > 0
        assert pipe.poh.metrics.get("mixins") > 0
        assert pipe.shred.metrics.get("data_shreds_out") > 0
        assert pipe.store.metrics.get("shreds_in") > 0
        res = pipe.seal()
        assert len(res.bank_hash) == 32
    finally:
        pipe.close()


# -- the shred stage follows poh's slot (ISSUE 25) ------------------------------
#
# Under the slot clock every entry frag names its slot (poh_stage.poh_sig)
# and the slot's last tick says so: each slot is a block of its own, with
# a shred index of its own, whether poh sealed the slot or missed it.


def _run_slots(fused: bool, native: bool):
    """The scripted clock of _run_clocked with microblocks large enough
    for a size close inside a slot: slot 1 seals, slot 2 is cut short by
    the jump (missed, with 3 and 4), slots 5 and 6 seal, the window
    closes.  -> (shreds, {slot: entry frames poh published under it})."""
    import os

    from firedancer_tpu.runtime import shred_native as sd
    from firedancer_tpu.runtime.poh_stage import poh_sig_fields

    prev = os.environ.get(sd.ENV_SWITCH)
    os.environ[sd.ENV_SWITCH] = "1" if native else "0"
    t = [0]
    clock = SlotClockCfg(
        slot_ms=100.0, slot0=1, ticks_per_slot=4, n_slots=6, t0_ns=0,
    ).build(now_fn=lambda: t[0])
    topo = None
    try:
        topo = _Topo(fused=fused, clock=clock)
        assert (topo.shred._sweep_client is not None) == native
        by_slot: dict[int, list[bytes]] = {}
        inner = topo.poh.publish

        def publish(out_idx, payload, sig=0, tsorig=0):
            slot, _last = poh_sig_fields(sig)
            by_slot.setdefault(slot, []).append(bytes(payload))
            return inner(out_idx, payload, sig=sig, tsorig=tsorig)

        topo.poh.publish = publish
        mbs = [_mb(i, n_txn=6) for i in range(40)]
        fed = 0
        for it in range(200):
            t[0] += 260 * MS if it == 80 else 2 * MS
            if it % 3 == 0 and fed < len(mbs):
                if topo.prod.try_publish(mbs[fed], sig=fed,
                                         tsorig=1000 + fed):
                    fed += 1
            topo.step()
            topo.drain()
        assert fed == len(mbs) and topo.poh.window_closed
        assert topo.shred.slot == 6
        topo.shred.flush(block_complete=True)   # nothing left: a no-op
        for _ in range(10):
            topo.step()
        topo.drain()
        m = topo.poh.metrics
        assert m.get("slots_sealed") == 3 and m.get("slot_missed") == 3
        return [s for s, _sig in topo.shreds], by_slot
    finally:
        if topo is not None:
            topo.close()
        if prev is None:
            os.environ.pop(sd.ENV_SWITCH, None)
        else:
            os.environ[sd.ENV_SWITCH] = prev


_SLOT_RUNS: dict = {}


def _slot_run(fused: bool, native: bool):
    from firedancer_tpu.runtime import shred_native as sd

    if native and not sd.available():
        pytest.skip("native shredder unavailable")
    key = (fused, native)
    if key not in _SLOT_RUNS:
        _SLOT_RUNS[key] = _run_slots(fused, native)
    return _SLOT_RUNS[key]


@pytest.mark.parametrize("native", [True, False], ids=["native", "python"])
@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_each_slot_is_a_block_of_its_own(fused, native):
    from firedancer_tpu.protocol import shred as fs
    from firedancer_tpu.runtime.shred_stage import deshred_entry_batch
    from firedancer_tpu.runtime.store import StoreStage

    shreds, by_slot = _slot_run(fused, native)
    # sealed 1, missed 2 (3 and 4 with it: nothing was produced), sealed 5, 6
    assert sorted(by_slot) == [1, 2, 5, 6]
    store = StoreStage("store", trust_membership=True)
    for buf in shreds:
        store.after_frag(0, None, buf)
    assert sorted(store.sets_by_slot) == [1, 2, 5, 6]
    parent = 0
    n_sets = 0
    for slot in sorted(by_slot):
        # every slot reassembles to exactly the entries poh gave it
        frames = deshred_entry_batch(store.entry_batch_bytes(slot))
        assert frames == by_slot[slot]
        sets = sorted(store.sets_by_slot[slot], key=lambda s: s.fec_set_idx)
        n_sets += len(sets)
        data = [fs.parse(b) for st in sets for b in st.data_shreds]
        assert all(d.slot == slot for d in data)
        # the shred index restarts with the slot
        assert [d.idx for d in data] == list(range(len(data)))
        assert sets[0].fec_set_idx == 0
        # the block's last data shred, and only it, says block-complete
        done = [bool(d.flags & fs.DATA_FLAG_SLOT_COMPLETE) for d in data]
        assert done == [False] * (len(data) - 1) + [True]
        # the parent is the block left behind
        assert {d.parent_off for d in data} == {slot - parent}
        parent = slot
    assert len(by_slot[1]) > 16 and n_sets > 4     # a size close happened


def test_slot_follow_unfused_fused_native_python_agree():
    runs = [_slot_run(f, n) for f in (False, True) for n in (True, False)]
    for shreds, by_slot in runs[1:]:
        assert by_slot == runs[0][1]
        assert shreds == runs[0][0]


def test_poh_sig_names_the_slot_only_under_the_clock():
    from firedancer_tpu.runtime.poh_stage import (
        POH_SIG_SLOT, poh_sig, poh_sig_fields,
    )

    assert poh_sig_fields(12345) == (None, False)      # a bare hashcnt
    s = poh_sig(7, 0x1234567, block_complete=True)
    assert s & POH_SIG_SLOT and s < 1 << 64
    assert poh_sig_fields(s) == (7, True)
    assert poh_sig_fields(poh_sig(300_000_000, 9)) == (300_000_000, False)
    # distinct within a ring depth: consecutive hashcnts never collide
    assert len({poh_sig(7, h) for h in range(100_000, 104_096)}) == 4096
    # free-running poh keeps hashcnt as the sig, and shred stays put
    topo = _Topo(fused=False)
    try:
        sigs = []
        inner = topo.poh.publish
        topo.poh.publish = lambda o, p, sig=0, tsorig=0: (
            sigs.append(sig), inner(o, p, sig=sig, tsorig=tsorig))[1]
        for _ in range(40):
            topo.step()
        assert sigs and all(not s & POH_SIG_SLOT for s in sigs)
        assert topo.shred.slot == 1
    finally:
        topo.close()
