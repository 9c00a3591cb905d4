"""Differential tests: the native pack scheduler + fused dedup lane vs
the Python lane.

The contract (ISSUE 9): across seeded adversarial workloads —
conflicting writers, ALT lock collisions, vote floods, limit-boundary
costs, duplicate signatures, malformed compute-budget instructions —
the native lane (native/fd_pack.cpp via pack/scheduler_native.py) must
emit BYTE-IDENTICAL microblock frames, make identical eviction
decisions, keep identical end_block accounting, and drop the identical
dedup set as pack/scheduler.Pack behind DedupStage+PackStage.

The whole module SKIPS (never fails) when the native lane is
unavailable (no toolchain, .so deleted, or FDTPU_NATIVE_PACK=0).
"""

from __future__ import annotations

import hashlib
import random

import pytest

from firedancer_tpu.pack import scheduler_native as sn

if not sn.available():  # pragma: no cover - toolchain-less host
    pytest.skip("native pack lane unavailable", allow_module_level=True)

from firedancer_tpu.ops.ref import ed25519_ref as ref
from firedancer_tpu.pack import cost as fc
from firedancer_tpu.pack.scheduler import BlockLimits, Pack
from firedancer_tpu.protocol import txn as ft
from firedancer_tpu.runtime.verify import encode_verified, sig_tag

BH = hashlib.sha256(b"pack-native-bh").digest()


def _keypair(tag: bytes):
    s = hashlib.sha256(tag).digest()
    return s, ref.public_key(s)


PAYERS = [_keypair(b"pnp%d" % i) for i in range(8)]
DESTS = [hashlib.sha256(b"pnd%d" % i).digest() for i in range(5)]
TABLES = [hashlib.sha256(b"lut%d" % i).digest() for i in range(3)]
VOTE_ACCTS = [hashlib.sha256(b"pnv%d" % i).digest() for i in range(3)]


def _sign_txn(sec, msg):
    return ft.txn_assemble([ref.sign(sec, msg)], msg)


def _transfer(rng, *, payer=None, dest=None, cb=(), lamports=None,
              extra_ro=0):
    sec, pub = PAYERS[payer if payer is not None else rng.randrange(8)]
    d = DESTS[dest if dest is not None else rng.randrange(5)]
    accts = [pub, d, ft.SYSTEM_PROGRAM]
    instrs = []
    if cb:
        accts.append(fc.COMPUTE_BUDGET_PROGRAM)
        instrs += [ft.InstrSpec(program_id=3, accounts=b"", data=x)
                   for x in cb]
    instrs.append(ft.InstrSpec(
        program_id=2, accounts=bytes([0, 1]),
        data=(2).to_bytes(4, "little")
        + (lamports if lamports is not None
           else rng.randrange(1, 1000)).to_bytes(8, "little")))
    msg = ft.message_build(
        version=ft.VLEGACY, signature_cnt=1, readonly_signed_cnt=0,
        readonly_unsigned_cnt=len(accts) - 2, acct_addrs=accts,
        recent_blockhash=BH, instrs=instrs)
    return _sign_txn(sec, msg)


def _lut_txn(rng, table_i):
    """v0 txn loading from a shared lookup table: the table ADDRESS
    write-locks, so two of these serialize (ALT lock collision)."""
    sec, pub = PAYERS[rng.randrange(8)]
    accts = [pub, ft.SYSTEM_PROGRAM]
    msg = ft.message_build(
        version=ft.V0, signature_cnt=1, readonly_signed_cnt=0,
        readonly_unsigned_cnt=1, acct_addrs=accts, recent_blockhash=BH,
        instrs=[ft.InstrSpec(program_id=1, accounts=b"", data=b"\x09")],
        luts=[ft.LutSpec(table_addr=TABLES[table_i],
                         writable=bytes([rng.randrange(4)]),
                         readonly=b"")])
    return _sign_txn(sec, msg)


def _vote(rng, i):
    sec, _pub = PAYERS[i % 8]
    va = VOTE_ACCTS[i % len(VOTE_ACCTS)]
    return ft.vote_txn(sec, va, 100 + i, BH,
                       bank_hash=hashlib.sha256(b"vbh").digest())


def _cb_price(p):
    return (3).to_bytes(1, "little") + p.to_bytes(8, "little")


def _cb_cu(cu):
    return (2).to_bytes(1, "little") + cu.to_bytes(4, "little")


def _workload(rng, n):
    """The adversarial mix; returns payloads (some deliberately equal =
    duplicate signatures)."""
    out = []
    for i in range(n):
        r = rng.random()
        if r < 0.22:
            out.append(_vote(rng, i))
        elif r < 0.35:
            # conflicting writers: a hot destination account
            out.append(_transfer(rng, dest=0))
        elif r < 0.45:
            out.append(_lut_txn(rng, rng.randrange(len(TABLES))))
        elif r < 0.65:
            # priority-fee spread incl. u64-scale prices (rewards must
            # compare exactly, not in floats)
            cb = [_cb_cu(rng.choice([1, 300, 200_000, 1_400_000])),
                  _cb_price(rng.choice([0, 1, 999_999, 10**6, 2**40,
                                        2**63]))]
            out.append(_transfer(rng, cb=cb))
        elif r < 0.72 and out:
            out.append(rng.choice(out))  # duplicate signature
        elif r < 0.78:
            # malformed compute budget: both lanes must DROP it
            bad = rng.choice([
                b"\x02\x01",                       # truncated
                _cb_cu(5) + b"x",                  # wrong size
                (9).to_bytes(1, "little") * 5,     # unknown tag
                (1).to_bytes(1, "little") + (31).to_bytes(4, "little"),
            ])
            out.append(_transfer(rng, cb=[bad]))
        else:
            out.append(_transfer(rng))
    return out


class _Lanes:
    """Drives both lanes through identical op sequences and compares.

    The python side replicates the DedupStage -> PackStage composition:
    the tag goes through a TCache first (duplicates dropped before pack
    sees them), then Pack.insert; the native side does both inside ONE
    fd_pack_insert_burst crossing.
    """

    def __init__(self, *, bank_cnt=3, depth=64, max_txn_per_microblock=9,
                 limits=None, tcache_depth=128):
        from firedancer_tpu.tango.rings import TCache
        from firedancer_tpu.tango.tcache_native import NativeTCache

        self.py = Pack(bank_cnt=bank_cnt, depth=depth,
                       max_txn_per_microblock=max_txn_per_microblock,
                       limits=limits)
        self.nat = sn.NativePack(bank_cnt=bank_cnt, depth=depth,
                                 max_txn_per_microblock=max_txn_per_microblock,
                                 limits=limits)
        self.py_tcache = TCache(tcache_depth)
        self.nat.attach_tcache(NativeTCache(tcache_depth))
        self.bank_cnt = bank_cnt
        self.mb_seq = 0
        self.frames = []
        self.py_drops = []   # (index, reason) of python-lane drops
        self.nat_drops = []

    def insert(self, i, payload):
        t = ft.txn_parse(payload)
        assert t is not None
        frag = encode_verified(payload, t)
        tag = sig_tag(t.signatures(payload)[0])
        # python lane: dedup stage first, then pack
        if self.py_tcache.insert(tag):
            py_ok, py_reason = False, "dup"
        else:
            py_ok = self.py.insert(payload, t)
            py_reason = None if py_ok else "drop"
        code = self.nat.insert_burst([(frag, tag, 7_000 + i)])[0]
        nat_ok = code == sn.INS_OK
        nat_reason = (None if nat_ok
                      else "dup" if code == sn.INS_DUP else "drop")
        assert (py_ok, py_reason) == (nat_ok, nat_reason), (
            i, py_reason, code)
        assert self.stats(self.py) == self.stats(self.nat), i
        if not py_ok:
            self.py_drops.append((i, py_reason))
            self.nat_drops.append((i, nat_reason))

    STATS = ("stat_evicted", "stat_dropped_votes",
             "stat_votes_dropped_regular_pending", "stat_scheduled_votes",
             "stat_conflict_skips")

    def stats(self, lane):
        return {k: getattr(lane, k) for k in self.STATS}

    def schedule(self, bank):
        chosen = self.py.schedule_next_microblock(bank)
        res = self.nat.schedule(bank, mb_seq=self.mb_seq)
        assert self.stats(self.py) == self.stats(self.nat)
        if not chosen:
            assert res is None, ("native scheduled, python did not",
                                 bank, res and res[1])
            return False
        frame = self.mb_seq.to_bytes(4, "little")
        frame += len(chosen).to_bytes(2, "little")
        for o in chosen:
            f = encode_verified(o.payload, o.desc)
            frame += len(f).to_bytes(2, "little") + f
        assert res is not None, ("python scheduled, native did not",
                                 bank, len(chosen))
        assert res[0] == frame, ("frame mismatch", bank)
        assert res[1] == len(chosen)
        assert res[2] == sum(o.cost.total for o in chosen)
        self.frames.append(frame)
        self.mb_seq += 1
        return True

    def done(self, bank):
        self.py.microblock_done(bank)
        self.nat.microblock_done(bank)

    def end_block(self):
        self.py.end_block()
        self.nat.end_block()
        self.check_accounting()

    def check_accounting(self):
        assert (
            self.py.cost_used,
            self.py.vote_cost_used,
            self.py.data_bytes_used,
        ) == self.nat.block_state()
        assert self.py.pending_cnt() == self.nat.pending_cnt()


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_randomized_streams_identical(seed):
    """The headline differential: a seeded adversarial workload with
    interleaved schedule/done/end_block ops produces byte-identical
    microblock streams, identical drops, identical accounting."""
    rng = random.Random(seed)
    lanes = _Lanes(depth=48, max_txn_per_microblock=7)
    for i, p in enumerate(_workload(rng, 300)):
        lanes.insert(i, p)
        r = rng.random()
        if r < 0.35:
            lanes.schedule(rng.randrange(lanes.bank_cnt))
        if r < 0.25:
            lanes.done(rng.randrange(lanes.bank_cnt))
        if rng.random() < 0.03:
            lanes.end_block()
    # drain everything schedulable
    for _ in range(200):
        progressed = False
        for b in range(lanes.bank_cnt):
            progressed |= lanes.schedule(b)
            lanes.done(b)
        if not progressed:
            break
    lanes.check_accounting()
    assert lanes.frames, "workload scheduled nothing"
    assert lanes.py_drops == lanes.nat_drops
    assert any(r == "dup" for _, r in lanes.py_drops), "no dedup coverage"


def test_limit_boundary_costs():
    """Tight block limits: every limit (total, vote, per-writer, data
    bytes) binds mid-stream and both lanes agree on the exact txn where
    it trips — including within-microblock accumulation."""
    rng = random.Random(99)
    limits = BlockLimits(
        max_cost_per_block=40_000,
        max_vote_cost_per_block=9_000,
        max_write_cost_per_acct=8_000,
        max_data_bytes_per_block=6_000,
    )
    lanes = _Lanes(bank_cnt=2, depth=64, max_txn_per_microblock=31,
                   limits=limits)
    for i, p in enumerate(_workload(rng, 150)):
        lanes.insert(i, p)
        if rng.random() < 0.3:
            lanes.schedule(rng.randrange(2))
        if rng.random() < 0.2:
            lanes.done(rng.randrange(2))
        if rng.random() < 0.1:
            lanes.end_block()
    lanes.check_accounting()


def test_eviction_parity_small_pool():
    """depth=8 pool under a 150-txn flood: the delete-worst rule (both
    pools' tails considered, ratio-only compare, ties keep the
    incumbent) decides identically in both lanes."""
    rng = random.Random(5)
    lanes = _Lanes(bank_cnt=2, depth=8)
    for i, p in enumerate(_workload(rng, 150)):
        lanes.insert(i, p)
    lanes.check_accounting()
    # what remains schedules identically
    while lanes.schedule(0):
        lanes.done(0)
    lanes.check_accounting()


def _is_vote(o):
    return o.cost.is_simple_vote


def _distinct_vote(i):
    """A vote of validator i: its own payer and vote account, so votes
    never conflict with each other (one a validator a slot)."""
    sec, _pub = _keypair(b"pnvv%d" % i)
    return ft.vote_txn(sec, hashlib.sha256(b"pnva%d" % i).digest(),
                       100 + i, BH, bank_hash=hashlib.sha256(b"vbh").digest())


def _distinct_transfer(i, cb=()):
    sec, pub = _keypair(b"pntp%d" % i)
    accts = [pub, hashlib.sha256(b"pntd%d" % i).digest(), ft.SYSTEM_PROGRAM]
    instrs = []
    if cb:
        accts.append(fc.COMPUTE_BUDGET_PROGRAM)
        instrs += [ft.InstrSpec(program_id=3, accounts=b"", data=x)
                   for x in cb]
    instrs.append(ft.InstrSpec(program_id=2, accounts=bytes([0, 1]),
                               data=(2).to_bytes(4, "little")
                               + (1 + i).to_bytes(8, "little")))
    msg = ft.message_build(
        version=ft.VLEGACY, signature_cnt=1, readonly_signed_cnt=0,
        readonly_unsigned_cnt=len(accts) - 2, acct_addrs=accts,
        recent_blockhash=BH, instrs=instrs)
    return _sign_txn(sec, msg)


def test_vote_flood_schedules_with_no_regular_txn():
    """An all-vote flood lands in the vote pool and schedules by the
    one rule, up to the votes' share of a microblock, identically in
    both lanes."""
    lanes = _Lanes(bank_cnt=2, depth=64, max_txn_per_microblock=8)
    for i in range(40):
        lanes.insert(i, _distinct_vote(i))
    assert lanes.schedule(0)
    assert lanes.frames[-1][4:6] == (6).to_bytes(2, "little")  # 3/4 of 8
    assert lanes.py.stat_scheduled_votes == 6
    lanes.check_accounting()


@pytest.mark.parametrize("max_txn,n_votes,n_regular,want", [
    (8, 20, 20, (6, 2)),     # votes take 3/4 of the slots, regular the rest
    (8, 3, 20, (3, 5)),      # fewer votes than their share: regular fills
    (8, 20, 0, (6, 0)),      # no regular: the share still caps votes
    (8, 0, 20, (0, 8)),      # no vote: the rule is silent
    (1, 5, 5, (1, 0)),       # a one-slot microblock still takes a vote
    (31, 40, 40, (23, 8)),   # the pipeline's default width
])
def test_reservation_votes_first_then_regular(max_txn, n_votes, n_regular,
                                              want):
    """Each microblock takes votes first, up to VOTE_FRACTION of its
    slots, then fills from the regular pool — though every regular
    txn here outranks every vote by fee/cost.  Both lanes, one frame."""
    lanes = _Lanes(bank_cnt=1, depth=256, max_txn_per_microblock=max_txn)
    price = [_cb_cu(20_000), _cb_price(1_000_000)]
    k = 0
    for i in range(max(n_votes, n_regular)):
        if i < n_regular:
            lanes.insert(k, _distinct_transfer(i, cb=price)); k += 1
        if i < n_votes:
            lanes.insert(k, _distinct_vote(i)); k += 1
    chosen = lanes.py.schedule_next_microblock(0)
    got = [_is_vote(o) for o in chosen]
    assert (sum(got), len(got) - sum(got)) == want
    assert got == sorted(got, reverse=True)           # votes lead the frame
    res = lanes.nat.schedule(0, mb_seq=0)
    assert res is not None and res[1] == len(chosen)
    assert res[0][6:] == b"".join(
        len(f).to_bytes(2, "little") + f for f in
        (encode_verified(o.payload, o.desc) for o in chosen))
    assert lanes.stats(lanes.py) == lanes.stats(lanes.nat)
    assert lanes.py.stat_scheduled_votes == want[0]
    lanes.check_accounting()


def test_reservation_vote_cost_share_of_what_the_block_has_left():
    """Votes stop at 3/4 of the cost the block has left, before their
    slot share is used up; the regular pool takes the rest."""
    vote_cost = fc.compute_cost(
        _distinct_vote(0), ft.txn_parse(_distinct_vote(0))).total
    limits = BlockLimits(max_cost_per_block=4 * vote_cost)
    lanes = _Lanes(bank_cnt=1, depth=64, max_txn_per_microblock=31,
                   limits=limits)
    for i in range(6):
        lanes.insert(2 * i, _distinct_vote(i))
        lanes.insert(2 * i + 1, _distinct_transfer(i))
    assert lanes.schedule(0)
    n = int.from_bytes(lanes.frames[-1][4:6], "little")
    assert lanes.py.stat_scheduled_votes == 3         # 3/4 of four votes' cost
    assert n > 3                                      # transfers filled the rest
    lanes.check_accounting()


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_mixed_pool_differential_under_flood(seed):
    """70 % votes over a hot account set into a small pool: both lanes
    emit the same frames, evict the same txns and count the same
    drops, and no vote is dropped while a non-vote is pooled."""
    rng = random.Random(seed)
    lanes = _Lanes(bank_cnt=2, depth=24, max_txn_per_microblock=8)
    for i in range(400):
        if rng.random() < 0.7:
            p = _distinct_vote(rng.randrange(64) * 1000 + i)
        else:
            cb = ([_cb_cu(20_000), _cb_price(rng.choice([1, 10**3, 10**6]))]
                  if rng.random() < 0.5 else ())
            p = _transfer(rng, dest=0 if rng.random() < 0.5 else None, cb=cb)
        lanes.insert(i, p)
        if rng.random() < 0.08:
            lanes.schedule(rng.randrange(2))
        if rng.random() < 0.06:
            lanes.done(rng.randrange(2))
    for _ in range(100):
        if not any([lanes.schedule(b) for b in range(2)]):
            break
        for b in range(2):
            lanes.done(b)
    lanes.check_accounting()
    st = lanes.stats(lanes.py)
    assert st["stat_evicted"] > 0 and st["stat_conflict_skips"] > 0
    assert st["stat_scheduled_votes"] > 0
    assert st["stat_votes_dropped_regular_pending"] == 0


def test_full_pool_never_gives_up_a_vote_for_a_non_vote():
    """A full pool: an arriving vote evicts the worst non-vote though
    it loses by ratio; a non-vote never evicts a vote; among votes
    alone the newcomer is refused and counted."""
    lanes = _Lanes(bank_cnt=1, depth=8)
    price = [_cb_cu(20_000), _cb_price(1_000_000)]
    for i in range(4):
        lanes.insert(i, _distinct_vote(i))
    for i in range(4):
        lanes.insert(4 + i, _distinct_transfer(i, cb=price))
    assert lanes.py.pending_cnt() == 8
    # four more votes push out the four (dearer) transfers
    for i in range(4):
        lanes.insert(8 + i, _distinct_vote(10 + i))
    assert len(lanes.py._pending_votes) == 8 and not lanes.py._pending
    assert lanes.py.stat_evicted == 4 and lanes.py.stat_dropped_votes == 0
    # a pool of votes refuses the best-paying non-vote...
    lanes.insert(12, _distinct_transfer(9, cb=price))
    assert lanes.py_drops[-1] == (12, "drop")
    assert len(lanes.py._pending_votes) == 8
    # ...and a ninth vote (equal ratio: the incumbent stays), counted
    lanes.insert(13, _distinct_vote(20))
    assert lanes.py_drops[-1] == (13, "drop")
    assert lanes.py.stat_dropped_votes == 1
    assert lanes.py.stat_votes_dropped_regular_pending == 0
    assert lanes.stats(lanes.py) == lanes.stats(lanes.nat)
    lanes.check_accounting()


def test_alt_lock_collision_serializes():
    """Two v0 txns loading from the SAME table conflict (the table
    address write-locks); both lanes schedule them one-per-microblock."""
    rng = random.Random(3)
    lanes = _Lanes(bank_cnt=2, depth=16)
    lanes.insert(0, _lut_txn(rng, 0))
    lanes.insert(1, _lut_txn(rng, 0))
    assert lanes.schedule(0)
    assert lanes.frames[-1][4:6] == (1).to_bytes(2, "little"), \
        "ALT twins must not share a microblock"
    # the second only schedules after the first bank's locks release
    assert not lanes.schedule(1)
    lanes.done(0)
    assert lanes.schedule(1)
    lanes.check_accounting()


def test_cost_model_fuzz_vs_python():
    """The native cost model (fd_pack_cost_probe) agrees with
    pack/cost.compute_cost — total cost, exact rewards (u128 priority
    fees included), simple-vote detection, malformed-CBP rejection —
    across the randomized workload."""
    rng = random.Random(77)
    n_reject = 0
    for p in _workload(rng, 250):
        t = ft.txn_parse(p)
        packed = ft.txn_pack(t)
        rc, totals, is_vote = sn.cost_probe(p, packed)
        c = fc.compute_cost(p, t)
        if c is None:
            assert rc == -2, "python rejected, native accepted"
            n_reject += 1
            continue
        assert rc == 0, "native rejected, python accepted"
        assert totals == (c.total, c.rewards(t.signature_cnt))
        assert is_vote == c.is_simple_vote
    assert n_reject > 0, "no malformed-CBP coverage"


def test_stage_streams_identical():
    """Stage-level differential: the SAME verified-frag stream (with
    duplicates) through DedupStage->PackStage vs the fused
    NativePackStage publishes byte-identical microblock frames."""
    from firedancer_tpu.runtime.dedup import DedupStage
    from firedancer_tpu.runtime.pack_stage import NativePackStage, PackStage
    from firedancer_tpu.tango import shm

    rng = random.Random(21)
    payloads = _workload(rng, 80)

    def run_lane(native: bool):
        uid = f"pn{random.randrange(1 << 30)}"
        links = []

        def mk(name, mtu=4096, depth=256):
            link = shm.ShmLink.create(f"fdtpu_{uid}_{name}", depth=depth,
                                      mtu=mtu)
            links.append(link)
            return link

        vd, bd, pb = mk("vd"), mk("bd", mtu=64), mk("pb", mtu=65536)
        feeder = shm.Producer(vd)
        stages = []
        # scheduling is held back (min_pending > stream size, adaptive
        # close off) until EVERY frag is pooled, so both lanes schedule
        # from the identical pool state — the comparison is about the
        # scheduler, not about sweep phasing between 1- and 2-stage
        # topologies
        policy = dict(bank_cnt=1, min_pending=10**9, mb_deadline_s=3600.0,
                      adaptive=False)
        if native:
            pack = NativePackStage(
                "pack", ins=[shm.Consumer(vd), shm.Consumer(bd)],
                outs=[shm.Producer(pb)], **policy)
            stages = [pack]
        else:
            dp = mk("dp")
            dedup = DedupStage("dedup", ins=[shm.Consumer(vd)],
                               outs=[shm.Producer(dp)])
            pack = PackStage(
                "pack", ins=[shm.Consumer(dp), shm.Consumer(bd)],
                outs=[shm.Producer(pb)], **policy)
            stages = [dedup, pack]
        done = shm.Producer(bd)
        sink = shm.Consumer(pb)
        frames = []
        try:
            for p in payloads:
                t = ft.txn_parse(p)
                feeder.try_publish(encode_verified(p, t),
                                   sig=sig_tag(t.signatures(p)[0]),
                                   tsorig=1)
            for _ in range(200):  # intake only: nothing schedules yet
                for s in stages:
                    s.run_once()
            assert not sink.has_pending()
            pack.flush()
            for _ in range(5000):
                for s in stages:
                    s.run_once()
                res = sink.poll()
                if res not in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
                    frames.append(res[1])
                    done.try_publish(b"", sig=0)  # release the bank lock
                elif not pack._pending_cnt():
                    break
            report = dict(pack.metrics.counters)
            if not native:
                report["dedup_dup"] = stages[0].metrics.get("dedup_dup")
        finally:
            for s in stages:
                s.ins = []
                s.outs = []
            feeder.link = None
            import gc

            gc.collect()
            for link in links:
                link.close()
                link.unlink()
        return frames, report

    py_frames, py_rep = run_lane(False)
    nat_frames, nat_rep = run_lane(True)
    assert py_frames, "python lane emitted nothing"
    assert py_frames == nat_frames
    assert py_rep["txn_in"] == nat_rep["txn_in"]
    assert py_rep["txn_scheduled"] == nat_rep["txn_scheduled"]
    assert py_rep["cu_consumed"] == nat_rep["cu_consumed"]
    assert py_rep["dedup_dup"] == nat_rep["dedup_dup"] > 0


def test_env_switch_disables(monkeypatch):
    monkeypatch.setenv(sn.ENV_SWITCH, "0")
    assert not sn.available()
    monkeypatch.delenv(sn.ENV_SWITCH)
    assert sn.available()
