"""The replay verify stage: the verify phase of a follower's replay tile.

A validator that is not the leader receives a block as shreds,
reassembles its entry batches, checks the PoH chain over the entries
and verifies every signature of every transaction of the block before
it executes it (the reference: src/app/fdctl/run/tiles/fd_replay.c
after_frag -> fd_runtime_execute_txns_in_waves_tpool, whose sigverify
tasks are src/flamenco/runtime/fd_runtime.c; Agave hands a slot's entry
transactions to its device in one batch, entry/src/entry.rs).  This
stage is that phase in front of the chip, on the system's normal path:

    in:   one ENTRY BATCH of a slot a frag, as the shred tile cuts them
          and `deshred_entry_batch` / `parse_entry` read them, behind a
          header (`frame`): the slot, the batch's index in it, a
          last-of-slot flag, and on its first batch the slot's PoH seed
    out:  in block order, each entry batch of a live slot — the frag it
          came in, byte for byte — once all its transactions'
          signatures have passed on the device and its entries' hashes
          follow; and one verdict frame a slot (`verdict_frame`): live,
          or dead with the reason (sig, poh, parse) and the index of
          the first failing entry batch

A slot is dead from its first failing entry batch on: nothing of it at
or after that batch leaves, what arrives of it later is dropped at the
door and what is held of it is skipped, both counted
(`dead_slot_txn_skipped`; the lanes of those already in a device batch
`dead_slot_lanes_spent`), and the next slot starts clean.  Within an
entry batch the checks are made in the order parse, poh, sig: what
does not parse cannot be hashed, and what does not follow is not sent
to the device.

The batch's life is `VerifyStage`'s own — slots of packed rows, the fit
rule (a transaction's signatures in one device batch), the window of
two, the close rule, the stamps, the one dispatch call and the one
program; device batches are filled across entry batches and slots.
What this class adds is the door and the way out:

  - no tag cache: a follower verifies a repeated transaction like any
    other (the bank's status cache rejects it, not verify);
  - the native intake (native/fd_verify.cpp, "The replay intake") walks
    the frag, parses each transaction once, checks the chain and packs
    the rows in C, inside the sweep's crossing; the Python lane below
    does the same in Python (tests hold the two to the same frames);
  - at a reap the mask names the transactions that failed; a held
    entry batch leaves, is rejected or is skipped when the device
    batch that took its last lane has been reaped.
"""

from __future__ import annotations

import struct
import time
from collections import deque

import numpy as np

from firedancer_tpu.utils import metrics as fm
from . import poh as fpoh
from . import verify_native as vn
from .stage import Stage
from .verify import (VerifyStage, _packed_fields, _parse_pair,
                     MCACHE_COL_TSORIG)

_now_ns = time.monotonic_ns

# -- the frag (one owner; native/fd_verify.cpp RP_* mirrors it) ---------------
#
#   u64 slot | u32 batch idx | u32 flags | [32 B PoH seed iff F_SEED] |
#   the entry batch: (u32 len | entry)*, an entry as poh_stage.build_entry
#
# The first batch of a slot (idx 0) carries the seed, and no other does.
# A verdict frame is the 16-byte header alone: flags F_VERDICT | reason
# << 8; idx is the first failing entry batch (dead) or how many entry
# batches the slot had (live).  A frag's sig is slot << 32 | idx, a
# verdict frame's has bit 63 set too.

HDR = struct.Struct("<QII")
SEED_SZ = 32
F_LAST, F_SEED, F_VERDICT = 1, 2, 4
SIG_VERDICT = 1 << 63
FRAG_MAX = 65536        # the in link's mtu (fd_verify.cpp RP_FRAG_MAX)
LIVE, DEAD_SIG, DEAD_POH, DEAD_PARSE = range(4)
REASONS = ("live", "sig", "poh", "parse")


def frame(slot: int, idx: int, entry_batch: bytes, *, last: bool = False,
          seed: bytes | None = None) -> bytes:
    flags = (F_LAST if last else 0) | (F_SEED if seed is not None else 0)
    return HDR.pack(slot, idx, flags) + (seed or b"") + entry_batch


def unframe(frag: bytes) -> tuple[int, int, int, bytes | None, bytes]:
    """-> (slot, idx, flags, seed or None, the entry batch's bytes)."""
    slot, idx, flags = HDR.unpack_from(frag)
    o = HDR.size
    seed = None
    if flags & F_SEED:
        seed = frag[o:o + SEED_SZ]
        o += SEED_SZ
    return slot, idx, flags, seed, frag[o:]


def frag_sig(slot: int, idx: int, verdict: bool = False) -> int:
    return (SIG_VERDICT if verdict else 0) | ((slot & 0x7FFFFFFF) << 32) | idx


def verdict_frame(slot: int, idx: int, reason: int) -> bytes:
    return HDR.pack(slot, idx, F_VERDICT | (reason << 8))


def parse_verdict(frag: bytes) -> tuple[int, int, str] | None:
    """A verdict frame -> (slot, idx, reason name); None for any other
    frag."""
    if len(frag) != HDR.size:
        return None
    slot, idx, flags = HDR.unpack(frag)
    if not flags & F_VERDICT:
        return None
    return slot, idx, REASONS[(flags >> 8) & 0xFF]


def build_slot_frames(slot: int, seed: bytes, txns: list[bytes], *,
                      txns_per_entry: int = 31, entries_per_batch: int = 2,
                      ticks_per_slot: int = 8,
                      hashes_per_tick: int = 64) -> list[bytes]:
    """A slot as a leader here would have made it, as this stage's
    frags: `txns` cut into entries of `txns_per_entry` (one mixin hash
    each), `ticks_per_slot` ticks of `hashes_per_tick` hashes spread
    evenly with the last closing the slot, the chain run from `seed`,
    the entries cut into batches of `entries_per_batch`.  The source
    stage's blocks, and the tests'."""
    from firedancer_tpu.protocol import txn as ft

    from .poh_stage import build_entry

    groups = [txns[i:i + txns_per_entry]
              for i in range(0, len(txns), txns_per_entry)]
    per_tick = -(-len(groups) // ticks_per_slot) if groups else 0
    h = seed
    entries = []
    ticks = 0
    for k, grp in enumerate(groups):
        sigs = [ft.txn_parse(p).signatures(p)[0] for p in grp]
        h = fpoh.poh_mixin(h, fpoh.entry_mixin(sigs))
        entries.append(build_entry(1, h, grp))
        if (k + 1) % per_tick == 0 and ticks < ticks_per_slot - 1:
            h = fpoh.poh_append(h, hashes_per_tick)
            entries.append(build_entry(hashes_per_tick, h, []))
            ticks += 1
    while ticks < ticks_per_slot:
        h = fpoh.poh_append(h, hashes_per_tick)
        entries.append(build_entry(hashes_per_tick, h, []))
        ticks += 1
    batches = [entries[i:i + entries_per_batch]
               for i in range(0, len(entries), entries_per_batch)]
    return [
        frame(slot, j, b"".join(len(e).to_bytes(4, "little") + e for e in b),
              last=j == len(batches) - 1, seed=seed if j == 0 else None)
        for j, b in enumerate(batches)]


class _Held:
    """One entry batch held until its verdict (the Python lane's form
    of fd_verify.cpp's rp_rec)."""

    __slots__ = ("frag", "slot", "idx", "flags", "tsorig", "n_txn",
                 "n_lanes", "fail", "lives", "done")

    def __init__(self, frag, slot, idx, flags, tsorig, n_txn):
        self.frag = frag
        self.slot, self.idx, self.flags = slot, idx, flags
        self.tsorig = tsorig
        self.n_txn = n_txn
        self.n_lanes = 0
        self.fail = LIVE
        self.lives: list = []   # device batches holding lanes of it
        self.done = False


_NO_BAD = np.zeros((0,), dtype=np.uint32)


class ReplayVerifyStage(VerifyStage):
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        c = self.metrics.counters
        for d in self._replay_schema().defs:
            c.setdefault(d.name, 0)
        c.setdefault("verify_fail", 0)
        c.setdefault("txn_verified", 0)
        # the slot the door is in
        self._rp_slot: int | None = None
        self._rp_next_idx = 0
        self._rp_dead = False
        self._rp_chain = bytes(32)
        # the Python lane's held entry batches, in block order, and
        # which of them each open or in-flight device batch holds
        # transactions of (by the batch's stamps, one list entry a txn)
        self._rp_held: deque[_Held] = deque()
        self._rp_txn_recs: dict[int, list[_Held]] = {}
        # the slot being skipped on the way out
        self._rp_emit_dead: int | None = None

    # -- which intake ---------------------------------------------------------

    def _native_frame_mtu(self) -> int:
        # what leaves is what came in
        return max((c.link.mtu for c in self.ins), default=0)

    def _new_sweep_client(self, n_slots: int):
        if self.shard_cnt != 1:
            raise vn.NativeUnavailable(
                "entry batches of one slot go to one replay verify stage")
        if max(c.link.mtu for c in self.ins) > FRAG_MAX:
            raise vn.NativeUnavailable(
                f"an in link's mtu is over {FRAG_MAX}")
        return vn.ReplayClient(batch=self.batch,
                               max_msg_len=self.max_msg_len, n_slots=n_slots)

    # -- observability --------------------------------------------------------

    @staticmethod
    def _replay_schema() -> fm.MetricsSchema:
        return (
            fm.MetricsSchema()
            .counter("entry_batches_in", "entry batches (frags) taken")
            .counter("entries_in", "entries of the entry batches walked")
            .counter("txn_in", "transactions put into device batches")
            .counter("elems_in", "signature elements put into device"
                     " batches (lanes)")
            .counter("slots_live",
                     "slots whose every entry batch left (verdict live)")
            .counter("slots_dead_sig",
                     "slots dead by a signature that does not verify")
            .counter("slots_dead_poh",
                     "slots dead by an entry whose hash does not follow"
                     " (a transaction entry with num_hashes 0 among them)")
            .counter("slots_dead_parse",
                     "slots dead by a transaction, an entry or a frag"
                     " that does not parse, or a frag out of sequence")
            .counter("dead_slot_txn_skipped",
                     "transactions of dead slots after the failing entry"
                     " batch: dropped at the door or skipped on the way"
                     " out, no verdict of theirs used")
            .counter("dead_slot_lanes_spent",
                     "lanes of those skipped transactions that were in a"
                     " device batch already: the deployment's waste")
            .counter("poh_hashes", "sha-256 hashes of the PoH check"
                     " (appends and mixins)")
            .counter("poh_check_ns",
                     "cumulative ns in the PoH check (stamped in C on"
                     " the native intake)")
            .counter("entry_unpack_ns",
                     "cumulative ns walking and parsing entry batches"
                     " and packing their transactions' rows")
            .counter("entry_batches_out", "entry batches that left")
            .counter("entry_txn_out",
                     "transactions of the entry batches that left")
            .counter("entry_txn_rejected",
                     "transactions of the entry batches slots died at")
        )

    @classmethod
    def extra_schema(cls) -> fm.MetricsSchema:
        s = super().extra_schema()
        have = s.names()
        for d in cls._replay_schema().defs:
            if d.name not in have:
                s.defs.append(d)
        return s

    def during_housekeeping(self) -> None:
        super().during_housekeeping()
        c = self.metrics.counters
        # txns whose every signature verified AND that left
        c["txn_verified"] = c["entry_txn_out"]

    # -- the door -------------------------------------------------------------

    def after_frag(self, in_idx: int, meta, payload: bytes) -> None:
        ts = int(meta[MCACHE_COL_TSORIG])
        c = self._sweep_client
        if c is not None:
            c.append(payload, ts)   # no room: dropped and counted in C
            return
        self._take_frag(payload, ts)

    def sweep_frags(self, rows, buf: bytes):
        ts_done = [row[5] for row in rows]
        for row in rows:
            off = row[2]
            self._take_frag(buf[off:off + row[3]], row[5])
        return len(rows), ts_done

    def _native_sweep(self, drainer) -> bool:
        c = self._sweep_client
        if not c.can_accept() and not self._flying() \
                and not c.sealed_waiting():
            # the door is shut for want of room for a frag, with the
            # open batch part empty and nothing on the device: what
            # waits in front will not fill this batch
            self.backlogged = False
        return super()._native_sweep(drainer)

    @staticmethod
    def _claimed_txns(body: bytes) -> int:
        """Transactions the entries of an entry batch claim (the count
        fields alone): what a frag dropped at the door is counted by."""
        o = n = 0
        while o + 4 <= len(body):
            ln = int.from_bytes(body[o:o + 4], "little")
            o += 4
            if ln < 38 or o + ln > len(body):
                break
            n += int.from_bytes(body[o + 36:o + 38], "little")
            o += ln
        return n

    def _unpack(self, body: bytes):
        """The walk: structure, one parse a transaction, the guards.
        -> [(num_hashes, hash, [(payload, sigs, msg, signers, t,
        packed)])] or None where it does not parse."""
        m = self.metrics
        entries = []
        o = 0
        while o < len(body):
            if o + 4 > len(body):
                return None
            ln = int.from_bytes(body[o:o + 4], "little")
            o += 4
            if ln < 38 or o + ln > len(body):
                return None
            e = body[o:o + ln]
            o += ln
            cnt = int.from_bytes(e[36:38], "little")
            q = 38
            txns = []
            for _ in range(cnt):
                if q + 2 > ln:
                    return None
                tl = int.from_bytes(e[q:q + 2], "little")
                q += 2
                if q + tl > ln:
                    return None
                p = e[q:q + tl]
                q += tl
                t, packed = _parse_pair(p)
                if packed is not None:
                    sigs, msg, signers = _packed_fields(p, packed)
                elif t is not None:
                    sigs, msg, signers = (t.signatures(p), t.message(p),
                                          t.signers(p))
                else:
                    m.inc("parse_fail")
                    return None
                # a transaction the device cannot be given is one the
                # slot cannot be verified with
                if len(msg) > self.max_msg_len:
                    m.inc("msg_too_long")
                    return None
                if len(sigs) > self.batch:
                    m.inc("too_many_sigs")
                    return None
                txns.append((p, sigs, msg, signers, t, packed))
            if q != ln:
                return None
            entries.append((int.from_bytes(e[:4], "little"), e[4:36], txns))
        return entries

    def _take_frag(self, frag: bytes, tsorig: int) -> None:
        """One frag at the door, on the Python lane (fd_verify.cpp
        rp_frag is the same rule in C)."""
        m = self.metrics
        t0 = _now_ns()
        m.inc("entry_batches_in")
        framed = HDR.size <= len(frag) <= FRAG_MAX
        slot = idx = flags = 0
        body = b""
        if framed:
            slot, idx, flags = HDR.unpack_from(frag)
            o = HDR.size + (SEED_SZ if flags & F_SEED else 0)
            framed = (o <= len(frag) and not flags & F_VERDICT
                      and bool(flags & F_SEED) == (idx == 0))
            body = frag[o:]
        if framed and idx == 0:     # a slot starts, clean
            self._rp_slot, self._rp_next_idx = slot, 0
            self._rp_dead = False
            self._rp_chain = frag[HDR.size:HDR.size + SEED_SZ]
        if self._rp_slot is not None and self._rp_dead \
                and (not framed or slot == self._rp_slot):
            # of a slot that is dead already: dropped at the door
            m.inc("dead_slot_txn_skipped",
                  self._claimed_txns(body) if framed else 0)
            m.inc("entry_unpack_ns", _now_ns() - t0)
            return
        entries = None
        if (not framed or self._rp_slot is None or slot != self._rp_slot
                or idx != self._rp_next_idx):
            # not the frag that follows: the stream does not parse here
            fail = DEAD_PARSE
            if self._rp_slot is None:
                self._rp_slot = slot if framed else 0
            if not framed:
                body = b""
            slot, idx, flags = self._rp_slot, self._rp_next_idx, 0
        else:
            entries = self._unpack(body)
            fail = LIVE if entries is not None else DEAD_PARSE
        t1 = _now_ns()
        m.inc("entry_unpack_ns", t1 - t0)
        if not fail:
            h = self._rp_chain
            for num_hashes, expect, txns in entries:
                ok, h = fpoh.check_entry(h, num_hashes, expect,
                                         [x[1][0] for x in txns])
                if num_hashes or not txns:
                    m.inc("poh_hashes", num_hashes)
                if not ok:
                    fail = DEAD_POH
                    break
            else:
                self._rp_chain = h
            t2 = _now_ns()
            m.inc("poh_check_ns", t2 - t1)
            t1 = t2
        if entries is not None:
            m.inc("entries_in", len(entries))
        self._rp_next_idx = idx + 1
        rec = _Held(None if fail else frag, slot, idx, flags, tsorig,
                    self._claimed_txns(body) if fail
                    else sum(len(x[2]) for x in entries))
        self._rp_held.append(rec)
        if fail:
            # dead here: nothing of the frag goes to the device
            rec.fail = fail
            rec.done = True
            self._rp_dead = True
            return
        for _, _, txns in entries:
            for p, sigs, msg, signers, t, packed in txns:
                if rec.fail or self._rp_dead:
                    break       # its slot died while it was going in
                life = self._accumulate((sigs, msg, signers, t, packed),
                                        p, tsorig)
                self._rp_txn_recs.setdefault(id(life), []).append(rec)
                if not rec.lives or rec.lives[-1] is not life:
                    rec.lives.append(life)
                rec.n_lanes += len(sigs)
                m.inc("txn_in")
                m.inc("elems_in", len(sigs))
        rec.done = True
        m.inc("entry_unpack_ns", _now_ns() - t1)

    # -- the way out ----------------------------------------------------------

    def _slot_known_dead(self, rec: _Held) -> bool:
        """An earlier held entry batch of `rec`'s slot failed, or the
        slot's verdict is out already."""
        if self._rp_emit_dead == rec.slot:
            return True
        for e in self._rp_held:
            if e is rec:
                return False
            if e.slot == rec.slot and e.fail:
                return True
        return False

    def _reaped_txns(self, head, mask: np.ndarray) -> list:
        """A reaped batch's mask on the Python lane: the first failing
        transaction of a slot not known dead kills it at its entry
        batch; -> the frames now due out, in block order."""
        recs = self._rp_txn_recs.pop(id(head.life), [])
        if not mask[:head.n_elems].all():
            for rec, (a, b) in zip(recs, head.elem_ranges):
                if mask[a:b].all() or rec.fail \
                        or self._slot_known_dead(rec):
                    continue
                rec.fail = DEAD_SIG
                self.metrics.inc("verify_fail")
                self.metrics.inc(fm.VERIFY_FAIL_ELEMS, b - a)
                if rec.slot == self._rp_slot:
                    self._rp_dead = True
        for rec in dict.fromkeys(recs):
            rec.lives = [x for x in rec.lives if x is not head.life]
        return self._collect()

    def _collect(self) -> list:
        """The held entry batches whose lanes have all been reaped, in
        block order: left, rejected with the slot's verdict, or skipped
        (fdv_replay_collect is the same rule in C).  -> emit items
        (frame, sig, tsorig)."""
        m = self.metrics
        held = self._rp_held
        out = []
        while held and held[0].done and not held[0].lives:
            rec = held.popleft()
            verdict = None
            if self._rp_emit_dead == rec.slot:
                m.inc("dead_slot_txn_skipped", rec.n_txn)
                m.inc("dead_slot_lanes_spent", rec.n_lanes)
            elif rec.fail:
                verdict = rec.fail
                self._rp_emit_dead = rec.slot
                m.inc("entry_txn_rejected", rec.n_txn)
                m.inc(f"slots_dead_{REASONS[rec.fail]}")
            else:
                out.append((rec.frag, frag_sig(rec.slot, rec.idx),
                            rec.tsorig))
                m.inc("entry_batches_out")
                m.inc("entry_txn_out", rec.n_txn)
                if rec.flags & F_LAST:
                    verdict = LIVE
                    m.inc("slots_live")
            if verdict is not None:
                vi = rec.idx if verdict else rec.idx + 1
                out.append((verdict_frame(rec.slot, vi, verdict),
                            frag_sig(rec.slot, vi, True), rec.tsorig))
        return out

    def after_credit(self) -> None:
        if self._sweep_client is None and self._rp_held \
                and self._rp_held[0].done and not self._rp_held[0].lives:
            # an entry batch with no lane on the device (ticks alone, or
            # dead at the door) waits for no reap
            self._emit_reaped(self._collect())
        super().after_credit()

    def _nv_reaped(self, slot: int, n_elems: int, n_txn: int,
                   mask: np.ndarray, life):
        """A reaped batch's mask on the native lane: name the failed
        transactions to the C side, hand the slot back, and queue what
        is now due out."""
        c = self._sweep_client
        ok = self._passed_txns(mask, n_elems, c.slots[slot].ranges[:n_txn])
        c.reap(slot, _NO_BAD if ok is None
               else np.flatnonzero(~ok).astype(np.uint32))
        c.release(slot)
        return self._nv_collect(life)

    def _nv_collect(self, life):
        """-> an emit-queue entry over the held frags' arena (its first
        field the held records it covers, freed when its rows are out),
        or None when nothing is due."""
        c = self._sweep_client
        tbl, n_recs = c.collect()
        if not len(tbl):
            if n_recs:
                c.out_done(n_recs)  # skipped: nothing of them leaves
            return None
        return [n_recs, c.held_ptr, tbl, 0, life]

    def _nv_published(self, n_recs) -> None:
        self._sweep_client.out_done(n_recs)

    def _nv_publish(self) -> None:
        c = self._sweep_client
        while c.emit_ready():
            # due without a reap of its own (ticks alone, dead at the
            # door), or more than one table held
            ent = self._nv_collect(None)
            if ent is None:
                break
            self._nv_emit.append(ent)
        super()._nv_publish()

    def held(self) -> int:
        """Entry batches held for their verdict or for room behind."""
        c = self._sweep_client
        return c.held() if c is not None else len(self._rp_held)

    def flush(self) -> None:
        super().flush()
        c = self._sweep_client
        if c is None:
            self._emit_reaped(self._collect())
            return
        for _ in range(4 * c.n_slots):
            self._nv_publish()
            if not self._nv_emit and not c.emit_ready():
                break


# -- the ends of the topology `run --config` builds ---------------------------


class ReplaySourceStage(Stage):
    """Offers `n_slots` slots of `slot_txns` transfers each as entry
    batches (`build_slot_frames`), a slot's seed the hash of its number:
    the store tile's hand-over, from a seeded pool (runtime/benchg's,
    replayed).  `corrupt_slots` get one flipped signature bit."""

    @classmethod
    def extra_schema(cls) -> fm.MetricsSchema:
        return (fm.MetricsSchema()
                .counter("slots_gen", "slots offered")
                .counter("txn_gen", "transactions in them"))

    def __init__(self, pool: list[bytes], *args, n_slots: int,
                 slot_txns: int, corrupt_slots=(), shape: dict | None = None,
                 **kwargs):
        super().__init__(*args, **kwargs)
        import hashlib

        self.require_credit = True
        self._frames: deque[bytes] = deque()
        for s in range(n_slots):
            txns = [pool[(s * slot_txns + k) % len(pool)]
                    for k in range(slot_txns)]
            if s in corrupt_slots and txns:
                bad = bytearray(txns[len(txns) // 2])
                bad[1] ^= 0x01          # a bit of its first signature
                txns[len(txns) // 2] = bytes(bad)
            seed = hashlib.sha256(b"replay-slot%d" % s).digest()
            self._frames.extend(
                build_slot_frames(s, seed, txns, **(shape or {})))
            self.metrics.inc("slots_gen")
            self.metrics.inc("txn_gen", len(txns))

    def after_credit(self) -> None:
        for _ in range(min(self.burst, len(self._frames))):
            f = self._frames[0]
            slot, idx, _ = HDR.unpack_from(f)
            if not self.publish(0, f, sig=frag_sig(slot, idx),
                                tsorig=_now_ns()):
                return
            self._frames.popleft()


class ReplayOutStage(Stage):
    """Where a replay tile would execute: counts the entry batches and
    reads the verdict frames."""

    @classmethod
    def extra_schema(cls) -> fm.MetricsSchema:
        return (fm.MetricsSchema()
                .counter("entry_batches", "entry batches received")
                .counter("verdicts_live", "verdict frames: live")
                .counter("verdicts_dead", "verdict frames: dead"))

    def after_frag(self, in_idx: int, meta, payload: bytes) -> None:
        v = parse_verdict(payload)
        if v is None:
            self.metrics.inc("entry_batches")
        else:
            self.metrics.inc("verdicts_live" if v[2] == "live"
                             else "verdicts_dead")
