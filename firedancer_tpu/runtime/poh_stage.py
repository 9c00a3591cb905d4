"""PoH stage: the hash clock ticking between microblock mixins.

Pipeline position mirrors the reference's poh tile
(/root/reference/src/app/fdctl/run/tiles/fd_poh.c:1-300): hash
continuously, mix in each executed microblock from the banks, emit ticks
on the tick cadence, and forward entries downstream to shred.  Generation
is sequential host work by design (SURVEY §7.1 — the chain can't be
parallelized forward); *verification* of the produced chain batches onto
the TPU via runtime/poh.verify_segments_tpu, which the e2e test exercises.

Inputs:  ins[b] = bank b -> poh executed microblocks.
Outputs: outs[0] = poh -> shred entries.

Entry frame: u32 num_hashes | 32B poh_hash | u16 txn_cnt |
(u16 len || raw txn payload)* — the Solana entry triple (num_hashes since
the previous entry, the chain hash after this entry, the txns).  Ticks are
entries with txn_cnt = 0.

Frag sig: the chain's hashcnt; under the slot clock `poh_sig` — every
entry names the slot it belongs to and the slot's last tick says so (the
reference's fd_disco_poh_sig + the entry batch's block_complete), which
is how the shred stage follows poh's slot across the ring.
"""

from __future__ import annotations

from firedancer_tpu.tango.rings import MCache
from firedancer_tpu.utils import metrics as fm
from .poh import PohChain
from .shred_native import (POH_SIG_BLOCK_COMPLETE, POH_SIG_SLOT,
                           POH_SIG_SLOT_MASK, POH_SIG_SLOT_SHIFT)
from .slot_clock import resolve_clock
from .stage import Stage


def poh_sig(slot: int, hashcnt: int, block_complete: bool = False) -> int:
    """The poh -> shred frag sig under the slot clock (the layout is
    shred_native.POH_SIG_*, one copy beside its C mirror)."""
    return (POH_SIG_SLOT
            | (POH_SIG_BLOCK_COMPLETE if block_complete else 0)
            | ((slot & POH_SIG_SLOT_MASK) << POH_SIG_SLOT_SHIFT)
            | (hashcnt & ((1 << POH_SIG_SLOT_SHIFT) - 1)))


def poh_sig_fields(sig: int) -> tuple[int | None, bool]:
    """-> (slot, block complete) of an entry frag's sig; (None, False)
    where poh runs without the slot clock and the sig names no slot."""
    if not sig & POH_SIG_SLOT:
        return None, False
    return ((sig >> POH_SIG_SLOT_SHIFT) & POH_SIG_SLOT_MASK,
            bool(sig & POH_SIG_BLOCK_COMPLETE))


def build_entry(num_hashes: int, poh_hash: bytes, txns: list[bytes]) -> bytes:
    out = bytearray()
    out += num_hashes.to_bytes(4, "little")
    out += poh_hash
    out += len(txns).to_bytes(2, "little")
    for p in txns:
        out += len(p).to_bytes(2, "little")
        out += p
    return bytes(out)


def parse_entry(frame: bytes) -> tuple[int, bytes, list[bytes]]:
    num_hashes = int.from_bytes(frame[:4], "little")
    poh_hash = frame[4:36]
    cnt = int.from_bytes(frame[36:38], "little")
    txns = []
    o = 38
    for _ in range(cnt):
        ln = int.from_bytes(frame[o : o + 2], "little")
        o += 2
        txns.append(frame[o : o + ln])
        o += ln
    return num_hashes, poh_hash, txns


class PohStage(Stage):
    @classmethod
    def extra_schema(cls) -> fm.MetricsSchema:
        return (
            fm.MetricsSchema()
            .counter("ticks", "tick entries emitted")
            .counter("mixins", "microblock mixin entries emitted")
            .counter("slots_sealed",
                     "slots whose final tick landed at the deadline"
                     " (slot-clock mode)")
            .counter("slot_missed",
                     "slots whose boundary passed unsealed — the first-"
                     "class MISSED outcome, never a hang or a drop")
            .counter("slot_skipped_ticks",
                     "ticks never emitted because their slot was missed")
            .histogram(
                "slot_seal_lag_ns",
                fm.exp_buckets(1e4, 1e10, 19),
                "final-tick landing time past the slot deadline"
                " (the seal jitter the cadence tests bound)",
            )
        )

    def __init__(
        self,
        *args,
        seed: bytes = b"\x00" * 32,
        hashes_per_tick: int = 64,
        ticks_per_slot: int = 8,
        hashes_per_iter: int = 16,
        clock=None,
        **kwargs,
    ):
        super().__init__(*args, **kwargs)
        self.chain = PohChain(hash=seed)
        self.hashes_per_tick = hashes_per_tick
        self.ticks_per_slot = ticks_per_slot
        self.hashes_per_iter = hashes_per_iter
        self._hashes_since_entry = 0
        self._tick_cnt = 0
        self.entries_out = 0
        # the slot's final entry hash (the poh_hash the bank hash chains);
        # entries is an optional in-memory record for replay tests
        self.last_entry_hash = seed
        self.entries: list[tuple[int, bytes, list[bytes]]] | None = None
        # slot-clock mode (runtime/slot_clock): ticks PACED to the wall-
        # clock deadline, the slot sealed at its boundary regardless of
        # pending load, and a boundary that passes unsealable (frozen
        # stage, starved credits) becomes a slot_missed VALUE — the
        # pipeline skips to the scheduled slot and keeps going
        self._clock = resolve_clock(clock)
        if self._clock is not None:
            self.ticks_per_slot = self._clock.cfg.ticks_per_slot
            self.slot = self._clock.cfg.slot0
            self._slot_hash_base = 0
            self.window_closed = False

    # -- callbacks ----------------------------------------------------------

    def after_credit(self) -> None:
        """The clock: advance the chain a bounded amount per loop sweep so
        the cooperative scheduler stays fair (the reference hashes in
        after_credit exactly the same way, fd_poh.c).  In slot-clock mode
        the wall clock, not the txn stream, decides when ticks land and
        when the slot seals."""
        if self._clock is not None:
            self._clock_sweep(self._clock.now())
            return
        room = self.hashes_per_tick - (self.chain.hashcnt % self.hashes_per_tick)
        n = min(self.hashes_per_iter, room)
        if n <= 0:  # clock stopped (drain mode)
            return
        self._loop_worked = True    # hashed: the call did work
        self.chain.append(n)
        self._hashes_since_entry += n
        if self.chain.hashcnt % self.hashes_per_tick == 0:
            self._emit_tick()

    # -- slot-clock mode -----------------------------------------------------

    def before_credit(self) -> None:
        """Miss detection must outrun backpressure: run_once skips
        after_credit while any output is starved, but a slot whose
        grace expired during the stall must STILL become a miss (the
        outcome is a value precisely because it needs no credit to be
        declared).  before_credit runs unconditionally every sweep."""
        if self._clock is None or self.window_closed:
            return
        now = self._clock.now()
        if self._clock.missed(self.slot, now):
            self._miss_slots(now)

    def _tick_progress(self) -> int:
        """Hashes into the CURRENT tick (slot-local; mixins may overshoot
        a boundary — the overshoot simply counts toward the next tick)."""
        return (self.chain.hashcnt - self._slot_hash_base
                - self._tick_cnt * self.hashes_per_tick)

    def _clock_sweep(self, now: int) -> None:
        clock = self._clock
        if self.window_closed:
            return
        if now >= clock.deadline_of(self.slot):
            # the boundary: seal NOW regardless of pending load — or,
            # past the grace, declare the slot missed and move on
            if clock.missed(self.slot, now):
                self._miss_slots(now)
            else:
                self._seal_rush()
            return  # pace the new slot from the next sweep on
        # paced hashing: tick k (1-based) may complete only once due;
        # catch-up after a stall is bounded per sweep (cooperative loop)
        for _ in range(4):
            if self._tick_cnt >= self.ticks_per_slot:
                return  # fully ticked; wait for the boundary roll
            k = self._tick_cnt + 1
            due = now >= clock.tick_deadline(self.slot, k)
            need = self.hashes_per_tick - self._tick_progress()
            if need > 0:
                cap = need if due else min(self.hashes_per_iter, need - 1)
                if cap > 0:
                    self._loop_worked = True    # hashed: the call did work
                    self.chain.append(cap)
                    self._hashes_since_entry += cap
            if not due or self._tick_progress() < self.hashes_per_tick:
                return
            if self.outs and self.outs[0].cr_avail <= 0:
                return  # starved: retry next sweep (the miss clock runs)
            self._emit_tick()

    def _seal_rush(self) -> None:
        """Deadline reached with the slot still open: land every
        remaining tick immediately (hashing is cheap; credits may not
        be) and roll to the next scheduled slot.  Called only inside the
        grace window — past it the slot is a miss, not a late seal."""
        clock = self._clock
        while self._tick_cnt < self.ticks_per_slot:
            if self.outs and self.outs[0].cr_avail <= 0:
                return  # retry next sweep; grace expiry turns this into a miss
            need = self.hashes_per_tick - self._tick_progress()
            if need > 0:
                self.chain.append(need)
                self._hashes_since_entry += need
            self._emit_tick()
        lag = clock.now() - clock.deadline_of(self.slot)
        self.metrics.inc("slots_sealed")
        self.metrics.observe("slot_seal_lag_ns", max(lag, 1))
        self.trace(fm.EV_SLOT_SEAL, self.slot)
        self._advance_slot(self.slot + 1)

    def _miss_slots(self, now: int) -> None:
        """The first-class MISSED outcome: the boundary (plus grace)
        passed before the slot's final tick could land — emit the event
        and the metric, skip the unsealed ticks, and continue cleanly at
        the slot the clock says is current."""
        clock = self._clock
        target = clock.slot_at(now)
        missed = max(target - self.slot, 1)
        skipped = (missed * self.ticks_per_slot) - self._tick_cnt
        for s in range(self.slot, self.slot + missed):
            self.trace(fm.EV_SLOT_MISSED, s)
        self.metrics.inc("slot_missed", missed)
        self.metrics.inc("slot_skipped_ticks", max(skipped, 0))
        self._advance_slot(self.slot + missed)

    def _advance_slot(self, slot: int) -> None:
        self._loop_worked = True    # a slot sealed or missed
        self.slot = slot
        self._tick_cnt = 0
        self._slot_hash_base = self.chain.hashcnt
        if not self._clock.in_window(slot):
            # the leader window ended: handoff fires on this schedule
            # (not on drain) — the clock plane stops sealing and the
            # supervisor observes slots_done via the metrics registry
            self.window_closed = True

    def slots_done(self) -> int:
        return (self.metrics.get("slots_sealed")
                + self.metrics.get("slot_missed"))

    def after_frag(self, in_idx: int, meta, payload: bytes) -> None:
        """A bank's executed microblock: mix its hash into the chain and
        emit the entry."""
        mixin = payload[:32]
        txn_cnt = int.from_bytes(payload[32:34], "little")
        txns = []
        o = 34
        for _ in range(txn_cnt):
            ln = int.from_bytes(payload[o : o + 2], "little")
            o += 2
            txns.append(payload[o : o + ln])
            o += ln
        self.chain.mixin(mixin)
        num_hashes = self._hashes_since_entry + 1  # mixin counts as one
        self._hashes_since_entry = 0
        self.metrics.inc("mixins")
        self.entries_out += 1
        self.last_entry_hash = self.chain.hash
        if self.entries is not None:
            self.entries.append((num_hashes, self.chain.hash, txns))
        self.publish(
            0,
            build_entry(num_hashes, self.chain.hash, txns),
            sig=self._entry_sig(),
            tsorig=int(meta[MCache.COL_TSORIG]),
        )

    # -- internals ----------------------------------------------------------

    def _entry_sig(self, block_complete: bool = False) -> int:
        if self._clock is None:
            return self.chain.hashcnt
        return poh_sig(self.slot, self.chain.hashcnt, block_complete)

    def _emit_tick(self) -> None:
        self.chain.tick()
        self._tick_cnt += 1
        num_hashes = self._hashes_since_entry
        self._hashes_since_entry = 0
        self.metrics.inc("ticks")
        self.entries_out += 1
        self.last_entry_hash = self.chain.hash
        if self.entries is not None:
            self.entries.append((num_hashes, self.chain.hash, []))
        # under the slot clock the slot's last tick closes its block
        last = self._clock is not None \
            and self._tick_cnt == self.ticks_per_slot
        self.publish(0, build_entry(num_hashes, self.chain.hash, []),
                     sig=self._entry_sig(last))

    def slot_complete(self) -> bool:
        return self._tick_cnt >= self.ticks_per_slot
