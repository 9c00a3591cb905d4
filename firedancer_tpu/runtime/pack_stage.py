"""Pack stage: the real conflict-aware scheduler wired into the pipeline.

Pipeline position and dataflow mirror the reference's pack tile
(/root/reference/src/app/fdctl/run/tiles/fd_pack.c): verified txns arrive
from dedup, conflict-free microblocks go out to B bank stages, and each
bank reports microblock completion back so its account locks release
(fd_pack.c microblock_done / bank_busy fseqs).  This build's pipeline is
always leader (the became_leader poh->pack message arrives when a poh stage
precedes pack in a full validator; the synthetic pipeline produces blocks
continuously).

Two lanes, one policy:

  - `PackStage` — the portable Python lane over pack/scheduler.Pack,
    fed by the dedup stage (runtime/dedup.py).
  - `NativePackStage` — the C++ fast lane (native/fd_pack.cpp behind
    pack/scheduler_native.py) with dedup FUSED into the same crossing:
    it consumes the verify output directly, probes the fd_tcache.so
    table inside `fd_pack_insert_burst`, and gets publish-ready
    microblock frames back from `fd_pack_schedule` — one FFI call per
    drained burst / per microblock (FD207), zero per-txn Python work.
    Byte-identical frames vs the Python lane (tests/test_pack_native).

Inputs:  ins[0..n_txn_ins) = txn links; ins[n_txn_ins+b] = bank b's done
feedback.  Outputs: outs[b] = pack->bank b microblock link.

Microblock frame: u32 bank_seq | u16 txn_cnt | (u16 len || verified-frag)*
where each verified-frag is payload||packed-desc||u16 (runtime/verify.py) —
banks never reparse.

What a microblock holds (one rule, both lanes; pack/scheduler.py
schedule_next_microblock): votes first, up to three quarters of the cost
the block has left and of the microblock's transaction slots, then the
regular pool fills the rest, all under the block's limits and the banks'
account locks.  A full pool never gives up a vote for a non-vote.

Batching policy (shared by both lanes): a microblock is scheduled for an
idle bank when at least `min_pending` txns are waiting, the oldest has
waited `mb_deadline_s`, or — the ADAPTIVE close — the txn inputs ran dry
this iteration (backlog exhausted: waiting for min_pending under light
load would only add latency, the 37/149 ms p50 batch-accumulation hops
ROADMAP item #4 measured).
"""

from __future__ import annotations

import time

from firedancer_tpu.pack.scheduler import Pack
from firedancer_tpu.tango import shm
from firedancer_tpu.tango.rings import MCache
from firedancer_tpu.utils import metrics as fm
from .slot_clock import resolve_clock
from .stage import Stage
from .verify import decode_verified


class PackStage(Stage):
    @classmethod
    def extra_schema(cls) -> fm.MetricsSchema:
        return (
            fm.MetricsSchema()
            .counter("txn_in", "verified txns accepted into the pool")
            .counter("txn_dropped",
                     "txns the pool rejected (full/limits) or evicted for"
                     " a better newcomer")
            .counter("bad_frag", "malformed verified-frags dropped")
            .counter("dedup_dup",
                     "duplicate txns dropped by the fused dedup probe"
                     " (native lane; the python lane's dedup stage counts"
                     " its own)")
            .counter("microblocks", "microblocks scheduled to banks")
            .counter("microblock_done", "bank completion acks consumed")
            .counter("txn_scheduled", "txns scheduled into microblocks")
            .counter("txn_scheduled_votes",
                     "simple votes among txn_scheduled (each microblock"
                     " takes votes first, up to 3/4 of its cost and slots)")
            .counter("txn_dropped_votes",
                     "simple votes among txn_dropped (refused by, or"
                     " evicted from, a pool full of votes)")
            .counter("votes_dropped_while_regular_pending",
                     "votes dropped while a non-vote was pooled: the"
                     " guarantee says none, so anything but 0 is a fault")
            .counter("conflict_skips",
                     "pending txns a schedule scan passed over because an"
                     " account they touch is locked by this or another"
                     " bank's microblock")
            .counter("cu_consumed",
                     "cost units of every txn scheduled (the block cost"
                     " model, pack/cost.py)")
            .histogram(
                "mb_fill",
                fm.exp_buckets(1, 64, 7),
                "txns per emitted microblock",
            )
            .counter("blocks_closed",
                     "slot boundaries where the block closed on the"
                     " deadline (slot-clock mode; the unscheduled tail"
                     " carries into the next slot's pool)")
            .counter("txn_shed",
                     "pending txns shed by the deadline load-shedding"
                     " degraded mode (lowest-priority first, never votes)")
            .counter("bank_idle_polls",
                     "times a scheduling pass left a bank without a"
                     " microblock though it was idle and its ring had"
                     " room: nothing pending, or every pending txn"
                     " touches an account another bank's microblock"
                     " holds")
        )

    @classmethod
    def metrics_schema_n(cls, bank_cnt: int) -> fm.MetricsSchema:
        """The class schema + a counter a bank of the microblocks
        scheduled to it (`mb_scheduled_b{i}`): what a pack stage in
        front of `bank_cnt` banks publishes, and what a process
        topology sizes its shm segment from."""
        s = cls.metrics_schema()
        for b in range(bank_cnt):
            s.counter(f"mb_scheduled_b{b}",
                      f"microblocks scheduled to bank {b}")
        return s

    def __init__(
        self,
        *args,
        bank_cnt: int = 2,
        depth: int = 4096,
        max_txn_per_microblock: int = 31,
        min_pending: int = 8,
        mb_deadline_s: float = 0.002,
        adaptive: bool = True,
        n_txn_ins: int = 1,
        clock=None,
        close_frac: float = 0.25,
        shed_keep: int | None = None,
        hold_when_full: bool = False,
        limits=None,
        **kwargs,
    ):
        super().__init__(*args, **kwargs)
        self._use_schema(self.metrics_schema_n(bank_cnt))
        self._mb_counter = [f"mb_scheduled_b{b}" for b in range(bank_cnt)]
        # a pool without room for one more burst leaves the txn inputs
        # unpolled (backpressure through the ring in front, up to the
        # source) instead of evicting its cheapest transaction for the
        # newcomer: for a deployment whose source can wait (a process a
        # tile under a generator).  The banks' done frames keep coming
        # in (`_drain_done`), or the pool would never drain
        self.hold_when_full = hold_when_full
        if len(self.outs) != bank_cnt:
            raise ValueError("need one output link per bank")
        self.bank_cnt = bank_cnt
        self.n_txn_ins = n_txn_ins
        self.pack = self._make_pack(
            bank_cnt=bank_cnt,
            depth=depth,
            max_txn_per_microblock=max_txn_per_microblock,
            # the block's limits (pack/scheduler.BlockLimits; None: the
            # stock ones), the same in both lanes
            limits=limits,
        )
        self.min_pending = min_pending
        self.mb_deadline_s = mb_deadline_s
        # adaptive close: schedule as soon as the txn inputs run dry —
        # accumulating toward min_pending only pays when a backlog exists
        self.adaptive = adaptive
        self.force_flush = False  # end-of-run: drain regardless of policy
        self._bank_busy = [False] * bank_cnt
        self._mb_seq = 0
        self._first_pending_at: float | None = None
        self._pack_stats = [0] * len(self._PACK_STATS)
        self._input_idle = False  # stamped in before_credit (has_pending)
        # first-sig -> tsorig for end-to-end latency attribution; bounded:
        # entries for txns evicted from the pool would otherwise leak
        self._tsorig_by_sig: dict[bytes, int] = {}
        # slot-clock mode (runtime/slot_clock): the DEADLINE-AWARE block
        # close.  At each slot boundary the block accounting resets
        # (pack.end_block) and the unscheduled tail simply stays in the
        # pool — it carries into the next slot, zero loss.  Inside the
        # final `close_frac` of a slot the policy schedules aggressively
        # (no min_pending accumulation), and with `shed_keep` set the
        # degraded mode sheds the lowest-priority pending REGULAR work
        # down to shed_keep when the clock says the slot cannot close in
        # time (votes are never shed).
        self._clock = resolve_clock(clock)
        self._close_ns = 0
        self._shed_keep = shed_keep
        self._deadline_near = False
        self._block_closing = False     # a boundary passed, banks busy
        if self._clock is not None:
            self._clock_slot = self._clock.cfg.slot0
            self._close_ns = int(self._clock.slot_ns * close_frac)

    def _make_pack(self, **kw):
        return Pack(**kw)

    def native_lanes(self) -> dict[str, bool]:
        return dict(super().native_lanes(), pack=False)

    # the pool's own cumulative counts (Pack.stat_*; the native lane's
    # come back with every crossing) -> this stage's counters
    _PACK_STATS = (("stat_evicted", "txn_dropped"),
                   ("stat_dropped_votes", "txn_dropped_votes"),
                   ("stat_votes_dropped_regular_pending",
                    "votes_dropped_while_regular_pending"),
                   ("stat_scheduled_votes", "txn_scheduled_votes"),
                   ("stat_conflict_skips", "conflict_skips"))

    def _sync_pack_stats(self) -> None:
        pack, seen = self.pack, self._pack_stats
        for k, (attr, counter) in enumerate(self._PACK_STATS):
            v = getattr(pack, attr)
            if v != seen[k]:
                self.metrics.inc(counter, v - seen[k])
                seen[k] = v

    # -- callbacks ----------------------------------------------------------

    def after_frag(self, in_idx: int, meta, payload: bytes) -> None:
        if in_idx < self.n_txn_ins:
            try:
                p, desc = decode_verified(payload)
            except ValueError:
                self.metrics.inc("bad_frag")
                return
            if self.pack.insert(p, desc):
                self.metrics.inc("txn_in")
                if len(self._tsorig_by_sig) > 2 * self.pack.depth:
                    self._tsorig_by_sig.clear()
                self._tsorig_by_sig[desc.signatures(p)[0]] = int(
                    meta[MCache.COL_TSORIG]
                )
            else:
                self.metrics.inc("txn_dropped")
        else:
            self._bank_done(in_idx - self.n_txn_ins)

    def _bank_done(self, bank: int) -> None:
        self.pack.microblock_done(bank)
        self._bank_busy[bank] = False
        self.metrics.inc("microblock_done")

    def _drain_done(self) -> None:
        """The banks' done frames, polled past the held intake, with
        the books the intake keeps (frags_in, the frag's wait)."""
        m = self.metrics
        now = 0
        for bank in range(self.bank_cnt):
            cons = self.ins[self.n_txn_ins + bank]
            while (res := cons.poll()) != shm.POLL_EMPTY:
                if res == shm.POLL_OVERRUN:
                    m.inc("overrun")
                    continue
                self._bank_done(bank)
                self._loop_worked = True
                m.inc("frags_in")
                ts = int(res[0][MCache.COL_TSORIG])
                now = now or shm.now_ns()
                if 0 < ts <= now:
                    m.observe("frag_latency_ns", now - ts)
                    m.inc("frag_wait_ns", now - ts)
                    m.inc("frag_wait_n")

    def before_credit(self) -> None:
        # the mb_deadline_s clock starts here, not in after_frag (the
        # per-frag path must stay free of wall-clock syscalls, fdlint
        # FD202) and not in after_credit (run_once skips that hook while
        # any bank link is backpressured): before_credit runs
        # unconditionally every iteration, so the stamp lags a txn's
        # arrival by at most one iteration even under backpressure
        self._flush_intake()
        if self.hold_when_full:
            full = self.pack.depth - self._pending_cnt() < self.burst
            self.intake_room = 0 if full else None
            if full:
                self._drain_done()
        if self._clock is not None:
            self._clock_roll(self._clock.now())
        if self.adaptive:
            # adaptive close probe: one mcache row read per txn input —
            # no syscalls, stamped here for the same FD202 reason
            self._input_idle = not any(
                self.ins[i].has_pending() for i in range(self.n_txn_ins)
            )
        if self._first_pending_at is None and self._pending_cnt():
            self._first_pending_at = time.monotonic()

    def after_credit(self) -> None:
        self._flush_intake()
        if not self._ready_to_schedule():
            return
        for bank in range(self.bank_cnt):
            if self._bank_busy[bank]:
                continue
            if self.outs[bank].cr_avail <= 0:
                continue
            if not self._try_emit(bank):
                # nothing schedulable right now (conflicts/empty): this
                # bank and every idle one behind it go without
                self.metrics.inc("bank_idle_polls", sum(
                    not self._bank_busy[b] and self.outs[b].cr_avail > 0
                    for b in range(bank, self.bank_cnt)))
                break
        if self._pending_cnt() == 0:
            self._first_pending_at = None

    def during_housekeeping(self) -> None:
        self._sync_pack_stats()

    # -- internals ----------------------------------------------------------

    def _clock_roll(self, now: int) -> None:
        """One clock read per loop sweep (before_credit cadence, FD202):
        close the block at each slot boundary — in-flight microblocks
        finish via the normal done-feedback, the unscheduled tail stays
        pooled for the next slot — and arm the deadline-close /
        load-shed posture for the slot's final stretch.

        The pool's `end_block` gives every bank's account locks back,
        so it waits for the microblocks in flight (`_block_closing`:
        nothing is scheduled meanwhile, one microblock's time at most):
        a lock given back under a microblock a bank is still executing
        would let pack hand the same account to another bank, and bank
        tiles in processes of their own would then both start from its
        old value.  (Upstream's pack tile drains its banks before
        fd_pack_end_block the same way.)"""
        clock = self._clock
        slot = clock.slot_at(now)
        last = clock.last_slot()
        if last is not None:
            # the leader window bounds the boundaries this stage owns:
            # one final close after the last slot, then the clock is
            # someone else's (keeps post-window accounting, and the
            # deterministic chaos summaries, from drifting with wall
            # time while the topology drains)
            slot = min(slot, last + 1)
        if slot > self._clock_slot:
            self._loop_worked = True    # a block closed
            self._block_closing = True
            self.metrics.inc("blocks_closed", slot - self._clock_slot)
            self.trace(fm.EV_SLOT_ROLL, slot)
            self._clock_slot = slot
        if self._block_closing and not any(self._bank_busy):
            self.pack.end_block()
            self._block_closing = False
        self._deadline_near = clock.remaining_ns(slot, now) <= self._close_ns
        if self._deadline_near and self._shed_keep is not None:
            excess = self._pending_cnt() - self._shed_keep
            if excess > 0:
                shed = self._shed(excess)
                if shed:
                    self.metrics.inc("txn_shed", shed)
                    self.trace(fm.EV_SLOT_SHED, shed)

    def _shed(self, n: int) -> int:
        return self.pack.shed_lowest(n)

    def _flush_intake(self) -> None:
        """Native-lane hook: push the accumulated frag burst through the
        single FFI crossing.  The Python lane inserts per frag already."""

    def _pending_cnt(self) -> int:
        return self.pack.pending_cnt()

    def _ready_to_schedule(self) -> bool:
        n = self._pending_cnt()
        if n == 0 or self._block_closing:
            return False
        if self.force_flush or n >= self.min_pending:
            return True
        if self._deadline_near:
            # the slot's final stretch: accumulating toward min_pending
            # risks the block closing with schedulable work stranded
            return True
        if self.adaptive and self._input_idle:
            # inputs ran dry: nothing else is coming this instant, so
            # waiting for min_pending would trade pure latency for nothing
            return True
        return (
            self._first_pending_at is not None
            and time.monotonic() - self._first_pending_at >= self.mb_deadline_s
        )

    def _try_emit(self, bank: int) -> bool:
        chosen = self.pack.schedule_next_microblock(bank)
        if not chosen:
            return False
        self._emit(bank, chosen)
        return True

    def _emit(self, bank: int, chosen) -> None:
        from .verify import encode_verified

        tsorig = 0
        cu = 0
        frame = bytearray()
        frame += self._mb_seq.to_bytes(4, "little")
        frame += len(chosen).to_bytes(2, "little")
        for o in chosen:
            frag = encode_verified(o.payload, o.desc)
            frame += len(frag).to_bytes(2, "little")
            frame += frag
            cu += o.cost.total
            ts = self._tsorig_by_sig.pop(o.first_sig(), 0)
            # the microblock inherits its OLDEST txn's origin stamp
            tsorig = min(tsorig, ts) if tsorig and ts else (tsorig or ts)
        self._publish_mb(bank, bytes(frame), len(chosen), cu, tsorig)

    def _publish_mb(self, bank: int, frame: bytes, txn_cnt: int, cu: int,
                    tsorig: int) -> None:
        self._mb_seq += 1
        self.publish(bank, frame, sig=self._mb_seq, tsorig=tsorig)
        self._bank_busy[bank] = True
        self.metrics.inc("microblocks")
        self.metrics.inc(self._mb_counter[bank])
        self.metrics.inc("txn_scheduled", txn_cnt)
        self.metrics.inc("cu_consumed", cu)
        self.metrics.observe("mb_fill", txn_cnt)
        self.trace(fm.EV_MICROBLOCK, txn_cnt)

    def flush(self) -> None:
        """Force remaining txns out (end of run); banks must keep draining
        their done feedback for this to terminate."""
        self.force_flush = True
        self.after_credit()
        self._sync_pack_stats()


class NativePackStage(PackStage):
    """The fused native lane: dedup + pack in one C++ structure.

    Consumes the verify stage's output links DIRECTLY (no dedup stage in
    the topology): `after_frag` only appends (frag, tag, tsorig) to a
    burst list, `before_credit`/`after_credit` push the burst through one
    `fd_pack_insert_burst` crossing that probes the shared fd_tcache.so
    table natively — duplicates never surface into Python — and
    `fd_pack_schedule` hands back a publish-ready frame, byte-identical
    to the Python lane's.  Construct only when pack/scheduler_native
    .available(); callers fall back to DedupStage + PackStage otherwise.
    """

    def __init__(self, *args, tcache_depth: int | None = None, **kwargs):
        from firedancer_tpu.runtime.dedup import DEDUP_TCACHE_DEPTH

        self._tcache_depth = tcache_depth or DEDUP_TCACHE_DEPTH
        self._burst: list = []
        super().__init__(*args, **kwargs)
        # intake is an append per frag (~no work): drain deeper bursts
        # per sweep so the stage-loop overhead (credits, sibling polls)
        # and the per-burst FFI crossing amortize over 4x the frags
        self.burst = 64

    def native_lanes(self) -> dict[str, bool]:
        return dict(super().native_lanes(), pack=True)

    def _make_pack(self, **kw):
        from firedancer_tpu.pack import scheduler_native as sn
        from firedancer_tpu.tango.tcache_native import NativeTCache

        pack = sn.NativePack(**kw)
        pack.attach_tcache(NativeTCache(self._tcache_depth))
        return pack

    # -- callbacks ----------------------------------------------------------

    def after_frag(self, in_idx: int, meta, payload: bytes) -> None:
        if in_idx < self.n_txn_ins:
            # append-only: the FFI crossing happens at burst granularity
            # in before_credit/after_credit (FD207)
            self._burst.append(
                (payload, int(meta[MCache.COL_SIG]),
                 int(meta[MCache.COL_TSORIG]))
            )
        else:
            self._bank_done(in_idx - self.n_txn_ins)

    def _flush_intake(self) -> None:
        if not self._burst:
            return
        from firedancer_tpu.pack import scheduler_native as sn

        self._loop_worked = True    # the last sweep's frags go in here
        codes = self.pack.insert_burst(self._burst)
        self._burst.clear()
        m = self.metrics
        n_ok = codes.count(sn.INS_OK)
        if n_ok:
            m.inc("txn_in", n_ok)
        n_dup = codes.count(sn.INS_DUP)
        if n_dup:
            m.inc("dedup_dup", n_dup)
        n_bad = codes.count(sn.INS_BAD_FRAG)
        if n_bad:
            m.inc("bad_frag", n_bad)
        n_drop = len(codes) - n_ok - n_dup - n_bad
        if n_drop:
            m.inc("txn_dropped", n_drop)

    def _pending_cnt(self) -> int:
        # the pool only changes through insert_burst/schedule, and every
        # crossing reports the post-op size: the policy checks that run
        # each loop iteration cost zero FFI
        return self.pack.last_pending + len(self._burst)

    def _try_emit(self, bank: int) -> bool:
        res = self.pack.schedule(bank, mb_seq=self._mb_seq)
        if res is None:
            return False
        frame, txn_cnt, cu, tsorig = res
        self._publish_mb(bank, frame, txn_cnt, cu, tsorig)
        return True

    def flush(self) -> None:
        self._flush_intake()
        super().flush()
