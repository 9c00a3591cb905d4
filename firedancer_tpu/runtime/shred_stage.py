"""Shred stage: entries -> entry batches -> FEC sets -> wire shreds.

Pipeline position mirrors the reference's shred tile
(/root/reference/src/app/fdctl/run/tiles/fd_shred.c): accumulate poh
entries into an entry batch, run the shredder (reedsol parity + merkle +
leader signature), and publish every data+parity shred to the outgoing
link (the net/turbine hop in a full validator; tests resolve them back
with the FEC resolver).

Inputs:  ins[0] = poh -> shred entries.
Outputs: outs[0] = wire shreds (mtu >= 1228).

Entry batches close when the accumulated serialized entries reach
`batch_target_sz` (the reference bounds batches by pending shred budget)
or on flush at slot end.  A close that finds the out ring short of
credits waits (`pending_flush`), and so does the tail of a burst the
ring could not take whole; the stage takes no further entry until both
are out (`before_credit`), so a slow consumer backpressures the stage
and everything in front of it, and no shred is dropped.

Under the slot clock the stage follows poh's slot (poh_stage.poh_sig on
every entry frag): the slot's last tick finishes the block (flush with
block-complete, credit-gated like a size close); an entry of another
slot — poh sealed or missed one — first sends what is buffered out as the
end of the old block, then the stage moves to that slot, its shred
index restarts and its parent is the block left behind.  A missed
slot has no last tick: its block ends with whatever was buffered when
the next slot's first entry came.  Without the clock the sig names no
slot and the stage stays where it was put.

Native lanes (ISSUE 11), chosen at construction when `secret` is given:

  - sweep mode: with the native shredder built, a native out producer,
    and no keep_sets requirement, the stage registers a
    shred_native.StageClient as its sweep-harness client — the ENTIRE
    run_once sweep (drain entries -> accumulate -> batch close -> shred
    -> publish) is one fdr_sweep crossing with zero Python per frag,
    the reference's mux-run-loop shape.  The Python callbacks below
    remain the fallback surface (mixed-lane/lossy splices) and forward
    into the SAME C-side batch buffer, so the lanes cannot diverge.
  - batch mode: keep_sets topologies, and those that stay on the Python
    frag path still shred through NativeShredder — one FFI crossing per
    entry batch, byte-identical sets.

`FDTPU_NATIVE_SHRED=0` (or a toolchain-less host) restores the pure
Python shredder end to end.
"""

from __future__ import annotations

from firedancer_tpu.tango.rings import MCache
from firedancer_tpu.utils import metrics as fm
from .poh_stage import PohStage, poh_sig_fields
from .shredder import EntryBatchMeta, FecSet, Shredder
from .stage import Stage


class ShredStage(Stage):
    @classmethod
    def extra_schema(cls) -> fm.MetricsSchema:
        # counted in C on the sweep lane (shred_native._COUNTERS, copied
        # in housekeeping) and by the Python lane alike
        return (
            fm.MetricsSchema()
            .counter("entries_in", "entries taken into a batch")
            .counter("entry_batches", "entry batches closed and shredded")
            .counter("fec_sets", "FEC sets made and published")
            .counter("data_shreds_out", "data shreds published")
            .counter("parity_shreds_out", "parity shreds published")
            .counter("batches_dropped",
                     "batches the C lane could not shred (never-path)")
        )

    def __init__(
        self,
        *args,
        signer,
        secret: bytes | None = None,
        slot: int = 1,
        shred_version: int = 1,
        batch_target_sz: int = 16384,
        keep_sets: bool = False,
        **kwargs,
    ):
        super().__init__(*args, **kwargs)
        self._slot = slot
        self.batch_target_sz = batch_target_sz
        self.keep_sets = keep_sets
        self.sets: list[FecSet] = []  # retained for tests/observers
        self._buf = bytearray()
        self._buf_tsorig = 0
        # slot follow (Python lane; the sweep client keeps its own): the
        # parent's distance, and a last tick's flush waiting for credits
        self._parent_off = 1
        self._pending_bc = False
        # -- lane selection ---------------------------------------------------
        # keep_sets needs materialized FecSets, so sweep mode is out
        self.shredder = None
        self._sweep_client = None
        self.native_shred = False
        if secret is not None:
            from . import shred_native as sd

            if sd.available():
                try:
                    nshred = sd.NativeShredder(secret=secret,
                                               shred_version=shred_version)
                    self.shredder = nshred
                    self.native_shred = True
                    if not keep_sets and self.outs and type(
                        self.outs[0]
                    ).__name__ == "NativeProducer":
                        self._sweep_client = sd.StageClient(
                            nshred._ctx, self.outs[0], slot=slot,
                            batch_target=batch_target_sz,
                        )
                except sd.NativeUnavailable:
                    self.shredder = None
                    self.native_shred = False
        if self.shredder is None:
            self.shredder = Shredder(signer=signer,
                                     shred_version=shred_version)

    # slot is a property so the sweep client's C-side state (and its
    # slot-scoped shred index reset) tracks reassignment exactly like
    # the Python Shredder's `if slot != self.slot` check does per batch
    @property
    def slot(self) -> int:
        c = self._sweep_client
        followed = c.slot() if c is not None else None
        return self._slot if followed is None else followed

    @slot.setter
    def slot(self, v: int) -> None:
        self._slot = v
        if self._sweep_client is not None:
            self._sweep_client.set_slot(v)

    def native_lanes(self) -> dict[str, bool]:
        return dict(super().native_lanes(),
                    shred=self._sweep_client is not None)

    def after_frag(self, in_idx: int, meta, payload: bytes) -> None:
        c = self._sweep_client
        if c is not None:
            # fallback surface (mixed-lane / lossy splice): forward into
            # the C-side buffer the sweep callback fills — one state
            c.append(payload, int(meta[MCache.COL_TSORIG]),
                     int(meta[MCache.COL_SIG]))
            return
        slot, last = poh_sig_fields(int(meta[MCache.COL_SIG]))
        if slot is not None and slot != self._slot:
            # poh moved on (a slot sealed, or missed): what is buffered
            # ends the old block, forced like an explicit flush
            if self._buf:
                self._shred_batch(block_complete=True)
            self._parent_off = min(max(slot - self._slot, 1), 0xFFFF)
            self._slot = slot
        if last:
            # the slot's last tick ends its block: flagged before the
            # append, so whichever close takes the tick carries it
            # (fd_shred.cpp stage_entry, same order)
            self._pending_bc = True
        # entries are appended verbatim: the entry frame IS this build's
        # entry-batch serialization (the reference ships bincode entries)
        self._buf += len(payload).to_bytes(4, "little")
        self._buf += payload
        ts = int(meta[MCache.COL_TSORIG])
        if ts and (self._buf_tsorig == 0 or ts < self._buf_tsorig):
            self._buf_tsorig = ts
        self.metrics.inc("entries_in")
        self._close_if_due()

    def _close_if_due(self) -> None:
        """Size close, or a last tick's block-complete close: both wait
        for credits (`_room`), the latter keeping its flag meanwhile."""
        if (len(self._buf) >= self.batch_target_sz or self._pending_bc) \
                and self._room():
            self._shred_batch(block_complete=self._pending_bc)

    def before_credit(self) -> None:
        # a closed batch, or a burst's tail, that waits for the out
        # ring's credits holds the intake: the ring in front fills and
        # poh, the banks and pack wait with it (a store tile that cannot
        # keep up slows the leader down; it does not lose shreds)
        c = self._sweep_client
        if c is not None:
            waiting = c.pending_flush
        else:
            waiting = (len(self._buf) >= self.batch_target_sz
                       or self._pending_bc) and not self._room()
        self.intake_room = 0 if waiting else None

    def after_credit(self) -> None:
        c = self._sweep_client
        if c is not None:
            # batch deferred for credits in C, or a burst's tail the
            # ring had no room for: retry with the flag the deferred
            # flush recorded (block_complete survives the wait)
            if c.pending_flush:
                self._loop_worked = True    # publishes in C
                c.retry_flush()
            return
        # batch closed for size but deferred for credits: retry here
        self._close_if_due()

    def during_housekeeping(self) -> None:
        c = self._sweep_client
        if c is not None:
            # C-side counters are authoritative in sweep mode: copy the
            # absolute values into the schema metrics at the same lazy
            # cadence every other stage metric has
            self.metrics.counters.update(c.counters())
            self._copy_sweep_counters()

    def _room(self) -> bool:
        """A batch bursts ~2 sets x ~65 shreds; don't start shredding unless
        the out ring can absorb it (dropping shreds mid-set wastes the set)."""
        if not self.outs:
            return True
        p = self.outs[0]
        if p.cr_avail < 256:
            p.refresh_credits()  # the cached count only ever falls
        return p.cr_avail >= 256

    def flush(self, *, block_complete: bool = True) -> None:
        c = self._sweep_client
        if c is not None:
            c.flush(block_complete=block_complete)
            self.metrics.counters.update(c.counters())
            return
        if self._buf:
            self._shred_batch(
                block_complete=block_complete or self._pending_bc)

    def _shred_batch(self, *, block_complete: bool) -> None:
        batch = bytes(self._buf)
        self._buf = bytearray()
        tsorig = self._buf_tsorig
        self._buf_tsorig = 0
        self._pending_bc = False
        sets = self.shredder.entry_batch_to_fec_sets(
            batch,
            slot=self._slot,
            meta=EntryBatchMeta(parent_offset=self._parent_off,
                                block_complete=block_complete),
        )
        self.metrics.inc("entry_batches")
        for st in sets:
            self.metrics.inc("fec_sets")
            if self.keep_sets:
                self.sets.append(st)
            if self.outs:
                # a whole FEC set's shreds in one ring crossing on the
                # native lane (~65 frames; _room() pre-gated the credits)
                items = [(buf, st.fec_set_idx, tsorig)
                         for buf in st.data_shreds]
                items += [(buf, st.fec_set_idx, tsorig)
                          for buf in st.parity_shreds]
                self.publish_burst_out(0, items)
                self.metrics.inc("data_shreds_out", len(st.data_shreds))
                self.metrics.inc("parity_shreds_out", len(st.parity_shreds))


class FusedPohShredStage(PohStage):
    """Fused poh+shred crash domain (ISSUE 16): ONE stage owns both the
    hash clock and the shredder, collapsing the poh->shred ring hop —
    each bank microblock's entry goes mixin -> entry batch -> FEC set
    inside a single run_once sweep, and ticks append to the same batch
    buffer with no intermediate ring crossing.

    Composition, not reimplementation: the PoH half IS PohStage (every
    slot-clock seal/miss semantic from PR 14 inherited verbatim); the
    shred half IS a ShredStage whose intake is called in-process where
    the unfused topology would publish to the poh_shred link.  The
    shred half's native sweep buffer (fd_shred.cpp stage_append closes
    batches at target size in C) still takes the entries, so the fused
    lane keeps the zero-Python shred path.  Crash-domain consequence:
    the supervisor restarts poh and shred together — entries can never
    be stranded on a ring between the two.

    outs[0] is the WIRE SHRED link (the unfused shred stage's out); the
    PoH half's credit checks therefore gate tick emission on the same
    downstream the shreds land on, which is exactly the backpressure
    the collapsed hop implies."""

    def __init__(self, *args, signer, secret: bytes | None = None,
                 shred_slot: int = 1, shred_version: int = 1,
                 batch_target_sz: int = 16384, keep_sets: bool = False,
                 **kwargs):
        super().__init__(*args, **kwargs)
        self.shred_half = ShredStage(
            f"{self.name}/shred", ins=[], outs=list(self.outs),
            signer=signer, secret=secret, slot=shred_slot,
            shred_version=shred_version, batch_target_sz=batch_target_sz,
            keep_sets=keep_sets,
        )

    def publish(self, out_idx: int, payload: bytes, sig: int = 0,
                tsorig: int = 0) -> bool:
        """The collapsed hop: every entry the PoH half emits feeds the
        shredder in-process instead of crossing a ring."""
        meta = [0] * 8
        meta[MCache.COL_SIG] = sig
        meta[MCache.COL_TSORIG] = tsorig
        self.shred_half.after_frag(0, meta, payload)
        self.metrics.inc("frags_out")  # unfused-poh metric parity
        return True

    def after_credit(self) -> None:
        super().after_credit()  # the clock: ticks / slot-clock sweep
        self.shred_half.after_credit()  # credit-deferred batch retry

    def during_housekeeping(self) -> None:
        self.shred_half.during_housekeeping()

    def flush(self, *, block_complete: bool = True) -> None:
        self.shred_half.flush(block_complete=block_complete)


def deshred_entry_batch(batch: bytes) -> list[bytes]:
    """Split a reassembled entry batch back into entry frames."""
    entries = []
    o = 0
    while o < len(batch):
        ln = int.from_bytes(batch[o : o + 4], "little")
        o += 4
        entries.append(batch[o : o + ln])
        o += ln
    return entries
