"""Store stage: consumes wire shreds, resolves FEC sets, stores batches.

Pipeline position mirrors the reference's store tile
(/root/reference/src/app/fdctl/run/tiles/fd_store.c — shreds into the
blockstore) fused with the receive half of fd_fec_resolver.c: the e2e
pipeline publishes every shred onto the wire link and this stage proves
they reassemble — the same component a non-leader validator runs on
turbine ingress.

Inputs: ins[0] = shred -> store wire shreds.
State:  completed FEC sets per slot + reassembled entry-batch bytes —
in this process's memory, or (`persist_dir`: a store tile that is a
process of its own) a file a slot that another process reads back
(`StoredSlots`).
"""

from __future__ import annotations

import os
import struct
from collections import namedtuple

from firedancer_tpu.protocol import shred as fs
from firedancer_tpu.utils import metrics as fm
from .fec_resolver import FecResolver
from .stage import Stage

# A slot's file, `slot_<n>.sets` under the store's directory: the slot's
# resolved FEC sets in the order they completed, each as
# u32 fec_set_idx | u32 n data shreds | (u16 len | data shred)*.
_SET_HDR = struct.Struct("<II")
_LEN = struct.Struct("<H")
_SLOT_FILE = "slot_%d.sets"

# what a reader needs of a resolved set (FecSet has both, and more)
StoredSet = namedtuple("StoredSet", "fec_set_idx data_shreds")


def entry_batch_of(sets) -> bytes:
    """Reassembled data-shred payloads of one slot's sets, in fec_set
    order."""
    out = bytearray()
    for st in sorted(sets, key=lambda s: s.fec_set_idx):
        for buf in st.data_shreds:
            out += fs.parse(buf).payload(buf)
    return bytes(out)


class StoredSlots:
    """What a store tile with `persist_dir` stored, read by another
    process once the tile has flushed (its housekeeping does, and its
    halt): the surface of StoreStage a reader of the stored block
    uses, `sets_by_slot` and `entry_batch_bytes`."""

    def __init__(self, persist_dir: str):
        self.sets_by_slot: dict[int, list[StoredSet]] = {}
        for fn in os.listdir(persist_dir):
            if not (fn.startswith("slot_") and fn.endswith(".sets")):
                continue
            with open(os.path.join(persist_dir, fn), "rb") as f:
                raw = f.read()
            sets = []
            o = 0
            while o < len(raw):
                idx, n = _SET_HDR.unpack_from(raw, o)
                o += _SET_HDR.size
                shreds = []
                for _ in range(n):
                    (ln,) = _LEN.unpack_from(raw, o)
                    shreds.append(raw[o + 2:o + 2 + ln])
                    o += 2 + ln
                sets.append(StoredSet(idx, shreds))
            if o != len(raw):
                raise ValueError(f"{fn}: a set is cut short")
            self.sets_by_slot[int(fn[5:-5])] = sets

    def entry_batch_bytes(self, slot: int) -> bytes:
        return entry_batch_of(self.sets_by_slot.get(slot, []))


class StoreStage(Stage):
    @classmethod
    def extra_schema(cls) -> fm.MetricsSchema:
        return (
            fm.MetricsSchema()
            .counter("shreds_in", "wire shreds taken")
            .counter("sets_stored", "FEC sets resolved and stored")
        )

    def __init__(self, *args, verify_sig=None, blockstore=None,
                 trust_membership: bool = False,
                 persist_dir: str | None = None, **kwargs):
        super().__init__(*args, **kwargs)
        # a store tile in a process of its own: each resolved set goes
        # to its slot's file under this directory (and not into
        # `sets_by_slot`: a run's shreds are hundreds of MB), where the
        # supervisor reads the stored block back after the drain
        self.persist_dir = persist_dir
        self._slot_files: dict[int, object] = {}
        # trust_membership: the leader's own store consuming its own
        # shred stream skips the per-shred merkle membership recompute
        # (~7 hashes/shred) — the fd_fec_resolver NULL-signer trust
        # boundary; receive-path stores keep full verification
        self.resolver = FecResolver(verify_sig=verify_sig, max_inflight=256,
                                    trust_membership=trust_membership)
        self.sets_by_slot: dict[int, list] = {}
        # optional persistent history (flamenco/blockstore.Blockstore):
        # every data shred lands there, making the slot replayable after
        # a restart (fd_store.c -> fd_blockstore insert path)
        self.blockstore = blockstore

    def after_frag(self, in_idx: int, meta, payload: bytes) -> None:
        out = self.resolver.add_shred(payload)
        self.metrics.inc("shreds_in")
        if out is not None:
            if self.persist_dir is None:
                self.sets_by_slot.setdefault(out.slot, []).append(out)
            else:
                self._persist(out)
            self.metrics.inc("sets_stored")
            if self.blockstore is not None:
                # persist only shreds of a RESOLVED set (FEC-complete,
                # leader-signature-checked): raw wire shreds must never
                # enter block history, or a forged (slot, idx) would
                # permanently displace the genuine shred (first-writer-
                # wins idempotency) and poison restart replay
                for buf in out.data_shreds:
                    self.blockstore.insert_shred(buf)

    def _persist(self, out) -> None:
        f = self._slot_files.get(out.slot)
        if f is None:
            # slots come in order: an older slot's file can close
            for slot in [s for s in self._slot_files if s < out.slot - 1]:
                self._slot_files.pop(slot).close()
            f = self._slot_files[out.slot] = open(
                os.path.join(self.persist_dir, _SLOT_FILE % out.slot), "ab")
        f.write(_SET_HDR.pack(out.fec_set_idx, len(out.data_shreds)))
        for buf in out.data_shreds:
            f.write(_LEN.pack(len(buf)))
            f.write(buf)

    def during_housekeeping(self) -> None:
        for f in self._slot_files.values():
            f.flush()       # what is stored is readable from outside

    def entry_batch_bytes(self, slot: int) -> bytes:
        """Reassembled data-shred payloads for `slot`, in fec_set order."""
        return entry_batch_of(self.sets_by_slot.get(slot, []))
