"""ctypes binding for the native verify sweep client (native/fd_verify.cpp).

The verify stage's host orchestration in one FFI crossing per sweep
(ISSUE 13): fdr_sweep drains the stage's input rings AND runs the C
frag callback — shard filter, fd_txn_parse (function pointer into
fd_txn_parse.so, the fd_pack/fd_shred precedent), tcache dedup, the
msg-length/fit guards, and fixed-shape batch assembly into a ring of
reusable slot buffers — with zero Python per frag.  Python touches the
pipeline at BATCH granularity only: hand a sealed slot's packed rows —
one contiguous (batch, row_width) uint8 view of the slot's own memory,
the one array the device program takes — to the device, and publish the
reaped frames straight from the slot's preassembled frame arena (one
fdr_publish_burst crossing).

`FDTPU_NATIVE_VERIFY=0` disables the lane; a missing toolchain (or a
missing fd_txn_parse.so) degrades to the Python intake path via
NativeUnavailable.  Differential parity with the Python lane is the
contract (tests/test_verify_native.py).
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

from firedancer_tpu.utils.nativebuild import NativeUnavailable, build_so

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
    "fd_verify.cpp",
)
_SO = os.path.join(os.path.dirname(_SRC), "fd_verify.so")

ENV_SWITCH = "FDTPU_NATIVE_VERIFY"

# slot states (fd_verify.cpp enum)
SLOT_FREE = 0
SLOT_OPEN = 1
SLOT_SEALED = 2
SLOT_INFLIGHT = 3

# why a slot was sealed (fd_verify.cpp enum; the order of
# utils/metrics.BATCH_CLOSES, whose counters the stage indexes with them)
CLOSE_FULL = 0
CLOSE_DEADLINE = 1
CLOSE_WINDOW = 2

_lib = None


def _load():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(build_so(_SRC, _SO))
        u64 = ctypes.c_uint64
        vp = ctypes.c_void_p
        lib.fdv_stage_new.argtypes = [u64, u64, u64, u64, u64, vp]
        lib.fdv_stage_new.restype = vp
        lib.fdv_stage_delete.argtypes = [vp]
        lib.fdv_frag_cb.restype = ctypes.c_int  # resolved by ADDRESS only
        lib.fdv_append.argtypes = [vp, ctypes.c_char_p, u64, u64]
        lib.fdv_append.restype = ctypes.c_int
        lib.fdv_seal.argtypes = [vp, u64]
        lib.fdv_pump.argtypes = [vp]
        lib.fdv_slot_release.argtypes = [vp, u64]
        for name in ("fdv_meta_ptr", "fdv_counters_ptr"):
            getattr(lib, name).argtypes = [vp]
            getattr(lib, name).restype = vp
        for name in ("fdv_slot_rows", "fdv_slot_frames",
                     "fdv_slot_ranges", "fdv_slot_arena"):
            getattr(lib, name).argtypes = [vp, u64]
            getattr(lib, name).restype = vp
        # the replay intake (entry batches in)
        lib.fdv_replay_new.argtypes = [u64, u64, u64, vp, u64, u64]
        lib.fdv_replay_new.restype = vp
        lib.fdv_replay_cb.restype = ctypes.c_int  # resolved by ADDRESS only
        lib.fdv_replay_append.argtypes = [vp, ctypes.c_char_p, u64, u64]
        lib.fdv_replay_append.restype = ctypes.c_int
        lib.fdv_replay_reap.argtypes = [vp, u64, vp, u64]
        lib.fdv_replay_collect.argtypes = [vp, ctypes.POINTER(u64)]
        lib.fdv_replay_collect.restype = u64
        lib.fdv_replay_out_done.argtypes = [vp, u64]
        lib.fdv_replay_held.argtypes = [vp]
        lib.fdv_replay_held.restype = u64
        for name in ("fdv_replay_arena", "fdv_replay_out_tbl",
                     "fdv_replay_counters_ptr"):
            getattr(lib, name).argtypes = [vp]
            getattr(lib, name).restype = vp
        _lib = lib
    return _lib


def enabled() -> bool:
    """The env switch: FDTPU_NATIVE_VERIFY=0 forces the Python intake."""
    return os.environ.get(ENV_SWITCH, "1") != "0"


def _parse_fn():
    """Address of fd_txn_parse — the one parser implementation, entered
    through a function pointer (no second parser to drift)."""
    from firedancer_tpu.protocol import txn_native

    lib = txn_native._load()
    return ctypes.cast(lib.fd_txn_parse, ctypes.c_void_p)


def available() -> bool:
    """enabled AND both .so's load (toolchain-less hosts degrade to the
    Python intake path gracefully)."""
    if not enabled():
        return False
    try:
        _load()
        _parse_fn()
        return True
    except (NativeUnavailable, OSError, AttributeError):
        return False


# counter tail, in fd_verify.cpp declaration order after `flags`,
# `open_elems` and `open_ns`; names match the stage's schema metrics so housekeeping
# copies them verbatim
_COUNTERS = ("filtered", "frags_in", "parse_fail", "dedup_dup",
             "msg_too_long", "too_many_sigs", "txn_in", "elems_in",
             "intake_dropped", "sealed_batches", "batch_fit_pad_lanes")
_TAIL_FLAGS = 0
_TAIL_OPEN_ELEMS = 1
_TAIL_OPEN_NS = 2
_TAIL_COUNTERS = 3

# the replay intake's counters, in fd_verify.cpp's fdv_replay
# declaration order; names match runtime/replay_verify's schema
_REPLAY_COUNTERS = (
    "entry_batches_in", "entries_in", "slots_live", "slots_dead_sig",
    "slots_dead_poh", "slots_dead_parse", "dead_slot_txn_skipped",
    "dead_slot_lanes_spent", "poh_hashes", "poh_check_ns",
    "entry_unpack_ns", "entry_batches_out", "entry_txn_out",
    "entry_txn_rejected", "verify_fail", "verify_fail_elems")
_RP_OUT_CAP = 1024      # fd_verify.cpp RP_OUT_CAP: frame-table rows
_RP_FRAG_MAX = 65536    # fd_verify.cpp RP_FRAG_MAX: the in link's mtu

# (state, n_elems, n_txn, arena_off, opened_ns, sealed_ns, close) per slot
_META_NCOL = 7

# One element's packed row (fd_verify.cpp fills it at intake; the ONE
# array a batch sends to the device):
#   msg[max_msg_len] (zero past msg_len) | sig[64] | pk[32] | msg_len u32 LE
# offsets from the end of the message, and what a row holds past it
ROW_SIG_OFF = 0
ROW_PK_OFF = 64
ROW_LEN_OFF = 96
ROW_TAIL = 100


def row_width(max_msg_len: int) -> int:
    """Bytes in one element's packed row."""
    return max_msg_len + ROW_TAIL


def row_lens(rows: np.ndarray, max_msg_len: int) -> np.ndarray:
    """The msg_len column of packed rows: a strided '<i4' view, no copy."""
    off = max_msg_len + ROW_LEN_OFF
    return rows[:, off:off + 4].view("<i4")[:, 0]


def byte_rows(rows: np.ndarray, max_msg_len: int):
    """Packed rows -> (msg (max_msg_len, B), msg_len (B,), sig (64, B),
    pk (32, B)): strided views, no copy — the four byte-row arrays of
    the kernels that do not take packed rows (the comb lane, the
    serving plane), which make their own copies."""
    tail = rows[:, max_msg_len:].T
    return (rows[:, :max_msg_len].T, row_lens(rows, max_msg_len),
            tail[ROW_SIG_OFF:ROW_SIG_OFF + 64],
            tail[ROW_PK_OFF:ROW_PK_OFF + 32])


def pack_rows(msg: np.ndarray, ln, sig: np.ndarray, pk: np.ndarray,
              batch: int | None = None) -> np.ndarray:
    """n elements, one per ROW of each array (msg (n, max_msg_len) zero
    past its length, ln (n,), sig (n, 64), pk (n, 32)) -> (batch or n,
    row_width) packed rows, the rest zero: byte for byte what the
    native intake writes for the same elements."""
    n, mm = msg.shape
    rows = np.zeros((batch or n, row_width(mm)), dtype=np.uint8)
    rows[:n, :mm] = msg
    tail = rows[:n, mm:]
    tail[:, ROW_SIG_OFF:ROW_SIG_OFF + 64] = sig
    tail[:, ROW_PK_OFF:ROW_PK_OFF + 32] = pk
    row_lens(rows, mm)[:n] = ln
    return rows


class _Owner:
    """The C stage and every buffer behind it, freed when the client
    AND the last numpy view over them are gone: a slot's rows go to the
    device as they lie (`jax.device_put` of the view, an asynchronous
    copy that keeps the view alive, not the memory under it), so the
    memory has to outlive a stage that is dropped with a copy in
    flight."""

    def __init__(self, lib, h):
        self._lib, self.h = lib, h

    def __del__(self):
        self._lib.fdv_stage_delete(self.h)


class _SlotViews:
    """Zero-copy numpy views over one slot's C buffers, built once;
    each keeps the stage's memory alive (_Owner)."""

    def __init__(self, lib, owner: _Owner, i: int, batch: int, mml: int):
        h = owner.h

        def view(ptr, n, dt):
            ct = (ctypes.c_uint8 * n) if dt == np.uint8 else \
                 (ctypes.c_uint32 * n) if dt == np.uint32 else \
                 (ctypes.c_uint64 * n)
            buf = ct.from_address(ptr)
            buf._owner = owner
            return np.frombuffer(buf, dtype=dt)

        w = row_width(mml)
        self.rows = view(lib.fdv_slot_rows(h, i), batch * w,
                         np.uint8).reshape(batch, w)
        self.ln = row_lens(self.rows, mml)  # the msg_len observe
        self.frames = view(lib.fdv_slot_frames(h, i), batch * 4,
                           np.uint64).reshape(batch, 4)
        self.ranges = view(lib.fdv_slot_ranges(h, i), batch * 2,
                           np.uint32).reshape(batch, 2)
        self.arena_ptr = int(lib.fdv_slot_arena(h, i))


class StageClient:
    """The verify stage's sweep-harness client: C-side intake + batch
    assembly over a cyclic slot ring.  Constructed by VerifyStage when
    the lane is armed (all-native rings, no plane, no comb bank);
    exposes the fdr_sweep callback address, zero-FFI slot/counters
    views, and the batch-granular control surface (seal / release /
    next sealed slot)."""

    def __init__(self, *, shard_idx: int, shard_cnt: int, batch: int,
                 max_msg_len: int, n_slots: int):
        lib = _load()
        self._lib = lib
        self.batch = batch
        self.max_msg_len = max_msg_len
        self.n_slots = n_slots
        self._h = self._new_stage(shard_idx, shard_cnt)
        if not self._h:
            raise NativeUnavailable("fdv_stage_new failed")
        owner = self._owner = _Owner(lib, self._h)
        self.cb = ctypes.cast(self._frag_cb(), ctypes.c_void_p)
        self.cb_ctx = ctypes.c_void_p(self._h)
        self.meta = np.frombuffer(
            (ctypes.c_uint64 * (n_slots * _META_NCOL)).from_address(
                int(lib.fdv_meta_ptr(self._h))),
            dtype=np.uint64,
        ).reshape(n_slots, _META_NCOL)
        n_tail = _TAIL_COUNTERS + len(_COUNTERS)
        self._tail = np.frombuffer(
            (ctypes.c_uint64 * n_tail).from_address(
                int(lib.fdv_counters_ptr(self._h))),
            dtype=np.uint64,
        )
        self.slots = [_SlotViews(lib, owner, i, batch, max_msg_len)
                      for i in range(n_slots)]
        self._next_dispatch = 0  # cyclic = the C acquire order

    def _new_stage(self, shard_idx: int, shard_cnt: int):
        return self._lib.fdv_stage_new(shard_idx, shard_cnt, self.batch,
                                       self.max_msg_len, self.n_slots,
                                       _parse_fn())

    def _frag_cb(self):
        return self._lib.fdv_frag_cb

    # -- intake surface ------------------------------------------------------

    @property
    def stash_pending(self) -> bool:
        return bool(self._tail[_TAIL_FLAGS] & 1)

    def can_accept(self) -> bool:
        """Room for at least one more txn without stashing: the sweep
        gate — when False the stage reaps/publishes first instead of
        sweeping frags it would immediately stash.  ONE u64 read (the C
        side maintains the bit); release()/pump() refresh it."""
        return bool(self._tail[_TAIL_FLAGS] & 2)

    def append(self, payload: bytes, tsorig: int) -> bool:
        """Per-frag fallback (mixed-lane / lossy splice): forward into
        the SAME C-side state the sweep callback fills.  True = handled
        now — ingested into the open slot, OR rejected-and-counted by a
        C-side guard (oversize/parse/dedup drops land in the stage
        counters, exactly like the sweep path); False = deferred to the
        C-side stash (order-preserving, drained by pump()).  Either
        way the C side fully accounts for the frag — the return is the
        BACKPRESSURE signal, not an acceptance signal (fdlint FD306: a
        signed rc must not be discarded)."""
        return self._lib.fdv_append(self._h, payload, len(payload),
                                    tsorig) == 0

    def counters(self) -> dict[str, int]:
        return {name: int(self._tail[_TAIL_COUNTERS + i])
                for i, name in enumerate(_COUNTERS)}

    # -- batch surface -------------------------------------------------------

    def open_elems(self) -> int:
        """Elements accumulated in the currently-open slot (0 = none).
        ONE u64 read (the C side maintains the word)."""
        return int(self._tail[_TAIL_OPEN_ELEMS])

    def open_since_ns(self) -> int:
        """When the open slot's first element entered it, on
        time.monotonic_ns()'s clock (the C side's own stamp, which the
        batch's open phase starts from); 0 while no slot holds elements
        — the deadline-close probe.  ONE u64 read, cheap enough for
        every pump; the stamp also names the batch, so a note about it
        cannot outlive a seal inside the crossing."""
        return int(self._tail[_TAIL_OPEN_NS])

    def seal(self, why: int) -> None:
        """Seal the open slot (no-op when it is empty); `why` is the
        stage's CLOSE_DEADLINE or CLOSE_WINDOW, handed back by
        take_sealed (a slot that filled says CLOSE_FULL itself)."""
        self._lib.fdv_seal(self._h, why)

    def sealed_waiting(self) -> bool:
        """A sealed slot is waiting for its dispatch (the next in ring
        order, so the oldest).  ONE u64 read."""
        return bool(self.meta[self._next_dispatch, 0] == SLOT_SEALED)

    def sealed_close(self) -> int:
        """What sealed the slot that is waiting (CLOSE_*; the reason
        take_sealed will hand back).  ONE u64 read."""
        return int(self.meta[self._next_dispatch, 6])

    def pump(self) -> None:
        self._lib.fdv_pump(self._h)

    def take_sealed(self) -> tuple[int, int, int, int, int, int] | None:
        """Next sealed slot in ring order as (slot idx, n_elems, n_txn,
        opened ns, sealed ns, close reason) — the two stamps are the C
        side's, on time.monotonic_ns()'s clock — marked INFLIGHT (python-owned
        until release); None when the next slot in order is not sealed —
        dispatch stays in submission order by construction."""
        i = self._next_dispatch
        row = self.meta[i]
        if row[0] != SLOT_SEALED:
            return None
        row[0] = SLOT_INFLIGHT
        self._next_dispatch = (i + 1) % self.n_slots
        return (i, int(row[1]), int(row[2]), int(row[4]), int(row[5]),
                int(row[6]))

    def release(self, slot: int) -> None:
        self._lib.fdv_slot_release(self._h, slot)

    def close(self) -> None:
        """Drop the client's views; the C stage is freed with the last
        of them (_Owner), which a device copy in flight may still hold."""
        self.meta = self._tail = None
        self.slots = []
        self._h = self._owner = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class ReplayClient(StageClient):
    """The replay intake's client (fd_verify.cpp, "The replay intake"):
    a frag is one entry batch of a received slot.  The device side is
    StageClient's — the same slots of packed rows, sealed, taken and
    released the same way; what differs is the door (fdv_replay_cb
    walks, parses, checks the PoH chain and holds the frag) and the way
    out (`reap` names the transactions a signature of which failed,
    `collect` hands back fdr_publish_burst's frame table of the held
    entry batches and verdict frames now due, in block order, over
    `held_ptr`; `out_done` returns their bytes once they are out)."""

    def __init__(self, *, batch: int, max_msg_len: int, n_slots: int):
        # held frags: room for every lane of every slot at 512 B a
        # lane (a 215 B transfer holds 218), at least 4 MiB; a record
        # for each of those lanes and as many again on their way out
        self._arena_sz = max(4 << 20, n_slots * batch * 512)
        n_recs = 1024
        while n_recs < 2 * n_slots * batch:
            n_recs *= 2
        self._n_recs = n_recs
        super().__init__(shard_idx=0, shard_cnt=1, batch=batch,
                         max_msg_len=max_msg_len, n_slots=n_slots)
        lib, h = self._lib, self._h
        self.held_ptr = int(lib.fdv_replay_arena(h))
        buf = (ctypes.c_uint64 * (_RP_OUT_CAP * 4)).from_address(
            int(lib.fdv_replay_out_tbl(h)))
        buf._owner = self._owner
        self._out_tbl = np.frombuffer(buf, dtype=np.uint64).reshape(
            _RP_OUT_CAP, 4)
        cbuf = (ctypes.c_uint64 * len(_REPLAY_COUNTERS)).from_address(
            int(lib.fdv_replay_counters_ptr(h)))
        cbuf._owner = self._owner
        self._rp_counters = np.frombuffer(cbuf, dtype=np.uint64)
        self._n_recs_out = ctypes.c_uint64(0)

    def _new_stage(self, shard_idx: int, shard_cnt: int):
        return self._lib.fdv_replay_new(self.batch, self.max_msg_len,
                                        self.n_slots, _parse_fn(),
                                        self._arena_sz, self._n_recs)

    def _frag_cb(self):
        return self._lib.fdv_replay_cb

    def append(self, payload: bytes, tsorig: int) -> bool:
        """Per-frag fallback: True = taken; False = the intake had no
        room and the frag was dropped and counted (`intake_dropped`)."""
        return self._lib.fdv_replay_append(self._h, payload, len(payload),
                                           tsorig) == 0

    def counters(self) -> dict[str, int]:
        out = super().counters()
        out.pop("dedup_dup")        # no tag cache on this path
        out.update(zip(_REPLAY_COUNTERS, self._rp_counters.tolist()))
        return out

    def emit_ready(self) -> bool:
        """The oldest held entry batch has its verdict: `collect` would
        hand something back.  ONE u64 read."""
        return bool(self._tail[_TAIL_FLAGS] & 4)

    def reap(self, slot: int, bad: np.ndarray) -> None:
        """Device batch `slot`'s mask is in: `bad` (uint32, ascending)
        are the slot's transactions a signature of which failed."""
        self._lib.fdv_replay_reap(self._h, slot, bad.ctypes.data, len(bad))

    def collect(self) -> tuple[np.ndarray, int]:
        """-> (a copy of the frame table of what is due out, how many
        held records it covers)."""
        n = self._lib.fdv_replay_collect(self._h,
                                         ctypes.byref(self._n_recs_out))
        return self._out_tbl[:n].copy(), int(self._n_recs_out.value)

    def out_done(self, n_recs: int) -> None:
        self._lib.fdv_replay_out_done(self._h, n_recs)

    def held(self) -> int:
        """Entry batches held: waiting for a verdict, or for room on
        the ring behind."""
        return int(self._lib.fdv_replay_held(self._h))

    def close(self) -> None:
        self._out_tbl = self._rp_counters = None
        super().close()
