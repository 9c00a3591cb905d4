"""ctypes facade for the native pack scheduler (native/fd_pack.cpp).

The pack stage's hot path: verified frags go into the pool through ONE
`fd_pack_insert_burst` crossing per drained burst (FD207 discipline,
the fd_exec_batch shape), and each `fd_pack_schedule` crossing returns a
complete ready-to-publish microblock frame — Python never touches
per-txn descriptors, cost arithmetic, or conflict sets on this lane.

Fused dedup: `attach_tcache` wires an existing `tango/tcache_native.
NativeTCache` (the same fd_tcache.so structure the dedup stage uses)
into the insert path, so duplicate txns are dropped inside the same
crossing and never surface into Python at all.

Parity contract: byte-identical microblock frames, identical evictions,
counts (`stat_*`) and end_block accounting vs `pack/scheduler.py` + identical drop sets
vs the DedupStage->PackStage python lane (tests/test_pack_native.py).
`FDTPU_NATIVE_PACK=0` disables the lane; a missing toolchain degrades
to the Python lane via NativeUnavailable (skip, never fail).
"""

from __future__ import annotations

import ctypes
import os

from firedancer_tpu.utils.nativebuild import NativeUnavailable, build_so
from . import cost as fc

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
    "fd_pack.cpp",
)
_SO = os.path.join(os.path.dirname(_SRC), "fd_pack.so")

ENV_SWITCH = "FDTPU_NATIVE_PACK"

# insert result codes (native/fd_pack.cpp INS_*)
INS_OK = 0        # accepted into the pool
INS_DUP = 1       # fused-dedup tcache hit
INS_REJECT = 2    # malformed compute-budget cost
INS_SIG_DUP = 3   # first signature already pooled
INS_BAD_FRAG = 4  # frag/descriptor fails validation
INS_FULL = 5     # pool full, newcomer loses

_lib = None


def _load():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(build_so(_SRC, _SO))
        u64, i64, vp = ctypes.c_uint64, ctypes.c_int64, ctypes.c_void_p
        lib.fd_pack_new.restype = vp
        lib.fd_pack_new.argtypes = [u64] * 8
        lib.fd_pack_delete.argtypes = [vp]
        lib.fd_pack_set_tcache.argtypes = [vp, vp, vp]
        lib.fd_pack_insert_burst.restype = i64
        lib.fd_pack_insert_burst.argtypes = [
            vp, ctypes.c_char_p, u64, u64, ctypes.c_char_p,
            ctypes.POINTER(u64),
        ]
        lib.fd_pack_pending_cnt.restype = u64
        lib.fd_pack_pending_cnt.argtypes = [vp]
        lib.fd_pack_block_state.argtypes = [vp, ctypes.POINTER(u64)]
        lib.fd_pack_schedule.restype = i64
        lib.fd_pack_schedule.argtypes = [
            vp, u64, ctypes.c_uint32, ctypes.c_char_p, u64,
            ctypes.POINTER(u64),
        ]
        lib.fd_pack_microblock_done.argtypes = [vp, u64]
        lib.fd_pack_end_block.argtypes = [vp]
        lib.fd_pack_shed.restype = u64
        lib.fd_pack_shed.argtypes = [vp, u64, ctypes.POINTER(u64)]
        lib.fd_pack_cost_probe.restype = i64
        lib.fd_pack_cost_probe.argtypes = [
            ctypes.c_char_p, u64, ctypes.c_char_p, u64, ctypes.POINTER(u64),
        ]
        _lib = lib
    return _lib


def enabled() -> bool:
    """The env switch: FDTPU_NATIVE_PACK=0 forces the Python lane."""
    return os.environ.get(ENV_SWITCH, "1") != "0"


def available() -> bool:
    """enabled AND the .so loads (builds on demand; toolchain-less or
    .so-less hosts degrade gracefully to the Python lane)."""
    if not enabled():
        return False
    try:
        _load()
        return True
    except (NativeUnavailable, OSError, AttributeError):
        # AttributeError: a stale/foreign .so that CDLL loads but lacks
        # the pack exports must degrade, not kill the pack stage
        return False


def cost_probe(payload: bytes, desc_bytes: bytes):
    """Differential hook: the native cost model's (total, rewards,
    is_simple_vote) for one (payload, packed-descriptor) pair, or None
    when the native side rejects it (-1 invalid desc, -2 malformed
    compute budget; the caller distinguishes via the second element)."""
    lib = _load()
    out = (ctypes.c_uint64 * 4)()
    rc = lib.fd_pack_cost_probe(payload, len(payload), desc_bytes,
                                len(desc_bytes), out)
    if rc != 0:
        return (int(rc), None, None)
    rewards = int(out[1]) | (int(out[2]) << 64)
    return (0, (int(out[0]), rewards), bool(out[3]))


class NativePack:
    """One native pack pool; mirrors pack/scheduler.Pack's lifecycle
    (insert / schedule_next_microblock / microblock_done / end_block)
    at burst granularity."""

    FRAME_CAP = 65536  # pack->bank link mtu

    def __init__(
        self,
        *,
        bank_cnt: int = 4,
        depth: int = 4096,
        max_txn_per_microblock: int = 31,
        max_schedule_search: int = 256,
        limits=None,
    ):
        lib = _load()
        lim = limits
        self._lib = lib
        self._h = lib.fd_pack_new(
            bank_cnt, depth, max_txn_per_microblock, max_schedule_search,
            getattr(lim, "max_cost_per_block", fc.MAX_COST_PER_BLOCK),
            getattr(lim, "max_vote_cost_per_block", fc.MAX_VOTE_COST_PER_BLOCK),
            getattr(lim, "max_write_cost_per_acct", fc.MAX_WRITE_COST_PER_ACCT),
            getattr(lim, "max_data_bytes_per_block", fc.MAX_DATA_PER_BLOCK),
        )
        if not self._h:
            raise NativeUnavailable("fd_pack_new failed")
        self.bank_cnt = bank_cnt
        self.depth = depth
        self._frame_buf = ctypes.create_string_buffer(self.FRAME_CAP)
        self._meta = (ctypes.c_uint64 * 9)()
        self._stats_out = (ctypes.c_uint64 * 6)()
        # pool size as of the last crossing: every insert_burst/schedule
        # reports it (with Pack's five stat_* counts behind it), so the
        # stage's scheduling policy never pays a dedicated
        # fd_pack_pending_cnt crossing per loop iteration
        self.last_pending = 0
        self.stat_evicted = 0
        self.stat_dropped_votes = 0
        self.stat_votes_dropped_regular_pending = 0
        self.stat_scheduled_votes = 0
        self.stat_conflict_skips = 0
        # keep the tcache object alive: the native side holds raw pointers
        self._tcache = None

    def attach_tcache(self, tcache) -> None:
        """Fuse dedup into the insert crossing: `tcache` is a
        tango/tcache_native.NativeTCache (the existing fd_tcache.so
        structure); its handle + insert entry point are wired straight
        into fd_pack_insert_burst's probe."""
        self._tcache = tcache
        insert_fn = ctypes.cast(tcache._lib.tcache_insert, ctypes.c_void_p)
        self._lib.fd_pack_set_tcache(
            self._h, ctypes.c_void_p(tcache._h), insert_fn
        )

    def _take_stats(self, out, at: int) -> None:
        """fd_pack.cpp write_stats: [pending, evicted, dropped_votes,
        votes_dropped_while_regular_pending, scheduled_votes,
        conflict_skips] at out[at:]."""
        (self.last_pending, self.stat_evicted, self.stat_dropped_votes,
         self.stat_votes_dropped_regular_pending, self.stat_scheduled_votes,
         self.stat_conflict_skips) = out[at:at + 6]

    def insert_burst(self, entries) -> bytes:
        """One crossing for a burst of verified frags.

        entries: list of (frag_bytes, tag, tsorig) where frag is the
        verify stage's payload||packed-desc||u16 layout unchanged and
        tag the 64-bit dedup tag riding the frag's mcache sig column.
        Returns the per-frag INS_* code bytes."""
        n = len(entries)
        parts = []
        for frag, tag, tsorig in entries:
            parts.append(len(frag).to_bytes(2, "little"))
            parts.append((tag & (2**64 - 1)).to_bytes(8, "little"))
            parts.append((tsorig & (2**64 - 1)).to_bytes(8, "little"))
            parts.append(frag)
        buf = b"".join(parts)
        codes = ctypes.create_string_buffer(max(n, 1))
        rc = self._lib.fd_pack_insert_burst(self._h, buf, len(buf), n, codes,
                                            self._stats_out)
        if rc != n:
            raise NativeUnavailable(f"fd_pack_insert_burst rc={rc}")
        self._take_stats(self._stats_out, 0)
        return codes.raw[:n]

    def schedule(self, bank: int, *, mb_seq: int = 0):
        """-> (frame_bytes, txn_cnt, cu, tsorig) or None when nothing is
        schedulable.  The frame is publish-ready (u32 mb_seq | u16 cnt |
        (u16 len || frag)*), byte-identical to the Python lane's _emit:
        votes first up to their share, then the regular pool
        (Pack.schedule_next_microblock), in ONE crossing."""
        rc = self._lib.fd_pack_schedule(
            self._h, bank, mb_seq & 0xFFFFFFFF,
            self._frame_buf, self.FRAME_CAP, self._meta,
        )
        if rc < 0:
            raise NativeUnavailable(f"fd_pack_schedule rc={rc}")
        self._take_stats(self._meta, 3)
        if rc == 0:
            return None
        return (
            self._frame_buf.raw[:rc],
            int(self._meta[0]),
            int(self._meta[1]),
            int(self._meta[2]),
        )

    def microblock_done(self, bank: int) -> None:
        self._lib.fd_pack_microblock_done(self._h, bank)

    def end_block(self) -> None:
        self._lib.fd_pack_end_block(self._h)

    def shed_lowest(self, n: int) -> int:
        """Pack.shed_lowest parity: drop up to n lowest-priority pending
        regular txns in ONE crossing (votes never shed); the post-op
        pool size piggybacks so the policy stays zero-FFI."""
        shed = int(self._lib.fd_pack_shed(self._h, n, self._stats_out))
        self.last_pending = int(self._stats_out[0])
        return shed

    def pending_cnt(self) -> int:
        return int(self._lib.fd_pack_pending_cnt(self._h))

    def block_state(self) -> tuple[int, int, int]:
        """(cost_used, vote_cost_used, data_bytes_used) — test hook."""
        out = (ctypes.c_uint64 * 3)()
        self._lib.fd_pack_block_state(self._h, out)
        return int(out[0]), int(out[1]), int(out[2])

    def close(self) -> None:
        if self._h:
            self._lib.fd_pack_delete(self._h)
            self._h = None

    def __del__(self):  # belt-and-braces; close() is the real API
        try:
            self.close()
        except Exception:
            pass
