"""ctypes binding for the native bank stage client (native/fd_bank.cpp).

The bank stage's sweep-harness lane (ISSUE 16): fdb_frag_cb runs the
whole per-microblock hot path — frame parse, fd_exec_batch2 session
exec, PoH-mixin entry build, credit-gated entry + done publish — inside
one `fdr_sweep` crossing, with zero Python per frag on the eligible
path.  The C side talks to the OTHER native modules through function
pointers (fd_exec_native.so's fd_exec_batch2, fd_ring.so's
fdr_try_publish/fdr_refresh_credits — the fd_reedsol precedent), so the
runtime and ring protocols each keep exactly one native implementation.

Python's half is the RESULT LOG: every microblock the C side touches
appends a group — its committed execution records (funk is still the
authoritative store, so writes must land there) plus, for punts and
backpressure, the raw frame for in-order Python-lane resume.
BankStage.before_credit drains it via `take_log`/`parse_log`, applies
state through SlotExecution.native_apply_rec, resumes stashes, re-syncs
the session, and `clear_log` un-freezes the lane.

`FDTPU_NATIVE_BANK=0` disables the lane; a missing toolchain degrades
to the Python bank path via NativeUnavailable.
"""

from __future__ import annotations

import ctypes
import os
import struct

import numpy as np

from firedancer_tpu.utils.nativebuild import NativeUnavailable, build_so

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
    "fd_bank.cpp",
)
_SO = os.path.join(os.path.dirname(_SRC), "fd_bank.so")

ENV_SWITCH = "FDTPU_NATIVE_BANK"

_lib = None


def _load():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(build_so(_SRC, _SO))
        u64 = ctypes.c_uint64
        vp = ctypes.c_void_p
        cp = ctypes.c_char_p
        lib.fdb_stage_new.argtypes = [
            vp, vp, vp, vp, vp, vp, vp, vp, u64, cp, u64,
        ]
        lib.fdb_stage_new.restype = vp
        lib.fdb_stage_delete.argtypes = [vp]
        lib.fdb_stage_flags_off.restype = u64
        lib.fdb_stage_set_hdr.argtypes = [vp, cp, u64]
        lib.fdb_stage_set_hdr.restype = ctypes.c_int
        lib.fdb_stage_set_funk.argtypes = [vp, vp, vp, vp, cp, u64]
        lib.fdb_stage_set_funk.restype = ctypes.c_int
        lib.fdb_stage_set_funk_shared.argtypes = [vp, vp, vp, vp, vp]
        lib.fdb_stage_set_metrics.argtypes = [vp, vp]
        lib.fdb_log_ptr.argtypes = [vp]
        lib.fdb_log_ptr.restype = vp
        lib.fdb_log_clear.argtypes = [vp]
        # fdb_frag_cb is resolved by ADDRESS for fdr_sweep, never called
        # from Python
        lib.fdb_frag_cb.restype = ctypes.c_int
        _lib = lib
    return _lib


def enabled() -> bool:
    """The env switch: FDTPU_NATIVE_BANK=0 forces the Python lane."""
    return os.environ.get(ENV_SWITCH, "1") != "0"


def available() -> bool:
    """enabled AND the .so loads (builds on demand; toolchain-less hosts
    degrade gracefully to the Python bank path)."""
    if not enabled():
        return False
    try:
        _load()
        return True
    except (NativeUnavailable, OSError, AttributeError):
        return False


def make_hdr(batch_ctx, *, gated: bool) -> bytes:
    """The FDX2 prefix the C side stamps into every request: the
    BatchContext env blob (lps, clock, slot hashes, recent blockhash,
    rent) + the steady-state gate section (flag 2 = keep the session's
    valid set, zero seen/refresh records — deltas ride the Python-side
    sync crossings instead)."""
    flag = 2 if gated else 0
    return bytes(batch_ctx._fixed) + struct.pack("<BIII", flag, 0, 0, 0)


# BankStageCtx flag+counter tail, in declaration order after log_sz; the
# offset comes from the C side (fdb_stage_flags_off) so the zero-FFI
# view can never drift from the struct layout
_COUNTERS = ("bank_mb_seen", "bank_mb_native", "bank_mb_stashed",
             "bank_txn_native", "bank_credit_waits", "bank_mb_dropped",
             "bank_funk_writes", "bank_funk_falls", "session_refreshed")

_GROUP_HEAD = struct.Struct("<QQQIBI")
_REC_HEAD = struct.Struct("<bQB")  # status | fee | n_writes


def parse_log(log: bytes) -> list:
    """Decode a drained result log into groups of
    (mb_seq, tsorig, lat_ns, n_done, published, recs, mb_raw) where
    recs = [(status, fee, [(acct_idx, value)])] — the fd_exec_batch2
    response records verbatim (writes is an empty tuple for stripped
    records), and mb_raw is the original microblock frame
    (runtime/bank.parse_microblock format)."""
    groups = []
    off = 0
    n = len(log)
    while off < n:
        mb_seq, tsorig, lat_ns, n_done, published, mb_sz = \
            _GROUP_HEAD.unpack_from(log, off)
        off += _GROUP_HEAD.size
        recs = []
        rec_unpack = _REC_HEAD.unpack_from
        for _ in range(n_done):
            status, fee, n_w = rec_unpack(log, off)
            off += 10
            if n_w:
                writes = []
                for _ in range(n_w):
                    idx = log[off]
                    vlen = int.from_bytes(log[off + 1:off + 5], "little")
                    off += 5
                    writes.append((idx, log[off:off + vlen]))
                    off += vlen
            else:
                # the native funk lane strips every record: share one
                # empty tuple instead of allocating a list per txn
                writes = ()
            recs.append((status, fee, writes))
        groups.append((mb_seq, tsorig, lat_ns, n_done, published,
                       recs, log[off:off + mb_sz]))
        off += mb_sz
    return groups


class StageClient:
    """The bank stage's sweep-harness client.  Constructed by BankStage
    when the lane is armed (exec session live AND both out producers
    native); exposes the fdr_sweep callback address, the result-log
    drain, and cheap struct reads for the stall flag + counters."""

    def __init__(self, session, hdr: bytes, ent_producer, done_producer,
                 *, bank_idx: int):
        from firedancer_tpu.flamenco import exec_native as fx
        from firedancer_tpu.tango import native as fn

        lib = _load()
        ring = fn._load()
        xlib = fx._load()
        self._lib = lib
        self._session = session          # keep the exec session alive
        self._ent_prod = ent_producer    # keep the NativeProducers alive
        self._done_prod = done_producer
        self._h = lib.fdb_stage_new(
            ctypes.c_void_p(session._h),
            ctypes.cast(xlib.fd_exec_batch2, ctypes.c_void_p),
            ctypes.cast(ent_producer._lsp, ctypes.c_void_p),
            ctypes.cast(ent_producer._pp, ctypes.c_void_p),
            ctypes.cast(done_producer._lsp, ctypes.c_void_p),
            ctypes.cast(done_producer._pp, ctypes.c_void_p),
            ctypes.cast(ring.fdr_try_publish, ctypes.c_void_p),
            ctypes.cast(ring.fdr_refresh_credits, ctypes.c_void_p),
            bank_idx, hdr, len(hdr),
        )
        if not self._h:
            raise NativeUnavailable("fdb_stage_new failed")
        self.cb = ctypes.cast(lib.fdb_frag_cb, ctypes.c_void_p)
        self.cb_ctx = ctypes.c_void_p(self._h)
        # zero-FFI reads: a u64 view over the ctx struct's flags+counters
        n_tail = 2 + len(_COUNTERS)
        self._tail = np.frombuffer(
            (ctypes.c_uint64 * n_tail).from_address(
                self._h + int(lib.fdb_stage_flags_off())
            ),
            dtype=np.uint64,
        )

    @property
    def log_sz(self) -> int:
        return int(self._tail[0])

    @property
    def stash_pending(self) -> bool:
        return bool(self._tail[1])

    def counters(self) -> dict[str, int]:
        return {name: int(self._tail[2 + i])
                for i, name in enumerate(_COUNTERS)}

    def set_hdr(self, hdr: bytes) -> None:
        """Re-stamp the env/gate prefix (slot roll: new clock + recent
        blockhash arm a fresh request header)."""
        if not self._lib.fdb_stage_set_hdr(self._h, hdr, len(hdr)):
            raise NativeUnavailable("fdb_stage_set_hdr failed")

    def set_funk(self, funk, xid: bytes | None) -> None:
        """Arm (or disarm: funk/xid None) the native funk plane: the C
        side writes committed records slot-direct into `funk`'s shm map
        and strips write payloads from the result log.  Called alongside
        set_hdr at every slot roll — the xid is the slot's funk fork.
        With it go the segment's lock (one hold a microblock's records)
        and, for a store that other processes write too, the
        read-through of every account a microblock names (fd_bank.cpp)."""
        if funk is None or xid is None:
            rc = self._lib.fdb_stage_set_funk(self._h, None, None, None,
                                              None, 0)
            self._lib.fdb_stage_set_funk_shared(self._h, None, None, None,
                                                None)
        else:
            from firedancer_tpu.funk import funk_native as fk

            flib = fk._load()
            rc = self._lib.fdb_stage_set_funk(
                self._h, ctypes.c_void_p(funk._h),
                ctypes.cast(flib.ffk_txn_slot, ctypes.c_void_p),
                ctypes.cast(flib.ffk_rec_insert_slot, ctypes.c_void_p),
                xid, len(xid),
            )
            self._lib.fdb_stage_set_funk_shared(
                self._h, *(ctypes.cast(getattr(flib, name), ctypes.c_void_p)
                           for name in ("ffk_lock", "ffk_unlock",
                                        "ffk_writers", "ffk_rec_read_slot")))
        if rc == 0:
            raise NativeUnavailable("fdb_stage_set_funk failed")

    def set_metrics(self, plane) -> None:
        """Arm the shm metrics plane (ISSUE 20): apply/publish brackets
        inside fdb_frag_cb accumulate into the SAME fdm_plane the sweep
        harness hands fdr_sweep, and per-txn commit latency observes
        into the stage's nbank_txn_lat_ns histogram in-crossing."""
        self._plane = plane  # keepalive: C holds the raw pointer
        self._lib.fdb_stage_set_metrics(
            self._h, plane.ptr if plane is not None else None)

    def take_log(self) -> bytes:
        """Copy out the pending result log (empty bytes when idle).
        Does NOT clear: call clear_log after the drain is fully applied
        — clearing is what un-freezes the native path."""
        sz = int(self._tail[0])
        if not sz:
            return b""
        return ctypes.string_at(self._lib.fdb_log_ptr(self._h), sz)

    def clear_log(self) -> None:
        self._lib.fdb_log_clear(self._h)

    def close(self) -> None:
        if self._h:
            self._tail = None
            self._lib.fdb_stage_delete(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
