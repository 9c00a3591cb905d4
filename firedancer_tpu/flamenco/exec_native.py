"""ctypes binding for the native executor fast lane (native/fd_exec_native.cpp).

The bank stage's per-microblock hot path: a drained burst of verified
frags goes through ONE fd_exec_batch call — payloads + packed descriptors
(the verify stage's trailer, fd_txn_parse's layout) + current funk values
in, record writes + per-txn (status, fee) out.  The FFI crossing
amortizes over the burst the same way stage.py's burst draining amortized
loop overhead (fdlint FD207 enforces that discipline).

Parity and fallback contract:

  - `eligible_packed` is the Executor's routing classifier: a txn whose
    every instruction is in the native subset (the full system surface
    including the durable-nonce family, stake ops, vote vote/
    vote_state_update/tower_sync, and the compute-budget instructions,
    whose limit and priority fee the lane applies) routes native; CPI, BPF, lookup
    tables and unsupported variants go through the Python lane
    byte-for-byte.
  - the C++ side may still PUNT any txn it is not sure about (old vote
    state versions, arithmetic Python's big ints would survive, bounds
    surprises); the batch stops before that txn mutates anything and the
    caller re-runs it in Python, then resubmits the remainder.
  - `FDTPU_NATIVE_EXEC=0` disables the lane; a missing toolchain degrades
    to the Python lane via NativeUnavailable (skip, never fail).
"""

from __future__ import annotations

import ctypes
import os
import struct

from firedancer_tpu.pack.cost import COMPUTE_BUDGET_PROGRAM
from firedancer_tpu.utils.nativebuild import NativeUnavailable, build_so
from firedancer_tpu.protocol.txn import (
    SYSTEM_PROGRAM,
    VOTE_PROGRAM,
    _DESC_HDR,
    _DESC_INSTR,
)

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
    "fd_exec_native.cpp",
)
_SO = os.path.join(os.path.dirname(_SRC), "fd_exec_native.so")

ENV_SWITCH = "FDTPU_NATIVE_EXEC"

_REQ_MAGIC = 0x42584446  # 'FDXB'
_REQ2_MAGIC = 0x32584446  # 'FDX2' (session + native gate)
_RESP_MAGIC = 0x52584446  # 'FDXR'

_U32 = struct.Struct("<I")
_TXN_HEAD = struct.Struct("<HHB")
_REC_HEAD = struct.Struct("<bQB")

_lib = None


def _load():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(build_so(_SRC, _SO))
        lib.fd_exec_batch.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.fd_exec_batch.restype = ctypes.c_int64
        lib.fd_exec_session_new.restype = ctypes.c_void_p
        lib.fd_exec_session_delete.argtypes = [ctypes.c_void_p]
        lib.fd_exec_batch2.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.fd_exec_batch2.restype = ctypes.c_int64
        _lib = lib
    return _lib


class Session:
    """One slot's native execution session (native/fd_exec_native.cpp
    Session): the status-cache gate (valid blockhashes + landed
    (blockhash, signature) pairs) and the cross-microblock account-value
    overlay live on the C++ side, so the per-txn Python gate and the
    per-call funk value marshalling disappear from the bank hot path."""

    def __init__(self):
        self._lib = _load()
        self._h = self._lib.fd_exec_session_new()
        if not self._h:
            raise NativeUnavailable("fd_exec_session_new failed")

    def close(self) -> None:
        if self._h:
            self._lib.fd_exec_session_delete(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def enabled() -> bool:
    """The env switch: FDTPU_NATIVE_EXEC=0 forces the Python lane."""
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
        # AttributeError: a stale/foreign .so that CDLL loads but that
        # lacks fd_exec_batch must degrade, not kill the bank stage
        return False


# -- eligibility classifier ----------------------------------------------------

_HDR_SZ = _DESC_HDR.size  # 17
_INSTR_SZ = _DESC_INSTR.size  # 9

# VoteInstruction tags the native lane executes (Vote/VoteSwitch,
# UpdateVoteState(Switch), TowerSync(Switch))
NATIVE_VOTE_TAGS = frozenset((2, 6, 8, 9, 14, 15))
# the stake program address (flamenco/stake.py STAKE_PROGRAM)
_STAKE_PROGRAM = b"Stake11111" + bytes(22)


def eligible_packed(payload: bytes, desc_bytes: bytes) -> bool:
    """May this txn route native?  Works on the packed descriptor so the
    zero-copy bank path never unpacks a Txn object for native traffic.
    Conservative by design: the C++ side re-checks and punts."""
    if len(desc_bytes) < _HDR_SZ or desc_bytes[13] != 0:  # lut_cnt
        return False
    acct_cnt = desc_bytes[8]
    acct_off = desc_bytes[9] | (desc_bytes[10] << 8)
    o = _HDR_SZ
    for _ in range(desc_bytes[16]):  # instr_cnt
        prog, _acnt, dsz, _aoff, doff = _DESC_INSTR.unpack_from(desc_bytes, o)
        o += _INSTR_SZ
        if prog >= acct_cnt:
            return False
        pa = acct_off + 32 * prog
        pk = payload[pa : pa + 32]
        if pk == SYSTEM_PROGRAM or pk == _STAKE_PROGRAM \
                or pk == COMPUTE_BUDGET_PROGRAM:
            # the whole native surface, durable-nonce family included
            # (the session's in-line durable gate owns the stale-
            # blockhash decision); stake tags 0..4 execute, others
            # no-op; a compute-budget instruction touches no account
            # (a malformed one punts)
            pass
        elif pk == VOTE_PROGRAM:
            if dsz >= 4:
                tag = int.from_bytes(payload[doff : doff + 4], "little")
                if tag not in NATIVE_VOTE_TAGS:
                    return False
            # dsz < 4: both lanes fail the txn with the same status
        else:
            return False  # BPF / other builtins / unknown programs
    return True


# -- batch runner --------------------------------------------------------------


class BatchContext:
    """One slot's native execution context: the request header (fee rate,
    clock, slot-hashes sysvar) prebuilt once, reused per microblock."""

    def __init__(
        self,
        *,
        lamports_per_sig: int,
        clock_slot: int | None = None,
        clock_epoch: int | None = None,
        slot_hashes: bytes | None = None,
        session: Session | None = None,
        recent_blockhash: bytes | None = None,
        rent: tuple[int, int, float] | None = None,
    ):
        self._lib = _load()
        self._session = session
        sh = bytes(slot_hashes or b"")
        rbh = bytes(recent_blockhash or b"")
        # (flag, lamports_per_byte_year, exemption_threshold); flag 2 =
        # the rent sysvar blob exists but does not decode — the C++ side
        # punts nonce partial withdraws instead of guessing a floor
        rent_flag, rent_lpby, rent_et = rent if rent is not None \
            else (1, 3480, 2.0)
        self._fixed = (
            struct.pack(
                "<QBQQB",
                lamports_per_sig,
                1 if clock_slot is not None else 0,
                clock_slot or 0,
                clock_epoch or 0,
                1 if sh else 0,
            )
            + _U32.pack(len(sh))
            + sh
            + struct.pack("<B32sBQd", 1 if rbh else 0, rbh,
                          rent_flag, rent_lpby, rent_et)
        )
        # request arena + response buffer, REUSED across microblocks
        # (ISSUE 11 bank-lane residual): the session path marshals with
        # pack_into/slice-assign into one bytearray instead of building
        # ~6 bytes objects per txn and joining per call — the ~5 us/txn
        # of Python allocation around fd_exec_batch2.  Lazily built:
        # only the session hot path uses them.
        self._arena: bytearray | None = None
        self._arena_view = None
        self._resp_cap = 1 << 16
        self._resp = None

    def _ensure_arena(self, need: int) -> None:
        if self._arena is None or need > len(self._arena):
            cap = 1 << 16 if self._arena is None else len(self._arena)
            while cap < need:
                cap *= 2
            self._arena_view = None  # drop the old from_buffer pin first
            self._arena = bytearray(cap)
            self._arena_view = (ctypes.c_char * cap).from_buffer(self._arena)
        if self._resp is None:
            self._resp = ctypes.create_string_buffer(self._resp_cap)

    def run(self, entries, *, gate=None, refresh=None) -> tuple[int, bool, list]:
        """One fd_exec_batch(2) call.  entries: [payload, desc_bytes,
        addrs, vals, ...] lists — only the first four fields are read
        here.  Returns (n_done, punted, [(status, fee, [(idx, value)])]).

        Session mode (constructed with one): vals entries may be None,
        meaning "the session already holds this account's current value"
        — only first-touch/dirtied values cross the FFI (Python-lane
        writes resync the same way: the dirty set forces the next touch
        to ship a fresh have=1 value).  `gate` arms the native
        status-cache gate: (valid_blockhashes | None = unchanged,
        seen_delta) where seen_delta is an iterable of 96-byte
        blockhash||signature entries landed OUTSIDE the session since
        the last call.  `refresh` (session mode) is an iterable of
        (key, value) records merged into the session overlay before any
        txn runs — the bank sweep's dirty-account resync, which has no
        per-txn have=1 slot to ride."""
        if self._session is not None:
            return self._run_session_arena(entries, gate, refresh)
        parts = [struct.pack("<II", _REQ_MAGIC, len(entries)), self._fixed]
        req_sz = 0
        for e in entries:
            payload, desc_bytes, _addrs, vals = e[0], e[1], e[2], e[3]
            parts.append(_TXN_HEAD.pack(len(payload), len(desc_bytes),
                                        len(vals)))
            parts.append(payload)
            parts.append(desc_bytes)
            for v in vals:
                v = v or b""
                parts.append(_U32.pack(len(v)))
                parts.append(v)
                req_sz += len(v)
            req_sz += len(payload) + 64
        req = b"".join(parts)
        cap = 4096 + 2 * req_sz
        while True:
            buf = ctypes.create_string_buffer(cap)
            rc = self._lib.fd_exec_batch(req, len(req), buf, cap)
            if rc == -2:
                # a CreateAccount/Allocate burst can outgrow the heuristic
                # capacity; the call did not commit (v1 is stateless, v2
                # commits only after serializing), so retry bigger
                cap *= 4
                if cap > 1 << 28:
                    raise NativeUnavailable("fd_exec_batch response > 256MB")
                continue
            if rc < 0:
                raise NativeUnavailable(f"fd_exec_batch rc={rc}")
            return self._parse(buf.raw[:rc])

    def _run_session_arena(self, entries, gate,
                           refresh=None) -> tuple[int, bool, list]:
        """Session-mode crossing through the preallocated request arena:
        one capacity pass (plain int sums), then pack_into/slice-assign
        into the reused bytearray — no per-txn bytes construction, no
        per-call join, no per-call response allocation."""
        fixed = self._fixed
        # -- capacity pass ----------------------------------------------------
        need = 8 + len(fixed) + 5 + 4 + 4  # headers + gate flag + counts
        if gate is not None:
            valid_bh, seen_delta = gate
            if valid_bh is not None:
                need += 32 * len(valid_bh)
            need += 96 * len(seen_delta)
        if refresh:
            for _k, v in refresh:
                need += 36 + len(v)
        for e in entries:
            need += _TXN_HEAD.size + len(e[0]) + len(e[1])
            for v in e[3]:
                need += 1 if v is None else 5 + len(v)
        self._ensure_arena(need)
        a = self._arena
        # -- serialize --------------------------------------------------------
        struct.pack_into("<II", a, 0, _REQ2_MAGIC, len(entries))
        o = 8
        a[o : o + len(fixed)] = fixed
        o += len(fixed)
        if gate is not None:
            valid_bh, seen_delta = gate
            if valid_bh is None:
                # gate on, valid set unchanged since last shipped
                # (flag 2): the session keeps its current set
                a[o] = 2
                struct.pack_into("<I", a, o + 1, 0)
                o += 5
            else:
                a[o] = 1
                struct.pack_into("<I", a, o + 1, len(valid_bh))
                o += 5
                for bh in valid_bh:
                    a[o : o + 32] = bh
                    o += 32
            struct.pack_into("<I", a, o, len(seen_delta))
            o += 4
            for s in seen_delta:
                a[o : o + 96] = s
                o += 96
        else:
            a[o] = 0
            struct.pack_into("<II", a, o + 1, 0, 0)
            o += 9
        # refresh records: session-overlay merges with no txn to ride
        # (the bank sweep's dirty-account resync); empty on the
        # execute_batch path, whose per-txn have=1 values carry resyncs
        struct.pack_into("<I", a, o, len(refresh) if refresh else 0)
        o += 4
        if refresh:
            for k, v in refresh:
                a[o : o + 32] = k
                struct.pack_into("<I", a, o + 32, len(v))
                o += 36
                a[o : o + len(v)] = v
                o += len(v)
        for e in entries:
            payload, desc_bytes, vals = e[0], e[1], e[3]
            _TXN_HEAD.pack_into(a, o, len(payload), len(desc_bytes),
                                len(vals))
            o += _TXN_HEAD.size
            a[o : o + len(payload)] = payload
            o += len(payload)
            a[o : o + len(desc_bytes)] = desc_bytes
            o += len(desc_bytes)
            for v in vals:
                if v is None:  # session-known: nothing crosses
                    a[o] = 0
                    o += 1
                else:
                    a[o] = 1
                    struct.pack_into("<I", a, o + 1, len(v))
                    o += 5
                    a[o : o + len(v)] = v
                    o += len(v)
        # -- the crossing (response buffer reused; grown on -2) ---------------
        while True:
            rc = self._lib.fd_exec_batch2(self._session._h, self._arena_view,
                                          o, self._resp, self._resp_cap)
            if rc == -2:
                self._resp_cap *= 4
                if self._resp_cap > 1 << 28:
                    raise NativeUnavailable("fd_exec_batch response > 256MB")
                self._resp = ctypes.create_string_buffer(self._resp_cap)
                continue
            if rc < 0:
                raise NativeUnavailable(f"fd_exec_batch rc={rc}")
            return self._parse(ctypes.string_at(self._resp, rc))

    @staticmethod
    def _parse(buf: bytes) -> tuple[int, bool, list]:
        magic, n_done = struct.unpack_from("<II", buf, 0)
        if magic != _RESP_MAGIC:
            raise NativeUnavailable("fd_exec_batch bad response magic")
        punted = buf[8] != 0
        o = 9
        out = []
        for _ in range(n_done):
            status, fee, n_w = _REC_HEAD.unpack_from(buf, o)
            o += _REC_HEAD.size
            writes = []
            for _ in range(n_w):
                idx = buf[o]
                (vlen,) = _U32.unpack_from(buf, o + 1)
                o += 5
                writes.append((idx, buf[o : o + vlen]))
                o += vlen
            out.append((status, fee, writes))
        return n_done, punted, out
