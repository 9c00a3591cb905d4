"""ctypes binding for the native net sweep client (native/fd_net.cpp).

The ingress stage's QUIC short-header steady state in one FFI crossing
per sweep of datagrams (ISSUE 18; a sweep since ISSUE 46: one recvmmsg
takes what the socket holds, up to the stage's rx_burst, into a receive
arena): DCID -> connection lookup over the interned table, header-protection unmask, AES-128-GCM open (AES-NI + PCLMUL with
a scalar fallback, byte-identical to ops/aes.py), packet-number dedup,
STREAM frame walk and fd_tpu_reasm-style reassembly.  Whole txns land in
a reusable out arena with an (off, sz, sig, tsorig) table shaped for
fdr_publish_burst; the credit-gated publish retires only the published
prefix (`pop`), the unpublished tail stays queued in C — never dropped.

Everything the C side cannot fully own PUNTs back to the Python lane in
arrival order (long headers, unknown CIDs, migration, CRYPTO /
PATH_CHALLENGE / PATH_RESPONSE / CONNECTION_CLOSE / HANDSHAKE_DONE /
multi-range-ACK frame mixes): waltz/quic.py stays the single source of
truth for the control plane.  The binding is RX-only — consumed packets
surface as events (pn sync, single-range acks, flow-window deltas) the
stage replays into the authoritative Python Connection after every
crossing.

`FDTPU_NATIVE_NET=0` disables the lane; a missing toolchain degrades to
the Python per-datagram path via NativeUnavailable.  Differential parity
with the Python lane is the contract (tests/test_net_native.py).
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

from firedancer_tpu.utils.nativebuild import NativeUnavailable, build_so

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
    "fd_net.cpp",
)
_SO = os.path.join(os.path.dirname(_SRC), "fd_net.so")

ENV_SWITCH = "FDTPU_NATIVE_NET"

# fdn_quic_sweep's returns below zero (fd_net.cpp enum); >= 0 is the
# receive-arena slot of a datagram the C lane punts
SWEEP_DONE = -1   # every datagram of the arena processed
SWEEP_FULL = -2   # stopped for want of headroom: drain, call again

PEER_KEY_LEN = 20  # NET_PEER_KEY: family | port | address, zero-padded

# event rows (type, conn_idx, a, b)
EV_PKT = 1   # a = pn, b = flag (0 ack-eliciting, 1 dup, 2 bad-frame, 3 pure-ack)
EV_ACK = 2   # a = largest, b = first_range_len
EV_WIN = 3   # a = rx_consumed delta, b = rx_data_total delta
EV_RETIRE = 4  # a = stream id, b = 1 oversize / 2 slot stolen: no txn came

_EV_CAP = 4096
_OUT_CAP = 1024

_lib = None


def _load():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(build_so(_SRC, _SO))
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        u64 = ctypes.c_uint64
        i64 = ctypes.c_int64
        i32 = ctypes.c_int32
        vp = ctypes.c_void_p
        cp = ctypes.c_char_p
        lib.fdn_new.argtypes = [i32, i32]
        lib.fdn_new.restype = vp
        lib.fdn_delete.argtypes = [vp]
        lib.fdn_conn_add.argtypes = [vp, cp, cp, cp, cp, cp, i64p, i32,
                                     u64, u64]
        lib.fdn_conn_add.restype = i32
        lib.fdn_conn_remove.argtypes = [vp, i32]
        lib.fdn_conn_set_addr.argtypes = [vp, i32, cp]
        lib.fdn_conn_window.argtypes = [vp, i32, u64, u64]
        lib.fdn_conn_pn_add.argtypes = [vp, i32, i64]
        lib.fdn_conn_streams.argtypes = [vp, i32, u64, u64]
        lib.fdn_quic_sweep.argtypes = [vp, i32, i32]
        lib.fdn_quic_sweep.restype = i32
        lib.fdn_rx_stage.argtypes = [vp, cp, i32, cp]
        lib.fdn_rx_stage.restype = i32
        for name in ("fdn_rx_ptr", "fdn_rx_peer"):
            getattr(lib, name).argtypes = [vp, i32]
            getattr(lib, name).restype = vp
        lib.fdn_rx_len.argtypes = [vp, i32]
        lib.fdn_rx_len.restype = i32
        lib.fdn_udp_sweep.argtypes = [vp, i32, i32]
        lib.fdn_udp_sweep.restype = i32
        lib.fdn_udp_sweep_scalar.argtypes = [vp, i32, i32]
        lib.fdn_udp_sweep_scalar.restype = i32
        lib.fdn_set_metrics.argtypes = [vp, vp]
        for name in ("fdn_counters_ptr", "fdn_events_ptr",
                     "fdn_out_tbl_ptr", "fdn_out_arena_ptr"):
            getattr(lib, name).argtypes = [vp]
            getattr(lib, name).restype = vp
        for name in ("fdn_counters_len", "fdn_events_count",
                     "fdn_out_count", "fdn_rx_pending"):
            getattr(lib, name).argtypes = [vp]
            getattr(lib, name).restype = i32
        lib.fdn_events_clear.argtypes = [vp]
        lib.fdn_out_pop.argtypes = [vp, i32]
        lib.fdn_aes_ecb.argtypes = [cp, i32, cp, i32, cp]
        lib.fdn_aes_ecb.restype = i32
        lib.fdn_gcm_seal.argtypes = [cp, i32, cp, cp, i32, cp, i32, cp, cp]
        lib.fdn_gcm_seal.restype = i32
        lib.fdn_gcm_open.argtypes = [cp, i32, cp, cp, i32, cp, i32, cp, cp]
        lib.fdn_gcm_open.restype = i32
        lib.fdn_simd_features.argtypes = []
        lib.fdn_simd_features.restype = i32
        _lib = lib
    return _lib


def enabled() -> bool:
    """The env switch: FDTPU_NATIVE_NET=0 forces the Python lane."""
    return os.environ.get(ENV_SWITCH, "1") != "0"


def available() -> bool:
    """enabled AND the .so loads (toolchain-less hosts degrade to the
    Python per-datagram path gracefully)."""
    if not enabled():
        return False
    try:
        _load()
        return True
    except (NativeUnavailable, OSError, AttributeError):
        return False


# counter tail, in fd_net.cpp declaration order
_COUNTERS = ("rx_dgram", "consumed", "punt", "dup", "bad_packet", "txn",
             "oversz", "evicted", "flow_violation", "auth_fail",
             "udp_pkts", "aesni", "pclmul", "tail_retained", "dup_stream",
             "multi_chunk", "defer", "rx_bytes")
COUNTER_IDX = {name: i for i, name in enumerate(_COUNTERS)}


class NetClient:
    """One ingress stage's native session: the interned connection
    table, the receive arena and the sweep over it, and the zero-FFI
    event/out/counter views the stage drains after every crossing."""

    def __init__(self, *, max_conns: int, reasm_depth: int):
        lib = _load()
        self._lib = lib
        self._h = lib.fdn_new(max_conns, reasm_depth)
        if not self._h:
            raise NativeUnavailable("fdn_new failed")

        def view(ptr, n, dt):
            ct = (ctypes.c_uint64 * n) if dt == np.uint64 else \
                 (ctypes.c_uint8 * n)
            return np.frombuffer(ct.from_address(ptr), dtype=dt)

        ncnt = int(lib.fdn_counters_len(self._h))
        self.counters_view = view(int(lib.fdn_counters_ptr(self._h)),
                                  ncnt, np.uint64)
        self.events = view(int(lib.fdn_events_ptr(self._h)),
                           _EV_CAP * 4, np.uint64).reshape(_EV_CAP, 4)
        self.out_tbl = view(int(lib.fdn_out_tbl_ptr(self._h)),
                            _OUT_CAP * 4, np.uint64).reshape(_OUT_CAP, 4)
        self.arena_ptr = int(lib.fdn_out_arena_ptr(self._h))
        self.arena = view(self.arena_ptr, _OUT_CAP * (1232 + 48), np.uint8)

    # -- connection table ----------------------------------------------------

    def conn_add(self, dcid: bytes, peer: bytes, key: bytes, iv: bytes,
                 hp: bytes, ranges: list[tuple[int, int]],
                 rx_max_data: int, rx_data_total: int) -> int:
        """Install an ESTABLISHED connection's rx side; `peer` is its
        home address as a peer key; ranges seed the pn dedup window
        from the Python tracker.  -1 = table full (the conn simply
        stays on the Python lane)."""
        flat = (ctypes.c_int64 * (2 * len(ranges)))()
        for i, (lo, hi) in enumerate(ranges):
            flat[2 * i] = lo
            flat[2 * i + 1] = hi
        return int(self._lib.fdn_conn_add(
            self._h, bytes(dcid), peer, bytes(key), bytes(iv),
            bytes(hp), flat, len(ranges), rx_max_data, rx_data_total))

    def conn_remove(self, idx: int) -> None:
        self._lib.fdn_conn_remove(self._h, idx)

    def conn_set_addr(self, idx: int, peer: bytes) -> None:
        self._lib.fdn_conn_set_addr(self._h, idx, peer)

    def conn_window(self, idx: int, rx_max_data: int,
                    rx_data_total: int) -> None:
        self._lib.fdn_conn_window(self._h, idx, rx_max_data, rx_data_total)

    def conn_pn_add(self, idx: int, pn: int) -> None:
        self._lib.fdn_conn_pn_add(self._h, idx, pn)

    def conn_streams(self, idx: int, rx_max_streams: int,
                     fin_floor: int = 0) -> None:
        """The peer's stream limit (0: none; Connection
        .rx_max_streams_uni) and, at export, the floor of the streams
        the Python lane already saw whole."""
        self._lib.fdn_conn_streams(self._h, idx, rx_max_streams, fin_floor)

    # -- the hot path --------------------------------------------------------

    def quic_sweep(self, fd: int, max_pkts: int) -> int:
        """The quic tile's crossing.  With the receive arena empty and
        `fd` >= 0: one recvmmsg of up to `max_pkts` datagrams with
        their source addresses; then the arena's datagrams through the
        fast path in arrival order.  SWEEP_DONE, SWEEP_FULL (drain and
        call again), or the arena slot of a datagram to run the Python
        lane on (`rx_datagram`) before calling again; what lies behind
        a stop stays in the arena."""
        return int(self._lib.fdn_quic_sweep(self._h, fd, max_pkts))

    def rx_stage(self, data: bytes, peer: bytes) -> bool:
        """A virtual socket's datagram behind what the arena holds,
        for the next `quic_sweep(-1, ...)`; False: the arena is full."""
        return self._lib.fdn_rx_stage(self._h, data, len(data), peer) == 0

    def rx_datagram(self, slot: int) -> tuple[bytes, bytes]:
        """(bytes, peer key) of arena slot `slot`: a punted datagram."""
        h = self._h
        return (ctypes.string_at(self._lib.fdn_rx_ptr(h, slot),
                                 self._lib.fdn_rx_len(h, slot)),
                ctypes.string_at(self._lib.fdn_rx_peer(h, slot),
                                 PEER_KEY_LEN))

    def rx_pending(self) -> int:
        """Datagrams the arena still holds unprocessed."""
        return int(self._lib.fdn_rx_pending(self._h))

    def set_metrics(self, plane) -> None:
        """Arm the shm metrics plane (ISSUE 20): plain-UDP sweeps
        observe the drain phase and a quic sweep's decrypt+apply the
        callback phase, straight from C.  `plane` None disarms."""
        self._plane = plane  # keepalive: C holds the raw pointer
        self._lib.fdn_set_metrics(
            self._h, plane.ptr if plane is not None else None)

    def udp_sweep(self, fd: int, max_pkts: int) -> int:
        """One real recvmmsg syscall per burst, kernel-scattered
        straight into the out arena (per-packet iovec slots — no bounce
        buffer, no second copy); datagrams taken."""
        return int(self._lib.fdn_udp_sweep(self._h, fd, max_pkts))

    def udp_sweep_scalar(self, fd: int, max_pkts: int) -> int:
        """The byte-identical scalar fallback: one recv per datagram
        through a bounce buffer (the pre-recvmmsg shape).  Differential
        suites drive both paths over the same socket load."""
        return int(self._lib.fdn_udp_sweep_scalar(self._h, fd, max_pkts))

    # -- drain surface -------------------------------------------------------

    def event_count(self) -> int:
        return int(self._lib.fdn_events_count(self._h))

    def events_clear(self) -> None:
        self._lib.fdn_events_clear(self._h)

    def out_count(self) -> int:
        return int(self._lib.fdn_out_count(self._h))

    def out_pop(self, n: int) -> None:
        self._lib.fdn_out_pop(self._h, n)

    def out_txn(self, row: int) -> bytes:
        off = int(self.out_tbl[row, 0])
        sz = int(self.out_tbl[row, 1])
        return bytes(self.arena[off : off + sz])

    def out_rows(self, n: int) -> list[tuple[bytes, int, int]]:
        """The first `n` out rows, in one read of the table and one of
        the arena: (transaction, connection idx, stream id) — whose
        stream credit each publish returns."""
        rows = self.out_tbl[:n].tolist()
        if not rows:
            return []
        lo = rows[0][0]
        blob = ctypes.string_at(self.arena_ptr + lo,
                                rows[-1][0] + rows[-1][1] - lo)
        return [(blob[off - lo:off - lo + sz], ci, sid)
                for off, sz, ci, sid in rows]

    def counters(self) -> dict[str, int]:
        return {name: int(self.counters_view[i])
                for i, name in enumerate(_COUNTERS)}

    def close(self) -> None:
        if self._h:
            self.counters_view = self.events = self.out_tbl = None
            self.arena = None
            self._lib.fdn_delete(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# -- standalone crypto surface (ops/aes.py acceleration) ----------------------


def aes_ecb_blocks(key: bytes, data: bytes) -> bytes:
    """AES-ECB over len(data)/16 blocks (ops/aes.py Aes.encrypt_block's
    accelerated body; callers validate lengths)."""
    lib = _load()
    n = len(data) // 16
    out = ctypes.create_string_buffer(16 * n)
    if lib.fdn_aes_ecb(key, len(key), data, n, out) != 0:
        raise ValueError("AES-128 or AES-256 keys only")
    return out.raw


def gcm_seal(key: bytes, iv: bytes, plaintext: bytes,
             aad: bytes) -> tuple[bytes, bytes]:
    lib = _load()
    ct = ctypes.create_string_buffer(max(len(plaintext), 1))
    tag = ctypes.create_string_buffer(16)
    if lib.fdn_gcm_seal(key, len(key), iv, aad, len(aad), plaintext,
                        len(plaintext), ct, tag) != 0:
        raise ValueError("AES-128 or AES-256 keys only")
    return ct.raw[: len(plaintext)], tag.raw[:16]


def gcm_open(key: bytes, iv: bytes, ciphertext: bytes, tag: bytes,
             aad: bytes) -> bytes | None:
    lib = _load()
    pt = ctypes.create_string_buffer(max(len(ciphertext), 1))
    rc = lib.fdn_gcm_open(key, len(key), iv, aad, len(aad), ciphertext,
                          len(ciphertext), tag, pt)
    if rc == -2:
        raise ValueError("AES-128 or AES-256 keys only")
    if rc != 0:
        return None
    return pt.raw[: len(ciphertext)]


def simd_features() -> int:
    """bit0 = AESNI, bit1 = PCLMUL (bench/test introspection)."""
    return int(_load().fdn_simd_features())
