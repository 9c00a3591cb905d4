"""ctypes binding for the native shm storage plane (native/fd_funk.cpp).

`NativeFunk` is the Python lane's thin view over the shared-memory
record map (ISSUE 19): the exact `funk/funk.py` API — fork-tree
prepare/publish/cancel with frozen/ancestry semantics, overlay queries,
tombstones, FunkError codes -1/-2/-3 — but every record lives inside
ONE shm segment that `native/fd_bank.cpp` writes into directly from its
sweep crossing.  Reads are copied out of the mapping inside the
crossing (under the segment's lock, or its seqlock for a read-only
handle); Python-lane batch writes cross the FFI once per batch
(`rec_insert_batch` / `_root_merge`), and the seal path's whole
before/after read-out is one `txn_diff` crossing.

This is a SEPARATE class, not a replacement of `Funk`:
`funk/persist.py`'s WAL journaling subclasses the dict-backed store and
stays on it.  `make_funk()` (funk/__init__.py) is the construction
funnel the topology builders use — native when the lane is enabled and
the toolchain builds the .so, dict-backed otherwise.

`FDTPU_NATIVE_FUNK=0` disables the lane; a missing toolchain degrades
to the Python store via NativeUnavailable.  Differential parity with
funk.py is the contract (tests/test_funk_native.py).

Because the map lives in shm under a public name (`shm_name`), an
uninvolved process can `attach_readonly()` the same store and observe a
seqlock-consistent view — the seed of the read-replica plane
(docs/OPERATIONS.md "Native funk plane") — and further processes can
`attach()` it to WRITE: the bank tiles of a process topology share one
store this way (models/leader_topo.build_bank), as upstream's share
fd_funk in a workspace.  Writers serialise on a lock inside the segment
(fd_funk.cpp "Concurrency"); a holder that died inside it fails every
other writer's next call with `FunkLockError`, which names it.
"""

from __future__ import annotations

import ctypes
import os
import struct

from firedancer_tpu.utils.nativebuild import NativeUnavailable, build_so

from .funk import ERR_FROZEN, ERR_KEY, ERR_TXN, FunkError

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
    "fd_funk.cpp",
)
_SO = os.path.join(os.path.dirname(_SRC), "fd_funk.so")

ENV_SWITCH = "FDTPU_NATIVE_FUNK"

# error codes beyond the funk.py trio (fd_funk.cpp enum)
_ERR_FULL = -4
_ERR_OOM = -5
_ERR_RDONLY = -6
_ERR_RANGE = -7
_ERR_LOCK = -8

_XID_MAX = 128  # FFK_XID_MAX

_DEFAULT_SZ = 1 << 28  # 256 MiB virtual; pages commit lazily
_DEFAULT_TXN_CAP = 1024

_lib = None


def _load():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(build_so(_SRC, _SO))
        u64 = ctypes.c_uint64
        i64 = ctypes.c_int64
        i32 = ctypes.c_int32
        vp = ctypes.c_void_p
        cp = ctypes.c_char_p
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.ffk_create.argtypes = [cp, u64, i32]
        lib.ffk_create.restype = vp
        for name in ("ffk_attach", "ffk_attach_rw"):
            getattr(lib, name).argtypes = [cp]
            getattr(lib, name).restype = vp
        lib.ffk_set_ready.argtypes = [vp]
        for name in ("ffk_writers", "ffk_writer_id"):
            getattr(lib, name).argtypes = [vp]
            getattr(lib, name).restype = ctypes.c_uint32
        lib.ffk_lock.argtypes = [vp]
        lib.ffk_lock.restype = i32
        lib.ffk_unlock.argtypes = [vp]
        lib.ffk_lock_stats.argtypes = [vp, u64p]
        lib.ffk_lock_failed_holder.argtypes = [vp]
        lib.ffk_lock_failed_holder.restype = u64
        lib.ffk_rec_read.argtypes = [vp, cp, i32, cp, i32, vp, i64, i64p]
        lib.ffk_rec_read.restype = i32
        lib.ffk_rec_read_slot.argtypes = [vp, i32, cp, i32, vp, i64, i64p]
        lib.ffk_rec_read_slot.restype = i32
        lib.ffk_close.argtypes = [vp, i32]
        lib.ffk_shm_name.argtypes = [vp]
        lib.ffk_shm_name.restype = cp
        for name in ("ffk_seq", "ffk_arena_used"):
            getattr(lib, name).argtypes = [vp]
            getattr(lib, name).restype = u64
        lib.ffk_txn_prepare.argtypes = [vp, cp, i32, cp, i32]
        lib.ffk_txn_prepare.restype = i32
        for name in ("ffk_txn_is_frozen", "ffk_txn_wcheck", "ffk_txn_cancel",
                     "ffk_txn_publish", "ffk_txn_slot"):
            getattr(lib, name).argtypes = [vp, cp, i32]
            getattr(lib, name).restype = i32
        lib.ffk_txn_cnt.argtypes = [vp]
        lib.ffk_txn_cnt.restype = i32
        lib.ffk_txn_ancestry.argtypes = [vp, cp, i32, cp, i64]
        lib.ffk_txn_ancestry.restype = i64
        lib.ffk_last_publish.argtypes = [vp, cp, i32]
        lib.ffk_last_publish.restype = i32
        lib.ffk_rec_insert.argtypes = [vp, cp, i32, cp, i32, cp, i32]
        lib.ffk_rec_insert.restype = i32
        lib.ffk_rec_insert_slot.argtypes = [vp, i32, cp, i32, cp, i32]
        lib.ffk_rec_insert_slot.restype = i32
        lib.ffk_rec_remove.argtypes = [vp, cp, i32, cp, i32]
        lib.ffk_rec_remove.restype = i32
        lib.ffk_rec_cnt_root.argtypes = [vp]
        lib.ffk_rec_cnt_root.restype = i64
        lib.ffk_root_keys.argtypes = [vp, cp, i64]
        lib.ffk_root_keys.restype = i64
        lib.ffk_txn_keys.argtypes = [vp, cp, i32, cp, i64]
        lib.ffk_txn_keys.restype = i64
        lib.ffk_txn_diff.argtypes = [vp, cp, i32, cp, i64]
        lib.ffk_txn_diff.restype = i64
        lib.ffk_batch_apply.argtypes = [vp, cp, i32, cp, i64, i32]
        lib.ffk_batch_apply.restype = i32
        _lib = lib
    return _lib


def enabled() -> bool:
    """The env switch: FDTPU_NATIVE_FUNK=0 forces the dict-backed lane."""
    return os.environ.get(ENV_SWITCH, "1") != "0"


def available() -> bool:
    """enabled AND the .so loads (toolchain-less hosts degrade to the
    Python store gracefully)."""
    if not enabled():
        return False
    try:
        _load()
        return True
    except (NativeUnavailable, OSError, AttributeError):
        return False


class FunkLockError(RuntimeError):
    """The segment's lock will not come free: its holder died inside
    it (or, for a read-only handle, a mutation has not ended within the
    limit).  `writer` / `pid` name whoever took the lock last; the
    store may be torn, and no handle writes it again."""

    def __init__(self, what: str, holder: int):
        self.writer, self.pid = holder & 0xFFFFFFFF, holder >> 32
        super().__init__(
            f"native funk {what}: the lock's holder, writer {self.writer} "
            f"(pid {self.pid}), died inside it")


def _raise(rc: int, what: str) -> None:
    if rc == ERR_TXN:
        raise FunkError(ERR_TXN, f"{what}: unknown/duplicate txn")
    if rc == ERR_FROZEN:
        raise FunkError(ERR_FROZEN, "txn has children; records frozen")
    if rc == ERR_KEY:
        raise FunkError(ERR_KEY, f"{what}: unknown key")
    if rc == _ERR_OOM:
        raise MemoryError(f"native funk arena exhausted ({what})")
    raise RuntimeError(f"native funk {what} failed: rc={rc}")


class _RecsProxy:
    """Write-through stand-in for `Funk.txn_recs_for_write`'s dict: the
    ancestry/frozen check ran once at acquisition; each __setitem__ is
    one insert into the shm overlay.  Batch writers should prefer
    NativeFunk.rec_insert_batch (one crossing for the whole batch)."""

    __slots__ = ("_f", "_slot")

    def __init__(self, f: "NativeFunk", slot: int):
        self._f = f
        self._slot = slot

    def __setitem__(self, key: bytes, val: bytes) -> None:
        rc = self._f._lib.ffk_rec_insert_slot(
            self._f._h, self._slot, bytes(key), len(key), bytes(val),
            len(val))
        if rc != 0:
            self._f._fail(rc, "rec_insert")

    def update(self, items) -> None:
        for k, v in (items.items() if hasattr(items, "items") else items):
            self[k] = v


class NativeFunk:
    """The funk API over the native shm record map.  One authoritative
    store for both lanes: the bank sweep writes records in C inside its
    crossing; this class is the Python lane's batched-write + copied-
    read surface over the same segment."""

    def __init__(self, *, shm_name: str | None = None,
                 max_sz: int = _DEFAULT_SZ,
                 txn_cap: int = _DEFAULT_TXN_CAP):
        lib = _load()
        self._lib = lib
        self._h = lib.ffk_create(
            shm_name.encode() if shm_name else None, max_sz, txn_cap)
        if not self._h:
            raise NativeUnavailable("ffk_create failed")
        self._owns = True
        self._init_cells()

    def _init_cells(self) -> None:
        # rec_query's out-cell and copy (no per-call ctypes churn)
        self._vlen = ctypes.c_int64(0)
        self._vlen_ref = ctypes.byref(self._vlen)
        self._val = ctypes.create_string_buffer(4096)

    @classmethod
    def _attached(cls, h, what: str) -> "NativeFunk":
        self = cls.__new__(cls)
        self._lib = _load()
        self._h = h
        if not h:
            raise NativeUnavailable(f"{what} failed")
        self._owns = False
        self._init_cells()
        return self

    @classmethod
    def attach_readonly(cls, shm_name: str) -> "NativeFunk":
        """Read-only attach from an uninvolved process (the metrics /
        read-replica shape).  Mutating calls raise RuntimeError;
        `rec_query` reads under the seqlock, so it is whole also while
        the store's writers are at work."""
        return cls._attached(_load().ffk_attach(shm_name.encode()),
                             f"ffk_attach({shm_name!r})")

    @classmethod
    def attach(cls, shm_name: str, timeout_s: float = 120.0) -> "NativeFunk":
        """One more WRITER of the store another process created under
        `shm_name`, once that process has said the store is whole
        (`set_ready`): asks until then, `timeout_s` at most.  The fork
        tree and the records are the creator's; this handle's writes
        take the segment's lock like the creator's."""
        import time

        lib = _load()
        t_end = time.monotonic() + timeout_s
        while not (h := lib.ffk_attach_rw(shm_name.encode())):
            if time.monotonic() > t_end:
                raise NativeUnavailable(
                    f"ffk_attach_rw({shm_name!r}): no ready store in "
                    f"{timeout_s} s")
            time.sleep(0.01)
        return cls._attached(h, f"ffk_attach_rw({shm_name!r})")

    def set_ready(self) -> None:
        """The creator's word that what joining writers expect (the
        genesis, the fork) is in the store: `attach` waits for it."""
        self._lib.ffk_set_ready(self._h)

    def writers(self) -> int:
        """Handles that may write the segment, the creator's included."""
        return int(self._lib.ffk_writers(self._h))

    @property
    def writer_id(self) -> int:
        """1 the creator, 2.. in order of `attach`, 0 read-only."""
        return int(self._lib.ffk_writer_id(self._h))

    def lock(self) -> None:
        """The writers' lock around a group of calls (they nest)."""
        rc = self._lib.ffk_lock(self._h)
        if rc != 0:
            self._fail(rc, "lock")

    def unlock(self) -> None:
        self._lib.ffk_unlock(self._h)

    def lock_stats(self) -> dict[str, int]:
        """This handle's use of the lock: acquisitions, those that found
        it held, ns waited, and of the waits over 100 us their number
        and the last one's ns and holder (writer id)."""
        out = (ctypes.c_uint64 * 6)()
        self._lib.ffk_lock_stats(self._h, out)
        return {"acquires": int(out[0]), "contended": int(out[1]),
                "wait_ns": int(out[2]), "long_waits": int(out[3]),
                "long_ns": int(out[4]),
                "long_holder": int(out[5]) & 0xFFFFFFFF}

    def _fail(self, rc: int, what: str) -> None:
        if rc == _ERR_LOCK:
            raise FunkLockError(
                what, int(self._lib.ffk_lock_failed_holder(self._h)))
        _raise(rc, what)

    # -- identity / shm surface ----------------------------------------------

    @property
    def shm_name(self) -> str:
        return self._lib.ffk_shm_name(self._h).decode()

    @property
    def handle(self) -> int:
        """The raw ffk handle fd_bank.cpp's set_funk crossing receives."""
        return int(self._h)

    def seq(self) -> int:
        return int(self._lib.ffk_seq(self._h))

    def arena_used(self) -> int:
        return int(self._lib.ffk_arena_used(self._h))

    # -- fork tree ------------------------------------------------------------

    def txn_prepare(self, parent: bytes | None, xid: bytes) -> bytes:
        if parent is None:
            rc = self._lib.ffk_txn_prepare(self._h, None, -1, bytes(xid),
                                           len(xid))
        else:
            rc = self._lib.ffk_txn_prepare(self._h, bytes(parent),
                                           len(parent), bytes(xid), len(xid))
        if rc != 0:
            self._fail(rc, "txn_prepare")
        return xid

    def txn_is_frozen(self, xid: bytes) -> bool:
        rc = self._lib.ffk_txn_is_frozen(self._h, bytes(xid), len(xid))
        if rc < 0:
            self._fail(rc, "txn_is_frozen")
        return bool(rc)

    def txn_cnt(self) -> int:
        return int(self._lib.ffk_txn_cnt(self._h))

    def txn_ancestry(self, xid: bytes) -> list[bytes]:
        lib = self._lib
        need = int(lib.ffk_txn_ancestry(self._h, bytes(xid), len(xid),
                                        None, 0))
        if need < 0:
            self._fail(need, "txn_ancestry")
        buf = ctypes.create_string_buffer(need or 1)
        n = int(lib.ffk_txn_ancestry(self._h, bytes(xid), len(xid), buf,
                                     need))
        if n < 0:
            self._fail(n, "txn_ancestry")
        out, p = [], 0
        raw = buf.raw[:n]
        while p < n:
            ln = raw[p] | (raw[p + 1] << 8)
            out.append(raw[p + 2: p + 2 + ln])
            p += 2 + ln
        return out

    def txn_cancel(self, xid: bytes) -> int:
        rc = self._lib.ffk_txn_cancel(self._h, bytes(xid), len(xid))
        if rc < 0:
            self._fail(rc, "txn_cancel")
        return int(rc)

    def txn_publish(self, xid: bytes) -> int:
        rc = self._lib.ffk_txn_publish(self._h, bytes(xid), len(xid))
        if rc < 0:
            self._fail(rc, "txn_publish")
        return int(rc)

    @property
    def last_publish(self) -> bytes | None:
        buf = ctypes.create_string_buffer(_XID_MAX)
        n = int(self._lib.ffk_last_publish(self._h, buf, _XID_MAX))
        if n <= 0:
            return None
        return buf.raw[:n]

    # -- records --------------------------------------------------------------

    def rec_insert(self, xid: bytes | None, key: bytes, val: bytes) -> None:
        if xid is None:
            rc = self._lib.ffk_rec_insert(self._h, None, -1, bytes(key),
                                          len(key), bytes(val), len(val))
        else:
            rc = self._lib.ffk_rec_insert(self._h, bytes(xid), len(xid),
                                          bytes(key), len(key), bytes(val),
                                          len(val))
        if rc != 0:
            self._fail(rc, "rec_insert")

    def txn_recs_for_write(self, xid: bytes) -> _RecsProxy:
        slot = int(self._lib.ffk_txn_slot(self._h, bytes(xid), len(xid)))
        if slot < 0:
            self._fail(slot, "txn_recs_for_write")
        return _RecsProxy(self, slot)

    def rec_insert_batch(self, xid: bytes | None, items) -> None:
        """One FFI crossing for a batch of (key, val-or-None) writes —
        the Python lane's hot write shape (None = tombstone/delete)."""
        parts = []
        n = 0
        for key, val in (items.items() if hasattr(items, "items")
                         else items):
            if val is None:
                parts.append(struct.pack("<Hi", len(key), -1))
                parts.append(bytes(key))
            else:
                parts.append(struct.pack("<Hi", len(key), len(val)))
                parts.append(bytes(key))
                parts.append(bytes(val))
            n += 1
        if not n:
            return
        blob = b"".join(parts)
        if xid is None:
            rc = self._lib.ffk_batch_apply(self._h, None, -1, blob,
                                           len(blob), n)
        else:
            rc = self._lib.ffk_batch_apply(self._h, bytes(xid), len(xid),
                                           blob, len(blob), n)
        if rc != 0:
            self._fail(rc, "batch_apply")

    def rec_remove(self, xid: bytes | None, key: bytes) -> None:
        if xid is None:
            rc = self._lib.ffk_rec_remove(self._h, None, -1, bytes(key),
                                          len(key))
        else:
            rc = self._lib.ffk_rec_remove(self._h, bytes(xid), len(xid),
                                          bytes(key), len(key))
        if rc != 0:
            self._fail(rc, "rec_remove")

    def rec_query(self, xid: bytes | None, key: bytes) -> bytes | None:
        """The value, copied out in the crossing: under the lock for a
        handle that may write, under the seqlock for a read-only one —
        whole also while other processes write the store."""
        lib, h, vlen = self._lib, self._h, self._vlen
        bx, xl = (None, -1) if xid is None else (bytes(xid), len(xid))
        bk = bytes(key)
        while True:
            rc = lib.ffk_rec_read(h, bx, xl, bk, len(bk), self._val,
                                  len(self._val), self._vlen_ref)
            if rc != _ERR_RANGE or vlen.value <= len(self._val):
                break
            self._val = ctypes.create_string_buffer(int(vlen.value))
        if rc < 0:
            self._fail(rc, "rec_query")
        return self._val[:vlen.value] if rc else None

    def rec_cnt_root(self) -> int:
        return int(self._lib.ffk_rec_cnt_root(self._h))

    def rec_keys(self, xid: bytes | None) -> list[bytes]:
        keys = set(self._root_keys())
        if xid is not None:
            for t_xid in self.txn_ancestry(xid):  # oldest -> newest
                for key, tomb in self._txn_keys(t_xid):
                    if tomb:
                        keys.discard(key)
                    else:
                        keys.add(key)
        return list(keys)

    def txn_diff(self, xid: bytes) -> list[tuple[bytes, bytes | None,
                                                 bytes | None]]:
        """The seal read-out in ONE crossing: [(key, before, after)] for
        every key in xid's own overlay, before = the parent view's value
        (start-of-slot), after = the overlay's (None = absent/tombstone)."""
        lib = self._lib
        bx = bytes(xid)
        need = int(lib.ffk_txn_diff(self._h, bx, len(bx), None, 0))
        if need < 0:
            self._fail(need, "txn_diff")
        buf = ctypes.create_string_buffer(need or 1)
        n = int(lib.ffk_txn_diff(self._h, bx, len(bx), buf, need))
        if n < 0:
            self._fail(n, "txn_diff")
        raw = buf.raw[:n]
        out = []
        p = 0
        while p < n:
            klen, blen, alen = struct.unpack_from("<Hqq", raw, p)
            p += 18
            key = raw[p: p + klen]
            p += klen
            before = None
            after = None
            if blen >= 0:
                before = raw[p: p + blen]
                p += blen
            if alen >= 0:
                after = raw[p: p + alen]
                p += alen
            out.append((key, before, after))
        return out

    # -- root iteration / merge funnel ----------------------------------------

    def _root_keys(self) -> list[bytes]:
        lib = self._lib
        need = int(lib.ffk_root_keys(self._h, None, 0))
        if need < 0:
            self._fail(need, "root_keys")
        buf = ctypes.create_string_buffer(need or 1)
        n = int(lib.ffk_root_keys(self._h, buf, need))
        if n < 0:
            self._fail(n, "root_keys")
        raw = buf.raw[:n]
        out, p = [], 0
        while p < n:
            ln = raw[p] | (raw[p + 1] << 8)
            out.append(raw[p + 2: p + 2 + ln])
            p += 2 + ln
        return out

    def _txn_keys(self, xid: bytes) -> list[tuple[bytes, bool]]:
        lib = self._lib
        bx = bytes(xid)
        need = int(lib.ffk_txn_keys(self._h, bx, len(bx), None, 0))
        if need < 0:
            self._fail(need, "txn_keys")
        buf = ctypes.create_string_buffer(need or 1)
        n = int(lib.ffk_txn_keys(self._h, bx, len(bx), buf, need))
        if n < 0:
            self._fail(n, "txn_keys")
        raw = buf.raw[:n]
        out, p = [], 0
        while p < n:
            ln = raw[p] | (raw[p + 1] << 8)
            tomb = bool(raw[p + 2])
            out.append((raw[p + 3: p + 3 + ln], tomb))
            p += 3 + ln
        return out

    @property
    def _root(self) -> dict[bytes, bytes]:
        """Dict view of the root store (the snapshot writer's iteration
        surface, utils/checkpt.funk_checkpt).  A COPY: cold-path only."""
        return {k: self.rec_query(None, k) for k in self._root_keys()}

    def _root_merge(self, items) -> None:
        """The single root-write funnel, one crossing per batch
        (None value = delete) — funk.py's contract, batched."""
        self.rec_insert_batch(None, items)

    # -- lifecycle ------------------------------------------------------------

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.ffk_close(self._h, 1 if self._owns else 0)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
