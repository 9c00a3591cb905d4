"""ctypes binding for the native shredder (native/fd_shred.cpp).

The shred stage's compute path in ONE FFI crossing per entry batch:
data-shred framing, GF(2^8) parity (the C++ side calls back into the
existing native/fd_reedsol.so kernel through a function pointer — one
native GF implementation), the SHA-256 merkle tree, and fixed-base-comb
ed25519 signing of the untruncated root.  Byte parity with
runtime/shredder.Shredder is the contract (tests/test_shred_native.py).

Two surfaces:

  - `NativeShredder`: a drop-in for Shredder — same
    `entry_batch_to_fec_sets` signature and FecSet results, so any
    Shredder consumer (tests, the keep_sets stage mode) can ride the
    lane without caring;
  - `StageClient`: the sweep-harness client (runtime/stage.py fdr_sweep)
    — owns the C-side entry accumulator + publish path so a full shred
    stage sweep executes with zero Python per frag.

`FDTPU_NATIVE_SHRED=0` disables the lane; a missing toolchain (or a
missing fd_reedsol.so — the parity kernel is a hard dependency of this
lane) degrades to the Python shredder via NativeUnavailable.  The
signer's expanded key (clamped scalar, prefix, compressed pubkey) comes
from ed25519_ref's key cache; the raw secret never crosses the FFI.
"""

from __future__ import annotations

import ctypes
import os
from typing import TYPE_CHECKING

import numpy as np

from firedancer_tpu.ops.ref import ed25519_ref as ref
from firedancer_tpu.utils.nativebuild import NativeUnavailable, build_so

if TYPE_CHECKING:
    from .shredder import FecSet

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
    "fd_shred.cpp",
)
_SO = os.path.join(os.path.dirname(_SRC), "fd_shred.so")

ENV_SWITCH = "FDTPU_NATIVE_SHRED"

_MIN_SZ = 1203
_MAX_SZ = 1228
_MAX_D = 67

# The poh -> shred frag sig under the slot clock (poh_stage.poh_sig
# writes it, ShredStage and fd_shred.cpp stage_entry read it; mirrored
# from native/fd_shred.cpp, fdlint FD305).  Bit 63: the entry names its
# slot; bit 62: it is the slot's last tick (the block is complete);
# bits 24..61: the slot; bits 0..23: hashcnt's low bits, which keep sigs
# distinct within a ring depth (the restart publish guard's contract).
POH_SIG_SLOT = 1 << 63
POH_SIG_BLOCK_COMPLETE = 1 << 62
POH_SIG_SLOT_SHIFT = 24
POH_SIG_SLOT_MASK = (1 << 38) - 1

_lib = None


def _load():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(build_so(_SRC, _SO))
        u64 = ctypes.c_uint64
        p64 = ctypes.POINTER(u64)
        pi64 = ctypes.POINTER(ctypes.c_int64)
        vp = ctypes.c_void_p
        cp = ctypes.c_char_p
        lib.fds_ctx_new.argtypes = [ctypes.c_uint, cp, cp, cp, vp]
        lib.fds_ctx_new.restype = vp
        lib.fds_ctx_delete.argtypes = [vp]
        lib.fds_shred_batch.argtypes = [
            vp, cp, u64, u64, ctypes.c_uint, ctypes.c_uint, ctypes.c_int,
            pi64, vp, u64, p64, u64, vp,
        ]
        lib.fds_shred_batch.restype = ctypes.c_int64
        lib.fds_stage_new.argtypes = [
            vp, vp, vp, vp, vp, u64, ctypes.c_uint, ctypes.c_uint, u64, u64,
        ]
        lib.fds_stage_new.restype = vp
        lib.fds_stage_delete.argtypes = [vp]
        lib.fds_stage_flags_off.restype = u64
        lib.fds_stage_set_slot.argtypes = [vp, u64]
        lib.fds_stage_slot.argtypes = [vp]
        lib.fds_stage_slot.restype = u64
        lib.fds_stage_set_metrics.argtypes = [vp, vp]
        lib.fds_stage_append.argtypes = [vp, cp, u64, u64, u64]
        lib.fds_stage_flush.argtypes = [vp, ctypes.c_int]
        lib.fds_stage_flush.restype = ctypes.c_int
        # fds_frag_cb is resolved by ADDRESS for fdr_sweep, never called
        # from Python
        lib.fds_frag_cb.restype = ctypes.c_int
        _lib = lib
    return _lib


def enabled() -> bool:
    """The env switch: FDTPU_NATIVE_SHRED=0 forces the Python lane."""
    return os.environ.get(ENV_SWITCH, "1") != "0"


def _reedsol_fn():
    """Address of fd_reedsol_encode — the parity kernel this lane calls
    through a function pointer (the fd_pack/fd_tcache precedent)."""
    from firedancer_tpu.ops import reedsol

    lib = reedsol._host_lib()
    if lib is None:
        raise NativeUnavailable("native shredder needs fd_reedsol.so")
    return ctypes.cast(lib.fd_reedsol_encode, ctypes.c_void_p)


def available() -> bool:
    """enabled AND both .so's load (builds on demand; toolchain-less
    hosts degrade gracefully to the Python shredder)."""
    if not enabled():
        return False
    try:
        _load()
        _reedsol_fn()
        return True
    except (NativeUnavailable, OSError, AttributeError):
        return False


class _Ctx:
    """One signer's native shredder context (comb key + gen cache)."""

    def __init__(self, secret: bytes, shred_version: int):
        lib = _load()
        a, prefix, apk = ref._expanded(secret)
        self._lib = lib
        self._h = lib.fds_ctx_new(
            shred_version, a.to_bytes(32, "little"), prefix, apk,
            _reedsol_fn(),
        )
        if not self._h:
            raise NativeUnavailable("fds_ctx_new failed")

    def close(self) -> None:
        if self._h:
            self._lib.fds_ctx_delete(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeShredder:
    """Drop-in for runtime/shredder.Shredder: one FFI crossing shreds a
    whole entry batch into wire-complete signed FEC sets.  Construct
    with the SECRET (not a signer callable) — the comb signing path
    needs the expanded key on the C++ side."""

    def __init__(self, *, secret: bytes, shred_version: int = 0):
        self._ctx = _Ctx(secret, shred_version)
        self.shred_version = shred_version
        self.slot = -1
        self.data_idx_offset = 0
        self.parity_idx_offset = 0
        self._idx = (ctypes.c_int64 * 2)()
        # reusable out arena + per-set meta/roots, grown on demand
        self._cap = 1 << 20
        self._out = ctypes.create_string_buffer(self._cap)
        self._meta = np.zeros((256, 4), dtype=np.uint64)
        self._roots = ctypes.create_string_buffer(32 * 256)

    def entry_batch_to_fec_sets(self, entry_batch: bytes, *, slot: int,
                                meta=None) -> list[FecSet]:
        # here, not at the top: the shredder pulls in the JAX ops, and
        # poh_stage reads this module's POH_SIG_* in a jax-free child
        from .shredder import EntryBatchMeta, FecSet, count_fec_sets

        if not entry_batch:
            raise ValueError("empty entry batch")
        meta = meta or EntryBatchMeta()
        if slot != self.slot:
            self.data_idx_offset = 0
            self.parity_idx_offset = 0
            self.slot = slot
        n_sets = count_fec_sets(len(entry_batch)) + 1
        need = n_sets * _MAX_D * (_MIN_SZ + _MAX_SZ)
        if need > self._cap:
            self._cap = need
            self._out = ctypes.create_string_buffer(self._cap)
        if n_sets > self._meta.shape[0]:
            # no batch-size ceiling: the Python lane shreds any batch,
            # so the meta/roots tables grow with the plan bound
            self._meta = np.zeros((n_sets, 4), dtype=np.uint64)
            self._roots = ctypes.create_string_buffer(32 * n_sets)
        self._idx[0] = self.data_idx_offset
        self._idx[1] = self.parity_idx_offset
        lib = self._ctx._lib
        n = lib.fds_shred_batch(
            self._ctx._h, entry_batch, len(entry_batch), slot,
            meta.parent_offset, meta.reference_tick,
            1 if meta.block_complete else 0, self._idx,
            ctypes.cast(self._out, ctypes.c_void_p), self._cap,
            self._meta.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            self._meta.shape[0],
            ctypes.cast(self._roots, ctypes.c_void_p),
        )
        if n < 0:
            raise NativeUnavailable("fds_shred_batch failed (capacity)")
        self.data_idx_offset = int(self._idx[0])
        self.parity_idx_offset = int(self._idx[1])
        if n:
            # copy only the produced bytes (.raw would copy the whole
            # preallocated arena per batch)
            d_l, p_l, _, off_l = (int(x) for x in self._meta[n - 1])
            total = off_l + d_l * _MIN_SZ + p_l * _MAX_SZ
            raw = ctypes.string_at(self._out, total)
        else:
            raw = b""
        roots = ctypes.string_at(self._roots, 32 * n)
        sets: list[FecSet] = []
        for s in range(n):
            d, p, fec_idx, off = (int(x) for x in self._meta[s])
            data = [raw[off + i * _MIN_SZ: off + (i + 1) * _MIN_SZ]
                    for i in range(d)]
            cbase = off + d * _MIN_SZ
            parity = [raw[cbase + j * _MAX_SZ: cbase + (j + 1) * _MAX_SZ]
                      for j in range(p)]
            sets.append(FecSet(
                data_shreds=data,
                parity_shreds=parity,
                merkle_root=roots[32 * s: 32 * s + 32],
                slot=slot,
                fec_set_idx=fec_idx,
            ))
        return sets

    def close(self) -> None:
        self._ctx.close()


# ShredStageCtx counter tail, in declaration order after pending_flush;
# the flag's byte offset comes from the C side (fds_stage_flags_off) so
# the zero-FFI view can never drift from the struct layout
_COUNTERS = ("entries_in", "entry_batches", "fec_sets",
             "data_shreds_out", "parity_shreds_out", "frags_out",
             "backpressure", "batches_dropped")


class StageClient:
    """The shred stage's sweep-harness client: a C-side entry
    accumulator + batch-close + shred + publish path.  Constructed by
    ShredStage when the lane is armed (native shredder available AND the
    out producer is native); exposes the fdr_sweep callback address and
    cheap struct reads for the deferred-flush flag + counters."""

    def __init__(self, shredder_ctx: _Ctx, out_producer, *, slot: int,
                 parent_off: int = 1, ref_tick: int = 0,
                 batch_target: int = 16384, min_credits: int = 256):
        from firedancer_tpu.tango import native as fn

        lib = _load()
        ring = fn._load()
        self._lib = lib
        self._ctx = shredder_ctx  # keep the ShredCtx alive
        self._prod = out_producer  # keep the NativeProducer alive
        self._h = lib.fds_stage_new(
            shredder_ctx._h,
            ctypes.cast(out_producer._lsp, ctypes.c_void_p),
            ctypes.cast(out_producer._pp, ctypes.c_void_p),
            ctypes.cast(ring.fdr_try_publish, ctypes.c_void_p),
            ctypes.cast(ring.fdr_refresh_credits, ctypes.c_void_p),
            slot, parent_off, ref_tick, batch_target, min_credits,
        )
        if not self._h:
            raise NativeUnavailable("fds_stage_new failed")
        self.cb = ctypes.cast(lib.fds_frag_cb, ctypes.c_void_p)
        self.cb_ctx = ctypes.c_void_p(self._h)
        # zero-FFI reads: a u64 view over the ctx struct's flag+counters
        n_tail = 1 + len(_COUNTERS)
        self._tail = np.frombuffer(
            (ctypes.c_uint64 * n_tail).from_address(
                self._h + int(lib.fds_stage_flags_off())
            ),
            dtype=np.uint64,
        )

    @property
    def pending_flush(self) -> bool:
        return bool(self._tail[0])

    def counters(self) -> dict[str, int]:
        return {name: int(self._tail[1 + i])
                for i, name in enumerate(_COUNTERS)}

    def append(self, payload: bytes, tsorig: int, sig: int = 0) -> None:
        """Per-frag fallback (mixed-lane / lossy splice): forward into
        the SAME C-side buffer the sweep callback fills.  `sig` is the
        frag's (poh_stage.poh_sig under the slot clock: the C side
        follows poh's slot off it, exactly as in the sweep)."""
        self._lib.fds_stage_append(self._h, payload, len(payload), tsorig,
                                   sig)

    def flush(self, *, block_complete: bool) -> bool:
        return bool(self._lib.fds_stage_flush(
            self._h, 1 if block_complete else 0
        ))

    def retry_flush(self) -> bool:
        """Retry a credit-deferred flush with its ORIGINAL
        block_complete flag (the C side recorded it)."""
        return bool(self._lib.fds_stage_flush(self._h, -1))

    def set_slot(self, slot: int) -> None:
        self._lib.fds_stage_set_slot(self._h, slot)

    def slot(self) -> int | None:
        """The slot the next batch is shredded under (the C side
        follows poh's inside the crossing); None once closed."""
        return int(self._lib.fds_stage_slot(self._h)) if self._h else None

    def set_metrics(self, plane) -> None:
        """Arm the shm metrics plane (ISSUE 20): shred/encode bursts
        and the wire loop attribute apply/publish phases in-crossing."""
        self._plane = plane  # keepalive: C holds the raw pointer
        self._lib.fds_stage_set_metrics(
            self._h, plane.ptr if plane is not None else None)

    def close(self) -> None:
        if self._h:
            self._tail = None
            self._lib.fds_stage_delete(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
