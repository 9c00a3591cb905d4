"""Batched Reed-Solomon erasure coding on TPU (the reedsol layer).

Capability parity with /root/reference/src/ballet/reedsol/fd_reedsol.h:
systematic RS over GF(2^8), d data + p parity shreds per FEC set
(d, p <= 67), encode and recover-from-any-d.  The reference reaches
~single-byte/cycle with an O(n log n) FFT over a GFNI/AVX2 backend; here
the whole code is a linear map, so both encode and recover are ONE
bit-block matmul on the MXU (ops/gf256.py), batched over every FEC set in
flight — the most TPU-native formulation, not a translation of the FFT.

Shapes: data is (d, sz) for one set or (nsets, d, sz) batched; all sets in
a batched call share (d, p).  Recovery is per erasure pattern: the host
inverts the surviving d x d generator submatrix (gf256_ref) and the device
applies it; patterns repeat heavily in practice (bursty loss), so the tiny
host solve amortizes.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from . import gf256 as g2
from .ref import gf256_ref as gr

DATA_SHREDS_MAX = 67
PARITY_SHREDS_MAX = 67

SUCCESS = 0
ERR_CORRUPT = -1
ERR_PARTIAL = -2


@functools.lru_cache(maxsize=None)
def _encode_bits(d: int, p: int):
    """Cached device-ready bit-block matrix for the (d, p) parity map."""
    g = gr.generator_matrix(d, d + p)
    return jnp.asarray(g2.gf_matrix_to_bits(g[d:]))


@functools.lru_cache(maxsize=512)
def _recover_bits(d: int, n: int, present_key: tuple):
    """Cached bit-block matrix rebuilding ALL n shreds from d survivors.

    Bounded: erasure patterns are attacker-influenced (which shreds arrive
    is network-controlled), so an unbounded cache keyed on the pattern is a
    memory-growth vector; 512 entries cover the bursty-loss reuse that
    makes caching worthwhile and cap the damage of adversarial patterns.
    """
    present_idx = np.flatnonzero(np.array(present_key, dtype=bool))[:d]
    g = gr.generator_matrix(d, n)
    sub_inv = gr.gf_mat_inv(g[present_idx])
    full = gr.gf_matmul(g, sub_inv)  # (n, d): survivors -> every shred
    return jnp.asarray(g2.gf_matrix_to_bits(full)), present_idx


def encode_core(bbits, data):
    """Jittable parity core: bit-block matrix (8p, 8d) x data (nsets, d,
    sz) -> (nsets, p, sz).  The single implementation the unsharded
    encode() AND the mesh-sharded leader step both call — one place owns
    the flatten/bit-matmul/pack layout."""
    nsets, d, sz = data.shape
    # (nsets, d, sz) -> (d, nsets*sz): one big matmul over all sets
    flat = data.transpose(1, 0, 2).reshape(d, nsets * sz)
    par = g2.pack_bits(g2._gf2_matmul_bits(bbits, g2.unpack_bits(flat)))
    return par.reshape(-1, nsets, sz).transpose(1, 0, 2)


def encode(data, parity_cnt: int):
    """(d, sz) or (nsets, d, sz) uint8 -> (p, sz) / (nsets, p, sz) parity."""
    data = jnp.asarray(data, dtype=jnp.uint8)
    batched = data.ndim == 3
    if not batched:
        data = data[None]
    nsets, d, sz = data.shape
    if not (0 < d <= DATA_SHREDS_MAX and 0 < parity_cnt <= PARITY_SHREDS_MAX):
        raise ValueError("bad shred counts")
    par = encode_core(_encode_bits(d, parity_cnt), data)
    return par if batched else par[0]


# -- host lane (native/fd_reedsol.cpp) ----------------------------------------
# The leader's shredder encodes one-to-few FEC sets per entry batch, where
# the device dispatch and fetch dwarf the GF work.
# The native kernel applies the SAME generator submatrix, so parity bytes
# are identical; no toolchain -> numpy ground truth (gf256_ref).

_HOST_LIB = None  # None = untried, False = unavailable


@functools.lru_cache(maxsize=None)
def _gen_parity_rows(d: int, p: int) -> bytes:
    """G[d:] as contiguous (p, d) bytes for the native/ numpy host lane."""
    return np.ascontiguousarray(gr.generator_matrix(d, d + p)[d:]).tobytes()


def _host_lib():
    global _HOST_LIB
    if _HOST_LIB is None:
        import ctypes
        import os

        from firedancer_tpu.utils.nativebuild import (
            NativeUnavailable, build_so,
        )

        src = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
            "native", "fd_reedsol.cpp",
        )
        so = os.path.join(os.path.dirname(src), "fd_reedsol.so")
        try:
            lib = ctypes.CDLL(build_so(src, so))
            lib.fd_reedsol_encode.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
                ctypes.c_uint64, ctypes.c_uint64, ctypes.c_char_p,
            ]
            _HOST_LIB = lib
        except (NativeUnavailable, OSError):
            _HOST_LIB = False
    return _HOST_LIB or None


def encode_host(data: np.ndarray, parity_cnt: int) -> np.ndarray:
    """Host-side encode, numpy in/out, no device round trip.  Same
    shapes and parity bytes as encode()."""
    data = np.ascontiguousarray(data, dtype=np.uint8)
    batched = data.ndim == 3
    if not batched:
        data = data[None]
    nsets, d, sz = data.shape
    if not (0 < d <= DATA_SHREDS_MAX and 0 < parity_cnt <= PARITY_SHREDS_MAX):
        raise ValueError("bad shred counts")
    lib = _host_lib()
    if lib is None:
        # numpy ground truth: XOR-accumulated GF rank-1 updates
        gen = np.frombuffer(_gen_parity_rows(d, parity_cnt),
                            dtype=np.uint8).reshape(parity_cnt, d)
        out = np.stack([gr.gf_matmul(gen, data[k]) for k in range(nsets)])
        return out if batched else out[0]
    import ctypes

    gen = _gen_parity_rows(d, parity_cnt)
    out = np.empty((nsets, parity_cnt, sz), dtype=np.uint8)
    for k in range(nsets):
        lib.fd_reedsol_encode(
            gen,
            data[k].tobytes(),
            d, parity_cnt, sz,
            out[k].ctypes.data_as(ctypes.c_char_p),
        )
    return out if batched else out[0]


def recover(shreds, present, d: int):
    """Rebuild every shred of one FEC set from any >= d survivors.

    shreds:  (n, sz) uint8, garbage rows where present is False
    present: (n,) bool
    Returns (status, rebuilt) with rebuilt (n, sz).  Status contract mirrors
    fd_reedsol_recover_fini (fd_reedsol.h:40-44): SUCCESS; ERR_PARTIAL when
    fewer than d shreds survive (rebuilt is None); ERR_CORRUPT when more
    than d survive and the extras are inconsistent with the rebuild from the
    first d — a present-but-corrupted shred (rebuilt is None).
    """
    shreds_np = np.asarray(shreds, dtype=np.uint8)
    present = np.asarray(present, dtype=bool)
    n, sz = shreds_np.shape
    if int(present.sum()) < d:
        return ERR_PARTIAL, None
    bbits, present_idx = _recover_bits(d, n, tuple(bool(x) for x in present))
    # pad the bit-matmul to power-of-two row/col buckets: zero rows and
    # columns are inert in GF(2) linear algebra, so the result is exact
    # while the compile count stays O(log^2) instead of one program per
    # (n, d) FEC shape — a streaming resolver sees a fresh shape per set
    # and was recompiling on nearly every recover
    n_pad = 1 << max(3, (n - 1).bit_length())
    d_pad = 1 << max(3, (d - 1).bit_length())
    bb = np.zeros((8 * n_pad, 8 * d_pad), dtype=np.asarray(bbits).dtype)
    bb[: 8 * n, : 8 * d] = np.asarray(bbits)
    surv = np.zeros((d_pad, sz), dtype=np.uint8)
    surv[:d] = shreds_np[present_idx]
    out = g2.pack_bits(
        g2._gf2_matmul_bits(jnp.asarray(bb), g2.unpack_bits(jnp.asarray(surv)))
    )[:n]
    extra = np.flatnonzero(present)[d:]
    if len(extra) and not np.array_equal(
        np.asarray(out)[extra], shreds_np[extra]
    ):
        return ERR_CORRUPT, None
    return SUCCESS, out


def recover_batch(shreds, present, d: int):
    """Batched recover over T same-shape FEC sets in ONE device dispatch.

    shreds:  (T, n, sz) uint8 — garbage rows where present is False
    present: (T, n) bool — may differ per set (each loss pattern lifts to
             its own rebuild matrix; the batched GF(2) bmm applies all T
             at once, the streaming shape of fd_fec_resolver.c)
    Returns (statuses, rebuilt): statuses (T,) int with the per-set
    SUCCESS/ERR_PARTIAL/ERR_CORRUPT contract of recover(); rebuilt
    (T, n, sz) uint8, valid only where statuses == SUCCESS.
    """
    shreds_np = np.asarray(shreds, dtype=np.uint8)
    present = np.asarray(present, dtype=bool)
    t, n, sz = shreds_np.shape
    statuses = np.full((t,), SUCCESS, dtype=np.int32)
    mats = np.zeros((t, 8 * n, 8 * d), dtype=np.int8)
    surv = np.zeros((t, d, sz), dtype=np.uint8)
    extras: list[np.ndarray] = []
    for k in range(t):
        if int(present[k].sum()) < d:
            statuses[k] = ERR_PARTIAL
            extras.append(np.empty(0, dtype=np.int64))
            continue
        bbits, present_idx = _recover_bits(d, n, tuple(bool(x) for x in present[k]))
        mats[k] = np.asarray(bbits)
        surv[k] = shreds_np[k, present_idx]
        extras.append(np.flatnonzero(present[k])[d:])
    data_bits = g2.unpack_bits(
        jnp.asarray(surv).transpose(1, 0, 2)
    ).transpose(1, 0, 2)  # (T, 8d, sz)
    out_bits = g2._gf2_bmm_bits(jnp.asarray(mats), data_bits)  # (T, 8n, sz)
    out = np.asarray(
        g2.pack_bits(out_bits.transpose(1, 0, 2)).transpose(1, 0, 2)
    )  # (T, n, sz)
    for k in range(t):
        if statuses[k] != SUCCESS:
            continue
        ex = extras[k]
        if len(ex) and not np.array_equal(out[k, ex], shreds_np[k, ex]):
            statuses[k] = ERR_CORRUPT
    return statuses, out
