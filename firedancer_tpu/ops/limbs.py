"""Batched GF(2^255-19) field arithmetic for TPU, in JAX.

Design (SURVEY.md §7.3): TPU has no wide-integer units, so field elements are
radix-2^13 limb vectors — 20 int32 limbs per element — chosen so a 20-term
schoolbook convolution of 13-bit limbs stays below 2^31 (20 * (2^13)^2 =
2^30.33) and everything runs in plain int32 VPU ops.  This plays the role the
reference's radix-2^43x6 AVX-512 IFMA representation plays on x86
(/root/reference/src/ballet/ed25519/avx512/fd_r43x6.h) and its radix-2^25.5
portable representation (/root/reference/src/ballet/ed25519/ref/) — but the
*lane* dimension here is the batch: every op below is elementwise in the
trailing batch axes, so one field op is a handful of batch-wide VPU
instructions regardless of batch size.

Layout: an fe is an int32 array of shape (20, ...batch) — limbs leading,
any number of batch axes behind (every pad, reshape and broadcast below is
written for `x.ndim - 1` of them).  The TPU tiles an array's two minor
dimensions (8 sublanes x 128 lanes a vreg), so what the layout costs is the
caller's choice of batch shape:

  - batch (R, 128), an fe (20, R, 128) — what the sigverify programs use
    (ops/sigverify.fold_batch, whenever the batch is a multiple of 128):
    the batch occupies BOTH tiled dimensions and the limb axis is untiled.
    Limb indexing, the shifted accumulates of the convolution and the
    carry shift are whole-register moves (at R = 8 a limb is exactly one
    vreg, an fe 20, a convolution accumulator 41);
  - batch (B,), an fe (20, B): the limb axis IS the sublane axis.  An fe
    is 24 sublane rows for 20 limbs, limb i sits on sublane i % 8, and
    every `a[i][None] * b`, every pad to row i of the accumulator and
    every carry shift is a sublane shuffle.  Correct at any B (the small
    batches of tests and tools, and the other curves' callers); ~2.5x the
    vreg ops of the folded form by count.

Invariants ("loose" form, maintained by every public op):
    limbs[1:] in [0, 2^13],  limbs[0] in [0, 2^14]
which keeps schoolbook products safely inside int32 (see _mul bounds note).
Values are only canonically reduced by fe_freeze/fe_tobytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NLIMB = 20
RADIX = 13
MASK = (1 << RADIX) - 1
# 2^260 = 2^5 * 2^255 == 19 * 32 (mod p): carries off the top limb fold back
# into limb 0 with this weight.
FOLD = 19 << 5  # 608

P = 2**255 - 19
SQRT_M1_INT = pow(2, (P - 1) // 4, P)
D_INT = (-121665 * pow(121666, P - 2, P)) % P


def _to_limbs_raw(x: int) -> np.ndarray:
    """Python int (< 2^260) -> (20,) int32 limbs, no reduction."""
    out = np.zeros(NLIMB, dtype=np.int32)
    for i in range(NLIMB):
        out[i] = x & MASK
        x >>= RADIX
    assert x == 0, "value too large for 20 limbs"
    return out


def int_to_limbs(x: int) -> np.ndarray:
    """Host helper: python int -> (20,) int32 limb vector (reduced mod p)."""
    return _to_limbs_raw(x % P)


def limbs_to_int(limbs) -> int:
    """Host helper: limb vector (any looseness) -> python int mod p."""
    limbs = np.asarray(limbs)
    return sum(int(v) << (RADIX * i) for i, v in enumerate(limbs)) % P


def fe_const(x: int, batch_shape=(1,)) -> jnp.ndarray:
    """Broadcastable constant field element."""
    limbs = int_to_limbs(x).reshape((NLIMB,) + (1,) * len(batch_shape))
    return jnp.asarray(limbs, dtype=jnp.int32)


_P_LIMBS = _to_limbs_raw(P)
_2P_LIMBS = (2 * _P_LIMBS).astype(np.int32)


def fe_zero(batch_shape) -> jnp.ndarray:
    return jnp.zeros((NLIMB,) + tuple(batch_shape), dtype=jnp.int32)


def fe_one(batch_shape) -> jnp.ndarray:
    return fe_zero(batch_shape).at[0].set(1)


def _shift_rows(hi: jnp.ndarray, head: jnp.ndarray) -> jnp.ndarray:
    """[head, hi[0], .., hi[-2]] along axis 0 — the carry-propagation shift.

    Written as a concatenate (pure data movement XLA folds into the
    surrounding elementwise DAG) rather than `.at[1:].add`: scatter-add
    lowers to a real scatter op on TPU and measured ~7x slower than an
    entire fe_mul (scripts/perf_probe.py, round 4).  Axis 0 is untiled
    when the batch has two axes (the module docstring's folded layout):
    the shift then renames registers; with a one-axis batch it is a
    one-row sublane shift of every vreg of the operand.
    """
    return jnp.concatenate([head[None], hi[:-1]], axis=0)


def _carry2(x: jnp.ndarray) -> jnp.ndarray:
    """Two parallel carry passes restoring the loose invariant.

    Input limbs must be < 2^27 or so (so `hi` stays small); output satisfies
    limbs[1:] <= 2^13, limbs[0] <= 2^14.
    """
    for _ in range(2):
        hi = x >> RADIX
        x = (x & MASK) + _shift_rows(hi, FOLD * hi[-1])
    return x


def fe_add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _carry2(a + b)


def fe_sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    # a + 2p - b keeps every limb non-negative for loose inputs.
    tp = jnp.asarray(_2P_LIMBS).reshape((NLIMB,) + (1,) * (a.ndim - 1))
    return _carry2(a + tp - b)


def fe_neg(a: jnp.ndarray) -> jnp.ndarray:
    tp = jnp.asarray(_2P_LIMBS).reshape((NLIMB,) + (1,) * (a.ndim - 1))
    return _carry2(tp - a)


def _conv_fold(c: jnp.ndarray) -> jnp.ndarray:
    """Reduce a (41, ...batch) convolution accumulator to 20 loose limbs mod p.

    Input terms are < 1.6e9 (see fe_mul bounds).  Three parallel carry passes
    bring every limb to ~2^13 (limb 40 only ever holds carry spill, < 2^5),
    then a single fold maps weights 2^(13k), k >= 20, back into 0..19:
        2^(13k) == 608 * 2^(13(k-20))  for 20 <= k <= 39   (2^260 == 19*32)
        2^520   == 2^10 * 19^2 == 369664
    """
    for _ in range(3):
        hi = c >> RADIX
        c = (c & MASK) + _shift_rows(hi, jnp.zeros_like(hi[-1]))
    r = c[:NLIMB] + FOLD * c[NLIMB : 2 * NLIMB]
    r = jnp.concatenate([(r[0] + 369664 * c[2 * NLIMB])[None], r[1:]], axis=0)
    return _carry2(r)


def _conv(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(20, ...batch) x (20, ...batch) -> (41, ...batch) schoolbook
    convolution via shifted adds."""
    pad = [(0, 0)] * (a.ndim - 1)
    acc = None
    for i in range(NLIMB):
        t = jnp.pad(a[i][None] * b, [(i, NLIMB + 1 - i)] + pad)
        acc = t if acc is None else acc + t
    return acc


def fe_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook 20x20 limb convolution, then fold mod p.

    Max conv term: two a0-class products (2^14 * 2^13) plus 18 full products
    (2^13.01 * 2^13.01 each) + one 2^14 * 2^14 < 1.6e9 < 2^31: safe int32.
    """
    return _conv_fold(_conv(a, b))


_SQR_DOUBLE = np.ones(NLIMB, dtype=np.int32) * 2
_SQR_DOUBLE[0] = 1


def fe_sqr(a: jnp.ndarray) -> jnp.ndarray:
    """Squaring with shared cross terms (~half the multiplies of fe_mul)."""
    pad = [(0, 0)] * (a.ndim - 1)
    dbl = jnp.asarray(_SQR_DOUBLE).reshape((NLIMB,) + (1,) * (a.ndim - 1))
    acc = None
    for i in range(NLIMB):
        # row i against rows i.. ; off-diagonal terms count twice
        t = a[i][None] * (a[i:] * dbl[: NLIMB - i])
        t = jnp.pad(t, [(2 * i, NLIMB + 1 - i)] + pad)  # total rows: 2N+1
        acc = t if acc is None else acc + t
    return _conv_fold(acc)


def fe_sqr_n(a: jnp.ndarray, n: int) -> jnp.ndarray:
    if n <= 2:
        for _ in range(n):
            a = fe_sqr(a)
        return a
    return jax.lax.fori_loop(0, n, lambda _, x: fe_sqr(x), a)


def fe_pow2523(x: jnp.ndarray) -> jnp.ndarray:
    """x^((p-5)/8) = x^(2^252 - 3); the core of combined sqrt/division.

    Standard sliding chain (same exponent schedule as the reference's
    portable backend uses for fd_ed25519_pow22523).
    """
    z2 = fe_sqr(x)
    z9 = fe_mul(fe_sqr_n(z2, 2), x)
    z11 = fe_mul(z9, z2)
    z_5_0 = fe_mul(fe_sqr(z11), z9)  # x^(2^5 - 2^0)
    z_10_0 = fe_mul(fe_sqr_n(z_5_0, 5), z_5_0)
    z_20_0 = fe_mul(fe_sqr_n(z_10_0, 10), z_10_0)
    z_40_0 = fe_mul(fe_sqr_n(z_20_0, 20), z_20_0)
    z_50_0 = fe_mul(fe_sqr_n(z_40_0, 10), z_10_0)
    z_100_0 = fe_mul(fe_sqr_n(z_50_0, 50), z_50_0)
    z_200_0 = fe_mul(fe_sqr_n(z_100_0, 100), z_100_0)
    z_250_0 = fe_mul(fe_sqr_n(z_200_0, 50), z_50_0)
    return fe_mul(fe_sqr_n(z_250_0, 2), x)


def fe_invert(x: jnp.ndarray) -> jnp.ndarray:
    """x^(p-2).  Shares the 2^250-1 chain with fe_pow2523."""
    z2 = fe_sqr(x)
    z9 = fe_mul(fe_sqr_n(z2, 2), x)
    z11 = fe_mul(z9, z2)
    z_5_0 = fe_mul(fe_sqr(z11), z9)
    z_10_0 = fe_mul(fe_sqr_n(z_5_0, 5), z_5_0)
    z_20_0 = fe_mul(fe_sqr_n(z_10_0, 10), z_10_0)
    z_40_0 = fe_mul(fe_sqr_n(z_20_0, 20), z_20_0)
    z_50_0 = fe_mul(fe_sqr_n(z_40_0, 10), z_10_0)
    z_100_0 = fe_mul(fe_sqr_n(z_50_0, 50), z_50_0)
    z_200_0 = fe_mul(fe_sqr_n(z_100_0, 100), z_100_0)
    z_250_0 = fe_mul(fe_sqr_n(z_200_0, 50), z_50_0)
    return fe_mul(fe_sqr_n(z_250_0, 5), z11)  # 2^255 - 21 = p - 2


def fe_freeze(x: jnp.ndarray) -> jnp.ndarray:
    """Full canonical reduction: output is the unique rep in [0, p)."""
    x = _carry2(x)
    # Two rounds of top-bit split (limb 19 holds bits 247..259; bits >= 255
    # fold back as *19) with sequential carries brings the value below 2^255.
    # Row-list form, not `.at[k].set/add` — scatters lower poorly on TPU
    # (see _shift_rows).
    rows = [x[k] for k in range(NLIMB)]
    for _ in range(2):
        hi = rows[NLIMB - 1] >> 8
        rows[NLIMB - 1] = rows[NLIMB - 1] & 0xFF
        rows[0] = rows[0] + 19 * hi
        for k in range(NLIMB - 1):
            hi = rows[k] >> RADIX
            rows[k] = rows[k] & MASK
            rows[k + 1] = rows[k + 1] + hi
    x = jnp.stack(rows)
    # Now x < 2^255 < 2p: one conditional subtract of p.
    p_l = jnp.asarray(_P_LIMBS).reshape((NLIMB,) + (1,) * (x.ndim - 1))
    t = x - p_l
    borrow = jnp.zeros_like(t[0])
    outs = []
    for k in range(NLIMB):
        v = t[k] - borrow
        borrow = (v < 0).astype(jnp.int32)
        outs.append(v + (borrow << RADIX))
    t = jnp.stack(outs)
    ge_p = (borrow == 0)  # x >= p
    return jnp.where(ge_p[None], t, x)


def fe_eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Canonical equality -> bool of batch shape."""
    return jnp.all(fe_freeze(a) == fe_freeze(b), axis=0)


def fe_is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(fe_freeze(a) == 0, axis=0)


def fe_parity(a: jnp.ndarray) -> jnp.ndarray:
    """Low bit of the canonical representative (the 'sign' in RFC 8032)."""
    return fe_freeze(a)[0] & 1


def fe_select(cond: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """cond (batch bool) ? a : b, limbwise."""
    return jnp.where(cond[None], a, b)


# Byte <-> limb packing.  Bytes are int32 arrays of shape (32, ...batch) with
# values 0..255, little-endian (Solana wire order).

def fe_frombytes(b: jnp.ndarray, mask_msb: bool = True) -> jnp.ndarray:
    """(32, B) bytes -> fe.  mask_msb drops bit 255 (the x-sign bit in point
    encodings); the value is *not* reduced mod p here (non-canonical
    encodings stay non-canonical until arithmetic folds them — matching the
    reference's accept-non-canonical decompress, fd_ed25519_user.c:170-189).
    """
    b = b.astype(jnp.int32)
    if mask_msb:
        b = jnp.concatenate([b[:31], (b[31] & 0x7F)[None]], axis=0)
    rows = []
    for i in range(NLIMB):
        bit_lo = RADIX * i
        byte0, sh = bit_lo >> 3, bit_lo & 7
        # bits [sh, sh+13) of the 3-byte window starting at byte0
        v = b[byte0] >> sh
        v = v | (b[byte0 + 1] << (8 - sh))
        if sh > 3 and byte0 + 2 < 32:  # 16 - sh < 13: need a third byte
            v = v | (b[byte0 + 2] << (16 - sh))
        rows.append(v & MASK)
    return jnp.stack(rows)


def fe_tobytes(x: jnp.ndarray) -> jnp.ndarray:
    """fe -> canonical (32, B) little-endian bytes (int32 values 0..255)."""
    x = fe_freeze(x)
    rows = []
    for i in range(32):
        bit_lo = 8 * i
        k, sh = bit_lo // RADIX, bit_lo % RADIX
        v = x[k] >> sh
        if sh + 8 > RADIX and k + 1 < NLIMB:
            v = v | (x[k + 1] << (RADIX - sh))
        rows.append(v & 0xFF)
    return jnp.stack(rows)
