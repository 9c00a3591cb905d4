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
any number of batch axes behind (every pad, slice and broadcast below is
written for `x.ndim - 1` of them).  The TPU tiles an array's two minor
dimensions (8 sublanes x 128 lanes a vreg), so what the layout costs is the
caller's choice of batch shape:

  - batch (R, 128), an fe (20, R, 128) — what the sigverify programs use
    (ops/sigverify.fold_batch, whenever the batch is a multiple of 128):
    the batch occupies BOTH tiled dimensions and the limb axis is untiled.
    Limb indexing, the shifted accumulates of the convolution and the
    carry shift are whole-register moves (at R = 8 a limb is exactly one
    vreg, an fe 20, the rows of a product 41);
  - batch (B,), an fe (20, B): the limb axis IS the sublane axis.  An fe
    is 24 sublane rows for 20 limbs, limb i sits on sublane i % 8, and
    every `a[i] * b`, every pad to row i of the accumulator and every
    carry shift is a sublane shuffle.  Correct at any B (the small
    batches of tests and tools, and the other curves' callers); ~2.5x the
    vreg ops of the folded form by count.

Invariant ("loose" form; LOOSE_MIN / LOOSE_MAX below, limb by limb), kept
by every public op and closed under any chain of them:
    limbs[0]  in [-608, 9407]      (8191 + 2 * 608: an add's top carry)
    limbs[1]  in [-1,   8238]      (8191 + 47: a product's limb-0 carry)
    limbs[2:] in [-1,   8194]
A limb can be NEGATIVE: the ops carry with arithmetic shifts, a limb keeps
its low 13 bits (>= 0) and hands a signed high part up, so limb 0 goes
below zero when the top limb's high part is -1 (a subtraction whose
limb 19 borrowed: -608) and a limb above it reads -1 when the limb below
was negative going into a product's last pass.  The value mod p is what
the limbs sum to with their signs.  What keeps schoolbook products inside
int32 is the magnitude (fe_mul's note); the invariant is the least one
that the ops close (tests/test_limbs.py reaches it from freshly unpacked
limbs and walks every op from its worst case in Python integers).
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

# The loose invariant, limb by limb (the module docstring).
LOOSE_MIN = np.array([-FOLD] + [-1] * (NLIMB - 1), dtype=np.int32)
LOOSE_MAX = np.array([MASK + 2 * FOLD, MASK + 47] + [MASK + 3] * (NLIMB - 2),
                     dtype=np.int32)


def fe_zero(batch_shape) -> jnp.ndarray:
    return jnp.zeros((NLIMB,) + tuple(batch_shape), dtype=jnp.int32)


def fe_one(batch_shape) -> jnp.ndarray:
    return fe_zero(batch_shape).at[0].set(1)


def _rows(x: jnp.ndarray, lo: int, hi: int) -> jnp.ndarray:
    """x[lo:hi] along the limb axis (a static slice: no index arithmetic
    is traced, and with the batch on both tiled axes it names registers)."""
    return jax.lax.slice_in_dim(x, lo, hi, axis=0)


def _carry(x: jnp.ndarray) -> jnp.ndarray:
    """One parallel carry pass over 20 limbs: every limb keeps its low 13
    bits and takes the (signed) high part of the limb below; the top
    limb's goes to limb 0 times 608 (2^260 == 608 mod p).

    Written as a concatenate (pure data movement XLA folds into the
    surrounding elementwise DAG) rather than `.at[1:].add`: scatter-add
    lowers to a real scatter op on TPU and measured ~7x slower than an
    entire fe_mul (scripts/perf_probe.py, round 4).  Axis 0 is untiled
    when the batch has two axes (the module docstring's folded layout):
    the shift then renames registers; with a one-axis batch it is a
    one-row sublane shift of every vreg of the operand.
    """
    hi = x >> RADIX
    head = FOLD * _rows(hi, NLIMB - 1, NLIMB)
    return (x & MASK) + jnp.concatenate([head, _rows(hi, 0, NLIMB - 1)], axis=0)


def _two_p(ndim: int) -> jnp.ndarray:
    return jnp.asarray(_2P_LIMBS).reshape((NLIMB,) + (1,) * (ndim - 1))


# The add-like ops carry ONCE.  The sum of two loose elements is under
# 2^15 in every limb, so every high part is in [-1, 2] and one pass leaves
# limbs[1:] <= 8191 + 2 and limb 0 <= 8191 + 2 * 608 = 9407.  2p - b can be
# negative in limb 19 alone (2p's limb 19 is 510, its others >= 16346):
# its high part is then -1 and limb 0 takes -608.

def fe_add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _carry(a + b)


def fe_sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _carry(a + _two_p(a.ndim) - b)


def fe_neg(a: jnp.ndarray) -> jnp.ndarray:
    return _carry(_two_p(a.ndim) - a)


def _conv_fold(c: jnp.ndarray) -> jnp.ndarray:
    """Reduce the rows of a product to 20 loose limbs mod p.  `c` is
    `_conv`'s: 41 rows, row k + 1 the product's row of weight 2^(13k)
    (k = 0..38), rows 0 and 40 zero.

    Rows are within +-1.38e9 (fe_mul's note).  ONE carry pass over the
    rows brings each under 8191 + 1.38e9 / 8192 < 176,300 in magnitude and
    makes row 39 out of row 38's high part; the fold
        2^(13(k+20)) == 608 * 2^(13k)   (2^260 == 19 * 32 mod p)
    then leaves 20 limbs under 609 * 176,300 < 2^27, where two passes of
    `_carry` restore the loose invariant (the first leaves limb 0 under
    4e5 and the others under 21,300; the second's high parts are then at
    most 47 into limb 1 and 2 elsewhere).  tests/test_limbs.py walks these
    bounds limb by limb in Python integers.

    The pass and the fold read four plain slices of `c` (a row, the row
    below it, and the same twenty rows up): the zero rows at both ends
    are what lets row 0 have a row below it and row 39 a low part, so
    nothing is concatenated or padded here and the stage is one fusion.
    """
    low = (_rows(c, 1, NLIMB + 1) & MASK) + (_rows(c, 0, NLIMB) >> RADIX)
    high = (_rows(c, NLIMB + 1, 2 * NLIMB + 1) & MASK) \
        + (_rows(c, NLIMB, 2 * NLIMB) >> RADIX)
    return _carry(_carry(low + FOLD * high))


def _conv(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(20, ...batch) x (20, ...batch) -> (41, ...batch): the 39 rows of
    the schoolbook product between two rows of zeros (_conv_fold reads
    them), by shifted adds of the twenty products a[i] * b.

    The pads are not what a product pays for (PERF.md section 6, PR 43):
    the twenty products are one fusion of 400 multiplies, the padded sum
    one more whose 800 adds cost a third of what the multiplies do, and a
    sum by output rows — no zeros added — is 39 or more fusions that each
    cost a launch."""
    pad = [(0, 0)] * (a.ndim - 1)
    acc = None
    for i in range(NLIMB):
        t = jnp.pad(_rows(a, i, i + 1) * b, [(i + 1, NLIMB - i)] + pad)
        acc = t if acc is None else acc + t
    return acc


# fe_mul is jitted on its own: a program that traces hundreds of products
# (the verify program: ~300) binds one cached call for each instead of
# tracing and lowering its ~150 primitives again, and XLA inlines the
# calls before it optimises, so the compiled program is the same.

@jax.jit
def fe_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook 20x20 limb convolution, then fold mod p.

    Largest row (k = 19, twenty terms) for loose inputs: two products
    with a limb 0 (9407 * 8194 each), two with a limb 1 (8238 * 8194) and
    16 of 8194^2: under 1.38e9 < 2^31, safe int32, negative limbs
    included (a row's negative terms are at most 2 * 608 * 8238 +
    18 * 8238).
    """
    return _conv_fold(_conv(a, b))


def fe_sqr(a: jnp.ndarray) -> jnp.ndarray:
    """a * a, as a product like any other.  A squaring that shares its
    cross terms needs 210 multiplies for 400, but its rows are ragged
    (a[m] against m + 1 fewer limbs each time): the compiler gives every
    one its own fusion, and it keeps four squarings side by side from
    being run as one, which it does for products of one shape
    (point_dbl's four squarings and four products: 88 fusions this way,
    180 before).  On the chip the shared form is the slower (PERF.md
    section 6, PR 43)."""
    return fe_mul(a, a)


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
    """Full canonical reduction: output is the unique rep in [0, p).

    From any loose input, negative limbs included.  Two parallel passes
    leave limbs 1.. in [-1, 8192] and limb 0 in [-608, 8799].  A round
    takes limb 19's bits from 8 up (signed: -1 >> 8 is -1) off the top and
    adds 19 times them to limb 0 — the value minus that many p — then
    carries limb by limb with arithmetic shifts, which leaves limbs 0..18
    in [0, 2^13) whatever their signs were.  Round one leaves limb 19's
    low 8 bits over 19 limbs that were within (-2^236, 2^247 + 2^236), so
    the value V is in (-2^236, 2^255 + 2^237) and limb 19 reads -1, 0..255
    or 256.  Round two adds p to the first (V + p is in [0, p)), takes p
    off the last (what is left is under 2^237) and changes nothing in
    between: 0 <= V < 2^255 with every limb canonical, and one conditional
    subtract of p ends it.
    fe_eq, fe_is_zero, fe_parity and fe_tobytes read nothing but this.
    """
    x = _carry(_carry(x))
    # Row-list form, not `.at[k].set/add` — scatters lower poorly on TPU
    # (see _carry).
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
