"""Differential tests: JAX limb field arithmetic vs python big-int ground truth.

Everything goes through jax.jit: eager dispatch is prohibitively slow in this
environment and the production path is always jitted anyway.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from firedancer_tpu.ops import limbs as fl
from firedancer_tpu.ops.sigverify import fold_batch

P = fl.P

j_add = jax.jit(fl.fe_add)
j_sub = jax.jit(fl.fe_sub)
j_neg = jax.jit(fl.fe_neg)
j_mul = jax.jit(fl.fe_mul)
j_sqr = jax.jit(fl.fe_sqr)
j_invert = jax.jit(fl.fe_invert)
j_pow2523 = jax.jit(fl.fe_pow2523)
j_freeze = jax.jit(fl.fe_freeze)
j_parity = jax.jit(fl.fe_parity)
j_eq = jax.jit(fl.fe_eq)
j_tobytes = jax.jit(fl.fe_tobytes)
j_frombytes = jax.jit(fl.fe_frombytes)
j_frombytes_raw = jax.jit(lambda b: fl.fe_frombytes(b, mask_msb=False))


def rand_ints(rng, n):
    """Random field values covering edge regions."""
    vals = [int.from_bytes(rng.bytes(32), "little") % P for _ in range(n - 6)]
    vals += [0, 1, P - 1, P - 19, 2**255 - 20, (1 << 255) - 1]  # non-canonical too
    return vals[:n]


def to_fe(vals):
    return jnp.asarray(
        np.stack([fl.int_to_limbs(v) for v in vals], axis=-1), dtype=jnp.int32
    )


def from_fe(fe):
    arr = np.asarray(fe)
    return [fl.limbs_to_int(arr[:, i]) for i in range(arr.shape[1])]


def test_roundtrip(rng):
    vals = rand_ints(rng, 32)
    assert from_fe(to_fe(vals)) == [v % P for v in vals]


def test_add_sub_neg_mul_sqr(rng):
    a, b = rand_ints(rng, 16), rand_ints(rng, 16)
    fa, fb = to_fe(a), to_fe(b)
    assert from_fe(j_add(fa, fb)) == [(x + y) % P for x, y in zip(a, b)]
    assert from_fe(j_sub(fa, fb)) == [(x - y) % P for x, y in zip(a, b)]
    assert from_fe(j_neg(fa)) == [(-x) % P for x in a]
    assert from_fe(j_mul(fa, fb)) == [(x * y) % P for x, y in zip(a, b)]
    assert from_fe(j_sqr(fa)) == [(x * x) % P for x in a]


@jax.jit
def _chain_step(fa):
    fa = fl.fe_mul(fl.fe_add(fa, fa), fa)
    return fl.fe_sub(fa, fl.fe_one((1,)))


def test_mul_stays_loose_after_chains(rng):
    # Long op chains must not overflow int32: deep chain, compare, check bounds.
    vals = rand_ints(rng, 8)
    fa = to_fe(vals)
    ref = [v % P for v in vals]
    for _ in range(20):
        fa = _chain_step(fa)
        ref = [(2 * r * r - 1) % P for r in ref]
    assert from_fe(fa) == ref
    arr = np.asarray(fa)
    assert arr.min() >= 0 and arr.max() < 1 << 15


@pytest.mark.slow  # ~16 s compile; invert/pow2523 are exercised inside
# every tier-1 decompress + sigverify kernel anyway
def test_invert_pow2523(rng):
    vals = [v for v in rand_ints(rng, 10) if v % P != 0]
    fa = to_fe(vals)
    assert from_fe(j_invert(fa)) == [pow(v, P - 2, P) for v in vals]
    assert from_fe(j_pow2523(fa)) == [pow(v, (P - 5) // 8, P) for v in vals]


def test_freeze_eq_parity(rng):
    vals = rand_ints(rng, 16)
    fa = to_fe(vals)
    frozen = np.asarray(j_freeze(fa))
    assert frozen.max() <= fl.MASK
    assert from_fe(jnp.asarray(frozen)) == [v % P for v in vals]
    assert list(np.asarray(j_parity(fa))) == [(v % P) & 1 for v in vals]
    # eq across the p boundary: v and v + p are the same element
    small = [1, 5, 19]
    shifted = to_fe([v + P for v in small])
    assert np.asarray(j_eq(to_fe(small), shifted)).all()


def test_bytes_roundtrip(rng):
    vals = rand_ints(rng, 16)
    raw = np.stack(
        [np.frombuffer(int.to_bytes(v, 32, "little"), dtype=np.uint8) for v in vals],
        axis=-1,
    ).astype(np.int32)
    fe = j_frombytes_raw(jnp.asarray(raw))
    assert from_fe(fe) == [v % P for v in vals]
    # tobytes emits the canonical little-endian encoding
    out = np.asarray(j_tobytes(fe))
    expect = np.stack(
        [
            np.frombuffer(int.to_bytes(v % P, 32, "little"), dtype=np.uint8)
            for v in vals
        ],
        axis=-1,
    )
    assert (out == expect).all()
    # msb masking drops bit 255
    fe2 = j_frombytes(jnp.asarray(raw))
    assert from_fe(fe2) == [(v & ((1 << 255) - 1)) % P for v in vals]


# -- the folded batch (ISSUE 38) ----------------------------------------------
#
# ops/sigverify.fold_batch lays a program's batch on both tiled axes,
# (20, B // 128, 128): the field ops are written for any batch rank, and
# here each is held, lane by lane, to itself on the one-axis batch.

FOLD_B = 256


def loose_extremes(rng, n=FOLD_B):
    """(20, n) limb columns at the edges of the loose invariant
    (limbs[1:] in [0, 2^13], limbs[0] in [0, 2^14]): every limb at its
    maximum, zero, p's own limbs, 2p's reduced, alternating, the rest
    random loose limbs."""
    top = np.full(fl.NLIMB, 1 << fl.RADIX, np.int32)
    top[0] = 1 << (fl.RADIX + 1)
    alt = np.where(np.arange(fl.NLIMB) % 2, top, 0).astype(np.int32)
    cols = [top, np.zeros(fl.NLIMB, np.int32), fl._P_LIMBS.astype(np.int32),
            fl.int_to_limbs(2 * P - 1), alt, top - alt]
    x = rng.integers(0, (1 << fl.RADIX) + 1, (fl.NLIMB, n)).astype(np.int32)
    x[0] = rng.integers(0, (1 << (fl.RADIX + 1)) + 1, n)
    for i, c in enumerate(cols):
        x[:, i] = c
        x[:, n - 1 - i] = c         # and in the last row of the fold
    return x


FOLD_OPS = {
    "mul": (j_mul, 2), "sqr": (j_sqr, 1), "sub": (j_sub, 2),
    "add": (j_add, 2), "neg": (j_neg, 1), "freeze": (j_freeze, 1),
    "tobytes": (j_tobytes, 1), "parity": (j_parity, 1),
}


@pytest.mark.parametrize("op", sorted(FOLD_OPS))
def test_folded_batch_equals_flat(op, rng):
    fn, nargs = FOLD_OPS[op]
    args = [loose_extremes(rng)]
    if nargs == 2:      # the extremes against each other and themselves
        args.append(np.roll(args[0], 3, axis=1))
    flat = np.asarray(fn(*[jnp.asarray(a) for a in args]))
    fold = np.asarray(fn(*[jnp.asarray(a) for a in fold_batch(*args)]))
    assert fold.shape == flat.shape[:-1] + (FOLD_B // 128, 128)
    assert np.array_equal(fold.reshape(flat.shape), flat)
    if op in ("mul", "sqr", "sub", "add", "neg"):   # and to the integers
        ints = [[fl.limbs_to_int(a[:, i]) for i in range(8)] for a in args]
        want = {"mul": lambda a, b: a * b, "sqr": lambda a: a * a,
                "sub": lambda a, b: a - b, "add": lambda a, b: a + b,
                "neg": lambda a: -a}[op]
        assert from_fe(flat[:, :8]) == [want(*v) % P for v in zip(*ints)]
        assert flat.min() >= 0 and flat.max() <= 1 << (fl.RADIX + 1)


def test_folded_frombytes_equals_flat(rng):
    raw = rng.integers(0, 256, (32, FOLD_B)).astype(np.int32)
    raw[:, 0], raw[:, 1], raw[31, 2] = 255, 0, 0x80
    for fn in (j_frombytes, j_frombytes_raw):
        flat = np.asarray(fn(jnp.asarray(raw)))
        fold = np.asarray(fn(jnp.asarray(*fold_batch(raw))))
        assert np.array_equal(fold.reshape(flat.shape), flat)
