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
    assert in_invariant(fa)


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


# -- the bound proof (ISSUE 43) -------------------------------------------------
#
# The schedule of ops/limbs.py once more, over any number type: on Python
# integers it is held, limb for limb, to what the module computes
# (test_folded_batch_equals_flat), and on intervals it is the proof that
# no intermediate leaves int32 and that the loose invariant is closed.

INT32 = 1 << 31


class Iv:
    """A closed interval of integers; every one ever made fits int32."""

    def __init__(self, lo, hi=None):
        self.lo, self.hi = lo, lo if hi is None else hi
        assert -INT32 < self.lo <= self.hi < INT32, (self.lo, self.hi)

    @staticmethod
    def of(x):
        return x if isinstance(x, Iv) else Iv(x)

    def __add__(self, o):
        o = Iv.of(o)
        return Iv(self.lo + o.lo, self.hi + o.hi)

    __radd__ = __add__

    def __sub__(self, o):
        o = Iv.of(o)
        return Iv(self.lo - o.hi, self.hi - o.lo)

    def __rsub__(self, o):
        return Iv.of(o) - self

    def __mul__(self, o):
        o = Iv.of(o)
        c = [self.lo * o.lo, self.lo * o.hi, self.hi * o.lo, self.hi * o.hi]
        return Iv(min(c), max(c))

    __rmul__ = __mul__

    def __rshift__(self, n):
        return Iv(self.lo >> n, self.hi >> n)

    def __and__(self, m):
        assert m == fl.MASK
        if self.lo >> fl.RADIX == self.hi >> fl.RADIX:
            return Iv(self.lo & m, self.hi & m)
        return Iv(0, m)

    def mag(self):
        return max(abs(self.lo), abs(self.hi))

    def within(self, lo, hi):
        return lo <= self.lo and self.hi <= hi


def sh_sum(terms):
    """A row's terms added up; in whatever order the compiler adds them,
    no partial sum passes the sum of their magnitudes."""
    if isinstance(terms[0], Iv):
        assert sum(t.mag() for t in terms) < INT32
    acc = terms[0]
    for t in terms[1:]:
        acc = acc + t
    return acc


def sh_carry(x):
    hi = [v >> fl.RADIX for v in x]
    lo = [v & fl.MASK for v in x]
    return [lo[0] + fl.FOLD * hi[-1]] + [
        lo[k] + hi[k - 1] for k in range(1, fl.NLIMB)]


TWO_P = [2 * int(v) for v in fl._P_LIMBS]


def sh_add(a, b):
    return sh_carry([x + y for x, y in zip(a, b)])


def sh_sub(a, b):
    return sh_carry([x + t - y for x, t, y in zip(a, TWO_P, b)])


def sh_neg(a):
    return sh_carry([t - x for t, x in zip(TWO_P, a)])


def sh_conv(a, b):
    n = fl.NLIMB
    return [sh_sum([a[i] * b[k - i]
                    for i in range(max(0, k - n + 1), min(k, n - 1) + 1)])
            for k in range(2 * n - 1)]


def sh_fold_stages(c):
    """-> (rows after the one pass, the folded limbs, after one carry,
    after two): _conv_fold's four stages."""
    n = fl.NLIMB
    hi = [v >> fl.RADIX for v in c]
    lo = [v & fl.MASK for v in c]
    one = [lo[0]] + [lo[k] + hi[k - 1] for k in range(1, 2 * n - 1)] \
        + [hi[2 * n - 2]]
    r = [one[k] + fl.FOLD * one[k + n] for k in range(n)]
    c1 = sh_carry(r)
    return one, r, c1, sh_carry(c1)


def sh_mul(a, b):
    return sh_fold_stages(sh_conv(a, b))[3]


def sh_sqr(a):
    return sh_mul(a, a)


SHADOW = {"add": sh_add, "sub": sh_sub, "neg": sh_neg, "mul": sh_mul,
          "sqr": sh_sqr}


def loosest():
    return [Iv(int(lo), int(hi)) for lo, hi in zip(fl.LOOSE_MIN, fl.LOOSE_MAX)]


@pytest.mark.parametrize("op", sorted(SHADOW))
def test_schedule_stays_in_int32_and_closes_the_invariant(op):
    # from the invariant's worst case in every limb at once (Iv refuses
    # an intermediate outside int32), back inside the invariant: closed
    # under any chain of ops
    fn = SHADOW[op]
    out = fn(*[loosest()] * (2 if op in ("add", "sub", "mul") else 1))
    for v, lo, hi in zip(out, fl.LOOSE_MIN, fl.LOOSE_MAX):
        assert v.within(lo, hi), (op, v.lo, v.hi, lo, hi)


def test_conv_fold_bounds():
    # the numbers _conv_fold's and fe_mul's notes give, stage by stage
    c = sh_conv(loosest(), loosest())
    assert max(v.mag() for v in c) < 1.38e9
    one, r, c1, c2 = sh_fold_stages(c)
    assert max(v.mag() for v in one) < 176_300
    assert max(v.mag() for v in r) < 1 << 27
    assert c1[0].mag() < 400_000 and max(v.mag() for v in c1[1:]) < 21_300
    assert all(v.within(lo, hi)
               for v, lo, hi in zip(c2, fl.LOOSE_MIN, fl.LOOSE_MAX))


def test_conv_rows_between_two_rows_of_zeros(rng):
    # what _conv_fold's four slices rest on: 41 rows, the product's 39
    # in the middle, limb for limb the integers'
    a, b = loose_extremes(rng, 16), np.roll(loose_extremes(rng, 16), 5, axis=1)
    c = np.asarray(jax.jit(fl._conv)(jnp.asarray(a), jnp.asarray(b)))
    assert c.shape == (2 * fl.NLIMB + 1, 16)
    assert not c[0].any() and not c[-1].any()
    for i in range(16):
        assert c[1:-1, i].tolist() == sh_conv(
            [int(v) for v in a[:, i]], [int(v) for v in b[:, i]])


def test_the_invariant_is_the_least_closed_one():
    # what the ops reach from freshly unpacked limbs ([0, 2^13)) is the
    # stated invariant, limb for limb: nothing in it is slack
    inv = [Iv(0, fl.MASK)] * fl.NLIMB
    for _ in range(8):
        outs = [sh_add(inv, inv), sh_sub(inv, inv), sh_neg(inv),
                sh_mul(inv, inv), sh_sqr(inv)]
        inv = [Iv(min(v.lo for v in vs), max(v.hi for v in vs))
               for vs in zip(inv, *outs)]
    assert [v.lo for v in inv] == fl.LOOSE_MIN.tolist()
    assert [v.hi for v in inv] == fl.LOOSE_MAX.tolist()


# -- the folded batch (ISSUE 38) ----------------------------------------------
#
# ops/sigverify.fold_batch lays a program's batch on both tiled axes,
# (20, B // 128, 128): the field ops are written for any batch rank, and
# here each is held, lane by lane, to itself on the one-axis batch.

FOLD_B = 256


def loose_extremes(rng, n=FOLD_B):
    """(20, n) limb columns at the edges of the loose invariant
    (fl.LOOSE_MIN <= limbs <= fl.LOOSE_MAX, negative limbs included):
    every limb at its maximum, every limb at its minimum, zero, p's own
    limbs, 2p - 1 reduced, maxima and minima alternating both ways, the
    rest random loose limbs."""
    lo, hi = fl.LOOSE_MIN, fl.LOOSE_MAX
    odd = np.arange(fl.NLIMB) % 2 == 1
    cols = [hi, lo, np.zeros(fl.NLIMB, np.int32), fl._P_LIMBS.astype(np.int32),
            fl.int_to_limbs(2 * P - 1), np.where(odd, hi, lo),
            np.where(odd, lo, hi)]
    x = rng.integers(lo[:, None], hi[:, None] + 1, (fl.NLIMB, n)).astype(np.int32)
    for i, c in enumerate(cols):
        x[:, i] = c
        x[:, n - 1 - i] = c         # and in the last row of the fold
    return x


def in_invariant(x):
    x = np.asarray(x).reshape(fl.NLIMB, -1)
    return bool((x >= fl.LOOSE_MIN[:, None]).all()
                and (x <= fl.LOOSE_MAX[:, None]).all())


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
    if op in SHADOW:    # to the integers, and limb for limb to the shadow
        cols = list(range(8)) + list(range(FOLD_B - 8, FOLD_B))
        ints = [[fl.limbs_to_int(a[:, i]) for i in cols] for a in args]
        want = {"mul": lambda a, b: a * b, "sqr": lambda a: a * a,
                "sub": lambda a, b: a - b, "add": lambda a, b: a + b,
                "neg": lambda a: -a}[op]
        assert from_fe(flat[:, cols]) == [want(*v) % P for v in zip(*ints)]
        assert in_invariant(flat)
        for i in cols:
            assert SHADOW[op](*[[int(v) for v in a[:, i]] for a in args]) \
                == flat[:, i].tolist()


def test_folded_frombytes_equals_flat(rng):
    raw = rng.integers(0, 256, (32, FOLD_B)).astype(np.int32)
    raw[:, 0], raw[:, 1], raw[31, 2] = 255, 0, 0x80
    for fn in (j_frombytes, j_frombytes_raw):
        flat = np.asarray(fn(jnp.asarray(raw)))
        fold = np.asarray(fn(jnp.asarray(*fold_batch(raw))))
        assert np.array_equal(fold.reshape(flat.shape), flat)
