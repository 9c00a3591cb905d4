"""Differential tests: JAX curve/scalar ops vs the pure-python ground truth."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from firedancer_tpu.ops import curve as fc
from firedancer_tpu.ops import limbs as fl
from firedancer_tpu.ops import scalar as fs
from firedancer_tpu.ops.ref import ed25519_ref as ref
from firedancer_tpu.ops.sigverify import fold_batch

import test_limbs as tl     # its loose extremes and jitted field ops

P = ref.P
L = ref.L


def bytes_cols(rows: list[bytes]) -> jnp.ndarray:
    """list of equal-length byte strings -> (len, B) int32 array."""
    return jnp.asarray(
        np.stack([np.frombuffer(r, dtype=np.uint8) for r in rows], axis=-1).astype(
            np.int32
        )
    )


def fe_ints(fe) -> list[int]:
    arr = np.asarray(fe)
    return [fl.limbs_to_int(arr[:, i]) for i in range(arr.shape[1])]


def points_from_jax(p):
    xs, ys, zs = fe_ints(p[0]), fe_ints(p[1]), fe_ints(p[2])
    out = []
    for x, y, z in zip(xs, ys, zs):
        zi = pow(z, P - 2, P)
        out.append((x * zi % P, y * zi % P))
    return out


def affine(p):
    x, y, z, _ = p
    zi = pow(z, P - 2, P)
    return (x * zi % P, y * zi % P)


def rand_points(rng, n):
    """n random points (python ref) plus torsion edge cases appended."""
    pts = []
    for i in range(n):
        k = int.from_bytes(rng.bytes(32), "little") % L
        pts.append(ref.point_mul(k or 1, ref.BASE))
    return pts


j_decompress = jax.jit(fc.point_decompress)
j_dbl = jax.jit(fc.point_dbl)
j_add = jax.jit(fc.point_add)
j_compress = jax.jit(fc.point_compress)
j_small = jax.jit(lambda b: fc.is_small_order(fc.point_decompress(b)[0]))
j_add_cached = jax.jit(fc.add_cached)
j_small_pt = jax.jit(fc.is_small_order)
j_validate = jax.jit(fs.sc_validate)
j_reduce = jax.jit(fs.sc_reduce512)


@pytest.mark.slow  # ~25 s of XLA compiles; decompress stays covered in
# tier-1 by test_decompress_rejects_non_points + the sigverify suites
def test_decompress_compress_roundtrip(rng):
    pts = rand_points(rng, 12)
    enc = [ref.point_compress(p) for p in pts]
    jp, ok = j_decompress(bytes_cols(enc))
    assert np.asarray(ok).all()
    assert points_from_jax(jp) == [affine(p) for p in pts]
    out = np.asarray(j_compress(jp))
    expect = np.stack(
        [np.frombuffer(e, dtype=np.uint8) for e in enc], axis=-1
    )
    assert (out == expect).all()


def test_decompress_rejects_non_points(rng):
    # y values whose x^2 is non-square: find some by brute force
    bad = []
    v = 2
    while len(bad) < 6:
        enc = int.to_bytes(v, 32, "little")
        if ref.point_decompress(enc) is None:
            bad.append(enc)
        v += 1
    _, ok = j_decompress(bytes_cols(bad))
    assert not np.asarray(ok).any()


@pytest.mark.slow  # ~30 s of XLA compiles; dbl/add correctness rides
# the tier-1 sigverify differential suites transitively
def test_dbl_add_vs_ref(rng):
    pts = rand_points(rng, 8)
    enc = bytes_cols([ref.point_compress(p) for p in pts])
    jp, _ = j_decompress(enc)
    assert points_from_jax(j_dbl(jp)) == [
        affine(ref.point_double(p)) for p in pts
    ]
    pts2 = rand_points(rng, 8)
    enc2 = bytes_cols([ref.point_compress(p) for p in pts2])
    jq, _ = j_decompress(enc2)
    assert points_from_jax(j_add(jp, jq)) == [
        affine(ref.point_add(p, q)) for p, q in zip(pts, pts2)
    ]


def small_order_encodings() -> list[bytes]:
    """All 8-torsion y-encodings, derived analytically (no scanning):
    identity y=1, order-2 y=-1, order-4 y=0; order-8 points satisfy
    x^2 = -y^2, which with the curve equation gives d*y^4 + 2y^2 - 1 = 0,
    i.e. y^2 = (+-sqrt(1+d) - 1)/d."""

    def sqrt_mod(a):
        a %= P
        x = pow(a, (P + 3) // 8, P)
        if (x * x - a) % P:
            x = x * ref.SQRT_M1 % P
        return x if (x * x - a) % P == 0 else None

    ys = [0, 1, P - 1]
    s = sqrt_mod(1 + ref.D)
    assert s is not None
    for r in (s, P - s):
        y2 = (r - 1) * pow(ref.D, P - 2, P) % P
        y = sqrt_mod(y2)
        if y is not None:
            ys += [y, P - y]
    out = []
    for y in ys:
        enc = int.to_bytes(y, 32, "little")
        p = ref.point_decompress(enc)
        if p is not None and ref.is_small_order(p):
            out.append(enc)
    return out


@pytest.mark.slow  # heaviest compile in the file (~40 s on 1 core)
def test_small_order_detection(rng):
    # All 8-torsion encodings must flag; random honest points must not.
    found = small_order_encodings()
    assert len(found) >= 5
    honest = [ref.point_compress(p) for p in rand_points(rng, 5)]
    flags = np.asarray(j_small(bytes_cols(found + honest)))
    assert flags[: len(found)].all()
    assert not flags[len(found):].any()


def test_scalar_validate(rng):
    cases = [0, 1, L - 1, L, L + 1, 2**252, (1 << 256) - 1] + [
        int.from_bytes(rng.bytes(32), "little") for _ in range(9)
    ]
    enc = bytes_cols([int.to_bytes(v, 32, "little") for v in cases])
    got = list(np.asarray(j_validate(enc)))
    assert got == [v < L for v in cases]


def test_scalar_reduce512(rng):
    cases = [0, 1, L, L - 1, 2**252, (1 << 512) - 1] + [
        int.from_bytes(rng.bytes(64), "little") for _ in range(10)
    ]
    enc = bytes_cols([int.to_bytes(v, 64, "little") for v in cases])
    out = np.asarray(j_reduce(enc))
    got = [fs.limbs_to_int(out[:, i]) for i in range(len(cases))]
    assert got == [v % L for v in cases]


@pytest.mark.slow  # jit-compiles the full double-scalar-mult (~2 min)
def test_double_scalar_mul_base(rng):
    # [s]B + [k]A vs python ref, including k or s = 0 edge cases
    ks = [int.from_bytes(rng.bytes(32), "little") % L for _ in range(6)] + [0, 1]
    ss = [int.from_bytes(rng.bytes(32), "little") % L for _ in range(6)] + [1, 0]
    pts = rand_points(rng, 8)
    enc = bytes_cols([ref.point_compress(p) for p in pts])

    @jax.jit
    def run(kb, sb, penc):
        a, _ = fc.point_decompress(penc)
        return fc.double_scalar_mul_base(kb, a, sb)

    def sc(vals):
        return fs.sc_frombytes(
            bytes_cols([int.to_bytes(v, 32, "little") for v in vals])
        )

    kb = jax.jit(fs.sc_bits)(sc(ks))
    sb = jax.jit(fs.sc_bits)(sc(ss))
    got = points_from_jax(run(kb, sb, enc))
    expect = [
        affine(ref.point_add(ref.point_mul(s, ref.BASE), ref.point_mul(k, p)))
        for k, s, p in zip(ks, ss, pts)
    ]
    assert got == expect


@pytest.mark.slow  # compiles BOTH scalar-mult paths (~100 s on 1 core)
def test_windowed_matches_ladder(rng):
    """Differential: the windowed fast path == the 1-bit Shamir ladder on
    random (k, s, A) triples (both must equal the host ref, but checking
    them against each other catches shared-helper regressions too)."""
    ks = [int.from_bytes(rng.bytes(32), "little") % L for _ in range(4)]
    ss = [int.from_bytes(rng.bytes(32), "little") % L for _ in range(4)]
    pts = rand_points(rng, 4)
    enc = bytes_cols([ref.point_compress(p) for p in pts])

    def sc(vals):
        return fs.sc_frombytes(
            bytes_cols([int.to_bytes(v, 32, "little") for v in vals])
        )

    kb = jax.jit(fs.sc_bits)(sc(ks))
    sb = jax.jit(fs.sc_bits)(sc(ss))
    a, _ = jax.jit(fc.point_decompress)(enc)
    fast = points_from_jax(jax.jit(fc.double_scalar_mul_base)(kb, a, sb))
    slow = points_from_jax(jax.jit(fc.double_scalar_mul_base_ladder)(kb, a, sb))
    assert fast == slow  # affine (x, y) pairs


# -- the folded batch (ISSUE 38) ----------------------------------------------
#
# ops/sigverify.fold_batch hands the ladder its batch as (..., B // 128,
# 128).  The group and scalar ops it is made of, each on that layout
# against itself on the one-axis batch, lane by lane.

FOLD_B = 256


def _fold(x):
    return fold_batch(jnp.asarray(x))[0]


def _loose_points(rng, k):
    """k-tuples of (20, FOLD_B) loose limb arrays (limbs inside the
    invariant, fl.LOOSE_MIN .. fl.LOOSE_MAX: what the ladder's state may
    hold), the first lane at the maxima and the last at the minima."""
    out = []
    for _ in range(k):
        x = rng.integers(fl.LOOSE_MIN[:, None], fl.LOOSE_MAX[:, None] + 1,
                         (fl.NLIMB, FOLD_B))
        x[:, 0], x[:, -1] = fl.LOOSE_MAX, fl.LOOSE_MIN
        out.append(jnp.asarray(x.astype(np.int32)))
    return tuple(out)


def _table(rng):
    return tuple(jnp.asarray(rng.integers(
        0, 1 << fl.RADIX, (16, fl.NLIMB, FOLD_B)).astype(np.int32))
        for _ in range(4))


def _fold_case(name, rng):
    """-> (jitted fn, flat args): the op under test and its inputs with
    the batch as their last axis."""
    if name == "point_dbl":
        return j_dbl, (_loose_points(rng, 4),)
    if name == "add_cached":
        return j_add_cached, (_loose_points(rng, 4), _loose_points(rng, 4))
    if name == "select16":
        sel = np.arange(FOLD_B, dtype=np.int32) % 16
        return jax.jit(fc._select16), (_table(rng), jnp.asarray(sel))
    if name == "select16_comb_row":     # the base comb's constant rows
        row = jnp.asarray(fc._comb_table()[3])          # (16, 4, NLIMB)
        sel = jnp.asarray(rng.integers(0, 16, FOLD_B).astype(np.int32))

        def comb(sel):
            one = (1,) * (sel.ndim)
            return fc._select16(tuple(
                row[:, c, :].reshape((16, fl.NLIMB) + one)
                for c in range(4)), sel)
        return jax.jit(comb), (sel,)
    if name == "windows":
        bits = rng.integers(0, 2, (fc.NBITS, FOLD_B)).astype(np.int32)
        bits[:, 0], bits[:, -1] = 1, 0
        return jax.jit(fc._windows), (jnp.asarray(bits),)
    if name == "sc_bits":
        return jax.jit(fs.sc_bits), (_loose_points(rng, 1)[0] & fl.MASK,)
    assert name == "sc_reduce512"
    enc = rng.integers(0, 256, (64, FOLD_B)).astype(np.int32)
    enc[:, 0], enc[:, -1] = 255, 0
    return j_reduce, (jnp.asarray(enc),)


@pytest.mark.parametrize("name", [
    "point_dbl", "add_cached", "select16", "select16_comb_row", "windows",
    "sc_bits", "sc_reduce512"])
def test_folded_batch_equals_flat(name, rng):
    fn, args = _fold_case(name, rng)
    flat = jax.tree_util.tree_leaves(fn(*args))
    fold = jax.tree_util.tree_leaves(
        fn(*jax.tree_util.tree_map(_fold, args)))
    assert len(flat) == len(fold) > 0
    for a, b in zip(flat, fold):
        a, b = np.asarray(a), np.asarray(b)
        assert b.shape == a.shape[:-1] + (FOLD_B // 128, 128)
        assert np.array_equal(b.reshape(a.shape), a)
    if name == "select16":      # lane e picked entry e % 16
        for got, t in zip(flat, args[0]):
            t = np.asarray(t)
            assert np.array_equal(
                np.asarray(got), t[np.arange(FOLD_B) % 16, :,
                                   np.arange(FOLD_B)].T)
    if name == "windows":
        w = np.asarray(flat[0])
        assert w.shape == (fc.NWIN, FOLD_B) and w[:, 0].tolist() == \
            [15] * 63 + [1] and not w[:, -1].any()


# -- the group ops over the field's lazier carries (ISSUE 43) -----------------
#
# Each against ops/ref on the CPU, fed what the ops before it left behind
# (limbs anywhere inside the loose invariant, limb 0 below zero among
# them), on the (20, FOLD_B) batch the fold cases above compile.


def _limb_cols(cols):
    """n rows of four integers -> a point of four (20, n) limb arrays."""
    return tuple(jnp.asarray(np.stack(
        [fl.int_to_limbs(c[k]) for c in cols], axis=-1)) for k in range(4))


def _ext_limbs(pts, rng):
    """ref points, each scaled by a random Z, as a point of (20, n) limbs."""
    zs = [int.from_bytes(rng.bytes(32), "little") % P or 1 for _ in pts]
    return _limb_cols([[x * z % P, y * z % P, z, x * y % P * z % P]
                       for (x, y), z in zip((affine(p) for p in pts), zs)])


def _in_invariant(p):
    return all(tl.in_invariant(c) for c in p)


@pytest.mark.parametrize("op", ["point_dbl", "add_cached"])
def test_group_op_chain_vs_ref(op, rng):
    pts = rand_points(rng, 14) + [ref.IDENT, (0, P - 1, 1, 0)]
    jp = _ext_limbs([pts[i % 16] for i in range(FOLD_B)], rng)
    look = list(range(16)) + list(range(FOLD_B - 16, FOLD_B))
    if op == "point_dbl":
        want = pts
        for _ in range(3):      # each doubling eats the last one's limbs
            jp = j_dbl(jp)
            want = [ref.point_double(p) for p in want]
            assert _in_invariant(jp)
    else:
        qs = rand_points(rng, 15) + [ref.IDENT]
        jq = _limb_cols([
            [(y + x) % P, (y - x) % P, 1, 2 * ref.D * x % P * y % P]
            for x, y in (affine(qs[i % 16]) for i in range(FOLD_B))])
        jp = j_dbl(j_add_cached(j_add_cached(jp, jq), jq))
        want = [ref.point_double(ref.point_add(ref.point_add(p, q), q))
                for p, q in zip(pts, qs)]
        assert _in_invariant(jp)
    got = points_from_jax(tuple(np.asarray(c)[:, look] for c in jp))
    assert got == [affine(want[i % 16]) for i in look]
    # T = XY / Z too: the next addition reads it
    x, y, z, t = (fe_ints(np.asarray(c)[:, look]) for c in jp)
    assert all((a * b - c * d) % P == 0 for a, b, c, d in zip(x, y, z, t))


def torsion_encodings() -> list[bytes]:
    """The eight points of order dividing 8, one encoding each."""
    out = []
    for enc in small_order_encodings():
        out.append(enc)
        if affine(ref.point_decompress(enc))[0]:    # the same y, the other root
            out.append(enc[:31] + bytes([enc[31] | 0x80]))
    return out


def test_is_small_order_on_the_eight_torsion_points(rng):
    enc = torsion_encodings()
    pts = [ref.point_decompress(e) for e in enc]
    assert len({affine(p) for p in pts}) == 8
    assert all(ref.is_small_order(p) for p in pts)
    enc += [ref.point_compress(p) for p in rand_points(rng, 4)]
    flags = []
    for half in (enc[:6], enc[6:]):     # the six-lane decompress program
        jp, ok = j_decompress(bytes_cols(half))
        assert np.asarray(ok).all()
        flags += np.asarray(j_small_pt(jp)).tolist()
    assert flags == [True] * 8 + [False] * 4


def test_decompress_edge_cases_vs_ref(rng):
    noncanon = [int.to_bytes(y, 32, "little") for y in range(P, 1 << 255)
                if ref.point_decompress(int.to_bytes(y, 32, "little"))]
    honest = ref.point_compress(rand_points(rng, 1)[0])
    nonsquare = next(
        e for e in (int.to_bytes(v, 32, "little") for v in range(2, 99))
        if ref.point_decompress(e) is None)
    one = int.to_bytes(1, 32, "little")
    enc = [
        noncanon[0], noncanon[-1],                  # y >= p, both ends
        one[:31] + b"\x80",                         # x = 0, the sign bit set
        nonsquare,
        honest,
        honest[:31] + bytes([honest[31] ^ 0x80]),   # the other root
    ]
    jp, ok = j_decompress(bytes_cols(enc))
    want = [ref.point_decompress(e) for e in enc]
    assert np.asarray(ok).tolist() == [w is not None for w in want]
    got = points_from_jax(jp)
    for g, w, e in zip(got, want, enc):
        if w is not None:
            assert g == affine(w), e.hex()
    assert got[2] == (0, 1)     # (0, y) whatever the sign bit says (dalek)


@pytest.mark.parametrize("what", ["freeze", "parity", "eq", "tobytes"])
def test_canonical_forms_from_the_loosest_limbs(what, rng):
    x = tl.loose_extremes(rng)
    vals = [fl.limbs_to_int(x[:, i]) for i in range(x.shape[1])]
    if what == "freeze":
        got = np.asarray(tl.j_freeze(jnp.asarray(x)))
        assert [got[:, i].tolist() for i in range(len(vals))] == [
            fl.int_to_limbs(v).tolist() for v in vals]
    elif what == "parity":
        assert np.asarray(tl.j_parity(jnp.asarray(x))).tolist() == [
            v & 1 for v in vals]
    elif what == "tobytes":
        got = np.asarray(tl.j_tobytes(jnp.asarray(x))).astype(np.uint8)
        assert [got[:, i].tobytes() for i in range(len(vals))] == [
            v.to_bytes(32, "little") for v in vals]
    else:
        # the same value in its canonical limbs, and one off it
        same = np.stack([fl.int_to_limbs(v) for v in vals], axis=-1)
        off = np.stack([fl.int_to_limbs(v + 1) for v in vals], axis=-1)
        assert np.asarray(tl.j_eq(jnp.asarray(x), jnp.asarray(same))).all()
        assert not np.asarray(tl.j_eq(jnp.asarray(x), jnp.asarray(off))).any()


@pytest.mark.parametrize("chain", ["pow2523", "invert"])
def test_power_chain_vs_pow(chain, rng):
    x = tl.loose_extremes(rng, 16)
    vals = [fl.limbs_to_int(x[:, i]) for i in range(16)]
    fn, e = {"pow2523": (tl.j_pow2523, (P - 5) // 8),
             "invert": (tl.j_invert, P - 2)}[chain]
    out = fn(jnp.asarray(x))
    assert tl.in_invariant(out)
    assert tl.from_fe(out) == [pow(v, e, P) for v in vals]
