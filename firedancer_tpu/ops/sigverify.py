"""The north-star op: batched ed25519 signature verification on TPU.

One jit-compiled program verifies B signatures at once, returning a pass/fail
mask — the TPU-native replacement for the reference's verify-tile call chain
fd_ed25519_verify_batch_single_msg (fd_ed25519_user.c:232) and the
wiredancer FPGA offload.  Semantics match fd_ed25519_verify
(fd_ed25519_user.c:136-231) exactly:

    1. reject s >= L                      (scalar malleability rule)
    2. decompress A (pubkey) and R (sig[0:32]); reject failures; accept
       non-canonical field encodings (dalek 2.x parity)
    3. reject small-order A and small-order R (verify_strict rule)
    4. k = SHA512(R || A || msg) mod L
    5. accept iff [S]B + [k](-A) == R     (Z2=1 comparison, no inversion)

Unlike the reference's batch call — which rejects the whole batch on the
first bad signature and makes the tile drop the txn — the kernel returns a
per-element mask; the verify *stage* (runtime/verify.py) applies the same
txn-level all-sigs-must-pass rule on top.

Differences from a CPU implementation worth noting: there is no
data-dependent control flow at all — invalid points flow through the ladder
as garbage and are masked at the end — so the program is one straight-line
XLA computation, fully batched on the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import curve as fc
from . import scalar as fs
from . import sha512 as fsha


def _verify_ok(
    msg: jnp.ndarray,
    msg_len: jnp.ndarray,
    sig: jnp.ndarray,
    pubkey: jnp.ndarray,
    *,
    max_msg_len: int,
) -> jnp.ndarray:
    """The verify ladder core (traced, unjitted): validate + sha512 +
    double-scalar-mult + compare.  ONE implementation — every kernel in
    the ladder (baseline, fused, the serving-plane step) traces exactly
    this, so their masks cannot diverge by construction."""
    msg = msg.astype(jnp.int32)
    sig = sig.astype(jnp.int32)
    pubkey = pubkey.astype(jnp.int32)
    r_enc = sig[:32]
    s_enc = sig[32:]

    ok_s = fs.sc_validate(s_enc)
    a_pt, ok_a = fc.point_decompress(pubkey)
    r_pt, ok_r = fc.point_decompress(r_enc)
    ok_a = ok_a & ~fc.is_small_order(a_pt)
    ok_r = ok_r & ~fc.is_small_order(r_pt)

    # k = SHA512(R || A || msg) mod L
    hmsg = jnp.concatenate([r_enc, pubkey, msg], axis=0)
    digest = fsha.sha512_msg(hmsg, msg_len + 64, max_msg_len + 64)
    k = fs.sc_reduce512(digest)

    k_bits = fs.sc_bits(k)
    s_bits = fs.sc_bits(fs.sc_frombytes(s_enc))
    r_cmp = fc.double_scalar_mul_base(k_bits, fc.point_neg(a_pt), s_bits)
    return ok_s & ok_a & ok_r & fc.point_eq_z1(r_cmp, r_pt)


@functools.partial(jax.jit, static_argnames=("max_msg_len",))
def ed25519_verify_batch(
    msg: jnp.ndarray,
    msg_len: jnp.ndarray,
    sig: jnp.ndarray,
    pubkey: jnp.ndarray,
    *,
    max_msg_len: int,
) -> jnp.ndarray:
    """Verify B independent (msg, sig, pubkey) triples.

    msg:     (max_msg_len, B) byte rows (uint8 or int32; bytes past
             msg_len ignored) — ship uint8: the host->device transfer is
             4x smaller and the widening is free on-device
    msg_len: (B,) int32
    sig:     (64, B) byte rows
    pubkey:  (32, B) byte rows
    Returns (B,) bool.
    """
    return _verify_ok(msg, msg_len, sig, pubkey, max_msg_len=max_msg_len)


@functools.partial(jax.jit, static_argnames=("max_msg_len",))
def ed25519_verify_batch_fused(
    msg: jnp.ndarray,
    msg_len: jnp.ndarray,
    sig: jnp.ndarray,
    pubkey: jnp.ndarray,
    n_real: jnp.ndarray,
    *,
    max_msg_len: int,
):
    """The generic-lane serving program (ISSUE 13): the WHOLE per-batch
    device computation — validate + sha512 + double-scalar-mult +
    compare, plus the pad-lane mask and the batch ok-count — in ONE
    compiled module, one dispatch per batch.

    Replaces the four-phase split chain (and the baseline kernel + host
    mask arithmetic) as the verify stage's default path: the split
    pipeline pays three inter-phase HBM round trips and four dispatch
    latencies per batch; here XLA fuses everything and the stage's reap
    point reads `n_ok == n_real` to take the common all-pass fast path
    without scanning the mask.

    n_real: scalar int32 — lanes >= n_real are padding and come back
    False (or a (B,) int32 vector of such limits, one a lane: lane p is
    real where n_real[p] > p).  Returns ((B,) bool mask, scalar int32
    ok-count over the real lanes).
    """
    ok = _verify_ok(msg, msg_len, sig, pubkey, max_msg_len=max_msg_len)
    lane = jnp.arange(ok.shape[0], dtype=jnp.int32)
    ok = ok & (lane < n_real)
    return ok, jnp.sum(ok.astype(jnp.int32))


# -- repeated-signer fast path ------------------------------------------------
#
# Vote-shaped traffic repeats a small signer set; with a per-pubkey comb
# bank resident in HBM (ops/curve.py: comb cache) a cached signer's verify
# skips A's decompress/small-order work AND all 256 dsm doublings: 128
# cached adds + R decompress + SHA-512.  The stage partitions each batch
# into cached/uncached elements and dispatches the matching kernel.


@functools.partial(jax.jit, static_argnames=("max_msg_len",))
def ed25519_verify_batch_cached(
    msg: jnp.ndarray,
    msg_len: jnp.ndarray,
    sig: jnp.ndarray,
    pubkey: jnp.ndarray,
    bank: jnp.ndarray,
    slots: jnp.ndarray,
    *,
    max_msg_len: int,
) -> jnp.ndarray:
    """Verify B triples whose signer combs live in `bank` at `slots`.

    The pubkey byte rows are still required (k = SHA512(R||A||msg)); A's
    point validity/small-order checks happened at bank-fill time
    (comb_fill), so invalid pubkeys never enter the bank.
    """
    msg = msg.astype(jnp.int32)
    sig = sig.astype(jnp.int32)
    pubkey = pubkey.astype(jnp.int32)
    r_enc = sig[:32]
    s_enc = sig[32:]

    ok_s = fs.sc_validate(s_enc)
    r_pt, ok_r = fc.point_decompress(r_enc)
    ok_r = ok_r & ~fc.is_small_order(r_pt)

    hmsg = jnp.concatenate([r_enc, pubkey, msg], axis=0)
    digest = fsha.sha512_msg(hmsg, msg_len + 64, max_msg_len + 64)
    k = fs.sc_reduce512(digest)

    k_bits = fs.sc_bits(k)
    s_bits = fs.sc_bits(fs.sc_frombytes(s_enc))
    r_cmp = fc.double_scalar_mul_comb(k_bits, s_bits, bank, slots)
    return ok_s & ok_r & fc.point_eq_z1(r_cmp, r_pt)


@jax.jit
def comb_fill(pubkey: jnp.ndarray):
    """(32, M) pubkey byte rows -> ((NWIN, 16, 4, NLIMB, M) int16, (M,) ok).

    Decompresses + strict-checks each pubkey once and builds the -A comb;
    elements with ok=False carry garbage tables and must not be installed.
    """
    a_pt, ok = fc.point_decompress(pubkey.astype(jnp.int32))
    ok = ok & ~fc.is_small_order(a_pt)
    tables = fc.comb_tables(a_pt).astype(jnp.int16)
    return tables, ok


@functools.partial(jax.jit, donate_argnames=("bank",))
def bank_install(bank, tables, slots):
    """Write `tables` (.., M) into bank slots (M,) in place (donated)."""
    return bank.at[..., slots].set(tables)


def bank_alloc(n_slots: int):
    """Zeroed device comb bank for `n_slots` signers (~164 KB per slot)."""
    import jax.numpy as jnp

    from . import curve as fc
    from . import limbs as fl

    return jnp.zeros(
        (fc.NWIN, 16, 4, fl.NLIMB, n_slots), dtype=jnp.int16
    )


# -- split-phase variant ------------------------------------------------------
#
# The same computation as four separately jitted programs: an A/B
# reference that shows what XLA's fusion buys (each phase boundary is an
# HBM round trip the fused program does not pay).  Same inputs, same
# mask; nothing dispatches to it by default or as a fallback.


@jax.jit
def _phase_validate(sig, pubkey):
    sig = sig.astype(jnp.int32)
    pubkey = pubkey.astype(jnp.int32)
    r_enc = sig[:32]
    ok_s = fs.sc_validate(sig[32:])
    a_pt, ok_a = fc.point_decompress(pubkey)
    r_pt, ok_r = fc.point_decompress(r_enc)
    ok = ok_s & ok_a & ~fc.is_small_order(a_pt)
    ok = ok & ok_r & ~fc.is_small_order(r_pt)
    return a_pt, r_pt, ok


@functools.partial(jax.jit, static_argnames=("max_msg_len",))
def _phase_hash(msg, msg_len, sig, pubkey, *, max_msg_len):
    msg = msg.astype(jnp.int32)
    sig = sig.astype(jnp.int32)
    pubkey = pubkey.astype(jnp.int32)
    hmsg = jnp.concatenate([sig[:32], pubkey, msg], axis=0)
    digest = fsha.sha512_msg(hmsg, msg_len + 64, max_msg_len + 64)
    return fs.sc_bits(fs.sc_reduce512(digest))


@jax.jit
def _phase_dsm(k_bits, a_pt, sig):
    s_bits = fs.sc_bits(fs.sc_frombytes(sig[32:].astype(jnp.int32)))
    return fc.double_scalar_mul_base(k_bits, fc.point_neg(a_pt), s_bits)


@jax.jit
def _phase_compare(r_cmp, r_pt, ok):
    return ok & fc.point_eq_z1(r_cmp, r_pt)


def ed25519_verify_batch_split(msg, msg_len, sig, pubkey, *, max_msg_len):
    """Drop-in for ed25519_verify_batch using the four-phase pipeline."""
    a_pt, r_pt, ok = _phase_validate(sig, pubkey)
    k_bits = _phase_hash(msg, msg_len, sig, pubkey, max_msg_len=max_msg_len)
    r_cmp = _phase_dsm(k_bits, a_pt, sig)
    return _phase_compare(r_cmp, r_pt, ok)


# -- the kernel ladder --------------------------------------------------------
#
# One registry for the generic-lane kernel choice (the verify stage's
# `kernel=` knob and the dispatch-count assertions in tests).  Every
# lane returns the SAME mask on the same inputs — they all trace
# _verify_ok — and differs only in how many compiled modules a batch
# dispatch enters:
#
#   fused    1 module  (mask + pad-lane mask + ok-count, the default)
#   baseline 1 module  (mask only; pad masking/count fall to the host)
#   split    4 modules (A/B reference: the cost of the phase boundaries)

KERNEL_LADDER = ("fused", "baseline", "split")

# the jitted callables each lane enters per batch dispatch, in call
# order — len() of a row IS that lane's dispatches-per-batch, and
# summing _cache_size() over a row counts its live compiled entries
_KERNEL_JITS = {
    "fused": (ed25519_verify_batch_fused,),
    "baseline": (ed25519_verify_batch,),
    "split": (_phase_validate, _phase_hash, _phase_dsm, _phase_compare),
}


def kernel_dispatch_count(kernel: str) -> int:
    """Compiled modules entered per batch dispatch on this lane."""
    return len(_KERNEL_JITS[kernel])


def kernel_compiled_entries(kernel: str) -> int:
    """Live compiled-executable entries across the lane's jit caches —
    after exactly one batch shape has run, this equals
    kernel_dispatch_count (the acceptance assertion for 'the fused
    program dispatches ONE compiled module per batch')."""
    return sum(int(f._cache_size()) for f in _KERNEL_JITS[kernel])


def kernel_clear_caches(kernel: str) -> None:
    """Drop the lane's compiled entries (test isolation for the
    entry-count assertions)."""
    for f in _KERNEL_JITS[kernel]:
        f.clear_cache()


def verify_dispatch(kernel: str, msg, msg_len, sig, pubkey, n_real,
                    *, max_msg_len: int):
    """Dispatch one batch on the chosen ladder lane.

    n_real: how many leading lanes are real (an int), or, where the real
    lanes are no prefix (a mesh dealt round-robin), a placed (B,) int32
    lane vector with a value above p in lane p where it is real.

    Returns (mask future, ok-count future | None): only the fused lane
    computes the count on device; callers fall back to host mask
    arithmetic when it is None.  Pad-lane masking is on-device for the
    fused lane and the caller's job otherwise (the stage ignores lanes
    >= n_real when reaping, so the masks agree on every REAL lane)."""
    if kernel == "fused":
        import jax.numpy as _jnp

        if getattr(n_real, "ndim", 0) == 0:
            n_real = _jnp.int32(n_real)
        return ed25519_verify_batch_fused(
            msg, msg_len, sig, pubkey, n_real, max_msg_len=max_msg_len,
        )
    if kernel == "baseline":
        return (
            ed25519_verify_batch(msg, msg_len, sig, pubkey,
                                 max_msg_len=max_msg_len),
            None,
        )
    if kernel == "split":
        return (
            ed25519_verify_batch_split(msg, msg_len, sig, pubkey,
                                       max_msg_len=max_msg_len),
            None,
        )
    raise ValueError(f"unknown verify kernel {kernel!r} "
                     f"(ladder: {', '.join(KERNEL_LADDER)})")
