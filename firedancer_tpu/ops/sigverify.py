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


# -- the layout of the batch inside a program ---------------------------------
#
# The TPU tiles an array's two minor dimensions (8 sublanes x 128 lanes a
# vreg), and the library under this file is written for any batch rank
# (ops/limbs.py's docstring has what each layout costs).  So a program
# folds its batch onto BOTH tiled axes, (..., B) -> (..., B // 128, 128):
# the limb, word, byte, bit and table-entry axes lead, untiled, and at the
# tile batch of 1,024 a limb is exactly one (8, 128) vreg, a field element
# 20 and a convolution accumulator 41.  One reshape at a program's entry,
# one of the mask at its exit.  On a v5e the folded program takes 0.78x the
# time of the one-axis one (/PERF.md section 6, PR 38).

FOLD_LANES = 128


def fold_lanes(batch: int) -> int:
    """128 when a program over `batch` lanes folds them to
    (batch // 128, 128), 0 when it runs them on their one trailing axis:
    the rule reads the input's shape and nothing else (the verify
    stage's gauge kernel_fold_lanes is this number)."""
    return FOLD_LANES if batch > 0 and batch % FOLD_LANES == 0 else 0


def fold_batch(*arrays: jnp.ndarray):
    """Every (..., B) array -> (..., B // 128, 128) where
    `fold_lanes(B)`, else as it is.  The ONE place a program decides its
    batch layout: each entry below folds what it was given and reshapes
    its mask back to (B,), so the lanes cannot diverge."""
    if not fold_lanes(arrays[0].shape[-1]):
        return arrays
    return tuple(
        x.reshape(x.shape[:-1] + (x.shape[-1] // FOLD_LANES, FOLD_LANES))
        for x in arrays)


def _verify_ok(
    msg: jnp.ndarray,
    msg_len: jnp.ndarray,
    sig: jnp.ndarray,
    pubkey: jnp.ndarray,
    *,
    max_msg_len: int,
) -> jnp.ndarray:
    """The verify core (traced, unjitted): validate + sha512 +
    double-scalar-mult + compare.  ONE implementation — the stage's
    program (ed25519_verify_batch_fused) and the four-array entry
    (ed25519_verify_batch) trace exactly this, so their masks cannot
    diverge by construction.  Lane-wise over whatever batch axes trail
    (msg_len's shape); -> bool of that shape."""
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
    return _verify_lanes(msg, msg_len, sig, pubkey, max_msg_len=max_msg_len)


def _verify_lanes(msg, msg_len, sig, pubkey, *, max_msg_len: int):
    """_verify_ok over the (..., B) byte-row arrays on the folded batch
    (fold_batch) -> the (B,) bool mask."""
    return _verify_ok(*fold_batch(msg, msg_len, sig, pubkey),
                      max_msg_len=max_msg_len).reshape(msg_len.shape)


# -- the stage's program: packed rows in, the mask out -------------------------
#
# A batch crosses the host-device boundary once each way.  Going in it is
# ONE (B, row_width) uint8 array, element e in row e:
#
#     msg[max_msg_len] (zero past msg_len) | sig[64] | pk[32] | msg_len u32 LE
#
# — the native intake fills slots of exactly these rows
# (native/fd_verify.cpp; runtime/verify_native mirrors the offsets, fdlint
# FD305) and the Python lane's _assemble builds the same — and coming back
# it is the (B,) bool mask.  The offsets count from the end of the message.

ROW_SIG_OFF = 0
ROW_PK_OFF = 64
ROW_LEN_OFF = 96
ROW_TAIL = 100


def unpack_rows(rows: jnp.ndarray, *, max_msg_len: int):
    """(B, max_msg_len + ROW_TAIL) uint8 packed rows -> the byte-row
    arrays _verify_ok takes: msg (max_msg_len, B), msg_len (B,) int32,
    sig (64, B), pubkey (32, B).  Traced: one transpose and four slices
    on the device."""
    t = rows.T
    tail = t[max_msg_len:]
    ln = tail[ROW_LEN_OFF:ROW_LEN_OFF + 4].astype(jnp.int32)
    msg_len = ln[0] | (ln[1] << 8) | (ln[2] << 16) | (ln[3] << 24)
    return (t[:max_msg_len], msg_len, tail[ROW_SIG_OFF:ROW_SIG_OFF + 64],
            tail[ROW_PK_OFF:ROW_PK_OFF + 32])


@functools.partial(jax.jit, static_argnames=("max_msg_len",))
def ed25519_verify_batch_fused(rows: jnp.ndarray, *,
                               max_msg_len: int) -> jnp.ndarray:
    """The generic-lane serving program: the WHOLE per-batch device
    computation — unpack the packed rows, validate + sha512 +
    double-scalar-mult + compare — in ONE compiled module, one dispatch
    per batch, one array in and one back: the (B,) bool mask.  Pad rows
    come back as whatever _verify_ok says of them (zeros, or an earlier
    batch's bytes); the stage's reap reads the real lanes only."""
    return _verify_lanes(*unpack_rows(rows, max_msg_len=max_msg_len),
                         max_msg_len=max_msg_len)


def verify_dispatch(rows, *, max_msg_len: int):
    """Dispatch one batch of packed rows (on the device already) -> the
    (B,) bool mask future: the ONE call the verify stage makes, one
    compiled module a batch (the seam a test wraps to count or stub
    dispatches).  Pad lanes are not masked: the stage ignores lanes
    past its fill when reaping."""
    return ed25519_verify_batch_fused(rows, max_msg_len=max_msg_len)


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
    (comb_fill), so invalid pubkeys never enter the bank.  The batch is
    folded like every other lane's (fold_batch): the bank gather takes
    the two-axis `slots` as it is.
    """
    batch = msg_len.shape
    msg, msg_len, sig, pubkey, slots = fold_batch(
        msg, msg_len, sig, pubkey, slots)
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
    return (ok_s & ok_r & fc.point_eq_z1(r_cmp, r_pt)).reshape(batch)


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
