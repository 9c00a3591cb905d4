"""Per-pubkey comb-cache verify path vs the generic kernel and host ref."""

import hashlib

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # XLA-compile-heavy tier (see conftest)

import jax.numpy as jnp

from firedancer_tpu.ops import sigverify as sv
from firedancer_tpu.ops.ref import ed25519_ref as ref

MAXLEN = 64


def _batch(n_signers, n_elems, corrupt=()):
    keys = []
    for i in range(n_signers):
        secret = hashlib.sha256(b"compat%d" % i).digest()
        keys.append((secret, ref.public_key(secret)))
    msg_a = np.zeros((MAXLEN, n_elems), np.uint8)
    ln = np.zeros((n_elems,), np.int32)
    sig_a = np.zeros((64, n_elems), np.uint8)
    pk_a = np.zeros((32, n_elems), np.uint8)
    signer = np.zeros((n_elems,), np.int32)
    for i in range(n_elems):
        s_idx = i % n_signers
        secret, pub = keys[s_idx]
        m = b"txn %d payload" % i
        sig = bytearray(ref.sign(secret, m))
        if i in corrupt:
            sig[7] ^= 0x40
        msg_a[: len(m), i] = np.frombuffer(m, np.uint8)
        ln[i] = len(m)
        sig_a[:, i] = np.frombuffer(bytes(sig), np.uint8)
        pk_a[:, i] = np.frombuffer(pub, np.uint8)
        signer[i] = s_idx
    return keys, msg_a, ln, sig_a, pk_a, signer


def test_comb_fill_and_cached_verify_match_generic():
    n_signers, n_elems = 3, 12
    corrupt = {5, 9}
    keys, msg_a, ln, sig_a, pk_a, signer = _batch(
        n_signers, n_elems, corrupt
    )

    # fill the bank with each signer's comb
    pk_fill = np.stack(
        [np.frombuffer(pub, np.uint8) for _, pub in keys], axis=1
    )
    tables, ok = sv.comb_fill(jnp.asarray(pk_fill))
    assert np.asarray(ok).all(), "honest pubkeys must fill"
    bank = sv.bank_alloc(n_signers + 2)
    bank = sv.bank_install(bank, tables, jnp.asarray(np.arange(n_signers)))

    got = np.asarray(
        sv.ed25519_verify_batch_cached(
            jnp.asarray(msg_a), jnp.asarray(ln), jnp.asarray(sig_a),
            jnp.asarray(pk_a), bank, jnp.asarray(signer),
            max_msg_len=MAXLEN,
        )
    )
    want = np.asarray(
        sv.ed25519_verify_batch(
            jnp.asarray(msg_a), jnp.asarray(ln), jnp.asarray(sig_a),
            jnp.asarray(pk_a), max_msg_len=MAXLEN,
        )
    )
    expect = np.ones(n_elems, bool)
    for i in corrupt:
        expect[i] = False
    assert (want == expect).all(), "generic kernel baseline wrong"
    assert (got == expect).all(), "cached kernel disagrees"


def test_comb_fill_rejects_bad_pubkeys():
    # a non-point pubkey and a small-order pubkey must come back not-ok
    bad = np.zeros((32, 2), np.uint8)
    bad[:, 0] = np.frombuffer(hashlib.sha256(b"junk").digest(), np.uint8)
    # identity point encoding (y=1): small order
    ident = bytearray(32)
    ident[0] = 1
    bad[:, 1] = np.frombuffer(bytes(ident), np.uint8)
    _tables, ok = sv.comb_fill(jnp.asarray(bad))
    ok = np.asarray(ok)
    # index 0 may or may not decode as a curve point (hash bytes), but the
    # identity at index 1 is definitely small-order
    assert not ok[1]


def test_bank_reinstall_overwrites_slot():
    keys, msg_a, ln, sig_a, pk_a, signer = _batch(2, 4)
    pk_fill = np.stack(
        [np.frombuffer(pub, np.uint8) for _, pub in keys], axis=1
    )
    tables, ok = sv.comb_fill(jnp.asarray(pk_fill))
    bank = sv.bank_alloc(2)
    # install signer1's comb into BOTH slots, then fix slot 0
    bank = sv.bank_install(
        bank, tables[..., 1:2].repeat(2, axis=-1), jnp.asarray([0, 1])
    )
    bank = sv.bank_install(bank, tables[..., 0:1], jnp.asarray([0]))
    got = np.asarray(
        sv.ed25519_verify_batch_cached(
            jnp.asarray(msg_a), jnp.asarray(ln), jnp.asarray(sig_a),
            jnp.asarray(pk_a), bank, jnp.asarray(signer),
            max_msg_len=MAXLEN,
        )
    )
    assert got.all()


def test_verify_stage_comb_path_end_to_end():
    """Stage-level: repeated signers promote into the device comb bank and
    the cached lane produces the same accept/reject decisions (on the
    CPU mesh)."""
    import os as _os
    import time as _time

    from firedancer_tpu.runtime.verify import VerifyStage, decode_verified
    from firedancer_tpu.tango import shm

    uid = f"{_os.getpid()}_{int(_time.monotonic_ns() % 1_000_000)}"
    nv = shm.ShmLink.create(f"fdtpu_cnv_{uid}", depth=256, mtu=1232)
    vo = shm.ShmLink.create(f"fdtpu_cvo_{uid}", depth=256, mtu=4096)
    try:
        from firedancer_tpu.runtime.benchg import gen_transfer_pool

        stage = VerifyStage(
            "verify0",
            ins=[shm.Consumer(nv, lazy=8)],
            outs=[shm.Producer(vo)],
            batch=8,
            max_msg_len=256,
            batch_deadline_s=0.0005,
            comb_slots=4,
            promote_threshold=2,
        )
        sink = shm.Consumer(vo, lazy=8)
        prod = shm.Producer(nv)
        pool = gen_transfer_pool(24, seed=b"combstage", n_payers=2)
        corrupt_idx = 21
        bad = bytearray(pool[corrupt_idx])
        bad[5] ^= 0x20  # inside signature 0
        pool[corrupt_idx] = bytes(bad)

        got = []

        def pump(n_iters=400):
            for _ in range(n_iters):
                stage.run_once()
                res = sink.poll()
                if isinstance(res, tuple):
                    got.append(res[1])

        # wave 1: both payers seen >= threshold on the generic lane
        for p in pool[:8]:
            assert prod.try_publish(p)
        pump()
        stage.during_housekeeping()  # builds + installs the combs
        pump()
        assert stage.metrics.get("comb_filled") == 2

        # wave 2: every txn's signer is banked -> cached lane
        for p in pool[8:]:
            assert prod.try_publish(p)
        pump()
        stage.flush()
        pump(100)
        assert stage.metrics.get("comb_elems") > 0, "cached lane unused"
        assert stage.metrics.get("verify_fail") == 1
        payloads = {decode_verified(f)[0] for f in got}
        want = {p for i, p in enumerate(pool) if i != corrupt_idx}
        assert payloads == want
    finally:
        for l in (nv, vo):
            l.close()
            l.unlink()
