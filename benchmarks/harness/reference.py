"""The plain reference for a transaction's verdict: OpenSSL's Ed25519
through `cryptography` — an implementation that shares nothing with the
program's kernel, its parser or its pure-Python `ed25519_ref`."""

from __future__ import annotations


def _compact_u16(p: bytes, o: int) -> tuple[int, int]:
    """-> (value, next offset) of Solana's compact-u16 at p[o:]."""
    v = shift = 0
    while True:
        b = p[o]
        o += 1
        v |= (b & 0x7F) << shift
        if not b & 0x80:
            return v, o
        shift += 7


def split(p: bytes) -> tuple[list[bytes], list[bytes], bytes]:
    """A wire transaction -> (signatures, the signers' account keys, the
    signed message): compact signature count | 64 B each | message; the
    message is an optional version byte (high bit set), the three header
    bytes, the compact account count and the 32-byte account keys, the
    first `signatures` of which sign."""
    n, o = _compact_u16(p, 0)
    sigs = [p[o + 64 * k:o + 64 * (k + 1)] for k in range(n)]
    msg = p[o + 64 * n:]
    a = 1 if msg[0] & 0x80 else 0
    n_acct, a = _compact_u16(msg, a + 3)
    if not 1 <= n <= n_acct or len(msg) < a + 32 * n_acct:
        raise ValueError("not a transaction")
    return sigs, [msg[a + 32 * k:a + 32 * (k + 1)] for k in range(n)], msg


def verdicts(pool, rows) -> dict[int, bool]:
    """row -> does every signature of the transaction verify over its
    message under the signer's key (a row fails whole)."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )

    keys: dict[bytes, Ed25519PublicKey] = {}
    out = {}
    for i in rows:
        sigs, pks, msg = split(pool.row(int(i)))
        ok = True
        for sig, pk in zip(sigs, pks):
            key = keys.get(pk)
            if key is None:
                key = keys[pk] = Ed25519PublicKey.from_public_bytes(pk)
            try:
                key.verify(sig, msg)
            except InvalidSignature:
                ok = False
                break
        out[int(i)] = ok
    return out
