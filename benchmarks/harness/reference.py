"""The plain reference for a signature's verdict: OpenSSL's Ed25519
through `cryptography` — an implementation that shares nothing with the
program's kernel or with its pure-Python `ed25519_ref`."""

from __future__ import annotations

import numpy as np

from . import traffic as T


def verdicts(pool: np.ndarray, rows) -> dict[int, bool]:
    """row -> does the transfer's one signature verify over its message
    under its fee payer's key."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )

    keys: dict[bytes, Ed25519PublicKey] = {}
    out = {}
    for i in rows:
        p = T.txn_bytes(pool, int(i))
        pk = p[T.PAYER_OFF:T.PAYER_OFF + 32]
        key = keys.get(pk)
        if key is None:
            key = keys[pk] = Ed25519PublicKey.from_public_bytes(pk)
        try:
            key.verify(p[T.SIG_OFF:T.SIG_OFF + 64], p[T.MSG_OFF:])
            out[int(i)] = True
        except InvalidSignature:
            out[int(i)] = False
    return out
