"""A toy shape that lives with the tests: what the harness's seam has to
carry that `transfer` does not.  Rows of 1, 2 and 3 signatures (so of
three lengths) in two classes, one row in ten offered twice (half of
those within the verify stage's tag cache, half far behind it), an
order that is a seeded permutation and no prefix of the pool, and the
corrupted bit in a seeded one of the row's signatures."""

from __future__ import annotations

import hashlib

import numpy as np

from harness import traffic as T

CLASSES = ("transfer", "cosigned")
SYSTEM_PROGRAM = bytes(32)
FAR = 200             # offers between a row and its far repeat


def build(seed, n_rows, accounts, traffic, lo=0, hi=None) -> T.Pool:
    """Row i: a system transfer of 1 + i % 3 signatures — the payer and
    the next payers as read-only co-signers."""
    hi = n_rows if hi is None else hi
    gseed = T.genesis_seed(seed)
    n_payers, n_dests = accounts["n_payers"], accounts["n_dests"]
    signers = T.signers(gseed, n_payers)
    bh = T.blockhash(gseed)
    rows, sigs = [], []
    for i in range(lo, hi):
        k = 1 + i % 3
        who = [signers[(i + j) % n_payers] for j in range(k)]
        dest = hashlib.sha256(gseed + b"to%d" % (i % n_dests)).digest()
        msg = (bytes([k, k - 1, 1, k + 2]) + b"".join(p for _, p in who)
               + dest + SYSTEM_PROGRAM + bh
               + bytes([1, k + 1, 2, 0, k, 12])
               + (2).to_bytes(4, "little") + (1 + i).to_bytes(8, "little"))
        rows.append(bytes([k]) + b"".join(key.sign(msg) for key, _ in who)
                    + msg)
        sigs.append(k)
    return T.join(rows, sigs, [int(k > 1) for k in sigs], CLASSES)


def corrupt(pool, every, seed) -> np.ndarray:
    if not every:
        return np.zeros((0,), dtype=np.int64)
    rng = np.random.default_rng([seed, 0xBAD])
    starts = np.arange(0, pool.n - every + 1, every, dtype=np.int64)
    bad = starts + rng.integers(0, every, size=starts.size)
    T.flip(pool, bad, rng.integers(0, pool.sigs[bad]),
           rng.integers(0, 64, size=bad.size),
           rng.integers(0, 8, size=bad.size))
    return bad


def order(pool, seed, traffic) -> np.ndarray:
    first = np.random.default_rng([seed, 0x70]).permutation(pool.n)
    again = np.flatnonzero(first % 10 == 3)
    at = again + np.where(np.arange(len(again)) % 2, FAR, 2)
    return np.insert(first, np.minimum(at, pool.n), first[again])


def genesis(accounts, seed) -> dict:
    return {"seed": T.genesis_seed(seed), "n_payers": accounts["n_payers"]}
