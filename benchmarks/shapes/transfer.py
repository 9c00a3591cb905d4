"""The `transfer` shape: signed 1-signature system transfers, every row
distinct, offered in pool order.  Pure functions of the seed.

The wire format is Solana's legacy transaction (benchmarks/tests hold
it to the program's own `transfer_txn` byte for byte).  Imports neither
JAX nor the program: the signing workers load this file alone.
"""

from __future__ import annotations

import hashlib

import numpy as np

from harness import traffic as T

CLASSES = ("transfer",)
TXN_SZ = 215          # 1 + 64 signature + 150-byte message
SYSTEM_PROGRAM = bytes(32)


def build(seed: int, n_rows: int, accounts: dict, traffic: dict,
          lo: int = 0, hi: int | None = None) -> T.Pool:
    """Rows [lo, hi) of the pool.  Transfer i: payer i mod n_payers
    (rotation keeps pack's one-per-payer-per-microblock rule fed),
    destination and lamports by index, so every row of a pool is
    distinct."""
    hi = n_rows if hi is None else hi
    gseed = T.genesis_seed(seed)
    n_payers, n_dests = accounts["n_payers"], accounts["n_dests"]
    signers = T.signers(gseed, n_payers)
    bh = T.blockhash(gseed)
    dests = [hashlib.sha256(gseed + b"to%d" % k).digest()
             for k in range(n_dests)]
    rows = []
    for i in range(lo, hi):
        key, pub = signers[i % n_payers]
        msg = (b"\x01\x00\x01\x03" + pub + dests[i % n_dests]
               + SYSTEM_PROGRAM + bh + b"\x01\x02\x02\x00\x01\x0c"
               + (2).to_bytes(4, "little") + (1 + i).to_bytes(8, "little"))
        rows.append(b"\x01" + key.sign(msg) + msg)
    n = hi - lo
    return T.join(rows, np.ones(n, np.int64), np.zeros(n, np.uint8), CLASSES)


def corrupt(pool: T.Pool, every: int, seed: int) -> np.ndarray:
    """Flip one seeded bit in the signature of one seeded row in each
    run of `every` (spread evenly so any window holds its share).  In
    place; -> sorted bad rows."""
    if not every:
        return np.zeros((0,), dtype=np.int64)
    rng = np.random.default_rng([seed, 0xBAD])
    starts = np.arange(0, pool.n - every + 1, every, dtype=np.int64)
    bad = starts + rng.integers(0, every, size=starts.size)
    byte = rng.integers(0, 64, size=bad.size)
    bit = rng.integers(0, 8, size=bad.size)
    T.flip(pool, bad, 0, byte, bit)
    return bad


def order(pool: T.Pool, seed: int, traffic: dict) -> np.ndarray:
    """The row offered k-th is `order[k % len(order)]`: the pool, in
    order."""
    return np.arange(pool.n, dtype=np.int64)


def genesis(accounts: dict, seed: int) -> dict:
    """What has to exist before traffic: the funded payers (the
    arguments the program's `default_bank_ctx` takes)."""
    return {"seed": T.genesis_seed(seed), "n_payers": accounts["n_payers"]}
