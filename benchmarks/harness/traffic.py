"""Seeded traffic: signed 1-signature system transfers, the corrupted
set, and the arrival schedule.  Pure functions of the seed.

Imports neither JAX nor the program: the signing workers are spawned
processes that load this module alone.  The transfer wire format is
Solana's legacy transaction (benchmarks/tests hold it to the program's
own `transfer_txn` byte for byte); signing is OpenSSL's Ed25519 through
`cryptography`, which is deterministic (RFC 8032), so a pool is the same
bytes wherever it is made.
"""

from __future__ import annotations

import hashlib
import os

import numpy as np

TXN_SZ = 215          # 1 + 64 signature + 150-byte message
SIG_OFF = 1           # byte 0 is the compact-u16 signature count
MSG_OFF = 65
PAYER_OFF = MSG_OFF + 4
SYSTEM_PROGRAM = bytes(32)
CHUNK = 4096          # transactions per signing task


def genesis_seed(seed: int) -> bytes:
    """The byte seed the payers, the blockhash and the destinations hang
    off; the leader topology funds the same payers from it."""
    return b"bench%d" % seed


def payer_secrets(gseed: bytes, n_payers: int) -> list[bytes]:
    return [hashlib.sha256(gseed + b"payer%d" % k).digest()
            for k in range(n_payers)]


def blockhash(gseed: bytes) -> bytes:
    return hashlib.sha256(gseed + b"bh").digest()


def _signers(gseed: bytes, n_payers: int):
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )
    from cryptography.hazmat.primitives.serialization import (
        Encoding, PublicFormat,
    )

    out = []
    for s in payer_secrets(gseed, n_payers):
        key = Ed25519PrivateKey.from_private_bytes(s)
        pub = key.public_key().public_bytes(Encoding.Raw, PublicFormat.Raw)
        out.append((key, pub))
    return out


def sign_range(args) -> bytes:
    """Transfers [lo, hi) of the pool for `gseed`, joined.  Transfer i:
    payer i mod n_payers (rotation keeps pack's one-per-payer-per-
    microblock rule fed), destination and lamports by index, so every
    transaction of a pool is distinct."""
    gseed, n_payers, n_dests, lo, hi = args
    signers = _signers(gseed, n_payers)
    bh = blockhash(gseed)
    dests = [hashlib.sha256(gseed + b"to%d" % k).digest()
             for k in range(n_dests)]
    out = bytearray()
    for i in range(lo, hi):
        key, pub = signers[i % n_payers]
        msg = (b"\x01\x00\x01\x03" + pub + dests[i % n_dests]
               + SYSTEM_PROGRAM + bh + b"\x01\x02\x02\x00\x01\x0c"
               + (2).to_bytes(4, "little") + (1 + i).to_bytes(8, "little"))
        out += b"\x01" + key.sign(msg) + msg
    return bytes(out)


class PoolJob:
    """A pool being signed by spawned workers while the parent does
    something else (the JAX warm-up).  `result()` joins them."""

    def __init__(self, seed: int, n: int, n_payers: int, n_dests: int,
                 workers: int | None = None):
        import multiprocessing as mp

        self.n = n
        gseed = genesis_seed(seed)
        tasks = [(gseed, n_payers, n_dests, lo, min(lo + CHUNK, n))
                 for lo in range(0, n, CHUNK)]
        if workers is None:
            workers = max(1, (os.cpu_count() or 2) - 1)
        workers = min(workers, len(tasks))
        self._pool = None
        if workers <= 1:
            self._parts = [sign_range(t) for t in tasks]
        else:
            self._pool = mp.get_context("spawn").Pool(workers)
            self._async = self._pool.map_async(sign_range, tasks)

    def result(self) -> np.ndarray:
        """-> the pool as one (n * TXN_SZ,) uint8 array."""
        if self._pool is not None:
            try:
                self._parts = self._async.get()
            finally:
                self._pool.close()
                self._pool.join()
                self._pool = None
        buf = np.frombuffer(b"".join(self._parts), dtype=np.uint8).copy()
        if buf.size != self.n * TXN_SZ:
            raise RuntimeError("signing workers returned a short pool")
        return buf

    def abort(self) -> None:
        if self._pool is not None:
            self._pool.terminate()
            self._pool.join()
            self._pool = None


def corrupt(buf: np.ndarray, n: int, every: int, seed: int) -> np.ndarray:
    """Flip one seeded bit in the signature of one seeded transaction in
    each run of `every` (chip_smoke.corrupt_pool's method, spread evenly
    so any window holds its share).  In place; -> sorted bad indices."""
    if not every:
        return np.zeros((0,), dtype=np.int64)
    rng = np.random.default_rng([seed, 0xBAD])
    starts = np.arange(0, n - every + 1, every, dtype=np.int64)
    bad = starts + rng.integers(0, every, size=starts.size)
    byte = rng.integers(0, 64, size=bad.size)
    bit = rng.integers(0, 8, size=bad.size)
    buf[bad * TXN_SZ + SIG_OFF + byte] ^= (1 << bit).astype(np.uint8)
    return bad


def poisson_due_ns(rate_per_s: float, n: int, seed: int) -> np.ndarray:
    """Offsets in ns, from the start of traffic, at which transaction i
    of an open loop is due: exponential gaps at `rate_per_s`."""
    rng = np.random.default_rng([seed, 0xA881])
    gaps = rng.exponential(1e9 / rate_per_s, size=n)
    return np.cumsum(gaps).astype(np.int64)


def txn_bytes(buf: np.ndarray, i: int) -> bytes:
    return buf[i * TXN_SZ:(i + 1) * TXN_SZ].tobytes()
