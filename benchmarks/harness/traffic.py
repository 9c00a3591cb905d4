"""What no traffic shape owns: the seed derivations, the pool a shape's
rows are joined into, the spawned workers that sign it, and the bit
flip that corrupts a signature.  What a row is, which rows are
corrupted and in which order they are offered is the shape's
(benchmarks/shapes/<name>.py, named by the cell's traffic file).

Imports neither JAX nor the program: the signing workers are spawned
processes that load this module and the shape alone.  Signing is
OpenSSL's Ed25519 through `cryptography`, which is deterministic
(RFC 8032), so a pool is the same bytes wherever it is made.
"""

from __future__ import annotations

import hashlib
import os
from dataclasses import dataclass, field

import numpy as np

from .manifest import load_module

CHUNK = 4096          # rows per signing task
TXN_MTU = 1232        # no row is longer
MAX_SIGS = 8          # nor carries more signatures


def genesis_seed(seed: int) -> bytes:
    """The byte seed the payers, the blockhash and the destinations hang
    off; the leader topology funds the same payers from it."""
    return b"bench%d" % seed


def payer_secrets(gseed: bytes, n_payers: int) -> list[bytes]:
    return [hashlib.sha256(gseed + b"payer%d" % k).digest()
            for k in range(n_payers)]


def blockhash(gseed: bytes) -> bytes:
    return hashlib.sha256(gseed + b"bh").digest()


def signers(gseed: bytes, n_payers: int):
    """-> [(OpenSSL private key, 32-byte public key)] of the payers."""
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


@dataclass
class Pool:
    """A shape's rows, joined: row i is `buf[off[i]:off[i] + len[i]]`, a
    whole transaction of `sigs[i]` signatures and class
    `classes[cls[i]]`.  `bad` is the rows the shape's `corrupt` broke
    (the runner keeps them here)."""

    buf: np.ndarray               # uint8
    off: np.ndarray               # int64[n]
    len: np.ndarray               # int64[n]
    sigs: np.ndarray              # int64[n], 1..MAX_SIGS
    cls: np.ndarray               # uint8[n], index into `classes`
    classes: tuple[str, ...]
    bad: np.ndarray = field(
        default_factory=lambda: np.zeros((0,), dtype=np.int64))

    @property
    def n(self) -> int:
        return len(self.off)

    @property
    def valid(self) -> np.ndarray:
        """bool[n]: every signature of the row verifies."""
        ok = np.ones((self.n,), dtype=bool)
        ok[self.bad] = False
        return ok

    def row(self, i: int) -> bytes:
        o = int(self.off[i])
        return self.buf[o:o + int(self.len[i])].tobytes()

    def first_sig_tags(self) -> np.ndarray:
        """-> uint64[n]: the low 8 bytes of each row's first signature
        (byte 0 of a row is its compact signature count), 0 read as 1:
        the tag the program's verify stage gives the frag."""
        at = self.off[:, None] + np.arange(1, 9)
        tag = np.ascontiguousarray(self.buf[at]).view("<u8").ravel()
        return np.where(tag == 0, np.uint64(1), tag)


def join(rows: list[bytes], sigs, cls, classes) -> Pool:
    """Rows (whole transactions) -> a Pool."""
    ln = np.fromiter((len(r) for r in rows), dtype=np.int64, count=len(rows))
    off = np.cumsum(ln) - ln
    return Pool(np.frombuffer(b"".join(rows), dtype=np.uint8).copy(), off, ln,
                np.asarray(sigs, dtype=np.int64),
                np.asarray(cls, dtype=np.uint8), tuple(classes))


def concat(parts: list[Pool]) -> Pool:
    base = np.cumsum([0] + [p.buf.size for p in parts[:-1]])
    return Pool(np.concatenate([p.buf for p in parts]),
                np.concatenate([p.off + b for p, b in zip(parts, base)]),
                np.concatenate([p.len for p in parts]),
                np.concatenate([p.sigs for p in parts]),
                np.concatenate([p.cls for p in parts]), parts[0].classes)


def _build_range(task) -> Pool:
    """In a worker: the shape from its file, as the harness loads it."""
    path, seed, n_rows, accounts, traffic, lo, hi = task
    shape = load_module(path, "shape_worker")
    return shape.build(seed, n_rows, accounts, traffic, lo, hi)


class PoolJob:
    """A shape's pool being built by spawned workers, a range of rows
    each, while the parent does something else (the JAX warm-up).
    `result()` joins them."""

    def __init__(self, shape_path: str, seed: int, n_rows: int,
                 accounts: dict, traffic: dict, workers: int | None = None):
        import multiprocessing as mp

        self.n = n_rows
        tasks = [(shape_path, seed, n_rows, accounts, traffic, lo,
                  min(lo + CHUNK, n_rows)) for lo in range(0, n_rows, CHUNK)]
        if workers is None:
            workers = max(1, (os.cpu_count() or 2) - 1)
        workers = min(workers, len(tasks))
        self._pool = None
        if workers <= 1:
            self._parts = [_build_range(t) for t in tasks]
        else:
            self._pool = mp.get_context("spawn").Pool(workers)
            self._async = self._pool.map_async(_build_range, tasks)

    def result(self) -> Pool:
        if self._pool is not None:
            try:
                self._parts = self._async.get()
            finally:
                self._pool.close()
                self._pool.join()
                self._pool = None
        pool = concat(self._parts)
        if pool.n != self.n or int(pool.len.max()) > TXN_MTU \
                or not 1 <= int(pool.sigs.min()) <= int(pool.sigs.max()) \
                <= MAX_SIGS:
            raise RuntimeError("the shape's workers returned a pool that is "
                               "short, or a row over the MTU or 8 signatures")
        return pool

    def abort(self) -> None:
        if self._pool is not None:
            self._pool.terminate()
            self._pool.join()
            self._pool = None


def flip(pool: Pool, rows, sig, byte, bit) -> None:
    """Flip bit `bit` of byte `byte` of signature `sig` of each of
    `rows`, in place (arrays of one length; chip_smoke.corrupt_pool's
    method)."""
    at = pool.off[rows] + 1 + 64 * np.asarray(sig) + byte
    pool.buf[at] ^= (1 << np.asarray(bit)).astype(np.uint8)
