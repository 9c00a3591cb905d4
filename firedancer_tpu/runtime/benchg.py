"""Synthetic transaction generator stage (the reference's benchg tile:
src/app/fddev/tiles/fd_benchg.c) and the synthetic-load harness
(src/disco/verify/verify_synth_load.c).

Signing in pure python is slow (~15 ms/txn), so a pool of unique signed
transfer txns is pregenerated once and streamed in a cycle.  For dedup
realism every txn in the pool is unique (distinct lamports); cycling the
pool re-sends duplicates, which is exactly what the dedup stage is for —
size the pool >= the txns you intend to count as distinct.
"""

from __future__ import annotations

import hashlib

from firedancer_tpu.protocol import txn as ft
from .stage import Stage


def pool_payers(seed: bytes = b"benchg", n_payers: int = 8) -> list[tuple[bytes, bytes]]:
    """The pool's payer keypairs [(secret, pubkey)] — deterministic from
    the seed so a bank ctx can pre-fund them (genesis for the synthetic
    load)."""
    from firedancer_tpu.ops.ref import ed25519_ref as ref

    payers = []
    for k in range(n_payers):
        secret = hashlib.sha256(seed + b"payer%d" % k).digest()
        payers.append((secret, ref.public_key(secret)))
    return payers


def pool_blockhash(seed: bytes = b"benchg") -> bytes:
    return hashlib.sha256(seed + b"bh").digest()


def gen_transfer_pool(
    n: int, seed: bytes = b"benchg", n_payers: int = 8, n_dests: int = 64
) -> list[bytes]:
    """Pool of signed transfers rotating over `n_payers` payer keypairs and
    `n_dests` destinations (fd_benchg.c rotates accounts the same way so
    pack sees schedulable parallelism, not one serializing hot account)."""
    n_payers = max(1, min(n_payers, n))
    payers = pool_payers(seed, n_payers)
    blockhash = pool_blockhash(seed)
    return [
        ft.transfer_txn(
            payers[i % n_payers][0],
            hashlib.sha256(seed + b"to%d" % (i % n_dests)).digest(),
            1 + i,
            blockhash,
            from_pubkey=payers[i % n_payers][1],
        )
        for i in range(n)
    ]


class BenchGStage(Stage):
    """Streams a pregenerated txn pool round-robin at max rate."""

    def __init__(self, pool: list[bytes], *args, limit: int | None = None, **kwargs):
        super().__init__(*args, **kwargs)
        self.pool = pool
        self.limit = limit
        self._i = 0
        self._pool_ref = None  # strong ref: the pool the native form mirrors
        self._pool_buf = b""
        self._pool_tbl = None

    def _native_pool(self):
        """The pool in fdr_publish_pool form (joined buffer + (off, sz)
        rows), rebuilt only when self.pool is swapped — so the sweep's
        crossing carries zero per-frame Python work.  The cache holds a
        strong reference (identity check, not id(): a freed list's id is
        routinely reused by the replacement).  Payload sizes validate
        against the link mtu here, once per pool — fdr_publish_pool
        itself trusts the table (no per-frame bound check in C++)."""
        if self._pool_ref is not self.pool:
            import numpy as np

            if not self.pool:
                # the Python lane raises ZeroDivisionError at
                # `pool[i % 0]`; an empty table handed to C++ would be a
                # process-killing SIGFPE at `% pool_n` instead
                raise ValueError("BenchGStage pool is empty")
            mtu = self.outs[0].link.mtu
            tbl = np.empty((len(self.pool), 2), dtype=np.uint64)
            off = 0
            for k, payload in enumerate(self.pool):
                if len(payload) > mtu:
                    raise ValueError(
                        f"pool payload {k} ({len(payload)}B) exceeds link"
                        f" mtu {mtu}"
                    )
                tbl[k, 0] = off
                tbl[k, 1] = len(payload)
                off += len(payload)
            self._pool_buf = b"".join(self.pool)
            self._pool_tbl = tbl
            self._pool_ref = self.pool
        return self._pool_buf, self._pool_tbl

    def after_credit(self) -> None:
        # burst-publish: one txn per sweep starves the burst-draining
        # consumers downstream (stage.py run_once)
        n = max(1, self.burst)
        if self.limit is not None:
            n = min(n, self.limit - self._i)
        if n <= 0:
            return
        p = self.outs[0]
        pub_pool = getattr(p, "publish_pool", None)
        if pub_pool is None:
            for _ in range(n):
                if not self.publish(0, self.pool[self._i % len(self.pool)],
                                    sig=self._i):
                    return
                self._i += 1
                self.metrics.inc("txn_gen")
            return
        # native ring lane: the whole sweep's frames in ONE crossing
        # (tsorig stamped in C++ — this stage is the stream's origin)
        buf, tbl = self._native_pool()
        done = pub_pool(buf, tbl, len(self.pool), self._i, n)
        self._i += done
        if done:
            self.metrics.inc("txn_gen", done)
            self.metrics.inc("frags_out", done)
        if done < n:
            self.metrics.inc("backpressure")
