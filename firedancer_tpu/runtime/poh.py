"""Proof of History: the sequential hash clock and its batched verifier.

The reference's PoH primitive is sha256 iterated in a chain with microblock
hashes mixed in (/root/reference/src/ballet/poh/fd_poh.c: fd_poh_append,
fd_poh_mixin; the poh tile fd_poh.c drives it).  Generation is inherently
sequential — it stays on host (hashlib's C core), per SURVEY §7.1.
*Verification* is embarrassingly parallel: split the chain into segments at
known (hashcnt, hash) checkpoints and recompute every segment as one batch
element on TPU (ops/sha256.sha256_iter32) — the axis the reference scales
with one core per chain, this framework scales with lanes.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field

import numpy as np


def poh_append(h: bytes, n: int) -> bytes:
    for _ in range(n):
        h = hashlib.sha256(h).digest()
    return h


def poh_mixin(h: bytes, mix: bytes) -> bytes:
    return hashlib.sha256(h + mix).digest()


@dataclass
class PohRecord:
    hashcnt: int
    hash: bytes
    mixin: bytes | None  # None = tick boundary record


@dataclass
class PohChain:
    """Host-side PoH state machine (generation side)."""

    hash: bytes
    hashcnt: int = 0
    records: list[PohRecord] = field(default_factory=list)

    def append(self, n: int) -> None:
        self.hash = poh_append(self.hash, n)
        self.hashcnt += n

    def mixin(self, mix: bytes) -> None:
        """Mix a microblock hash into the chain (counts as one hash)."""
        self.hash = poh_mixin(self.hash, mix)
        self.hashcnt += 1
        self.records.append(PohRecord(self.hashcnt, self.hash, mix))

    def tick(self) -> None:
        self.records.append(PohRecord(self.hashcnt, self.hash, None))


def verify_segments_host(
    starts: list[bytes], counts: list[int], ends: list[bytes]
) -> list[bool]:
    return [poh_append(s, n) == e for s, n, e in zip(starts, counts, ends)]


def entry_mixin(first_sigs: list[bytes]) -> bytes:
    """A transaction entry's mixin: sha256 over its transactions' first
    signatures (the bank stage's entry hash)."""
    return hashlib.sha256(b"".join(first_sigs)).digest()


def check_entry(h: bytes, num_hashes: int, expect: bytes,
                first_sigs: list[bytes]) -> tuple[bool, bytes]:
    """One entry of a received block against the chain standing at `h`:
    `num_hashes` appends, the last of them the mixin over `first_sigs`
    for a transaction entry (empty: a tick), compare.  -> (the entry's
    hash follows, the chain after it).  A transaction entry consumes at
    least its own mixin hash: `num_hashes` 0 would let a block deflate
    the clock, and does not follow.  The per-entry check of the replay
    verify stage (runtime/replay_verify.py), fed from its own parse."""
    if first_sigs:
        if num_hashes < 1:
            return False, h
        h = poh_mixin(poh_append(h, num_hashes - 1), entry_mixin(first_sigs))
    else:
        h = poh_append(h, num_hashes)
    return h == expect, h


def replay_entries(
    seed: bytes, entries: list[tuple[int, bytes, list[bytes]]],
    first_sigs: list[list[bytes]] | None = None,
) -> tuple[bool, list[tuple[bytes, int, bytes]]]:
    """Re-run the PoH chain over wire entries (num_hashes, hash, txns) —
    the validation-side check that a received block's clock is honest
    (what the reference's replay does before executing a slot).

    The mixin for a txn entry is sha256 over the txns' first signatures
    (matching the bank stage's entry hash).  `first_sigs`, one list an
    entry, hands them over from a parse the caller has made already
    (flamenco/runtime.replay_block parses a block once, for this and
    for execution); without it every transaction is parsed here.
    Returns (ok, segments) where segments are the pure append runs
    (start, n, end) suitable for batched TPU verification via
    verify_segments_tpu.
    """
    h = seed
    segments = []
    ok = True
    for k, (num_hashes, expect, txns) in enumerate(entries):
        if txns and num_hashes < 1:
            # a txn entry consumes at least its own mixin hash; accepting
            # num_hashes=0 would let a block deflate the clock
            return False, segments
        n_append = num_hashes - (1 if txns else 0)
        start = h
        h = poh_append(h, n_append)
        if n_append:
            segments.append((start, n_append, h))
        if txns:
            if first_sigs is not None:
                sigs = first_sigs[k]
            else:
                from firedancer_tpu.protocol import txn as ft

                sigs = []
                for p in txns:
                    t = ft.txn_parse(p)
                    if t is None:
                        return False, segments
                    sigs.append(t.signatures(p)[0])
            h = poh_mixin(h, entry_mixin(sigs))
        if h != expect:
            ok = False
    return ok, segments


def verify_segments_tpu(
    starts: list[bytes], count: int, ends: list[bytes]
) -> np.ndarray:
    """Batch-verify equal-length segments: sha256^count(start_i) == end_i.

    Equal counts keep the compiled program static-shaped; a real block's
    mixed-length segments get bucketed by count by the caller.
    """
    import jax.numpy as jnp

    from firedancer_tpu.ops import sha256 as fsha

    s = np.stack(
        [np.frombuffer(x, dtype=np.uint8) for x in starts], axis=-1
    ).astype(np.int32)
    out = np.asarray(fsha.sha256_iter32(jnp.asarray(s), count))
    expect = np.stack(
        [np.frombuffer(x, dtype=np.uint8) for x in ends], axis=-1
    ).astype(np.int32)
    return (out == expect).all(axis=0)
