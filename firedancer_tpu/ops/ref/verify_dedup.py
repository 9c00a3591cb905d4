"""The plain reference for the verify tile with the dedup tile behind
it (the reference's fd_verify in front of fd_dedup): wire transactions
in offer order -> which leave the pair, how many fail, how many each
tag cache drops.  Plain Python over the bytes; imports nothing of
`runtime/`, `tango/` or `native/`.  What it knows of the pair's rules:

  - a transaction is a compact-u16 signature count, that many 64-byte
    signatures, and the signed message, whose first `count` account
    keys are the signers'; what does not parse so is dropped;
  - the verify tile keeps the tags (the low 8 bytes of the first
    signature, 0 read as 1) of the last `verify_depth` transactions it
    let through: a transaction whose tag is among them is dropped
    before anything is verified, and a dropped one is not inserted
    again (fd_tcache's rule; tag 0 never dedups);
  - a transaction whose message is longer than `max_msg_len`, or that
    carries more signatures than a batch has lanes, is dropped after
    the tag cache has seen it;
  - EVERY signature is verified over the message under its signer's
    key, and the transaction passes only if all pass;
  - a transaction's signatures are verified in one batch of `batch`
    lanes: one that does not fit into what the open batch has left
    seals it, and the lanes it had left are spent on nothing
    (`fit_pad_lanes`; a batch is otherwise sealed only when full or at
    the end of the stream);
  - the dedup tile keeps the tags of the last `dedup_depth`
    transactions it let through, by the same rule, over what passed
    verify.

The verdict of one signature is a function passed in: OpenSSL's Ed25519
by default, `ops/ref/ed25519_ref.verify` where no OpenSSL is at hand.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Iterable

VERIFY_DEPTH = 16        # fd_verify.h: guards racing duplicates
DEDUP_DEPTH = 1 << 16    # fd_dedup: the authoritative filter

Verdict = Callable[[bytes, bytes, bytes], bool]   # (sig, signer, message)


def openssl_verdict() -> Verdict:
    """One signature under OpenSSL's Ed25519 (through `cryptography`);
    the parsed keys are kept, a signer signs many transactions."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )

    keys: dict[bytes, Ed25519PublicKey] = {}

    def verdict(sig: bytes, pk: bytes, msg: bytes) -> bool:
        key = keys.get(pk)
        if key is None:
            try:
                key = keys[pk] = Ed25519PublicKey.from_public_bytes(pk)
            except ValueError:
                return False
        try:
            key.verify(sig, msg)
        except InvalidSignature:
            return False
        return True

    return verdict


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


def split(p: bytes) -> tuple[list[bytes], list[bytes], bytes] | None:
    """A wire transaction -> (signatures, the signers' account keys, the
    signed message), None where it is not one: compact signature count
    | 64 B each | message; the message is an optional version byte
    (high bit set), the three header bytes, the compact account count
    and the 32-byte account keys, the first `signatures` of which
    sign."""
    try:
        n, o = _compact_u16(p, 0)
        msg = p[o + 64 * n:]
        a = 1 if msg[0] & 0x80 else 0
        n_acct, a = _compact_u16(msg, a + 3)
    except IndexError:
        return None
    if not 1 <= n <= n_acct or len(msg) < a + 32 * n_acct:
        return None
    return ([p[o + 64 * k:o + 64 * (k + 1)] for k in range(n)],
            [msg[a + 32 * k:a + 32 * (k + 1)] for k in range(n)], msg)


def tag_of(first_sig: bytes) -> int:
    return int.from_bytes(first_sig[:8], "little") or 1


class TagCache:
    """The last `depth` tags let through (fd_tcache.h): `seen(tag)` is
    True for a tag among them, and otherwise takes it in, the oldest
    making room."""

    def __init__(self, depth: int):
        self.ring: list[int | None] = [None] * depth
        self.live: set[int] = set()
        self.at = 0

    def seen(self, tag: int) -> bool:
        if tag == 0:
            return False
        if tag in self.live:
            return True
        self.live.discard(self.ring[self.at])
        self.ring[self.at] = tag
        self.live.add(tag)
        self.at = (self.at + 1) % len(self.ring)
        return False


@dataclass
class Outcome:
    """What the pair did with a stream.  `out` is the offers (by index)
    that left it, in order; the rest are counts in the program's own
    units (transactions, and where the name says so signatures)."""

    out: list[int] = field(default_factory=list)
    parse_fail: int = 0
    verify_dup: int = 0          # verify's tag cache dropped
    msg_too_long: int = 0
    too_many_sigs: int = 0
    lanes: int = 0               # signatures that reached verification
    txn_in: int = 0              # transactions that did
    verify_fail: int = 0
    verify_fail_elems: int = 0   # signatures of the failed
    fit_pad_lanes: int = 0       # lanes of batches sealed for want of room
    full_batches: int = 0        # batches sealed full or for want of room
    dedup_dup: int = 0           # dedup's tag cache dropped
    dedup_dup_sigs: int = 0      # signatures of those


def run(txns: Iterable[bytes], *, verdict: Verdict | None = None,
        batch: int = 1024, max_msg_len: int = 1232,
        verify_depth: int = VERIFY_DEPTH,
        dedup_depth: int = DEDUP_DEPTH) -> Outcome:
    """The stream through verify and then dedup, one transaction at a
    time."""
    if verdict is None:
        verdict = openssl_verdict()
    verify_tags, dedup_tags = TagCache(verify_depth), TagCache(dedup_depth)
    r = Outcome()
    open_lanes = 0
    for i, p in enumerate(txns):
        got = split(p)
        if got is None:
            r.parse_fail += 1
            continue
        sigs, pks, msg = got
        tag = tag_of(sigs[0])
        if verify_tags.seen(tag):
            r.verify_dup += 1
            continue
        if len(msg) > max_msg_len:
            r.msg_too_long += 1
            continue
        k = len(sigs)
        if k > batch:
            r.too_many_sigs += 1
            continue
        if open_lanes and open_lanes + k > batch:
            r.fit_pad_lanes += batch - open_lanes
            r.full_batches += 1
            open_lanes = 0
        open_lanes += k
        if open_lanes >= batch:
            r.full_batches += 1
            open_lanes = 0
        r.txn_in += 1
        r.lanes += k
        # every signature, not the first that fails: the program's lanes
        # all run, and a verdict function may count its calls
        if not all([verdict(s, pk, msg) for s, pk in zip(sigs, pks)]):
            r.verify_fail += 1
            r.verify_fail_elems += k
            continue
        if dedup_tags.seen(tag):
            r.dedup_dup += 1
            r.dedup_dup_sigs += k
            continue
        r.out.append(i)
    return r
