"""The plain reference for the verify phase of a replayed block: the
frames a follower's replay tile is handed, parsed here on their own,
the PoH chain under `hashlib`, every signature of every transaction
under OpenSSL's Ed25519 (`cryptography`) -> per slot live or (dead,
reason, entry batch index), and the frames due out.

It shares nothing with the program: not its transaction parser
(protocol/txn, native/fd_txn_parse.cpp), not its stage
(runtime/replay_verify.py, native/fd_verify.cpp), not its sha-256, not
its Ed25519.  What it states is the deployment's semantics:

  frame    u64 slot | u32 batch idx | u32 flags | [32 B seed iff SEED] |
           (u32 len | u32 num_hashes | 32 B hash | u16 cnt |
            (u16 len | txn)*)*            flags: 1 LAST, 2 SEED, 4 VERDICT
  chain    a slot's chain starts at its seed (carried by its batch 0,
           and by no other); an entry's hash follows when num_hashes
           appends — the last of them, for an entry with transactions,
           sha256(h || sha256(first signatures)) — give it; a
           transaction entry with num_hashes 0 does not follow
  verdict  an entry batch fails by `parse` (the frame, an entry or a
           transaction does not parse, a message is over the
           deployment's bound, or the frame is not the one that
           follows), else by `poh`, else by `sig` (any signature of any
           transaction is invalid).  A slot is dead from its first
           failing entry batch on: the batches before it leave, it is
           rejected, every later one of the slot is skipped.  A slot
           whose LAST batch leaves is live.
  out      in block order, each entry batch that leaves, byte for byte
           as it came; after a slot's rejected batch, or after its last
           one, a verdict frame: slot | idx | VERDICT | reason << 8,
           idx the failing batch (dead) or the number of batches (live)
"""

from __future__ import annotations

import hashlib
import struct
from dataclasses import dataclass, field

_HDR = struct.Struct("<QII")
_LAST, _SEED, _VERDICT = 1, 2, 4
REASONS = ("live", "sig", "poh", "parse")
TXN_MTU = 1232


class _Bad(Exception):
    pass


def _cu16(p: bytes, o: int) -> tuple[int, int]:
    """Solana's compact-u16 at p[o:] -> (value, next offset): at most
    three bytes, minimally encoded, under 2**16."""
    v = 0
    for k in range(3):
        if o >= len(p):
            raise _Bad
        b = p[o]
        o += 1
        v |= (b & 0x7F) << (7 * k)
        if not b & 0x80:
            if k and not b:
                raise _Bad          # not minimal
            if v > 0xFFFF:
                raise _Bad
            return v, o
    raise _Bad


def split_txn(p: bytes) -> tuple[list[bytes], list[bytes], bytes]:
    """A wire transaction -> (signatures, their signers' keys, the
    signed message), or _Bad where it is not one: every length has to
    be in bounds and the last byte the last instruction's (or lookup's)
    last."""
    if not 0 < len(p) <= TXN_MTU:
        raise _Bad
    n_sig, o = _cu16(p, 0)
    if not 1 <= n_sig <= 127 or o + 64 * n_sig > len(p):
        raise _Bad
    sigs = [p[o + 64 * k:o + 64 * (k + 1)] for k in range(n_sig)]
    m0 = o = o + 64 * n_sig
    if o >= len(p):
        raise _Bad
    v0 = bool(p[o] & 0x80)
    if v0:
        if p[o] & 0x7F:
            raise _Bad              # only version 0 exists
        o += 1
    if o + 3 > len(p):
        raise _Bad
    n_req, n_ro_signed, n_ro_unsigned = p[o], p[o + 1], p[o + 2]
    n_acct, o = _cu16(p, o + 3)
    if n_req != n_sig or n_ro_signed >= n_req or not n_sig <= n_acct <= 128 \
            or n_ro_unsigned > n_acct - n_req or o + 32 * n_acct + 32 > len(p):
        raise _Bad
    keys = [p[o + 32 * k:o + 32 * (k + 1)] for k in range(n_sig)]
    o += 32 * n_acct + 32           # the keys, the recent blockhash
    n_ins, o = _cu16(p, o)
    for _ in range(n_ins):
        if o >= len(p):
            raise _Bad
        o += 1                      # program id index
        for _ in range(2):          # account indices, data
            n, o = _cu16(p, o)
            o += n
            if o > len(p):
                raise _Bad
    if v0:
        n_lut, o = _cu16(p, o)
        for _ in range(n_lut):
            o += 32
            for _ in range(2):      # writable, readonly indices
                n, o = _cu16(p, o)
                o += n
            if o > len(p):
                raise _Bad
    if o != len(p):
        raise _Bad
    return sigs, keys, p[m0:]


def _entries(body: bytes) -> list[tuple[int, bytes, list[bytes]]]:
    """An entry batch -> [(num_hashes, hash, [txn])], or _Bad."""
    out = []
    o = 0
    while o < len(body):
        if o + 4 > len(body):
            raise _Bad
        ln = int.from_bytes(body[o:o + 4], "little")
        o += 4
        if ln < 38 or o + ln > len(body):
            raise _Bad
        e = body[o:o + ln]
        o += ln
        cnt = int.from_bytes(e[36:38], "little")
        q = 38
        txns = []
        for _ in range(cnt):
            if q + 2 > ln:
                raise _Bad
            tl = int.from_bytes(e[q:q + 2], "little")
            q += 2
            if q + tl > ln:
                raise _Bad
            txns.append(e[q:q + tl])
            q += tl
        if q != ln:
            raise _Bad
        out.append((int.from_bytes(e[:4], "little"), e[4:36], txns))
    return out


def _claimed(body: bytes) -> int:
    """The transactions an entry batch's count fields claim, as far as
    its lengths can be followed (what a batch that is not parsed is
    counted by)."""
    o = n = 0
    while o + 4 <= len(body):
        ln = int.from_bytes(body[o:o + 4], "little")
        o += 4
        if ln < 38 or o + ln > len(body):
            break
        n += int.from_bytes(body[o + 36:o + 38], "little")
        o += ln
    return n


def _sha(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


class _Keys:
    """OpenSSL public keys by their 32 bytes."""

    def __init__(self):
        self._k: dict = {}

    def verifies(self, pk: bytes, sig: bytes, msg: bytes) -> bool:
        from cryptography.exceptions import InvalidSignature
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PublicKey,
        )

        key = self._k.get(pk)
        if key is None:
            try:
                key = Ed25519PublicKey.from_public_bytes(pk)
            except ValueError:
                key = False
            self._k[pk] = key
        if key is False:
            return False
        try:
            key.verify(sig, msg)
            return True
        except InvalidSignature:
            return False


@dataclass
class Slot:
    slot: int
    verdict: str = "open"       # live | dead | open (its LAST never came)
    reason: str | None = None   # sig | poh | parse
    at: int | None = None       # dead: the first failing entry batch
    n_batches: int = 0          # frames of it seen
    left: list = field(default_factory=list)     # batch indices that left
    txn_left: int = 0
    txn_rejected: int = 0
    txn_skipped: int = 0
    sigs_left: int = 0
    sigs_rejected: int = 0


@dataclass
class Result:
    out: list           # the frames due out, in order (verdicts among them)
    slots: list         # [Slot], in the order the slots began


def check_batch(body: bytes, chain: bytes, keys: _Keys, max_msg_len: int):
    """One entry batch against the chain standing at `chain`.
    -> (reason index 0..3, the chain after it, transactions, signatures)."""
    try:
        entries = _entries(body)
        parsed = [[split_txn(p) for p in txns] for _, _, txns in entries]
        if any(len(msg) > max_msg_len for e in parsed for _, _, msg in e):
            raise _Bad
    except (_Bad, IndexError):
        return 3, chain, _claimed(body), 0
    n_txn = sum(len(e) for e in parsed)
    n_sig = sum(len(s) for e in parsed for s, _, _ in e)
    h = chain
    for (num_hashes, expect, _), txns in zip(entries, parsed):
        if txns:
            if num_hashes < 1:
                return 2, chain, n_txn, n_sig
            for _ in range(num_hashes - 1):
                h = _sha(h)
            h = _sha(h + _sha(b"".join(s[0] for s, _, _ in txns)))
        else:
            for _ in range(num_hashes):
                h = _sha(h)
        if h != expect:
            return 2, chain, n_txn, n_sig
    for txns in parsed:
        for sigs, pks, msg in txns:
            if not all(keys.verifies(pk, s, msg) for s, pk in zip(sigs, pks)):
                return 1, h, n_txn, n_sig
    return 0, h, n_txn, n_sig


def replay(frames: list[bytes], *, max_msg_len: int = TXN_MTU) -> Result:
    """The frames a replay tile is handed, in order -> what is due out
    and each slot's verdict."""
    keys = _Keys()
    out: list[bytes] = []
    slots: list[Slot] = []
    cur: Slot | None = None
    chain = bytes(32)
    nxt = 0
    for f in frames:
        framed = _HDR.size <= len(f) <= 65536
        slot = idx = flags = 0
        body = b""
        if framed:
            slot, idx, flags = _HDR.unpack_from(f)
            o = _HDR.size + (32 if flags & _SEED else 0)
            framed = (o <= len(f) and not flags & _VERDICT
                      and bool(flags & _SEED) == (idx == 0))
            body = f[o:]
        if framed and idx == 0:             # a slot starts, clean
            cur = Slot(slot)
            slots.append(cur)
            chain, nxt = f[_HDR.size:_HDR.size + 32], 0
        if cur is not None and cur.verdict == "dead" \
                and (not framed or slot == cur.slot):
            cur.n_batches += 1
            cur.txn_skipped += _claimed(body) if framed else 0
            continue
        if not framed or cur is None or slot != cur.slot or idx != nxt:
            if cur is None:
                cur = Slot(slot if framed else 0)
                slots.append(cur)
            why, n_txn, n_sig = 3, _claimed(body) if framed else 0, 0
            idx, flags = nxt, 0
        else:
            why, chain, n_txn, n_sig = check_batch(body, chain, keys,
                                                   max_msg_len)
        cur.n_batches += 1
        nxt = idx + 1
        if why:
            cur.verdict, cur.reason, cur.at = "dead", REASONS[why], idx
            cur.txn_rejected += n_txn
            cur.sigs_rejected += n_sig
            out.append(_HDR.pack(cur.slot, idx, _VERDICT | why << 8))
            continue
        out.append(f)
        cur.left.append(idx)
        cur.txn_left += n_txn
        cur.sigs_left += n_sig
        if flags & _LAST:
            cur.verdict = "live"
            out.append(_HDR.pack(cur.slot, idx + 1, _VERDICT))
    return Result(out, slots)
