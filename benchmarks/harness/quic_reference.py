"""The plain reference of the QUIC front: from a sender's 1-RTT keys and
the datagrams it sent, the transactions a receiver must reassemble.

Independent of waltz/quic.py, runtime/net*.py and native/fd_net.cpp (it
imports none of them): header protection and the AEAD are OpenSSL's
AES-128 (`cryptography`, as the Ed25519 reference's signatures are), the
frame walk and the reassembly are straightforward Python over RFC 9000 /
RFC 9001.  The rules it states:

  - a short-header packet is opened with the sender's keys; one that
    does not authenticate, a long-header packet, and a packet number
    seen before are skipped;
  - a stream's chunks are joined by offset (overlaps carry the same
    bytes; the first copy is kept);
  - a stream whose FIN is known and whose bytes [0, fin) are all there
    emits ONE transaction, at the datagram that completed it: later
    copies of its chunks emit nothing;
  - a stream longer than the 1,232-byte MTU emits nothing at all.

benchmarks/harness/quic_reference.py is this file, byte for byte.
"""

from __future__ import annotations

import json
import struct

from cryptography.exceptions import InvalidTag
from cryptography.hazmat.primitives.ciphers import Cipher, algorithms, modes
from cryptography.hazmat.primitives.ciphers.aead import AESGCM

TXN_MTU = 1232


def _varint(buf: bytes, off: int) -> tuple[int, int]:
    """RFC 9000 §16 -> (value, next offset); IndexError when truncated."""
    ln = 1 << (buf[off] >> 6)
    if off + ln > len(buf):
        raise IndexError("truncated varint")
    v = int.from_bytes(buf[off:off + ln], "big") & ((1 << (8 * ln - 2)) - 1)
    return v, off + ln


def _full_pn(truncated: int, nbits: int, largest: int) -> int:
    """RFC 9000 Appendix A.3."""
    expected = largest + 1
    win = 1 << nbits
    half = win >> 1
    cand = (expected & ~(win - 1)) | truncated
    if cand <= expected - half and cand < (1 << 62) - win:
        return cand + win
    if cand > expected + half and cand >= win:
        return cand - win
    return cand


def stream_frames(payload: bytes):
    """The STREAM frames of one packet payload: (stream id, offset,
    bytes, fin).  Every other frame a QUIC v1 endpoint may send is
    stepped over by its own layout; an unknown type ends the packet."""
    off, n = 0, len(payload)
    while off < n:
        ft = payload[off]
        off += 1
        if ft in (0x00, 0x01, 0x1E):            # PADDING, PING, HS_DONE
            continue
        if ft in (0x02, 0x03):                   # ACK (+ECN)
            _, off = _varint(payload, off)       # largest
            _, off = _varint(payload, off)       # delay
            cnt, off = _varint(payload, off)
            _, off = _varint(payload, off)       # first range
            for _ in range(2 * cnt):
                _, off = _varint(payload, off)
            if ft == 0x03:
                for _ in range(3):
                    _, off = _varint(payload, off)
        elif 0x08 <= ft <= 0x0F:                 # STREAM
            sid, off = _varint(payload, off)
            soff = 0
            if ft & 0x04:
                soff, off = _varint(payload, off)
            if ft & 0x02:
                ln, off = _varint(payload, off)
                if off + ln > n:
                    raise IndexError("STREAM past the packet end")
            else:
                ln = n - off
            yield sid, soff, payload[off:off + ln], bool(ft & 0x01)
            off += ln
        elif ft == 0x06:                         # CRYPTO
            _, off = _varint(payload, off)
            ln, off = _varint(payload, off)
            off += ln
        elif ft == 0x04:                         # RESET_STREAM
            for _ in range(3):
                _, off = _varint(payload, off)
        elif ft in (0x05, 0x11, 0x15):           # two varints
            for _ in range(2):
                _, off = _varint(payload, off)
        elif ft in (0x10, 0x12, 0x13, 0x14, 0x16, 0x17, 0x19):
            _, off = _varint(payload, off)
        elif ft == 0x18:                         # NEW_CONNECTION_ID
            _, off = _varint(payload, off)
            _, off = _varint(payload, off)
            off += 1 + payload[off] + 16
        elif ft in (0x1A, 0x1B):                 # PATH_CHALLENGE/RESPONSE
            off += 8
        elif ft in (0x1C, 0x1D):                 # CONNECTION_CLOSE
            _, off = _varint(payload, off)
            if ft == 0x1C:
                _, off = _varint(payload, off)
            ln, off = _varint(payload, off)
            off += ln
        else:
            return


class PlainReceiver:
    """One sender's datagrams -> the transactions due of them."""

    def __init__(self, key: bytes, iv: bytes, hp: bytes, dcid_len: int = 8,
                 mtu: int = TXN_MTU):
        self._aead = AESGCM(key)
        self._iv = iv
        self._hp = Cipher(algorithms.AES(hp), modes.ECB()).encryptor()
        self._dcid_len = dcid_len
        self.mtu = mtu
        self.largest = -1
        self.seen: set[int] = set()
        self.streams: dict[int, dict] = {}   # sid -> {"seg": {}, "fin": n}
        self.done: set[int] = set()
        self.out: list[bytes] = []
        self.multi_chunk = 0    # emitted transactions joined from > 1 chunk
        self.skipped = 0        # datagrams that opened to nothing

    def open(self, dg: bytes) -> bytes | None:
        """A short-header datagram -> its packet's plaintext payload
        (None: long header, too short, a packet number seen before, or
        it does not authenticate)."""
        pn_off = 1 + self._dcid_len
        if not dg or dg[0] & 0x80 or len(dg) < pn_off + 4 + 16:
            return None
        mask = self._hp.update(dg[pn_off + 4:pn_off + 20])
        first = dg[0] ^ (mask[0] & 0x1F)
        pn_len = (first & 0x03) + 1
        pn_bytes = bytes(b ^ m for b, m in
                         zip(dg[pn_off:pn_off + pn_len], mask[1:1 + pn_len]))
        pn = _full_pn(int.from_bytes(pn_bytes, "big"), 8 * pn_len,
                      self.largest)
        header = bytes([first]) + dg[1:pn_off] + pn_bytes
        nonce = (int.from_bytes(self._iv, "big") ^ pn).to_bytes(12, "big")
        try:
            payload = self._aead.decrypt(nonce, dg[pn_off + pn_len:], header)
        except InvalidTag:
            return None
        if pn in self.seen:
            return None
        self.seen.add(pn)
        self.largest = max(self.largest, pn)
        return payload

    def feed(self, dg: bytes) -> list[bytes]:
        """One datagram -> the transactions it completed."""
        payload = self.open(dg)
        if payload is None:
            self.skipped += 1
            return []
        fresh: list[bytes] = []
        try:
            for sid, off, data, fin in stream_frames(payload):
                txn = self._chunk(sid, off, data, fin)
                if txn is not None:
                    fresh.append(txn)
        except IndexError:
            pass    # a malformed tail: what came before it stands
        self.out += fresh
        return fresh

    def _chunk(self, sid: int, off: int, data: bytes, fin: bool):
        if sid in self.done:
            return None
        st = self.streams.setdefault(sid, {"seg": {}, "fin": None})
        if data and off not in st["seg"]:
            st["seg"][off] = data
        if fin:
            st["fin"] = off + len(data)
        if st["fin"] is None:
            return None
        buf = bytearray()
        for o in sorted(st["seg"]):
            if o > len(buf):
                return None                     # a hole below the FIN
            buf += st["seg"][o][len(buf) - o:]
        if len(buf) < st["fin"]:
            return None
        self.done.add(sid)
        n_chunks = len(st["seg"])
        del self.streams[sid]
        if st["fin"] > self.mtu:
            return None
        self.multi_chunk += n_chunks > 1
        return bytes(buf[:st["fin"]])


def read_capture(prefix: str) -> tuple[dict, list[bytes]]:
    """A sender's capture (runtime/benchs.py: `<prefix>.keys` JSON with
    hex key / iv / hp and dcid_len, `<prefix>.dgrams` u16 length +
    bytes a datagram) -> (keys, datagrams)."""
    with open(prefix + ".keys") as f:
        k = json.load(f)
    keys = {"key": bytes.fromhex(k["key"]), "iv": bytes.fromhex(k["iv"]),
            "hp": bytes.fromhex(k["hp"]), "dcid_len": int(k["dcid_len"])}
    with open(prefix + ".dgrams", "rb") as f:
        raw = f.read()
    dgs, off = [], 0
    while off + 2 <= len(raw):
        (ln,) = struct.unpack_from("<H", raw, off)
        dgs.append(raw[off + 2:off + 2 + ln])
        off += 2 + ln
    return keys, dgs


def reassemble(keys: dict, datagrams) -> PlainReceiver:
    """Every datagram through a fresh receiver; `.out` is the
    transactions, in the order their streams completed."""
    rx = PlainReceiver(keys["key"], keys["iv"], keys["hp"],
                       keys.get("dcid_len", 8))
    for dg in datagrams:
        rx.feed(dg)
    return rx
