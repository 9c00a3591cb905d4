"""TPU stream reassembly: QUIC stream fragments -> whole transactions.

Counterpart of /root/reference/src/disco/quic/fd_tpu.h (fd_tpu_reasm_t):
the buffer between a stream transport and the verify stage.  A fixed pool
of reassembly slots accumulates per-stream fragments; a stream's slot
publishes one whole txn when the stream FINishes, and the pool reclaims
the least-recently-active slot under pressure (peers that open streams
and stall must not pin memory — the reference's slot-stealing rule).
Oversized streams (> TXN_MTU) cancel immediately.

The transport (QUIC when it lands; any stream framing today) calls:
    append(stream_key, data, fin) -> None | completed txn bytes
"""

from __future__ import annotations

from collections import OrderedDict, deque

from firedancer_tpu.protocol.txn import TXN_MTU


class TpuReasm:
    _DEAD = None  # tombstone slot value: stream poisoned until FIN/reset

    def __init__(self, depth: int = 64, mtu: int = TXN_MTU):
        if depth <= 0:
            raise ValueError("depth must be positive")
        self.depth = depth
        self.mtu = mtu
        self._slots: OrderedDict[object, bytearray | None] = OrderedDict()
        self.metrics = {
            "published": 0,
            "oversz": 0,
            "evicted": 0,
            "cancelled": 0,
        }
        # keys of streams that ended without a transaction since the
        # owner last took them (`take_ended`): (key, "evicted" |
        # "oversz").  Their packets were acknowledged, so the transport
        # returns their stream credit and drops their later chunks (an
        # owner that never asks keeps only the newest)
        self._ended: deque = deque(maxlen=4096)

    def append(self, key, data: bytes, fin: bool = False) -> bytes | None:
        """Accumulate stream bytes; returns the whole txn at FIN."""
        if key in self._slots:
            slot = self._slots[key]
            self._slots.move_to_end(key)
            if slot is self._DEAD:
                # poisoned (oversize) stream: swallow its continuation
                # frames so it can't churn fresh slots / evict honest
                # streams; the tombstone clears at FIN or reset
                if fin:
                    del self._slots[key]
                    self._ended.append((key, "oversz"))
                return None
        else:
            if len(self._slots) >= self.depth:
                # steal the least-recently-active slot (its stream stalls
                # out and is dropped: its chunks were acknowledged, so no
                # retransmission brings them again)
                old, _ = self._slots.popitem(last=False)
                self._ended.append((old, "evicted"))
                self.metrics["evicted"] += 1
            slot = bytearray()
            self._slots[key] = slot
        slot += data
        if len(slot) > self.mtu:
            self.metrics["oversz"] += 1
            if fin:  # stream ended at the crossing: nothing to poison
                del self._slots[key]
                self._ended.append((key, "oversz"))
            else:  # poison the KEY so continuation frames can't churn
                # fresh slots and evict honest streams
                self._slots[key] = self._DEAD
            return None
        if not fin:
            return None
        del self._slots[key]
        self.metrics["published"] += 1
        return bytes(slot)

    def cancel(self, key) -> bool:
        """Transport-level stream reset: drop the slot (or tombstone)."""
        if key in self._slots:
            del self._slots[key]
            self.metrics["cancelled"] += 1
            return True
        return False

    def take_ended(self) -> list:
        """[(key, why)] of the streams that ended without a
        transaction since the last call."""
        ended = list(self._ended)
        self._ended.clear()
        return ended

    def active(self) -> int:
        return len(self._slots)
