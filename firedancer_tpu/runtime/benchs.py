"""The sender tile: whole transactions off a ring, out as QUIC to the
quic tile (the reference's benchs tile, src/app/fddev/tiles/fd_benchs.c:
`fddev bench` offers its load as benchg -> benchs -> QUIC to self).

One QUIC connection a tile, pinned to the quic tile's identity, opened
at set-up.  One client-initiated unidirectional stream a transaction
(ids 2, 6, 10, ...), `fin` on its last chunk, a datagram a chunk and no
datagram over `max_datagram` UDP payload bytes: a transaction that does
not fit one packet goes as two, which the quic tile's reassembler
joins.  Packets are sealed by waltz/quic.py `seal_packet`, whose AES
runs in native/fd_net.cpp where that lane is up and in ops/aes.py where
not, byte for byte the same.

The tile is paced by nothing but the peer: a call drains what came
back (ACKs, MAX_STREAMS / MAX_DATA, probe timeouts), then takes from
its ring what the peer's credit allows.  What it cannot send stays on
the ring, so the generator in front sees ring backpressure and the load
is closed by QUIC flow control end to end.  A call that finds no credit
and nothing to service waits for its socket to turn readable, a
millisecond at most, rather than spin beside tiles that share its host.

`QuicSender` is the one send path: this tile's, and the tests' blocking
client's (runtime/net.py `QuicTxnClient`).
"""

from __future__ import annotations

import json
import select
import socket
import struct
import time

from firedancer_tpu.utils import metrics as fm
from .stage import Stage

# short header (1) + DCID (8) + packet number (2) + GCM tag (16)
_PACKET_OVERHEAD = 27
# STREAM frame: type (1) + stream id (<= 8) + offset (<= 2: under the
# MTU) + length (2)
_FRAME_OVERHEAD = 13
CAPTURE_DATAGRAMS = 4096
# a sender without credit waits for its socket (the peer's ACKs and
# MAX_STREAMS arrive there) at most this long a call, instead of
# spinning on a core the tiles beside it need
CREDIT_WAIT_S = 0.001


class QuicSender:
    """A client connection that ships whole transactions, a stream each.

    `service()` takes what the peer sent back and sends what loss
    recovery owes; `credit()` says how many more transactions may go;
    `send_txn()` puts one on the wire.  Nothing here waits.

    capture: a path.  The connection's 1-RTT send keys go to
    `<capture>.keys` (JSON) when the handshake is done, and the first
    CAPTURE_DATAGRAMS short-header datagrams sent to `<capture>.dgrams`
    (u16 length, bytes), for a reader that reassembles them on its own
    (ops/ref/quic_plain.py)."""

    def __init__(self, addr, *, expected_peer: bytes | None = None,
                 max_datagram: int = 1200, tx_filter=None,
                 capture: str | None = None):
        from firedancer_tpu.waltz import quic

        self._quic = quic
        self.addr = addr
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.sock.setblocking(False)
        self.conn = quic.Connection.client_new(expected_peer=expected_peer)
        self.max_datagram = max_datagram
        self.conn.max_payload = max_datagram - _PACKET_OVERHEAD
        self.chunk_max = max_datagram - _PACKET_OVERHEAD - _FRAME_OVERHEAD
        if self.chunk_max < 64:
            raise ValueError(f"max_datagram {max_datagram} leaves no room "
                             f"for a STREAM chunk")
        self.tx_filter = tx_filter
        self.n_streams = 0          # opened so far: the next is this index
        self.txn_tx = 0
        self.dgram_tx = 0
        self.dgram_rx = 0
        self.stream_rtx = 0         # STREAM chunks sent again
        self.capture = capture
        self._cap_file = None
        self._cap_left = 0

    # -- set-up ---------------------------------------------------------------

    def handshake(self, timeout_s: float = 10.0) -> None:
        """Blocks (set-up only) until the connection is established."""
        conn = self.conn
        deadline = time.monotonic() + timeout_s
        self._flush()
        while not conn.established:
            if not self._recv():
                time.sleep(0.002)
            # PTO keeps a lossy handshake moving (lost Initial/Handshake
            # flights retransmit; without this a single drop deadlocks)
            conn.poll_timers()
            self._flush()
            if time.monotonic() > deadline:
                raise TimeoutError("QUIC handshake timed out")
        if self.capture:
            keys = self._quic.export_tx_app_keys(conn)
            with open(self.capture + ".keys", "w") as f:
                json.dump({"key": keys[0].hex(), "iv": keys[1].hex(),
                           "hp": keys[2].hex(),
                           "dcid_len": len(conn.remote_cid)}, f)
            # unbuffered: a reader in another process sees every
            # datagram once it is written (4,096 small writes, then none)
            self._cap_file = open(self.capture + ".dgrams", "wb",
                                  buffering=0)
            self._cap_left = CAPTURE_DATAGRAMS

    # -- the wire -------------------------------------------------------------

    def _tx(self, dg: bytes) -> None:
        self.dgram_tx += 1
        if self._cap_left and not dg[0] & 0x80:
            self._cap_file.write(struct.pack("<H", len(dg)) + dg)
            self._cap_left -= 1
            if not self._cap_left:
                self._close_capture()
        if self.tx_filter is not None and not self.tx_filter(dg):
            return
        try:
            self.sock.sendto(dg, self.addr)
        except (BlockingIOError, InterruptedError):
            pass    # a full send buffer is a lost datagram: recovery's

    def _close_capture(self) -> None:
        if self._cap_file is not None:
            self._cap_file.close()
            self._cap_file = None
            self._cap_left = 0

    def _recv(self) -> int:
        """Take everything the socket holds.  -> datagrams."""
        n = 0
        recv = self.sock.recvfrom
        conn = self.conn
        while True:
            try:
                data, _ = recv(2048)
            except (BlockingIOError, InterruptedError, socket.timeout):
                break
            n += 1
            try:
                conn.receive(data)
            except (self._quic.QuicError, ValueError, IndexError, KeyError,
                    struct.error):
                pass    # an undecryptable or malformed packet is dropped
        self.dgram_rx += n
        return n

    def _flush(self) -> int:
        conn = self.conn
        self.stream_rtx += len(conn.stream_rtx)
        n = 0
        for dg in conn.flush():
            self._tx(dg)
            n += 1
        return n

    def service(self) -> int:
        """What came back, and what is owed: ACKs and credit in, probe
        timeouts, then retransmissions, ACKs and parked writes out.
        -> datagrams moved either way."""
        n = self._recv()
        conn = self.conn
        if conn.sent[self._quic.APPLICATION]:
            conn.poll_timers()
        if n or conn.stream_rtx or conn.ack_pending or conn.raw_rtx \
                or conn.ctrl_out:
            n += self._flush()
        return n

    # -- transactions ---------------------------------------------------------

    def credit(self, txn_sz: int = 1232) -> int:
        """Transactions of `txn_sz` bytes the peer's windows allow now:
        streams it lets us open (MAX_STREAMS; no limit named: as many
        as its data window holds) and bytes (MAX_DATA)."""
        conn = self.conn
        by_data = (conn.tx_max_data - conn.tx_data_total) // max(txn_sz, 1)
        if conn.tx_max_streams_uni is None:
            return by_data
        return min(conn.tx_max_streams_uni - self.n_streams, by_data)

    def send_txn(self, txn: bytes) -> bool:
        """One transaction on a stream of its own, now.  False (and
        nothing sent) without credit for it."""
        if self.credit(len(txn)) <= 0:
            return False
        conn = self.conn
        sid = 2 + 4 * self.n_streams
        self.n_streams += 1
        now = time.monotonic()
        step = self.chunk_max
        sz = len(txn)
        off = 0
        while True:
            end = min(off + step, sz)
            self._tx(conn.send_stream_packet(
                sid, off, txn[off:end], end == sz, now))
            if end == sz:
                break
            off = end
        self.txn_tx += 1
        return True

    def unacked(self) -> bool:
        return self.conn.has_unacked()

    def close(self) -> None:
        self._close_capture()
        self.sock.close()


def wait_addr(path: str, timeout_s: float) -> tuple[str, int]:
    """Where the quic tile listens, once it has written it down."""
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            with open(path) as f:
                d = json.load(f)
            return d["host"], d["port"]
        except (OSError, ValueError, KeyError):
            if time.monotonic() > deadline:
                raise TimeoutError(f"no quic tile address in {path}")
            time.sleep(0.01)


class BenchSStage(Stage):
    """ring of whole transactions -> QUIC datagrams to the quic tile.

    With `shard_cnt` senders on one ring, sender k takes the frags
    with seq % shard_cnt == k (fd_benchs.c's round robin)."""

    @classmethod
    def extra_schema(cls) -> fm.MetricsSchema:
        return (
            fm.MetricsSchema()
            .counter("txn_tx", "transactions sent, a stream each")
            .counter("dgram_tx", "datagrams sent")
            .counter("dgram_rx", "datagrams received (ACKs, credit)")
            .counter("dgram_rtx", "STREAM chunks sent again (loss, PTO)")
            .counter("streams_acked",
                     "streams whose last chunk the peer acknowledged")
            .counter("send_blocked_credit",
                     "calls that left a transaction on the ring for want "
                     "of the peer's stream or data credit")
        )

    def __init__(self, *args, addr, expected_peer: bytes | None = None,
                 max_datagram: int = 1200, shard_idx: int = 0,
                 shard_cnt: int = 1, capture: str | None = None,
                 handshake_timeout_s: float = 30.0, **kwargs):
        super().__init__(*args, **kwargs)
        self.shard_idx = shard_idx
        self.shard_cnt = shard_cnt
        self.sender = QuicSender(addr, expected_peer=expected_peer,
                                 max_datagram=max_datagram, capture=capture)
        self.sender.handshake(handshake_timeout_s)
        self._serviced = False

    def run_once(self) -> bool:
        """`run` naps on calls that moved nothing: a call that took
        ACKs or credit off the socket moved something."""
        self._serviced = False
        return super().run_once() or self._serviced

    def before_credit(self) -> None:
        s = self.sender
        if s.service():
            self._serviced = self._loop_worked = True
        room = s.credit()
        if room <= 0:
            self.intake_room = 0
            if self.ins and self.ins[0].has_pending():
                self.metrics.inc("send_blocked_credit")
            if not self._serviced:
                # held by the peer's flow control: the next thing that
                # can change it arrives on the socket
                try:
                    select.select([s.sock], [], [], CREDIT_WAIT_S)
                except (OSError, ValueError, TypeError):
                    pass
        else:
            # of any `shard_cnt` frags in a row one is this sender's
            self.intake_room = room * self.shard_cnt

    def before_frag(self, in_idx: int, seq: int, sig: int) -> bool:
        return seq % self.shard_cnt == self.shard_idx

    def after_frag(self, in_idx: int, meta, payload: bytes) -> None:
        if not self.sender.send_txn(payload):
            # cannot happen while intake_room is kept (credit is asked
            # for a whole MTU a transaction): counted, never silent
            self.metrics.inc("txn_unsent_no_credit")

    def during_housekeeping(self) -> None:
        s = self.sender
        c = self.metrics.counters
        c["txn_tx"] = s.txn_tx
        c["dgram_tx"] = s.dgram_tx
        c["dgram_rx"] = s.dgram_rx
        c["dgram_rtx"] = s.stream_rtx
        c["streams_acked"] = s.conn.streams_fin_acked

    def close(self) -> None:
        self.sender.close()


class OutStage(Stage):
    """The end of a tile topology: takes what the tile in front
    publishes and counts it (`frags_in`), so that a verify tile under
    test has a reliable consumer behind it."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.burst = 1024

    def sweep_frags(self, rows, buf: bytes):
        return len(rows), [r[5] for r in rows]
