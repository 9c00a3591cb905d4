"""UDP ingress: real packets off a socket into the pipeline.

The plain-UDP transport position of the reference
(/root/reference/src/waltz/udpsock/fd_udpsock.c — the non-XDP fallback,
and the TPU/UDP half of the quic tile, src/app/fdctl/run/tiles/fd_quic.c:
one datagram = one whole transaction, no stream reassembly).  The QUIC
server is its own milestone; this stage makes the pipeline's front door a
real socket today: ingress -> verify is network bytes, not an in-process
generator.

Nonblocking: each loop iteration drains up to `rx_burst` datagrams into
the out link (credits permitting), so the cooperative scheduler never
stalls on an idle socket.  Oversized datagrams (> TXN_MTU) are dropped
and counted, mirroring fd_quic's MTU policy.

Native net lane (ISSUE 18): with `FDTPU_NATIVE_NET` on and the toolchain
present, plain-UDP intake runs as a recvmmsg-style batched sweep in
native/fd_net.cpp (one FFI crossing per burst) and QuicIngressStage
takes a sweep of datagrams off its socket in one crossing (ISSUE 46:
one recvmmsg of up to `rx_burst`, then the native QUIC short-header
fast path over them) and does its Python — event replay, ACK, publish,
credit — once a sweep — whatever the C side cannot fully own PUNTs back
to the Python lane below in arrival order, so waltz/quic.py stays the
single source of truth for the control plane.
"""

from __future__ import annotations

import errno
import os
import select
import socket

from firedancer_tpu.protocol.txn import TXN_MTU
from firedancer_tpu.utils.nativebuild import NativeUnavailable
from . import net_native
from .stage import Stage


class UdpIngressStage(Stage):
    # the native recvmmsg sweep bypasses _on_datagram entirely, so only
    # the class whose per-datagram handling IS "publish the raw bytes"
    # may take it; framed subclasses keep the Python receive loop and
    # hook the native lane at their own seam (QuicIngressStage) or not
    # at all (StreamIngressStage)
    _NATIVE_UDP = True

    def __init__(
        self,
        *args,
        host: str = "127.0.0.1",
        port: int = 0,
        sock: socket.socket | None = None,
        rx_burst: int = 64,
        **kwargs,
    ):
        super().__init__(*args, **kwargs)
        if sock is None:
            sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            sock.bind((host, port))
        sock.setblocking(False)
        self.sock = sock
        self.rx_burst = rx_burst
        self._net_client = None
        if self._NATIVE_UDP and net_native.available():
            try:
                self._net_client = net_native.NetClient(
                    max_conns=1, reasm_depth=1)
            except NativeUnavailable:
                self._net_client = None

    @property
    def addr(self) -> tuple[str, int]:
        return self.sock.getsockname()

    def after_credit(self) -> None:
        """One receive loop for every ingress flavor; subclasses override
        only the per-datagram handling (_on_datagram)."""
        if (self._NATIVE_UDP and self._net_client is not None
                and isinstance(self.sock, socket.socket)):
            self._native_udp_sweep()
            return
        self._py_recv_loop()

    def _py_recv_loop(self) -> None:
        """The Python fallback lane: one recvfrom per datagram."""
        for _ in range(self.rx_burst):
            try:
                data, src = self.sock.recvfrom(2048)
            except (BlockingIOError, InterruptedError):
                return
            except OSError as e:  # pragma: no cover - platform specific
                if e.errno in (errno.EAGAIN, errno.EWOULDBLOCK):
                    return
                raise
            self._loop_worked = True    # the thread's ledger: a receive
            if not self._on_datagram(data, src):
                return  # backpressured: stop draining the socket

    def _native_udp_sweep(self) -> None:
        """Batched intake: one crossing drains the socket into the C out
        arena, one burst publishes it.  The credit-gated tail stays
        queued on the native side — never dropped.

        The crossing is one real recvmmsg(2) kernel-scattered straight
        into the arena; FDTPU_NET_SCALAR_RECV=1 pins the byte-identical
        per-datagram recv fallback (differential baseline, non-Linux)."""
        nc = self._net_client
        # lazy plane arm (ISSUE 20): the shm registry attaches after the
        # client exists, so re-arm whenever the stage's plane rebuilds
        plane = self._native_plane()
        if plane is not getattr(nc, "_plane", None):
            nc.set_metrics(plane)
        oi = net_native.COUNTER_IDX["oversz"]
        before = int(nc.counters_view[oi])
        if os.environ.get("FDTPU_NET_SCALAR_RECV", "0") == "1":
            nc.udp_sweep_scalar(self.sock.fileno(), self.rx_burst)
        else:
            nc.udp_sweep(self.sock.fileno(), self.rx_burst)
        oversz = int(nc.counters_view[oi]) - before
        if oversz:
            self.metrics.inc("oversize_drop", oversz)
        n = nc.out_count()
        if not n:
            return
        # sig mirrors the Python lane's running pkt_rx sequence; the
        # arithmetic keeps a retried tail's sigs stable across sweeps
        base = self.metrics.get("pkt_rx")
        items = [(nc.out_txn(i), base + 1 + i, 0) for i in range(n)]
        done = self.publish_burst_out(0, items)
        nc.out_pop(done)
        if done:
            self.metrics.inc("pkt_rx", done)
        if done < n:
            self.metrics.inc("pkt_drop_backpressure", n - done)

    def _on_datagram(self, data: bytes, src) -> bool:
        """Handle one datagram; False = stop the burst (backpressure)."""
        if len(data) > TXN_MTU:
            self.metrics.inc("oversize_drop")
            return True
        self.metrics.inc("pkt_rx")
        if not self.publish(0, data, sig=self.metrics.get("pkt_rx")):
            self.metrics.inc("pkt_drop_backpressure")
            return False
        return True

    def close(self) -> None:
        if self._net_client is not None:
            self._net_client.close()
            self._net_client = None
        self.sock.close()


def send_txns(addr: tuple[str, int], txns: list[bytes]) -> None:
    """Test/bench helper: blast txns at a UDP ingress (benchs analog)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        for t in txns:
            s.sendto(t, addr)
    finally:
        s.close()


# -- stream ingress: multi-datagram txns through the reassembler --------------
#
# The QUIC-position transport: a txn larger than one datagram arrives as
# stream FRAMES that reassemble before verify (fd_quic.c + fd_tpu_reasm).
# Frame format (this framework's stream framing; QUIC proper replaces the
# outer layer, the reassembly discipline stays):
#     "FDST" | u64 conn_id | u32 stream_id | u8 flags (1 = FIN) | data

import struct as _struct

_FRAME_HDR = _struct.Struct("<8sQIB")
_FRAME_MAGIC = b"FDST\x00\x00\x00\x00"


def encode_stream_frame(
    conn_id: int, stream_id: int, data: bytes, fin: bool
) -> bytes:
    return _FRAME_HDR.pack(_FRAME_MAGIC, conn_id, stream_id, 1 if fin else 0) + data


class StreamIngressStage(UdpIngressStage):
    """UDP datagrams carrying stream frames -> reassembled whole txns.

    Extends UdpIngressStage (same socket scaffolding and receive loop):
    each datagram is a stream FRAME fed through the reassembler; whole
    txns publish downstream.  One-frame streams take the fast path
    through the same slot logic.
    """

    _NATIVE_UDP = False  # frames need the per-datagram parse below

    def __init__(self, *args, reasm_depth: int = 64, **kwargs):
        super().__init__(*args, **kwargs)
        from .tpu_reasm import TpuReasm

        self.reasm = TpuReasm(depth=reasm_depth)

    def _on_datagram(self, data: bytes, src) -> bool:
        if len(data) < _FRAME_HDR.size:
            self.metrics.inc("bad_frame")
            return True
        magic, conn_id, stream_id, flags = _FRAME_HDR.unpack_from(data)
        if magic != _FRAME_MAGIC:  # all 8 bytes, not a 4-byte prefix
            self.metrics.inc("bad_frame")
            return True
        self.metrics.inc("frame_rx")
        # the slot key includes the SENDER: peer-chosen (conn, stream) ids
        # must never interleave two peers' frames or let one peer poison
        # another's in-flight stream (QUIC's conn identity plays this
        # role; the UDP source address is its stand-in here)
        txn = self.reasm.append(
            (src, conn_id, stream_id),
            data[_FRAME_HDR.size :],
            fin=bool(flags & 1),
        )
        if txn is None:
            return True
        self.metrics.inc("txn_rx")
        if not self.publish(0, txn, sig=self.metrics.get("txn_rx")):
            self.metrics.inc("txn_drop_backpressure")
            return False
        return True


def send_stream_txn(
    addr: tuple[str, int],
    txn: bytes,
    *,
    conn_id: int = 1,
    stream_id: int = 0,
    frame_sz: int = 512,
) -> None:
    """Send one txn as a fragmented stream (test/bench helper)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        if not txn:  # empty payload still ends with an explicit FIN frame
            s.sendto(encode_stream_frame(conn_id, stream_id, b"", True), addr)
            return
        for off in range(0, len(txn), frame_sz):
            chunk = txn[off : off + frame_sz]
            fin = off + frame_sz >= len(txn)
            s.sendto(encode_stream_frame(conn_id, stream_id, chunk, fin), addr)
    finally:
        s.close()


# the C block's words a crossing's datagrams are counted from
_C_WORDS = tuple(net_native.COUNTER_IDX[n]
                 for n in ("rx_dgram", "rx_bytes", "consumed", "punt"))


class QuicIngressStage(UdpIngressStage):
    """The real QUIC/TPU server position (fd_quic tile,
    /root/reference/src/app/fdctl/run/tiles/fd_quic.c): QUIC v1 packets
    off the UDP socket, one waltz.quic server connection per peer
    address (the reference shards by UDP flow the same way), handshake
    via the embedded TLS engine, stream chunks through the TPU
    reassembler, whole txns published downstream.

    The stage owns the server's Ed25519 identity (in production the
    sign stage holds it; QUIC cert self-signing is the one role fd_tls
    keeps near the socket)."""

    _NATIVE_UDP = False  # the native seam is the QUIC datagram path

    # kernel receive buffer asked for (the effective size is the gauge
    # rcvbuf_bytes): every sender's stream window has to fit in it, or
    # the kernel drops what the tile has not read yet
    RCVBUF_BYTES = 8 << 20

    @classmethod
    def extra_schema(cls):
        from firedancer_tpu.utils import metrics as fm

        return (
            fm.MetricsSchema()
            .counter("dgram_rx", "datagrams taken off the socket")
            .counter("dgram_rx_bytes", "their UDP payload bytes")
            .counter("net_punts", "datagrams the C lane handed to Python")
            .counter("pkt_rx", "datagrams whose packets were accepted")
            .counter("bad_packet", "datagrams dropped: auth, flow, frames")
            .counter("handshakes_done", "connections established")
            .gauge("conn_active", "connections held")
            .counter("conn_drop", "Initials refused: the table is full")
            .counter("conn_evict", "connections evicted for a newcomer")
            .counter("txn_rx", "transactions published to the ring behind")
            .counter("reasm_published", "transactions reassembled whole")
            .counter("reasm_multi_chunk",
                     "of them, joined from more than one STREAM chunk")
            .counter("reasm_evicted",
                     "streams dropped: their reassembly slot was stolen")
            .counter("reasm_oversz", "streams dropped: over the MTU")
            .counter("reasm_cancelled", "streams reset by the transport")
            .counter("reasm_dup_stream",
                     "STREAM chunks of streams already over (late copies)")
            .counter("txn_held_for_credit",
                     "whole transactions that waited for the ring behind")
            .gauge("txn_held", "whole transactions waiting now")
            .counter("streams_granted", "stream credit returned to senders")
            .counter("ack_tx",
                     "datagrams sent that carry nothing but an ACK frame")
            .gauge("rcvbuf_bytes", "the socket's receive buffer")
        )

    def __init__(self, *args, identity_secret: bytes, reasm_depth: int = 64,
                 max_conns: int = 64, tx_filter=None, retry: bool = False,
                 stream_window: int = 64, addr_file: str | None = None,
                 **kwargs):
        super().__init__(*args, **kwargs)
        import hashlib
        from collections import deque

        from firedancer_tpu.waltz import quic
        from .tpu_reasm import TpuReasm

        self.identity_secret = identity_secret
        self.max_conns = max_conns
        # stream credit (RFC 9000 §4.6): a connection may have
        # `stream_window` unidirectional streams that this tile has
        # not yet handed to the ring behind it; credit goes back as
        # transactions are PUBLISHED (or dropped under a named
        # counter), so a full ring holds the senders, not the kernel's
        # buffer.  The senders learn the window from the handshake
        # (initial_max_streams_uni)
        self.stream_window = stream_window
        # credit goes back a quarter window at a time: a MAX_STREAMS
        # frame is ack-eliciting, so one a sweep would cost the sender
        # an ACK datagram, and this tile its crossing, almost every
        # transaction; a sender is never left with less than three
        # quarters of its window
        self._grant_quantum = max(1, stream_window // 4)
        self._tp = quic.encode_transport_params(
            {quic.TP_INITIAL_MAX_STREAMS_UNI: stream_window})
        # the Python lane's whole transactions the ring behind had no
        # credit for, in order: [payload, connection, stream id, native
        # out rows that stood before it].  Bounded by the stream credit
        # outstanding (connections x stream_window)
        self._held: deque = deque()
        self._held_counted = 0      # native out rows already counted held
        self._py_dup_stream = 0     # the Python lane's share of two counters
        self._py_multi_chunk = 0
        self._grant: dict = {}      # cid -> (connection, credit to return)
        if isinstance(self.sock, socket.socket):
            for opt in (socket.SO_RCVBUF, 33):   # 33: SO_RCVBUFFORCE
                try:
                    self.sock.setsockopt(socket.SOL_SOCKET, opt,
                                         self.RCVBUF_BYTES)
                except OSError:
                    pass
            self.metrics.counters["rcvbuf_bytes"] = self.sock.getsockopt(
                socket.SOL_SOCKET, socket.SO_RCVBUF)
        self.conns: dict = {}
        self._addr_by_cid: dict = {}   # server CID -> current peer addr
        self._migrations: dict = {}    # CID -> (candidate addr, token)
        self.reasm = TpuReasm(depth=reasm_depth)
        # tx_filter(datagram) -> bool; False drops the datagram before the
        # socket (loss-recovery tests simulate lossy links with it)
        self.tx_filter = tx_filter
        # address validation (fd_quic's retry path): with retry=True an
        # unvalidated Initial costs us a STATELESS Retry, never a conn
        # slot or a crypto handshake — the amplification defense on the
        # public TPU port
        static = hashlib.sha256(b"quic-static:" + identity_secret).digest()
        self.retry_required = retry
        self.retry_gate = quic.RetryGate(static)
        self._reset_key = static
        # §8: until an address is validated, send at most 3x what it
        # sent us (tracked only pre-handshake; validated addrs drop out)
        # src -> [rx_bytes, tx_bytes, created_monotonic_s]
        self._addr_budget: dict = {}
        # native net lane (ISSUE 18): established conns export their rx
        # application keys into the C table; short-header steady-state
        # datagrams then never touch Python crypto.  The event drain
        # keeps the Python Connection authoritative (tracker, acks, rx
        # windows) so the control plane and every PUNT stay correct.
        self._peer_keys: dict = {}    # src -> peer key (the C side's form)
        self._virtual_src: dict = {}  # peer key -> src, virtual sockets'
        self._c_seen = [0, 0, 0, 0]   # C's rx_dgram, rx_bytes, consumed, punt
        self._native_idx: dict = {}   # local cid bytes -> native idx
        self._by_idx: dict = {}       # native idx -> Connection
        self._native_src: dict = {}   # native idx -> current home addr
        if net_native.available():
            try:
                self._net_client = net_native.NetClient(
                    max_conns=max_conns, reasm_depth=reasm_depth)
            except NativeUnavailable:
                self._net_client = None
        # the crossing receives for itself where the kernel's source
        # addresses are ones it can compare (sockaddr_in); any other
        # socket (a virtual one, IPv6) is read a datagram at a time and
        # each datagram staged into the same sweep
        self._sweeps_socket = (
            self._net_client is not None
            and isinstance(self.sock, socket.socket)
            and self.sock.family == socket.AF_INET)
        if addr_file:
            # a tile in a process of its own: where its senders find it
            import json

            host, port = self.addr
            tmp = addr_file + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"host": host, "port": port}, f)
            os.replace(tmp, addr_file)

    def native_lanes(self) -> dict[str, bool]:
        lanes = super().native_lanes()
        lanes["net"] = self._net_client is not None
        return lanes

    def run_once(self) -> bool:
        """A source stage: what `run` naps on is whether the call
        worked (a receive, a publish), since no frag is consumed."""
        c = self.metrics.counters
        w0 = c["loop_work_n"]
        super().run_once()
        return c["loop_work_n"] != w0

    def _input_pending(self) -> bool:
        nc = self._net_client
        if self._held or (nc is not None
                          and (nc.out_count() or nc.rx_pending())):
            return True
        try:
            return bool(select.select([self.sock], [], [], 0)[0])
        except (OSError, ValueError, TypeError):
            return False

    def during_housekeeping(self) -> None:
        c = self.metrics.counters
        r = self.reasm.metrics
        nc = self._net_client
        n = nc.counters() if nc is not None else {}
        c["reasm_published"] = r["published"] + n.get("txn", 0)
        c["reasm_multi_chunk"] = (self._py_multi_chunk
                                  + n.get("multi_chunk", 0))
        c["reasm_evicted"] = r["evicted"] + n.get("evicted", 0)
        c["reasm_oversz"] = r["oversz"] + n.get("oversz", 0)
        c["reasm_cancelled"] = r["cancelled"]
        c["reasm_dup_stream"] = n.get("dup_stream", 0) + self._py_dup_stream
        c["conn_active"] = len(self.conns)
        c["txn_held"] = len(self._held) + (nc.out_count() if nc else 0)
        self._copy_sweep_counters()

    def _send(self, dg: bytes, dst) -> None:
        if self.tx_filter is not None and not self.tx_filter(dg):
            self.metrics.inc("tx_dropped_by_filter")
            return
        budget = self._addr_budget.get(dst)
        if budget is not None:
            # §8.1 anti-amplification: an unvalidated path gets at most
            # 3x the bytes it sent; the surplus waits for more from the
            # peer (PTO resends it) — a spoofed victim address can never
            # be used as an amplifier
            if budget[1] + len(dg) > 3 * budget[0]:
                self.metrics.inc("tx_amplification_capped")
                return
            budget[1] += len(dg)
        self.sock.sendto(dg, dst)

    def _flush_conn(self, conn, dst) -> None:
        """Send what the connection has to say now; a datagram that
        carries nothing but an ACK frame counts under `ack_tx`."""
        a0 = conn.ack_only_tx
        for dg in conn.flush():
            self._send(dg, dst)
        if conn.ack_only_tx != a0:
            self.metrics.inc("ack_tx", conn.ack_only_tx - a0)

    def before_credit(self) -> None:
        # credit for what the last sweep published goes back even when
        # the ring behind has just filled (after_credit is then skipped)
        if self._grant:
            self._return_credit()

    def after_credit(self) -> None:
        # retry the credit-gated tail before taking more off the socket
        # (queued, never dropped: a drain point that does not depend
        # on further ingress); while any of it waits, nothing more is
        # read, so the senders run out of stream credit and stop
        if self._flush_waiting():
            if self._net_client is None:
                self._py_recv_loop()
            elif self._sweeps_socket:
                self._quic_sweep(self.sock.fileno())
            elif self._quic_sweep(-1):   # what the arena still holds goes first
                self._py_recv_loop()
        if self._grant:
            self._return_credit()
        # loss-recovery housekeeping: fire PTO retransmissions even when
        # the socket is quiet (a lost server flight must not deadlock the
        # handshake — fd_quic's service loop runs its timers the same way)
        for src, conn in list(self.conns.items()):
            conn.poll_timers()
            self._flush_conn(conn, src)

    def _quic_sweep(self, fd: int) -> bool:
        """The unit of the tile's work (ISSUE 46): ONE crossing takes
        what the socket holds, up to `rx_burst` datagrams, and runs the
        C fast path over them; then the Python is done once for all of
        them (`_drain_native`: one event replay, one publish burst, the
        credit, one ACK datagram a touched connection).  The C side
        stops at a datagram it PUNTs: the sweep is drained as far as it
        got, the Python lane runs on that datagram's exact bytes, and
        the same sweep resumes behind it, so arrival order holds across
        a punt.  A socket that holds one datagram gives a sweep of one.
        `fd` -1: only what the receive arena already holds.
        -> nothing waits for the ring behind."""
        nc = self._net_client
        # lazy plane arm (ISSUE 20): the shm registry attaches after the
        # client exists, so re-arm whenever the stage's plane rebuilds
        plane = self._native_plane()
        if plane is not getattr(nc, "_plane", None):
            nc.set_metrics(plane)
        while True:
            rc = nc.quic_sweep(fd, self.rx_burst)
            fd = -1     # one receive a sweep: what follows resumes it
            self._count_crossing()
            ok = self._drain_native()
            if rc >= 0:
                data, key = nc.rx_datagram(rc)
                self._punt(data, self._src_of(key))
            elif rc == net_native.SWEEP_DONE or not ok:
                # (SWEEP_FULL with the ring behind full too: the rest
                # of the sweep waits in the arena for the next call)
                return not self._held and not nc.out_count()

    def _count_crossing(self) -> None:
        """What the last crossing took, from the C block's own words:
        per-sweep deltas, not four counter updates a datagram.  Every
        datagram is consumed, punted (`_punt` counts what becomes of
        it) or dropped as the Python lane would have dropped it."""
        v = self._net_client.counters_view
        seen = self._c_seen
        now = [int(v[i]) for i in _C_WORDS]
        rx = now[0] - seen[0]
        if not rx:
            return
        self._c_seen = now
        self._loop_worked = True    # the thread's ledger: a receive
        consumed = now[2] - seen[2]
        inc = self.metrics.inc
        inc("dgram_rx", rx)
        inc("dgram_rx_bytes", now[1] - seen[1])
        if consumed:
            inc("pkt_rx", consumed)
        bad = rx - consumed - (now[3] - seen[3])
        if bad:
            inc("bad_packet", bad)

    def _on_datagram(self, data: bytes, src) -> bool:
        """One datagram that did not come through the crossing's own
        receive (the Python lane; a virtual or non-IPv4 socket).  With
        the C lane armed it is staged into the receive arena and swept:
        a sweep of one, through the same code as a sweep of 64."""
        nc = self._net_client
        if nc is not None and nc.rx_stage(data, self._peer_key(src)):
            return self._quic_sweep(-1)
        # the Python lane — or, with the arena full of datagrams that
        # wait for the ring behind, out of order as the network may
        self.metrics.inc("dgram_rx")
        self.metrics.inc("dgram_rx_bytes", len(data))
        if nc is None:
            return self._py_datagram(data, src)
        return self._punt(data, src)

    def _peer_key(self, src) -> bytes:
        """`src` as the C side compares addresses (NET_PEER_KEY): what
        recvmmsg's sockaddr_in gives for an IPv4 (host, port); an
        interned id for a virtual socket's addresses."""
        key = self._peer_keys.get(src)
        if key is None:
            try:
                key = (_struct.pack("=H", socket.AF_INET)
                       + _struct.pack("!H", src[1])
                       + socket.inet_pton(socket.AF_INET, src[0])
                       + bytes(12))
            except (OSError, TypeError, ValueError, IndexError,
                    _struct.error):
                key = (b"\xff\xff"
                       + _struct.pack("<Q", len(self._virtual_src) + 1)
                       + bytes(10))
                self._virtual_src[key] = src
            self._peer_keys[src] = key
        return key

    def _src_of(self, key: bytes):
        src = self._virtual_src.get(key)
        if src is None:
            src = (socket.inet_ntop(socket.AF_INET, key[4:8]),
                   int.from_bytes(key[2:4], "big"))
        return src

    def _punt(self, data: bytes, src) -> bool:
        """Python-lane handling for a datagram the native side declined,
        then state re-sync: pns/windows/address the Python conn just
        advanced push back down so the C table never goes stale."""
        from firedancer_tpu.waltz import quic

        self.metrics.inc("net_punts")
        conn = self.conns.get(src)
        prev = None
        if conn is not None:
            idx = self._native_idx.get(bytes(conn.local_cid))
            if idx is not None:
                prev = (conn, idx,
                        [(int(r[0]), int(r[1]))
                         for r in conn.recv[quic.APPLICATION].ranges])
        ok = self._py_datagram(data, src)
        if prev is not None:
            self._sync_after_punt(*prev, src)
        else:
            self._maybe_export(src)
        return ok

    def _maybe_export(self, src) -> None:
        """Install a newly-established conn's rx side into the native
        table (or re-home an already-exported conn after migration)."""
        from firedancer_tpu.waltz import quic

        nc = self._net_client
        conn = self.conns.get(src)
        if nc is None or conn is None or not conn.established:
            return
        cid = bytes(conn.local_cid)
        idx = self._native_idx.get(cid)
        if idx is not None:
            if self._native_src.get(idx) != src:
                nc.conn_set_addr(idx, self._peer_key(src))
                self._native_src[idx] = src
            return
        keys = quic.export_rx_app_keys(conn)
        if keys is None:
            return
        key, iv, hp = keys
        ranges = [(int(lo), int(hi))
                  for lo, hi in conn.recv[quic.APPLICATION].ranges]
        idx = nc.conn_add(cid, self._peer_key(src), key, iv, hp,
                          ranges, conn.rx_max_data, conn.rx_data_total)
        if idx >= 0:
            self._native_idx[cid] = idx
            self._by_idx[idx] = conn
            self._native_src[idx] = src
            nc.conn_streams(idx, conn.rx_max_streams_uni or 0,
                            conn.rx_fin_floor)
            self.metrics.inc("net_conn_exported")

    def _sync_after_punt(self, conn, idx: int, old_ranges, src) -> None:
        from firedancer_tpu.waltz import quic

        nc = self._net_client
        if conn.closed:
            self._native_remove(conn)
            return
        # pns the Python lane just admitted (at most the packets of one
        # datagram) feed the native dedup window
        for lo, hi in ((int(r[0]), int(r[1]))
                       for r in conn.recv[quic.APPLICATION].ranges):
            cur = lo
            for olo, ohi in old_ranges:
                if ohi < cur or olo > hi:
                    continue
                for pn in range(cur, min(olo - 1, hi) + 1):
                    nc.conn_pn_add(idx, pn)
                cur = max(cur, ohi + 1)
                if cur > hi:
                    break
            for pn in range(cur, hi + 1):
                nc.conn_pn_add(idx, pn)
        nc.conn_window(idx, conn.rx_max_data, conn.rx_data_total)
        nc.conn_streams(idx, conn.rx_max_streams_uni or 0,
                        conn.rx_fin_floor)
        if self.conns.get(src) is conn and self._native_src.get(idx) != src:
            nc.conn_set_addr(idx, self._peer_key(src))  # migrated
            self._native_src[idx] = src

    def _native_remove(self, conn) -> None:
        idx = self._native_idx.pop(bytes(conn.local_cid), None)
        if idx is not None:
            self._net_client.conn_remove(idx)
            self._by_idx.pop(idx, None)
            self._native_src.pop(idx, None)

    def _drain_native(self) -> bool:
        """Once a crossing: replay the C side's events into the
        authoritative Python conns (tracker/ack/rtt/window state),
        publish completed txns (credit-gated; the tail stays queued
        native-side), return the stream credit they free, and flush
        each touched connection once — one datagram a connection a
        sweep, its ACK frame covering every packet number the sweep
        admitted, sent only now that the packets' frames are applied
        and their finished transactions stand in the out queue."""
        import time as _t

        from firedancer_tpu.waltz import quic

        nc = self._net_client
        now = _t.monotonic()
        nev = nc.event_count()
        ev = nc.events[:nev].tolist() if nev else ()
        touched = {}
        for typ, idx, a, b in ev:
            conn = self._by_idx.get(idx)
            if conn is None:
                continue
            if typ == net_native.EV_PKT:
                conn._processed_any = True
                if b != 1:  # dup re-acks only, never re-adds
                    conn.recv[quic.APPLICATION].add(a)
                if b in (0, 1):  # ack-eliciting or dup
                    conn.ack_pending.add(quic.APPLICATION)
                touched[idx] = conn
            elif typ == net_native.EV_ACK:
                conn._on_ack(quic.APPLICATION, [(a - b, a)], now)
                touched[idx] = conn
            elif typ == net_native.EV_WIN:
                conn.rx_consumed += a
                conn.rx_data_total += b
                if conn.rx_window_low():
                    # _rx_window_updates' MAX_DATA advertisement, pushed
                    # back down so the native flow check tracks it
                    conn.rx_max_data = (conn.rx_consumed
                                        + quic.DEFAULT_MAX_DATA)
                    conn.ctrl_out.append(
                        bytes([quic.FT_MAX_DATA])
                        + quic.varint_encode(conn.rx_max_data))
                    nc.conn_window(idx, conn.rx_max_data,
                                   conn.rx_data_total)
                touched[idx] = conn
            elif typ == net_native.EV_RETIRE:
                # the stream ended without a transaction (counted:
                # reasm_oversz / reasm_evicted): over here too, and
                # its credit goes back
                conn.stream_finish(a)
                self._retire(conn)
        if nev:
            nc.events_clear()
        ok = self._flush_waiting()
        if self._grant:
            # before the flush below: a MAX_STREAMS frame rides the
            # datagram that carries the sweep's ACK
            self._return_credit()
        for idx, conn in touched.items():
            home = self._native_src.get(idx)
            if home is not None:
                self._flush_conn(conn, home)
        return ok

    def _flush_waiting(self) -> bool:
        """Publish what waits for the ring behind, oldest first, as far
        as it has credit: the native out rows, and the Python lane's
        transactions, each behind the native rows that stood before it.
        -> none is left."""
        held = self._held
        while held:
            txn, conn, sid, before = held[0]
            if before:
                done = self._publish_native(before)
                for h in held:
                    h[3] = max(h[3] - done, 0)
                if done < before:
                    return False
            if not self.publish(0, txn, sig=self.metrics.get("txn_rx") + 1):
                return False
            held.popleft()
            self.metrics.inc("txn_rx")
            self._retire(conn)
        nc = self._net_client
        if nc is None:
            return True
        n = nc.out_count()
        return self._publish_native(n) == n

    def _publish_native(self, want: int) -> int:
        """One burst of the first `want` native out rows -> how many
        the ring behind took."""
        if not want:
            return 0
        nc = self._net_client
        n = nc.out_count()
        want = min(want, n)
        base = self.metrics.get("txn_rx") + 1
        rows = nc.out_rows(want)
        done = self.publish_burst_out(
            0, [(row[0], base + i, 0) for i, row in enumerate(rows)])
        for _txn, ci, sid in rows[:done]:
            conn = self._by_idx.get(ci)
            if conn is not None:
                conn.stream_finish(sid)
                self._retire(conn)
        nc.out_pop(done)
        if done:
            self.metrics.inc("txn_rx", done)
        # each waiting transaction is counted held once, however often
        # its publish is tried again
        left = n - done
        fresh = left - max(self._held_counted - done, 0)
        self._held_counted = left
        if fresh > 0:
            self.metrics.inc("txn_held_for_credit", fresh)
            # (the counter's older name: it never counted a drop here)
            self.metrics.inc("txn_drop_backpressure", fresh)
        return done

    def _retire(self, conn, n: int = 1) -> None:
        """`n` of the connection's streams left this tile (published,
        or dropped under a named counter): their credit goes back with
        the sweep's last flush (`_return_credit`)."""
        cid = bytes(conn.local_cid)
        had = self._grant.get(cid)
        self._grant[cid] = (conn, n + (had[1] if had else 0))

    def _return_credit(self) -> None:
        nc = self._net_client
        quantum = self._grant_quantum
        for cid, (conn, n) in list(self._grant.items()):
            home = self._addr_by_cid.get(cid)
            if home is None or conn.closed:
                del self._grant[cid]
                continue
            if n < quantum:
                continue
            del self._grant[cid]
            conn.grant_streams_uni(n)
            self.metrics.inc("streams_granted", n)
            if nc is not None:
                idx = self._native_idx.get(cid)
                if idx is not None:
                    nc.conn_streams(idx, conn.rx_max_streams_uni)
            self._flush_conn(conn, home)

    def net_counters(self) -> dict:
        """The native lane's counter block ({} on the Python lane) —
        storm summaries and bench read it without touching the FFI."""
        nc = self._net_client
        return nc.counters() if nc is not None else {}

    def _py_datagram(self, data: bytes, src) -> bool:
        from firedancer_tpu.waltz import quic, tls13

        conn = self.conns.get(src)
        fresh = conn is None
        migrating_cid = None
        if fresh:
            # connection migration (RFC 9000 §9): an unknown address
            # whose packet carries a KNOWN connection id belongs to an
            # established peer that changed path — look the conn up by
            # CID, process normally, and validate the new path with a
            # PATH_CHALLENGE before replies move there
            cid = quic.peek_dcid(data, short_dcid_len=8)
            home = self._addr_by_cid.get(cid) if cid else None
            if home is not None and home in self.conns:
                conn = self.conns[home]
                fresh = False
                migrating_cid = cid
        if fresh:
            ver = quic.packet_version(data)
            if ver is None:
                # short header from an unknown address with an unknown
                # CID: stateless reset keyed to that CID (§10.3) so a
                # rebooted peer's connection dies fast, not by timeout
                cid = quic.peek_dcid(data, short_dcid_len=8)
                if cid and len(data) >= 43:
                    self._send(quic.build_stateless_reset(
                        quic.stateless_reset_token(self._reset_key, cid)
                    ), src)
                    self.metrics.inc("stateless_reset_tx")
                return True
            if ver == 0:
                return True  # §6.1: never answer VN with VN
            if ver != quic.QUIC_V1:
                # §6: a long header in a version we don't speak gets a
                # Version Negotiation response — for big-enough
                # datagrams only (tiny spoofed probes get nothing)
                if len(data) >= 1200 and len(data) > 6:
                    dlen = data[5]
                    dcid = data[6 : 6 + dlen]
                    so = 6 + dlen
                    scid = data[so + 1 : so + 1 + data[so]] \
                        if len(data) > so else b""
                    self._send(
                        quic.build_version_negotiation(scid, dcid), src)
                    self.metrics.inc("version_negotiation_tx")
                return True
            if len(data) < 1200:
                # §14.1: servers MUST discard Initials in datagrams
                # smaller than 1200 bytes — and never answer them (a
                # tiny spoofed Initial must not amplify via Retry)
                self.metrics.inc("small_initial_dropped")
                return True
            if self.retry_required:
                peek = quic.peek_initial_token(data)
                if peek is None:
                    self.metrics.inc("bad_packet")
                    return True
                dcid, scid, token = peek
                odcid = self.retry_gate.validate(src, token) if token \
                    else None
                if odcid is None:
                    # STATELESS: no conn, no TLS, just a Retry carrying
                    # a token bound to (src, original dcid)
                    new_scid = os.urandom(8)
                    self._send(quic.build_retry(
                        odcid=dcid, dcid=scid, scid=new_scid,
                        token=self.retry_gate.make_token(src, dcid),
                    ), src)
                    self.metrics.inc("retry_tx")
                    return True
            if len(self.conns) >= self.max_conns and not self._evict():
                self.metrics.inc("conn_drop")
                return True
            if not self.retry_required and src not in self._addr_budget:
                # no token validation: the 3x budget guards this address
                # until its handshake completes.  FAIL CLOSED when the
                # tracking table is full — evicting a LIVE unvalidated
                # entry would exempt that path from the cap (the
                # amplification hole) — but entries past the handshake
                # deadline are dead weight and reclaimable, else a spray
                # of spoofed Initials locks out new clients forever
                import time as _t

                now = _t.monotonic()
                if len(self._addr_budget) >= 4 * self.max_conns:
                    # reclaim only DEAD weight: entries past the
                    # handshake deadline with no live conn — purging a
                    # tracked conn's entry would lift its cap while PTO
                    # keeps retransmitting to that (possibly spoofed)
                    # address
                    for a in [a for a, b in self._addr_budget.items()
                              if now - b[2] > 30.0 and a not in self.conns]:
                        del self._addr_budget[a]
                if len(self._addr_budget) >= 4 * self.max_conns:
                    self.metrics.inc("addr_budget_full_drop")
                    return True
                self._addr_budget[src] = [0, 0, now]
            conn = quic.Connection.server_new(
                self.identity_secret, transport_params=self._tp)
            conn.rx_max_streams_uni = self.stream_window
        was_established = conn.established
        dup0 = conn.rx_dup_stream
        if src in self._addr_budget:
            self._addr_budget[src][0] += len(data)
            if conn is not None and conn.established:
                del self._addr_budget[src]  # address validated
        try:
            events = conn.receive(data)
        except (quic.QuicError, tls13.TlsError, ValueError, IndexError,
                KeyError, _struct.error):
            # drop the bad packet only: a fresh conn that failed its
            # first datagram never occupies a slot (garbage sprayers
            # can't fill max_conns), and an ESTABLISHED conn must
            # survive spoofed noise aimed at its address (RFC 9000:
            # discard undecryptable packets, never tear down).
            # The non-Quic/Tls types matter: untrusted datagrams reach
            # struct unpacking (truncated ClientHello -> struct.error/
            # IndexError) and x25519 (all-zero key share -> ValueError);
            # the stage run loop has no catch-all, so any escape here
            # would be a remote DoS of the TPU ingress.
            self.metrics.inc("bad_packet")
            return True
        if fresh:
            self.conns[src] = conn
            self._addr_by_cid[bytes(conn.local_cid)] = src
        if conn.established and not was_established:
            self.metrics.inc("handshakes_done")
            # the handshake validated the address (§8.1): the 3x cap
            # ends HERE, not at the next datagram this lane sees — on
            # the native lane none comes, and a capped tile stops
            # answering after ~7 KB of ACKs
            self._addr_budget.pop(src, None)
        self.metrics.inc("pkt_rx")
        home = (self._addr_by_cid.get(migrating_cid, src)
                if migrating_cid else src)
        if migrating_cid is not None:
            # complete or advance path validation for the new address
            pend = self._migrations.get(migrating_cid)
            if pend is not None and any(
                r == pend[1] for r in conn.path_responses
            ):
                conn.path_responses.clear()
                del self._migrations[migrating_cid]
                old = self._addr_by_cid[migrating_cid]
                self.conns.pop(old, None)
                self.conns[src] = conn
                self._addr_by_cid[migrating_cid] = src
                home = src
                self.metrics.inc("migrated")
            elif pend is None or pend[0] != src:
                token = os.urandom(8)
                self._migrations[migrating_cid] = (src, token)
                probe = conn.probe_datagram(
                    bytes([quic.FT_PATH_CHALLENGE]) + token
                )
                if probe is not None:
                    self._send(probe, src)
                    self.metrics.inc("path_challenge_tx")
        self._flush_conn(conn, home)
        ok = True
        held = self._held
        nc = self._net_client
        chunks = conn.receive_stream_events(events)
        self._py_dup_stream += conn.rx_dup_stream - dup0
        multi = conn.rx_multi_sids
        for sid, chunk, fin in chunks:
            # every chunk feeds reassembly even under backpressure — the
            # datagram is already ACKed, so a skipped chunk would be a
            # permanent hole in its stream; a completed txn the ring
            # behind has no credit for WAITS (as the native lane's
            # does): its stream was acknowledged, so nobody sends it
            # again
            txn = self.reasm.append((src, sid), chunk, fin=fin)
            if txn is None:
                continue
            if sid in multi:    # of the PUBLISHED ones, as the C lane counts
                self._py_multi_chunk += 1
            before = nc.out_count() if nc is not None else 0
            if held or before or not self.publish(
                    0, txn, sig=self.metrics.get("txn_rx") + 1):
                held.append([txn, conn, sid, before])
                self.metrics.inc("txn_held_for_credit")
                ok = False
                continue
            self.metrics.inc("txn_rx")
            self._retire(conn)
        for (ksrc, ksid), _why in self.reasm.take_ended():
            # ended without a transaction (reasm_evicted /
            # reasm_oversz): later chunks are dropped, not joined into
            # a short transaction, and the credit goes back
            kconn = self.conns.get(ksrc)
            if kconn is not None:
                kconn.stream_finish(ksid)
                self._retire(kconn)
        return ok

    def _evict(self) -> bool:
        """Drop a closed or not-yet-established connection to make room
        (handshake-stalled peers lose their slot first)."""
        for src, conn in list(self.conns.items()):
            if conn.closed or not conn.established:
                del self.conns[src]
                if self._net_client is not None:
                    self._native_remove(conn)
                self.metrics.inc("conn_evict")
                return True
        return False


from .benchs import QuicSender  # noqa: E402  (benchs imports no net)


class QuicTxnClient(QuicSender):
    """The tests' blocking client over the sender tile's send path
    (runtime/benchs.py `QuicSender`): handshakes to a QuicIngressStage
    in its constructor and ships txns, one client-initiated
    unidirectional stream (ids 2, 6, 10, ...) per txn.  `send_txn`
    waits (pumping) where the tile would leave the transaction on its
    ring: for the peer's stream or data credit."""

    def __init__(self, addr, *, expected_peer: bytes | None = None,
                 timeout_s: float = 10.0, tx_filter=None):
        from firedancer_tpu.waltz import quic

        super().__init__(addr, expected_peer=expected_peer,
                         max_datagram=quic.MAX_DATAGRAM,
                         tx_filter=tx_filter)
        self.timeout_s = timeout_s
        self.handshake(timeout_s)

    def _drain_rx(self) -> None:
        """Nonblocking drain of inbound datagrams (acks, credit); a
        test may have swapped the socket for a blocking one."""
        self.sock.setblocking(False)
        self._recv()

    def _flush_out(self) -> None:
        self._flush()

    def send_txn(self, txn: bytes) -> None:
        import time as _time

        # learn window updates BEFORE sending: past the peer's windows
        # its MAX_DATA / MAX_STREAMS must be seen first
        self._drain_rx()
        deadline = _time.monotonic() + self.timeout_s
        while not QuicSender.send_txn(self, txn):
            if _time.monotonic() > deadline:
                raise TimeoutError("no stream or data credit from the peer")
            _time.sleep(0.001)
            self.pump()

    def pump(self) -> None:
        """Process inbound datagrams (acks, window updates) and fire any
        due retransmissions.  Call while waiting for delivery on lossy
        links or during long send loops."""
        self._drain_rx()
        self.conn.poll_timers()
        self._flush()
