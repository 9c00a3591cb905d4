"""The front door (ISSUE 41): the sender tile's send path, the quic
tile and the plain reference, held to each other; the process topology
benchs -> quic -> verify -> out at toy size; the deployment's config."""

from __future__ import annotations

import hashlib
import os
import time

import pytest

from firedancer_tpu.ops.ref import quic_plain
from firedancer_tpu.runtime import net_native

IDENTITY = hashlib.sha256(b"quic-front").digest()
ADDR = ("10.9.8.7", 4242)


def seeded_txns(seed: bytes, n: int, size: int) -> list[bytes]:
    out = []
    for i in range(n):
        buf = b""
        h = hashlib.sha256(seed + i.to_bytes(4, "little")).digest()
        while len(buf) < size:
            h = hashlib.sha256(h).digest()
            buf += h
        out.append(buf[:size])
    return out


class Collector:
    """Producer stub: what the tile published, behind a credit gate."""

    def __init__(self, credits=None):
        self.frames: list[bytes] = []
        self.credits = credits

    def try_publish(self, payload, sig=0, tsorig=0):
        if self.credits is not None:
            if self.credits <= 0:
                return False
            self.credits -= 1
        self.frames.append(bytes(payload))
        return True


class Wire:
    """The sender's socket, virtual: every datagram it sends goes
    through a link fault into the stage, and what the stage sends back
    is read from its ChaosSock.  `delivered` is what reached the tile,
    in order: the plain reference is fed exactly that."""

    def __init__(self, stage, fault: str):
        self.stage = stage
        self.fault = fault
        self.delivered: list[bytes] = []
        self.n_short = 0
        self.dropped = 0
        self._held = None

    def setblocking(self, flag) -> None:
        pass

    def close(self) -> None:
        pass

    def _deliver(self, dg: bytes) -> None:
        self.delivered.append(dg)
        self.stage._on_datagram(dg, ADDR)

    def sendto(self, dg: bytes, _addr) -> None:
        if dg[0] & 0x80 or self.fault == "inorder":
            return self._deliver(dg)
        self.n_short += 1
        if self.fault == "dup":
            self._deliver(dg)
            self._deliver(dg)
        elif self.fault == "loss":
            if self.n_short % 5 == 0:
                self.dropped += 1
            else:
                self._deliver(dg)
        elif self.fault == "reorder":
            if self._held is None:
                self._held = dg
            else:
                self._deliver(dg)
                self._deliver(self._held)
                self._held = None

    def idle(self) -> None:
        """Nothing more is coming right now: a held datagram goes."""
        if self._held is not None:
            held, self._held = self._held, None
            self._deliver(held)

    def recvfrom(self, n: int):
        q = self.stage.sock.tx.get(ADDR)
        if not q:
            raise BlockingIOError
        return q.popleft(), ("chaos", 0)


def make_front(monkeypatch, native: bool, fault: str, *, out=None,
               stream_window: int = 16, max_datagram: int = 1200):
    from firedancer_tpu.chaos.population import ChaosSock
    from firedancer_tpu.ops.ref import ed25519_ref as ref
    from firedancer_tpu.runtime.benchs import QuicSender
    from firedancer_tpu.runtime.net import QuicIngressStage

    monkeypatch.setenv("FDTPU_NATIVE_NET", "1" if native else "0")
    out = out if out is not None else Collector()
    stage = QuicIngressStage(
        "quic", outs=[out], sock=ChaosSock(), rx_burst=8,
        identity_secret=IDENTITY, stream_window=stream_window)
    assert (stage._net_client is not None) == native
    sender = QuicSender(ADDR, expected_peer=ref.public_key(IDENTITY),
                        max_datagram=max_datagram)
    sender.sock.close()
    sender.sock = wire = Wire(stage, fault)
    sender.handshake(10.0)
    return stage, sender, wire, out


def plain_of(sender, wire) -> quic_plain.PlainReceiver:
    from firedancer_tpu.waltz import quic

    key, iv, hp = quic.export_tx_app_keys(sender.conn)
    return quic_plain.reassemble(
        {"key": key, "iv": iv, "hp": hp,
         "dcid_len": len(sender.conn.remote_cid)}, wire.delivered)


def drive(stage, sender, wire, txns, *, limit_s: float = 20.0) -> None:
    """Send every transaction as credit allows, then pump until the
    peer has acknowledged everything."""
    todo = list(txns)
    deadline = time.monotonic() + limit_s
    while time.monotonic() < deadline:
        sender.service()
        while todo and sender.send_txn(todo[0]):
            todo.pop(0)
        wire.idle()
        stage.after_credit()
        if not todo and not sender.unacked() and not stage._held \
                and not (stage._net_client
                         and stage._net_client.out_count()):
            return
        time.sleep(0.0005)
    raise AssertionError(f"not delivered in {limit_s}s: {len(todo)} unsent, "
                         f"unacked={sender.unacked()}")


LANES = [pytest.param(True, id="native", marks=pytest.mark.skipif(
    not net_native.available(), reason="fd_net.so unavailable")),
    pytest.param(False, id="python")]


@pytest.mark.parametrize("fault", ["inorder", "reorder", "dup", "loss"])
@pytest.mark.parametrize("size", [215, 1232])
@pytest.mark.parametrize("native", LANES)
def test_tile_equals_plain_reference(monkeypatch, native, size, fault):
    """What the quic tile publishes is what the plain reference
    reassembles from the datagrams that reached it: every transaction
    once, in the order the streams completed, on both lanes."""
    txns = seeded_txns(b"front%d" % size, 48, size)
    stage, sender, wire, out = make_front(monkeypatch, native, fault)
    try:
        drive(stage, sender, wire, txns)
        rx = plain_of(sender, wire)
        assert out.frames == rx.out
        assert sorted(out.frames) == sorted(txns)
        stage.during_housekeeping()
        c = stage.metrics.counters
        assert c["reasm_published"] == len(txns) == c["txn_rx"]
        two = size > sender.chunk_max
        assert c["reasm_multi_chunk"] == (len(txns) if two else 0)
        assert rx.multi_chunk == c["reasm_multi_chunk"]
        assert c["reasm_evicted"] == c["reasm_oversz"] == 0
        assert max(len(d) for d in wire.delivered if not d[0] & 0x80) \
            <= sender.max_datagram
        if fault == "loss":
            assert wire.dropped and sender.stream_rtx
        assert sender.conn.streams_fin_acked == len(txns)
        # the senders were held by credit, not by luck: 48 streams
        # through a window of 16, returned four at a time
        assert len(txns) - 3 <= c["streams_granted"] <= len(txns)
    finally:
        stage.close()
        sender.close()


@pytest.mark.parametrize("size", [215, 1232])
@pytest.mark.parametrize("native", LANES)
def test_late_copy_of_a_finished_stream_is_swallowed(monkeypatch, native,
                                                     size):
    """A spurious retransmission (new packet numbers, a stream the
    tile already published): swallowed and counted, on both lanes, and
    the reference emits nothing for it either."""
    txns = seeded_txns(b"late", 4, size)
    stage, sender, wire, out = make_front(monkeypatch, native, "inorder")
    try:
        drive(stage, sender, wire, txns)
        conn = sender.conn
        sent0 = conn.tx_data_total
        step = sender.chunk_max
        for k, t in enumerate(txns[:2]):        # streams 2 and 6 again
            for off in range(0, size, step):
                sender._tx(conn.send_stream_packet(
                    2 + 4 * k, off, t[off:off + step], off + step >= size))
        conn.tx_data_total = sent0              # no new bytes were offered
        drive(stage, sender, wire, [])
        assert out.frames == txns == plain_of(sender, wire).out
        stage.during_housekeeping()
        c = stage.metrics.counters
        assert c["reasm_dup_stream"] == 2 * -(-size // step)
        assert c["reasm_published"] == c["txn_rx"] == 4
    finally:
        stage.close()
        sender.close()


@pytest.mark.parametrize("native", LANES)
def test_oversize_stream_is_counted_not_published(monkeypatch, native):
    """Over the MTU: neither the tile nor the reference emits it, the
    tile counts it, and its stream credit comes back."""
    stage, sender, wire, out = make_front(monkeypatch, native, "inorder",
                                          stream_window=2)
    try:
        big = seeded_txns(b"big", 1, 1300)[0]
        ok = seeded_txns(b"ok", 3, 215)
        # the send path refuses nothing by size: the tile has to
        drive(stage, sender, wire, [ok[0], big, ok[1], ok[2]])
        assert out.frames == ok == plain_of(sender, wire).out
        stage.during_housekeeping()
        c = stage.metrics.counters
        assert c["reasm_oversz"] == 1 and c["reasm_published"] == 3
        assert sender.conn.streams_fin_acked \
            - c["txn_rx"] - c["reasm_oversz"] - c["reasm_evicted"] == 0
    finally:
        stage.close()
        sender.close()


@pytest.mark.parametrize("native", LANES)
def test_full_ring_holds_transactions_and_senders(monkeypatch, native):
    """The repair (ISSUE 41): a completed transaction whose packet is
    already acknowledged WAITS when the ring behind has no credit, on
    both lanes; nothing is dropped, order is kept, and the sender runs
    out of stream credit instead of the kernel's buffer filling."""
    txns = seeded_txns(b"hold", 40, 215)
    out = Collector(credits=3)
    stage, sender, wire, _ = make_front(monkeypatch, native, "inorder",
                                        out=out, stream_window=8)
    try:
        sent = 0
        for _ in range(200):
            sender.service()
            while sent < len(txns) and sender.send_txn(txns[sent]):
                sent += 1
            stage.after_credit()
        assert len(out.frames) == 3
        # the window: 3 published + 8 outstanding, no more (credit
        # returns two at a time: the connection may be owed one)
        assert 3 + 7 <= sent <= 3 + 8 and sender.credit() == 0
        held = len(stage._held) + (stage._net_client.out_count()
                                   if native else 0)
        assert held == sent - 3
        assert stage.metrics.get("txn_held_for_credit") == held
        assert stage._input_pending()
        out.credits = None
        drive(stage, sender, wire, txns[sent:])
        assert out.frames == txns
        stage.during_housekeeping()
        c = stage.metrics.counters
        assert c["reasm_evicted"] == c["reasm_oversz"] == 0
        assert c["txn_held"] == 0
    finally:
        stage.close()
        sender.close()


# -- the sweep (ISSUE 46): real sockets, a burst a crossing ---------------------

needs_net = pytest.mark.skipif(
    not net_native.available(), reason="fd_net.so unavailable")


def socket_front(monkeypatch, native: bool, *, burst: int, senders: int = 1,
                 out=None, stream_window: int = 64, tx_filter=None):
    """A quic tile on a loopback socket and `senders` connections to
    it, each from a socket of its own, handshaken on this thread."""
    from firedancer_tpu.ops.ref import ed25519_ref as ref
    from firedancer_tpu.runtime.benchs import QuicSender
    from firedancer_tpu.runtime.net import QuicIngressStage

    monkeypatch.setenv("FDTPU_NATIVE_NET", "1" if native else "0")
    out = out if out is not None else Collector()
    stage = QuicIngressStage(
        "quic", outs=[out], rx_burst=burst, identity_secret=IDENTITY,
        stream_window=stream_window, tx_filter=tx_filter)
    assert (stage._net_client is not None) == native
    assert stage._sweeps_socket == native
    conns = [QuicSender(stage.addr, expected_peer=ref.public_key(IDENTITY))
             for _ in range(senders)]
    for s in conns:
        s._flush()
    settle(stage, conns)
    assert all(s.conn.established for s in conns)
    return stage, conns, out


def settle(stage, conns, limit_s: float = 10.0) -> None:
    """Tile and senders in turn until nothing moves either way."""
    quiet, deadline = 0, time.monotonic() + limit_s
    while quiet < 3 and time.monotonic() < deadline:
        stage.after_credit()
        moved = sum(s.service() for s in conns) or stage._input_pending()
        quiet = 0 if moved else quiet + 1


def drain_socket(stage, want: int, limit_s: float = 10.0) -> int:
    """`after_credit` until the tile has counted `want` datagrams.
    -> the calls that took."""
    calls, deadline = 0, time.monotonic() + limit_s
    while stage.metrics.get("dgram_rx") < want:
        assert time.monotonic() < deadline, (
            stage.metrics.get("dgram_rx"), want)
        stage.after_credit()
        calls += 1
    return calls


class Tape:
    """A sender's socket that records (who, datagram) and sends
    nothing: the seeded capture is played from it afterwards."""

    def __init__(self, tape: list, who):
        self.tape, self.who = tape, who

    def sendto(self, dg: bytes, _addr) -> None:
        self.tape.append((self.who, bytes(dg)))


def seeded_capture(conns) -> tuple[list, list]:
    """The traffic of the differential test, sealed under this front's
    keys -> ([(socket index, datagram)], the transactions in the order
    their streams complete).  Sockets 0-3 are the four connections', 4
    a stranger's, 5 a new source address for connection 1."""
    from firedancer_tpu.waltz import quic

    tape: list = []
    socks = [s.sock for s in conns]
    for k, s in enumerate(conns):
        s.sock = Tape(tape, k)
    small = seeded_txns(b"sweep-small", 40, 215)
    large = seeded_txns(b"sweep-large", 24, 1232)
    want = []
    for r in range(8):                          # one- and two-chunk streams
        for k, s in enumerate(conns):
            for t in (small[4 * r + k], large[(4 * r + k) % 24]) \
                    if r % 2 == 0 else (small[4 * r + k],):
                assert s.send_txn(t)
                want.append(t)
        if r == 1:
            tape.append(tape[-1])               # a duplicate packet
        if r == 2:
            # mid-stream: a long header from a stranger, one from a
            # known address, and connection 1's next stream from a new
            # source address (all three are the Python lane's)
            tape.append((4, bytes([0xC3]) + (1).to_bytes(4, "big")
                         + bytes(60)))
            tape.append((0, bytes([0xC3]) + (1).to_bytes(4, "big")
                         + bytes(60)))
            assert conns[1].send_txn(small[32])
            want.append(small[32])
            tape[-1] = (5, tape[-1][1])
        if r == 3:                              # an oversize stream
            assert conns[2].send_txn(seeded_txns(b"sweep-big", 1, 1300)[0])
        if r == 5:                              # a late copy: stream 2 again
            c = conns[3].conn
            sent0 = c.tx_data_total
            conns[3]._tx(c.send_stream_packet(2, 0, small[3], True))
            c.tx_data_total = sent0
    for s, sock in zip(conns, socks):
        s.sock = sock
    assert quic.MAX_DATAGRAM >= max(len(d) for _, d in tape)
    return tape, want


def play(monkeypatch, native: bool, burst: int) -> dict:
    """The seeded capture through one lane at one burst -> what the
    tile published and how it stands afterwards."""
    import socket as _socket

    from firedancer_tpu.waltz import quic

    stage, conns, out = socket_front(monkeypatch, native, burst=burst,
                                     senders=4)
    extra = [_socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
             for _ in range(2)]
    try:
        homes = [("127.0.0.1", s.sock.getsockname()[1]) for s in conns]
        tape, want = seeded_capture(conns)
        socks = [s.sock for s in conns] + extra
        base = stage.metrics.get("dgram_rx")
        punts0 = stage.metrics.get("net_punts")
        calls = 0
        for lo in range(0, len(tape), 64):      # 64 at a time into the socket
            part = tape[lo:lo + 64]
            for who, dg in part:
                socks[who].sendto(dg, stage.addr)
            calls += drain_socket(stage, base + lo + len(part))
        # as the capture left them: what the senders say once they read
        # their sockets (ACKs of MAX_STREAMS) is not part of it
        state = []
        for home in homes:
            conn = stage.conns[home]
            state.append({
                "ranges": [list(r) for r in
                           conn.recv[quic.APPLICATION].ranges],
                "rx_data_total": conn.rx_data_total,
                "rx_max_data": conn.rx_max_data,
                "rx_fin_floor": conn.rx_fin_floor})
        punts = stage.metrics.get("net_punts") - punts0
        stage._grant_quantum = 1                # the credit owed goes back
        settle(stage, conns)
        for home, st in zip(homes, state):
            st["rx_max_streams_uni"] = stage.conns[home].rx_max_streams_uni
        stage.during_housekeeping()
        c = stage.metrics.counters
        return {
            "frames": list(out.frames), "want": want, "state": state,
            "counters": {k: c.get(k, 0) for k in (
                "reasm_published", "reasm_multi_chunk", "reasm_evicted",
                "reasm_oversz", "reasm_cancelled", "reasm_dup_stream",
                "bad_packet", "dgram_rx", "dgram_rx_bytes", "txn_rx")},
            "net_punts": punts,
            "datagrams": len(tape), "calls": calls,
            "fin_acked": [s.conn.streams_fin_acked for s in conns],
            "streams": [s.n_streams for s in conns]}
    finally:
        stage.close()
        for s in conns:
            s.close()
        for x in extra:
            x.close()


_PLAYED: dict = {}


def played(monkeypatch, native: bool, burst: int) -> dict:
    key = (native, burst)
    if key not in _PLAYED:
        _PLAYED[key] = play(monkeypatch, native, burst)
    return _PLAYED[key]


@needs_net
@pytest.mark.parametrize("burst", [1, 7, 64])
def test_the_sweep_equals_the_python_lane(monkeypatch, burst):
    """One seeded capture — four connections, one- and two-chunk
    streams, a duplicate packet, a late copy of a finished stream, an
    oversize stream, and mid-stream two long headers and a packet from
    a new source address — through the sweep lane at this burst and
    through the Python lane a datagram at a time: the same transactions
    in the same order, the same connection state, the same counters."""
    ref = played(monkeypatch, False, 1)
    got = played(monkeypatch, True, burst)
    assert ref["frames"] == ref["want"]
    assert got["frames"] == ref["frames"]
    assert got["state"] == ref["state"]
    assert got["counters"] == ref["counters"]
    c = got["counters"]
    assert c["reasm_oversz"] == 1 and c["reasm_published"] == len(ref["want"])
    assert c["reasm_dup_stream"] == 1 and c["bad_packet"] >= 1
    # the three datagrams that are the Python lane's, and no more
    assert got["net_punts"] == 3
    # every stream's last packet acknowledged (the oversize one's too,
    # and connection 3's late copy)
    assert got["fin_acked"] == ref["fin_acked"] \
        == [n + (k == 3) for k, n in enumerate(got["streams"])]
    # a socket that holds `burst` gives a sweep of `burst`: the punts
    # cut three of them short
    assert got["calls"] <= -(-got["datagrams"] // burst) + 3 + 3


@needs_net
def test_a_sweep_sends_one_ack_covering_it_all(monkeypatch):
    """N ack-eliciting packets of one connection in one crossing: ONE
    datagram back, nothing in it but an ACK frame whose ranges cover
    all N; the crossing is one, the datagrams a crossing N."""
    stage, (s,), out = socket_front(monkeypatch, True, burst=64)
    try:
        txns = seeded_txns(b"one-ack", 10, 215)
        stage.during_housekeeping()
        c = stage.metrics.counters
        a0, x0, r0 = c.get("ack_tx", 0), c.get("sweep_crossings", 0), \
            s.dgram_rx
        for t in txns:
            assert s.send_txn(t)
        stage.after_credit()
        assert out.frames == txns
        assert s._recv() == 1 and s.dgram_rx == r0 + 1
        assert s.conn.streams_fin_acked == 10 and not s.unacked()
        stage.during_housekeeping()
        assert c["ack_tx"] == a0 + 1
        if "sweep_crossings" in c:      # C's words, with a metrics plane
            assert c["sweep_crossings"] == x0 + 1
    finally:
        stage.close()
        s.close()


@needs_net
def test_a_lost_ack_is_covered_by_the_next_sweeps(monkeypatch):
    """`tx_filter` drops the sweep's one ACK datagram: nothing is
    acknowledged; the next sweep's ACK covers both sweeps."""
    lossy = {"on": False, "dropped": 0}

    def tx_filter(dg):
        if lossy["on"]:
            lossy["dropped"] += 1
            return False
        return True

    stage, (s,), out = socket_front(monkeypatch, True, burst=64,
                                    tx_filter=tx_filter)
    try:
        txns = seeded_txns(b"lost-ack", 12, 215)
        lossy["on"] = True
        for t in txns[:6]:
            assert s.send_txn(t)
        stage.after_credit()
        assert lossy["dropped"] == 1 and s._recv() == 0
        assert s.conn.streams_fin_acked == 0
        lossy["on"] = False
        for t in txns[6:]:
            assert s.send_txn(t)
        stage.after_credit()
        assert s._recv() == 1
        assert s.conn.streams_fin_acked == 12 and not s.unacked()
        assert out.frames == txns
    finally:
        stage.close()
        s.close()


@needs_net
def test_a_ring_that_fills_in_mid_sweep_holds_and_drops_nothing(monkeypatch):
    """The ring behind takes 3 of a sweep of 8: the other 5 were taken
    off the socket and acknowledged, so they WAIT in the native out
    queue, counted held once each however often the publish is tried;
    the sender gets its ACK and the credit of the 3, sends 3 more and
    stops on stream credit; those wait unread in the socket.  Then the
    ring drains: everything lands once, in order."""
    txns = seeded_txns(b"mid-sweep", 40, 215)
    out = Collector(credits=3)
    stage, (s,), _ = socket_front(monkeypatch, True, burst=64, out=out,
                                  stream_window=8)
    try:
        sent = 0
        while sent < len(txns) and s.send_txn(txns[sent]):
            sent += 1
        assert sent == 8
        rx0 = stage.metrics.get("dgram_rx")
        for _ in range(5):
            stage.after_credit()
            s.service()
            while sent < len(txns) and s.send_txn(txns[sent]):
                sent += 1
        assert len(out.frames) == 3 and sent == 8 + 3 and s.credit() == 0
        assert stage._net_client.out_count() == 5
        assert stage.metrics.get("txn_held_for_credit") == 5
        # one sweep took the 8; what came after waits in the socket
        assert stage.metrics.get("dgram_rx") == rx0 + 8
        assert stage._input_pending()
        assert s.conn.streams_fin_acked == 8
        out.credits = None
        deadline = time.monotonic() + 20
        while len(out.frames) < len(txns) or s.unacked():
            assert time.monotonic() < deadline
            stage.after_credit()
            s.service()
            while sent < len(txns) and s.send_txn(txns[sent]):
                sent += 1
        assert out.frames == txns
        stage.during_housekeeping()
        c = stage.metrics.counters
        assert c["reasm_evicted"] == c["reasm_oversz"] == c["txn_held"] == 0
        assert c["txn_rx"] == len(txns)
    finally:
        stage.close()
        s.close()


@needs_net
def test_a_punt_behind_a_full_ring_keeps_its_place(monkeypatch):
    """The ring behind is full, native out rows wait, and a datagram
    the C lane punts (a new source address) completes a transaction on
    the Python lane: it waits BEHIND the rows that stood before it and
    ahead of those that came after; everything lands in arrival order,
    each counted held once."""
    txns = seeded_txns(b"punt-hold", 6, 215)
    out = Collector(credits=1)
    stage, sender, wire, _ = make_front(monkeypatch, True, "inorder",
                                        out=out)
    try:
        src = {"now": ADDR}

        def deliver(dg):
            wire.delivered.append(dg)
            stage._on_datagram(dg, src["now"])

        wire._deliver = deliver
        punts0 = stage.metrics.get("net_punts")
        for i, t in enumerate(txns):
            src["now"] = ("10.9.8.8", 4243) if i == 3 else ADDR
            assert sender.send_txn(t)
        assert out.frames == txns[:1]
        assert stage.metrics.get("net_punts") == punts0 + 1
        assert [h[0] for h in stage._held] == [txns[3]]
        assert stage._held[0][3] == 2           # txns 1 and 2 go first
        assert stage._net_client.out_count() == 4
        assert stage.metrics.get("txn_held_for_credit") == 5
        out.credits = 2                         # not enough for the punt's
        stage.after_credit()
        assert out.frames == txns[:3] and stage._held[0][3] == 0
        out.credits = None
        stage.after_credit()
        assert out.frames == txns == plain_of(sender, wire).out
        assert stage.metrics.get("txn_held_for_credit") == 5
        assert not stage._held and not stage._net_client.out_count()
    finally:
        stage.close()
        sender.close()


@pytest.mark.parametrize("native", LANES)
def test_packet_numbers_pass_two_bytes(monkeypatch, native):
    """The wire carries a packet number's low 16 bits: a connection's
    65,537th packet is opened like its first, on both lanes (a sender
    tile sends more than that in a run)."""
    from firedancer_tpu.waltz import quic

    stage, sender, wire, out = make_front(monkeypatch, native, "inorder")
    try:
        sender.conn.pn_next[quic.APPLICATION] = 65530
        txns = seeded_txns(b"pn-wrap", 12, 215)
        drive(stage, sender, wire, txns)
        assert out.frames == txns
        assert sender.conn.pn_next[quic.APPLICATION] > 65536
        assert stage.metrics.get("bad_packet") == 0
    finally:
        stage.close()
        sender.close()


@pytest.mark.parametrize("mets, ratios", [
    # one datagram a crossing, an ACK each: the tile is not loaded
    ({"dgram_rx": 100, "ack_tx": 97, "nsweep_crossings": 100},
     {"dgram/crossing": 1.0, "ack/dgram": 0.97}),
    # a full socket each time it looks: it amortises
    ({"dgram_rx": 6400, "ack_tx": 400, "nsweep_crossings": 100},
     {"dgram/crossing": 64.0, "ack/dgram": 0.062}),
    # the Python lane: no crossings into C to divide by
    ({"dgram_rx": 50, "ack_tx": 50}, {"ack/dgram": 1.0}),
    # nothing received yet: no ratio
    ({"dgram_rx": 0, "ack_tx": 0, "nsweep_crossings": 0}, {}),
])
def test_the_front_row_ends_with_the_two_ratios(mets, ratios):
    from firedancer_tpu.utils import metrics as fm

    row = fm.front_row(dict(mets, reasm_published=1))
    assert {k: v for k, v in row.items() if "/" in k} == ratios
    assert "nsweep_crossings" not in row and row["ack_tx"] == mets["ack_tx"]
    # a sender tile's row has none
    assert fm.front_row({"txn_tx": 5, "dgram_rx": 9}) == {
        "dgram_rx": 9, "txn_tx": 5}
    assert "ack_tx" in fm.FRONT_COUNTERS
    # where operators look: the monitor's `front` line
    from firedancer_tpu.runtime import monitor as mon

    text = mon.MonitorSession.render(
        [{"stage": "quic", "signal": 1, "heartbeat_age_ms": 1.0, "in": 0,
          "out": 0, "overrun": 0, "backpressure": 0, "iters": 1,
          "front": row}], None, 1.0)
    line = next(ln for ln in text.splitlines() if ln.startswith("quic: front"))
    assert f"ack_tx={mets['ack_tx']:,}" in line
    for k, v in ratios.items():
        assert f"{k}={v:,}" in line


def test_plain_reference_imports_nothing_of_the_front():
    src = open(quic_plain.__file__).read()
    for name in ("waltz", "runtime", "fd_net", "net_native"):
        assert f"import {name}" not in src and f"from {name}" not in src \
            and f"firedancer_tpu.{name}" not in src \
            and f"firedancer_tpu import {name}" not in src, name
    twin = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "harness", "quic_reference.py")
    assert open(twin).read() == src


# -- the process topology at toy size -----------------------------------------

import signal  # noqa: E402

from firedancer_tpu.models import leader_topo as lt  # noqa: E402
from firedancer_tpu.runtime import topo as ft  # noqa: E402
from firedancer_tpu.tango import shm  # noqa: E402
from firedancer_tpu.utils.config import ConfigError, load_config  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
needs_rings = pytest.mark.skipif(
    not shm.native_ring_enabled(), reason="the native ring lane is off")
N_TOPO = 160
SENDERS = ["benchs0", "benchs1"]


def _front_cfg(**quic):
    return load_config(None, overrides={
        "layout": {"benchs_stage_count": 2},
        "verify": {"batch": 16, "max_msg_len": 256,
                   "receive_buffer_depth": 64},
        "links": {"verify_pack": 64},
        "quic": dict({"stream_window": 8}, **quic)})


def _launch_front(held=("verify0", "out"), **quic):
    topo = lt.build_quic_topology_from_config(
        _front_cfg(**quic), n_txns=N_TOPO, pool_size=N_TOPO,
        verify_precomputed=True, capture=True)
    h = ft.launch(topo, held=held)
    try:
        stages = [h.build_held(name) for name in held]
        h.wait_running(120)
    except BaseException:
        h.close()
        raise
    return h, stages


def _drive_front(h, held, until, limit_s: float = 60.0) -> dict:
    t_end = time.monotonic() + limit_s
    while time.monotonic() < t_end:
        for _ in range(200):
            for s in held:
                s.run_once()
        c = h.counters()
        if until(c):
            return c
        assert not h.dead(), h.format_monitor()
    raise AssertionError(f"not reached in {limit_s} s:\n{h.format_monitor()}")


def _release(held) -> None:
    for s in held:
        s.ins, s.outs = [], []
        s.drop_native_views()


def _no_trace_of(h) -> None:
    assert h.left_behind() == []
    assert all(not p.is_alive() for p in h.procs.values())
    assert not [n for n in os.listdir("/dev/shm")
                if n.endswith("_" + h.uid) or f"_{h.uid}_" in n]
    assert not os.path.exists(lt.quic_dir(h))


@needs_rings
def test_the_front_topology_lands_every_transaction_once():
    """benchg -> benchs x 2 -> loopback UDP/QUIC -> quic -> verify
    (all-pass) -> out, a process a tile but verify and out: every
    transaction out once, the front's counters add up across
    processes, the captures hold to the plain reference, every tile's
    lanes armed, rows for the monitor and slotreport, nothing left."""
    from firedancer_tpu.runtime.benchg import gen_transfer_pool
    from firedancer_tpu.utils import metrics as fm

    h, held = _launch_front()
    try:
        assert sorted(h.procs) == ["benchg", "benchs0", "benchs1", "quic"]
        _drive_front(h, held, lambda c: c["out"]["frags_in"] == N_TOPO
                     and sum(c[s]["streams_acked"] for s in SENDERS)
                     == N_TOPO)
        c = h.counters()
        q = c["quic"]
        assert sum(c[s]["txn_tx"] for s in SENDERS) == N_TOPO
        assert c["benchs0"]["txn_tx"] == c["benchs1"]["txn_tx"]  # seq % 2
        assert q["reasm_published"] == q["txn_rx"] == N_TOPO
        assert q["handshakes_done"] == q["conn_active"] == 2
        assert q["reasm_evicted"] == q["reasm_oversz"] == 0
        assert q["reasm_multi_chunk"] == 0         # 215 bytes: one chunk
        assert q["dgram_rx"] >= N_TOPO and q["dgram_rx_bytes"] > 215 * N_TOPO
        assert q["streams_granted"] >= N_TOPO - 2 * 8     # two at a time
        assert c["verify0"]["frags_in"] == N_TOPO
        for name, k in c.items():
            assert k["native_lanes"] > 0 and k["native_lanes_off"] == 0, name
        if net_native.available():
            assert q["native_lanes"] == 2           # rings, net
            assert q["net_punts"] <= 0.25 * q["dgram_rx"]   # handshakes
            assert q["sweep_crossings"] > 0         # C's words, from shm
        # (whether credit ever HELD a sender here depends on who is
        # faster, the generator or the tile's sweep: the full-ring test
        # below holds them by construction)
        assert all(c[s]["send_blocked_credit"] >= 0 for s in SENDERS)
        # what the tile published, off the senders' own captures
        pool = gen_transfer_pool(N_TOPO, n_payers=8)
        got = []
        for s in SENDERS:
            keys, dgs = quic_plain.read_capture(f"{lt.quic_dir(h)}/{s}")
            got += quic_plain.reassemble(keys, dgs).out
        assert sorted(got) == sorted(pool)
        rows = {s: fm.front_row(k) for s, k in c.items()}
        assert rows["quic"]["reasm_published"] == N_TOPO
        assert rows["benchs0"]["txn_tx"] > 0 and rows["verify0"] is None
        # the two ratios an operator sizes quic tiles by: C's crossings
        # came over shm, the ACK-only datagrams are at most one a datagram
        assert 0 < q["ack_tx"] <= q["dgram_rx"]
        assert rows["quic"]["ack/dgram"] == round(
            q["ack_tx"] / q["dgram_rx"], 3)
        assert "dgram/crossing" not in rows["benchs0"]
        if net_native.available():
            assert rows["quic"]["dgram/crossing"] >= 1.0
        table = h.format_monitor()
        assert all(n in table for n in ["quic"] + SENDERS)
    finally:
        _release(held)
        h.halt()
        h.close()
    _no_trace_of(h)


@needs_rings
def test_a_full_verify_ring_holds_the_senders_and_drops_nothing():
    """Nobody runs verify: its ring (64 deep) fills, the quic tile
    holds what it reassembled, the senders run out of stream credit,
    the generator sees ring backpressure; probe timeouts fire while
    nothing is acknowledged, and their late copies are swallowed.  Then
    verify runs: every transaction comes out once, both drop counters
    read 0."""
    h, held = _launch_front()
    verify, out = held
    try:
        def stuck(c):
            q = c["quic"]
            # (what the tile had reassembled when the ring filled it
            # holds; what came after waits unread in its socket)
            # (credit returns two at a time: a connection may be owed one)
            return (q["frags_out"] == 64
                    and sum(c[s]["txn_tx"] for s in SENDERS) >= 64 + 2 * 7
                    and all(c[s]["send_blocked_credit"] > 0
                            for s in SENDERS))

        c = _drive_front(h, [], stuck)
        time.sleep(0.5)                 # well past a probe timeout
        c = h.counters()
        assert c["quic"]["frags_out"] == 64
        assert c["quic"]["txn_held_for_credit"] == c["quic"]["txn_held"]
        # 64 on the ring, at most two windows behind them
        sent = sum(c[s]["txn_tx"] for s in SENDERS)
        assert 64 + 2 * 7 <= sent <= 64 + 2 * 8
        assert c["quic"]["loop_backp_ns"] > 0       # the ledger says why
        c = _drive_front(h, held, lambda c: c["out"]["frags_in"] == N_TOPO)
        c = h.counters()
        q = c["quic"]
        assert q["reasm_evicted"] == q["reasm_oversz"] == 0
        assert q["txn_rx"] == N_TOPO and q["txn_held"] == 0
        assert c["verify0"]["frags_in"] == N_TOPO   # once each
        assert sum(c[s]["streams_acked"] for s in SENDERS) == N_TOPO
    finally:
        _release(held)
        h.halt()
        h.close()
    _no_trace_of(h)


@needs_rings
def test_a_sigkilled_quic_tile_is_named_and_its_socket_reclaimed():
    h, held = _launch_front()
    try:
        _drive_front(h, held, lambda c: c["out"]["frags_in"] > 0)
        import json

        with open(lt.quic_addr_file(lt.quic_dir(h))) as f:
            port = json.load(f)["port"]
        os.kill(h.procs["quic"].pid, signal.SIGKILL)
        h.procs["quic"].join(10)
        assert h.dead() == ["quic"]
        c = h.counters()                # the dead tile: as last flushed
        assert c["quic"]["dgram_rx"] > 0
        import socket

        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.bind(("127.0.0.1", port))     # the kernel took the socket back
        s.close()
    finally:
        _release(held)
        h.close()
    _no_trace_of(h)


def test_the_deployments_config_round_trips_and_builds():
    cfg = load_config(os.path.join(ROOT, "config", "verify-quic-v5e.toml"))
    assert (cfg.layout.benchs_stage_count, cfg.layout.verify_stage_count) \
        == (4, 1)
    assert (cfg.verify.batch, cfg.verify.max_msg_len,
            cfg.verify.batch_deadline_ms,
            cfg.verify.receive_buffer_depth) == (1024, 1232, 2.0, 1024)
    q = cfg.quic
    assert (q.reasm_depth, q.max_conns, q.retry, q.stream_window,
            q.max_datagram) == (64, 64, False, 64, 1200)
    assert (cfg.net.listen_host, cfg.net.listen_port) == ("127.0.0.1", 0)
    # the benchmark's configuration is the same deployment
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "verify-quic-v5e.json")) as f:
        bench = load_config(None, overrides=json.load(f)["program_config"])
    assert bench == cfg
    topo = lt.build_quic_topology_from_config(cfg)
    topo.validate()
    assert [s.name for s in topo.stages] == [
        "benchg", "benchs0", "benchs1", "benchs2", "benchs3", "quic",
        "verify0", "out"]
    gb = next(ln for ln in topo.links if ln.name == "gb")
    assert (gb.n_consumers, gb.depth, gb.mtu) == (4, 1024, 1232)


@pytest.mark.parametrize("overrides, named", [
    ({"layout": {"benchs_stage_count": 0}}, "benchs_stage_count"),
    ({"layout": {"benchs_stage_count": 2, "verify_stage_count": 2}},
     "verify_stage_count"),
    ({"layout": {"benchs_stage_count": 2},
      "verify": {"batch": 16, "devices": 2}}, "verify.devices"),
])
def test_the_builder_refuses_by_name_what_it_cannot_build(overrides, named):
    with pytest.raises(ValueError, match=named):
        lt.build_quic_topology_from_config(
            load_config(None, overrides=overrides))


@pytest.mark.parametrize("overrides, named", [
    ({"quic": {"stream_window": 0}}, "quic.stream_window"),
    ({"quic": {"max_datagram": 100}}, "quic.max_datagram"),
    ({"quic": {"max_conns": 1}, "layout": {"benchs_stage_count": 2}},
     "quic.max_conns"),
    ({"quic": {"bogus": 1}}, "quic.bogus"),
    ({"layout": {"benchs_stage_count": -1}}, "benchs_stage_count"),
])
def test_the_quic_section_is_validated(overrides, named):
    with pytest.raises(ConfigError, match=named):
        load_config(None, overrides=overrides)
