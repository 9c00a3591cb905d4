"""Differential suite: the native net lane vs the Python lane (ISSUE 18).

Every test drives the SAME client traffic into two QuicIngressStages —
one with the native fast path armed, one pinned to the Python lane via
FDTPU_NATIVE_NET=0 — and diffs the published txn streams byte-for-byte.
The PUNT boundary (handshakes, stateless resets, control frames) and the
credit-gated no-loss/no-reorder contract get their own tests, plus a
seeded AES-GCM fuzz parity pass against ops/aes.py (incl. tag rejects).

The module skips entirely when the .so cannot build or the lane is
disabled (FDTPU_NATIVE_NET=0): differential claims need both lanes.
"""

from __future__ import annotations

import hashlib
import os

import pytest

from firedancer_tpu.runtime import net_native

pytestmark = pytest.mark.skipif(
    not net_native.available(),
    reason="fd_net.so unavailable or FDTPU_NATIVE_NET=0",
)

IDENTITY = hashlib.sha256(b"net-native-diff").digest()


class _Collector:
    """Producer stub: records every published frame; optional credit
    budget for the backpressure tests (None = unlimited)."""

    def __init__(self, credits=None):
        self.frames = []  # (payload, sig)
        self.credits = credits

    def try_publish(self, payload, sig=0, tsorig=0):
        if self.credits is not None:
            if self.credits <= 0:
                return False
            self.credits -= 1
        self.frames.append((bytes(payload), sig))
        return True

    def payloads(self):
        return [p for p, _ in self.frames]


def _make_stage(native: bool, monkeypatch, **kw):
    from firedancer_tpu.chaos.population import ChaosSock
    from firedancer_tpu.runtime.net import QuicIngressStage

    monkeypatch.setenv("FDTPU_NATIVE_NET", "1" if native else "0")
    st = QuicIngressStage(
        "quic", outs=[kw.pop("out", None) or _Collector()],
        sock=ChaosSock(), rx_burst=8, identity_secret=IDENTITY, **kw)
    assert (st._net_client is not None) == native
    return st


class _Driver:
    """In-process QUIC client against a ChaosSock'd stage: datagrams are
    injected straight into _on_datagram (on the native lane: staged into
    the receive arena and swept, a sweep of one), responses read back
    off the virtual socket — the chaos population's wire, without loss."""

    def __init__(self, stage, addr, *, mangle=None):
        from firedancer_tpu.ops.ref import ed25519_ref as ref
        from firedancer_tpu.waltz import quic

        self.stage = stage
        self.addr = addr
        self.mangle = mangle  # fn(datagram) -> datagram(s) to inject
        self.conn = quic.Connection.client_new(
            expected_peer=ref.public_key(IDENTITY))
        self.next_sid = 2
        self.pump()
        assert self.conn.established

    def _inject(self, dg: bytes) -> None:
        dgs = [dg] if self.mangle is None else self.mangle(dg)
        for d in dgs:
            self.stage._on_datagram(d, self.addr)

    def pump(self, rounds: int = 40) -> None:
        for _ in range(rounds):
            moved = False
            for dg in self.conn.flush():
                moved = True
                self._inject(dg)
            q = self.stage.sock.tx.get(self.addr)
            while q:
                moved = True
                self.conn.receive(q.popleft())
            if not moved:
                return

    def send_txn(self, txn: bytes) -> None:
        sid = self.next_sid
        self.next_sid += 4
        self.conn.send_stream(sid, txn, fin=True)
        self.pump()


def _txn_set(seed: bytes, sizes=(1, 96, 512, 900, 1232)) -> list[bytes]:
    out = []
    for i, n in enumerate(sizes):
        h = hashlib.sha256(seed + bytes([i]))
        buf = b""
        while len(buf) < n:
            h = hashlib.sha256(h.digest() + seed)
            buf += h.digest()
        out.append(buf[:n])
    return out


def _run_both(monkeypatch, drive, **stage_kw):
    """drive(stage, collector) on a native and a Python-lane stage;
    returns both collectors."""
    outs = []
    for native in (True, False):
        out = _Collector()
        st = _make_stage(native, monkeypatch, out=out, **stage_kw)
        drive(st, out)
        st.close()
        outs.append(out)
    return outs


# -- stream diffs -------------------------------------------------------------


def test_honest_streams_byte_identical(monkeypatch):
    txns = _txn_set(b"honest")

    def drive(st, out):
        d = _Driver(st, ("c", 1))
        for t in txns:
            d.send_txn(t)
        st.after_credit()

    on, off = _run_both(monkeypatch, drive)
    assert on.payloads() == txns
    assert on.frames == off.frames  # payloads AND sig sequence


def test_garbled_datagrams_rejected_identically(monkeypatch):
    """Every steady-state datagram is duplicated with one flipped
    ciphertext byte: the mangled twin must fail auth on both lanes
    while the honest stream stays byte-identical."""
    txns = _txn_set(b"garble", sizes=(64, 700, 1232))
    stats = []

    def drive(st, out):
        def mangle(dg):
            if dg[0] & 0x80:
                return [dg]  # leave the handshake alone
            bad = bytearray(dg)
            bad[-1] ^= 0x5A
            return [bytes(bad), dg]

        d = _Driver(st, ("c", 1), mangle=mangle)
        for t in txns:
            d.send_txn(t)
        st.after_credit()
        stats.append(st.metrics.get("bad_packet"))

    on, off = _run_both(monkeypatch, drive)
    assert on.payloads() == txns
    assert on.frames == off.frames
    assert stats[0] == stats[1] > 0
    # and the native lane's verdicts were its own, not punts
    assert stats[0] >= 1


def test_duplicate_datagrams_deliver_once(monkeypatch):
    txns = _txn_set(b"dup", sizes=(96, 1100))

    def drive(st, out):
        d = _Driver(st, ("c", 1), mangle=lambda dg: [dg, dg])
        for t in txns:
            d.send_txn(t)
        st.after_credit()

    on, off = _run_both(monkeypatch, drive)
    assert on.payloads() == txns
    assert on.frames == off.frames


def test_oversize_stream_tombstoned_on_both_lanes(monkeypatch):
    """A stream past TXN_MTU publishes nothing anywhere; honest streams
    around it are unaffected."""
    good = _txn_set(b"oversz-good", sizes=(96, 1232))

    def drive(st, out):
        d = _Driver(st, ("c", 1))
        d.send_txn(good[0])
        sid = d.next_sid
        d.next_sid += 4
        d.conn.send_stream(sid, b"\xAA" * 2000, fin=True)
        d.pump()
        d.send_txn(good[1])
        st.after_credit()

    on, off = _run_both(monkeypatch, drive)
    assert on.payloads() == good
    assert on.frames == off.frames


def test_unknown_cid_stateless_reset_parity(monkeypatch):
    """Short header, unknown address, unknown CID: both lanes answer
    with a stateless reset committing to the SAME token (the datagram's
    random padding differs by design; the token is the commitment)."""
    from firedancer_tpu.waltz import quic

    dg = b"\x40" + b"\x77" * 8 + os.urandom(40)  # >= 43 bytes
    tokens = []

    def drive(st, out):
        st._on_datagram(dg, ("stranger", 9))
        q = st.sock.tx.get(("stranger", 9))
        assert q and len(q) == 1
        reset = q.popleft()
        assert not reset[0] & 0x80
        tokens.append(bytes(reset[-16:]))
        assert st.metrics.get("stateless_reset_tx") == 1

    _run_both(monkeypatch, drive)
    expect = quic.stateless_reset_token(
        hashlib.sha256(b"quic-static:" + IDENTITY).digest(), b"\x77" * 8)
    assert tokens[0] == tokens[1] == expect


# -- PUNT boundary ------------------------------------------------------------


def test_handshake_mid_stream_punts_cleanly(monkeypatch):
    """A second client handshakes (long headers -> PUNT) while the first
    streams through the native fast path; both clients' txns arrive, in
    their own order, identically on both lanes."""
    txns_a = _txn_set(b"mid-a", sizes=(200, 800))
    txns_b = _txn_set(b"mid-b", sizes=(96,))

    def drive(st, out):
        da = _Driver(st, ("a", 1))
        da.send_txn(txns_a[0])
        db = _Driver(st, ("b", 2))  # handshake mid-stream
        da.send_txn(txns_a[1])
        db.send_txn(txns_b[0])
        st.after_credit()

    on, off = _run_both(monkeypatch, drive)
    assert on.payloads() == [txns_a[0], txns_a[1], txns_b[0]]
    assert on.frames == off.frames


def test_control_frame_splice_keeps_conn_coherent(monkeypatch):
    """PATH_CHALLENGE probes (native PUNT) spliced between short-header
    stream datagrams (native consume) on ONE conn: the punted packets'
    pns must land in the native dedup window and the PATH_RESPONSEs must
    come back — the mixed-lane conn stays fully coherent."""
    from firedancer_tpu.waltz import quic

    txns = _txn_set(b"splice", sizes=(96, 600, 1232))

    def drive(st, out):
        d = _Driver(st, ("c", 1))
        for i, t in enumerate(txns):
            probe = d.conn.probe_datagram(
                bytes([quic.FT_PATH_CHALLENGE]) + bytes([i]) * 8)
            assert probe is not None
            st._on_datagram(probe, d.addr)
            d.pump()
            d.send_txn(t)
        st.after_credit()
        d.pump()
        # PATH_RESPONSE echoes arrived back at the client conn
        # (the Python control plane answered the punted frames)
        assert st.metrics.get("pkt_rx") > 0

    on, off = _run_both(monkeypatch, drive)
    assert on.payloads() == txns
    assert on.frames == off.frames


def test_punted_pns_are_deduped_natively(monkeypatch):
    """Replaying a punted control datagram must not double-process it:
    the punt-path pn sync keeps the native window honest."""
    from firedancer_tpu.waltz import quic

    st = _make_stage(True, monkeypatch)
    d = _Driver(st, ("c", 1))
    probe = d.conn.probe_datagram(
        bytes([quic.FT_PATH_CHALLENGE]) + b"\x11" * 8)
    st._on_datagram(probe, d.addr)
    before = st.net_counters()["dup"]
    st._on_datagram(probe, d.addr)  # replay: now short-header + known pn
    assert st.net_counters()["dup"] == before + 1
    st.close()


# -- backpressure: queued, never dropped, never reordered ---------------------


def test_backpressure_native_tail_queued_no_loss_no_reorder(monkeypatch):
    txns = _txn_set(b"bp", sizes=(96, 96, 96, 96, 96, 96))
    out = _Collector(credits=2)
    st = _make_stage(True, monkeypatch, out=out)
    d = _Driver(st, ("c", 1))
    for t in txns:
        d.send_txn(t)
    assert len(out.frames) == 2
    assert st.metrics.get("txn_drop_backpressure") > 0
    assert st.net_counters()["tail_retained"] > 0
    out.credits = None  # lift the gate; after_credit retries the tail
    st.after_credit()
    assert out.payloads() == txns  # nothing lost, nothing reordered
    sigs = [s for _, s in out.frames]
    assert sigs == list(range(1, len(txns) + 1))  # stable across retries
    st.close()


# -- AES-GCM fuzz parity ------------------------------------------------------


def _py_lane_aes(monkeypatch):
    from firedancer_tpu.ops import aes
    monkeypatch.setattr(aes, "_NATIVE", False)
    return aes


def test_aes_gcm_fuzz_parity(monkeypatch):
    """Seeded seal/open fuzz: native vs pure-Python ops/aes.py over both
    key sizes, ragged lengths, and tag-mismatch rejects."""
    aes = _py_lane_aes(monkeypatch)
    rng = hashlib.sha256(b"aes-fuzz")

    def take(n):
        nonlocal rng
        buf = b""
        while len(buf) < n:
            rng = hashlib.sha256(rng.digest())
            buf += rng.digest()
        return buf[:n]

    for trial in range(40):
        klen = 16 if trial % 2 == 0 else 32
        key, iv = take(klen), take(12)
        pt = take(trial * 37 % 1400)
        aad = take(trial * 11 % 64)
        g = aes.AesGcm(key)
        ct, tag = g.seal(iv, pt, aad)
        assert net_native.gcm_seal(key, iv, pt, aad) == (ct, tag)
        assert net_native.gcm_open(key, iv, ct, tag, aad) == pt
        bad = bytes([tag[0] ^ 1]) + tag[1:]
        assert net_native.gcm_open(key, iv, ct, bad, aad) is None
        assert g.open(iv, ct, bad, aad) is None
        if pt:
            bad_ct = bytes([ct[0] ^ 1]) + ct[1:]
            assert net_native.gcm_open(key, iv, bad_ct, tag, aad) is None
        blk = take(16)
        assert net_native.aes_ecb_blocks(key, blk) == \
            aes.Aes(key).encrypt_block(blk)


def test_aes_bad_key_length_rejected():
    with pytest.raises(ValueError):
        net_native.aes_ecb_blocks(b"short", b"\x00" * 16)
    with pytest.raises(ValueError):
        net_native.gcm_seal(b"\x00" * 24, b"\x00" * 12, b"", b"")


# -- plain-UDP sweep: one recvmmsg crossing vs the scalar fallback ------------


def _sweep_drain(method_name: str, payloads):
    """Bind a fresh loopback socket, blast payloads at it, drain with the
    named sweep entry point (small max_pkts so the multi-sweep resume
    path is exercised); return (txn bytes in order, final counters)."""
    import socket
    import time

    nc = net_native.NetClient(max_conns=1, reasm_depth=1)
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.bind(("127.0.0.1", 0))
        s.setblocking(False)
        tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            for p in payloads:
                tx.sendto(p, s.getsockname())
        finally:
            tx.close()
        sweep = getattr(nc, method_name)
        txns = []
        deadline = time.monotonic() + 60
        while (int(nc.counters()["udp_pkts"]) < len(payloads)
               and time.monotonic() < deadline):
            sweep(s.fileno(), 3)
            n = nc.out_count()
            txns.extend(nc.out_txn(i) for i in range(n))
            nc.out_pop(n)
        return txns, nc.counters()
    finally:
        s.close()
        nc.close()


def test_udp_sweep_scalar_vs_scatter_byte_identical():
    """The recvmmsg scatter path and the per-datagram recv fallback must
    deliver the same txn stream and counters over the same load — the
    MTU-stride gaps scatter leaves in the arena are layout, not
    protocol."""
    sizes = (1, 17, 200, 1232, 900, 1232, 64)
    payloads = [bytes([i + 1]) * sz for i, sz in enumerate(sizes)]
    payloads.insert(3, b"J" * 1400)  # > MTU: dropped + counted, no row
    sc_txns, sc_cnt = _sweep_drain("udp_sweep", payloads)
    fb_txns, fb_cnt = _sweep_drain("udp_sweep_scalar", payloads)
    assert sc_txns == fb_txns
    assert [len(t) for t in sc_txns] == list(sizes)
    for key in ("udp_pkts", "oversz"):
        assert sc_cnt[key] == fb_cnt[key], key
    assert sc_cnt["oversz"] == 1
    assert sc_cnt["udp_pkts"] == len(payloads)


def test_udp_ingress_scalar_toggle_parity(monkeypatch):
    """FDTPU_NET_SCALAR_RECV=1 pins UdpIngressStage to the scalar sweep;
    both stage configurations publish identical frames and metrics."""
    import time

    from firedancer_tpu.runtime.net import UdpIngressStage, send_txns
    from firedancer_tpu.tango import shm

    pool = [bytes([i + 1]) * sz
            for i, sz in enumerate((8, 300, 1232, 96))]

    def drive(scalar: bool):
        monkeypatch.setenv("FDTPU_NATIVE_NET", "1")
        monkeypatch.setenv("FDTPU_NET_SCALAR_RECV", "1" if scalar else "0")
        uid = f"{os.getpid()}_{int(time.monotonic_ns() % 1_000_000)}"
        link = shm.ShmLink.create(f"fdtpu_sw{int(scalar)}_{uid}",
                                  depth=64, mtu=1232)
        sink = shm.Consumer(link, lazy=8)
        st = UdpIngressStage("net", outs=[shm.Producer(link)], rx_burst=8)
        assert st._net_client is not None
        try:
            send_txns(st.addr, pool + [b"Z" * 1300])  # oversize rides along
            got = []
            deadline = time.monotonic() + 60
            while ((len(got) < len(pool)
                    or st.metrics.get("oversize_drop") < 1)
                   and time.monotonic() < deadline):
                st.run_once()
                res = sink.poll()
                if isinstance(res, tuple):
                    got.append(bytes(res[1]))
            return got, st.metrics.get("oversize_drop")
        finally:
            st.close()
            link.close()
            link.unlink()

    scatter = drive(False)
    scalar = drive(True)
    assert scatter[0] == scalar[0] == pool
    assert scatter[1] == scalar[1] == 1
