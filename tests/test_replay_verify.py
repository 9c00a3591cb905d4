"""The replay verify stage (runtime/replay_verify.py, the replay intake
of native/fd_verify.cpp) held to the plain reference
(ops/ref/replay_verify_plain.py): entry batches in, entry batches and
verdicts out, a slot dead from its first failing entry batch on.

Everything runs on the CPU at device batch 16 and 128.  The program's
arithmetic is conftest's toy (a lane passes iff a sum of its bytes is
even), which compiles in no time; the blocks are made so that the toy
and OpenSSL agree on every transaction in them — every valid
transaction's lanes are even, and a corruption flips bit 0 of a
signature's first byte, which makes the lane odd and the signature
invalid — so the reference's verdicts are OpenSSL's own, and the stage
around the arithmetic is the real thing: the packed rows, the fit rule,
the window, the one dispatch call, the reap.
"""

from __future__ import annotations

import contextlib
import hashlib

import pytest
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
)
from cryptography.hazmat.primitives.serialization import (
    Encoding, PublicFormat,
)

from firedancer_tpu.ops.ref import replay_verify_plain as plain
from firedancer_tpu.runtime import replay_verify as rr
from firedancer_tpu.runtime import verify_native as vn
from firedancer_tpu.runtime.poh_stage import build_entry
from firedancer_tpu.tango import shm

from conftest import toy_lane_ok

LANES = ["native", "python"]
BATCHES = [16, 128]
SHAPE = dict(txns_per_entry=5, entries_per_batch=2, ticks_per_slot=4,
             hashes_per_tick=8)
SYSTEM = bytes(32)


# -- blocks on which the toy and OpenSSL agree ---------------------------------


def _signers(n: int):
    out = []
    for k in range(n):
        key = Ed25519PrivateKey.from_private_bytes(
            hashlib.sha256(b"replay-test%d" % k).digest())
        out.append((key, key.public_key().public_bytes(
            Encoding.Raw, PublicFormat.Raw)))
    return out


SIGNERS = _signers(24)


def _toy_even(t: bytes) -> bool:
    sigs, pks, msg = plain.split_txn(t)
    return all(toy_lane_ok(len(msg), msg[0], s[0], s[63], pk[0], pk[31])
               for s, pk in zip(sigs, pks))


_made: dict = {}


def txn(i: int, k: int = 1) -> bytes:
    """The i-th valid k-signer transfer every lane of which the toy
    passes: a legacy transfer from signer 0 to a destination, its
    lamports searched until every signature's lane is even."""
    if (i, k) in _made:
        return _made[i, k]
    who = [SIGNERS[(i + j) % len(SIGNERS)] for j in range(k)]
    dest = hashlib.sha256(b"dest%d" % i).digest()
    for nonce in range(1 << 20):
        msg = (bytes([k, 0, 1, k + 2]) + b"".join(pk for _, pk in who)
               + dest + SYSTEM + hashlib.sha256(b"bh").digest()
               + bytes([1, k + 1, 2, 0, k, 12]) + (2).to_bytes(4, "little")
               + ((i << 24) + nonce).to_bytes(8, "little"))
        t = bytes([k]) + b"".join(key.sign(msg) for key, _ in who) + msg
        if _toy_even(t):
            _made[i, k] = t
            return t
    raise AssertionError("no even transfer found")


def corrupt(t: bytes, sig_i: int = 0) -> bytes:
    """Bit 0 of the first byte of signature `sig_i` flipped: its lane
    odd to the toy, the signature invalid to OpenSSL."""
    b = bytearray(t)
    b[1 + 64 * sig_i] ^= 1
    return bytes(b)


def slot_frames(slot: int, txns: list[bytes], **shape) -> list[bytes]:
    seed = hashlib.sha256(b"seed%d" % slot).digest()
    return rr.build_slot_frames(slot, seed, txns, **dict(SHAPE, **shape))


def txns_of(n: int, start: int = 0, k: int = 1) -> list[bytes]:
    return [txn(start + i, k) for i in range(n)]


# -- the stage over real rings -------------------------------------------------


@contextlib.contextmanager
def tile(lane: str, batch: int, **stage_kw):
    if lane == "native" and not vn.available():
        pytest.skip("native verify client unavailable")
    uid = shm.fresh_uid()
    lin = shm.ShmLink.create(f"trv_i_{uid}", depth=64, mtu=65536, n_fseq=1)
    lout = shm.ShmLink.create(f"trv_o_{uid}", depth=256, mtu=65536, n_fseq=1)
    st = None
    try:
        kw = dict(batch=batch, max_msg_len=512, batch_deadline_s=0.001,
                  native_client=lane == "native")
        kw.update(stage_kw)
        st = rr.ReplayVerifyStage(
            "verify0", ins=[shm.make_consumer(lin, lazy=8)],
            outs=[shm.make_producer(lout)], **kw)
        assert (st._sweep_client is not None) == (lane == "native")
        yield st, shm.make_producer(lin), shm.make_consumer(lout, lazy=4)
    finally:
        if st is not None:
            st.ins, st.outs = [], []
            st.drop_native_views()
        lin.close()
        lout.close()


def drain(cons) -> list[tuple[int, bytes]]:
    out = []
    while True:
        res = cons.poll()
        if res in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
            return out
        meta, payload = res
        out.append((int(meta[1]), bytes(payload)))


def run(st, prod, cons, frames, *, flush: bool = True, loops: int = 200000):
    """Offer `frames` as the ring takes them, run the stage until what
    it holds has left.  -> [(sig, payload)] in the order they came."""
    fed = 0
    out = []
    idle = 0
    for _ in range(loops):
        while fed < len(frames):
            slot, idx, _ = rr.HDR.unpack_from(frames[fed].ljust(16, b"\0"))
            if not prod.try_publish(frames[fed], sig=rr.frag_sig(
                    slot & 0x7FFFFFFF, idx & 0xFFFFFFFF), tsorig=1):
                break
            fed += 1
        st.run_once()
        got = drain(cons)
        out += got
        idle = 0 if got or fed < len(frames) else idle + 1
        if fed == len(frames) and idle > 200 and not st.held() \
                and not st._flying():
            break
    if flush:
        st.flush()
        out += drain(cons)
    return out


def check(st, out, frames, max_msg_len=512):
    """The stage's frames and counters against the plain reference's."""
    ref = plain.replay(frames, max_msg_len=max_msg_len)
    assert [p for _, p in out] == ref.out
    for sig, p in out:
        v = rr.parse_verdict(p)
        slot, idx, _ = rr.HDR.unpack_from(p)
        assert sig == rr.frag_sig(slot, idx, v is not None)
    st.during_housekeeping()
    c = st.metrics.counters
    done = [s for s in ref.slots if s.verdict != "open"]
    assert c["slots_live"] == sum(s.verdict == "live" for s in done)
    for why in ("sig", "poh", "parse"):
        assert c[f"slots_dead_{why}"] == sum(s.reason == why for s in done)
    assert c["entry_batches_in"] == len(frames)
    assert c["entry_batches_out"] == sum(len(s.left) for s in ref.slots)
    assert c["entry_txn_out"] == sum(s.txn_left for s in ref.slots)
    assert c["entry_txn_rejected"] == sum(s.txn_rejected for s in ref.slots)
    assert c["dead_slot_txn_skipped"] == sum(s.txn_skipped
                                             for s in ref.slots)
    # every lane is a signature of what left or of what a signature
    # killed a slot at, or a lane spent on a dead slot
    served = sum(s.sigs_left + (s.sigs_rejected if s.reason == "sig" else 0)
                 for s in ref.slots)
    assert c["elems_in"] == served + c["dead_slot_lanes_spent"]
    assert c["verify_fail"] == c["slots_dead_sig"]
    assert c["batch_close_full"] + c["batch_close_deadline"] \
        + c["batch_close_window"] == c["batches"]
    assert c.get("dedup_dup", 0) == 0
    return ref


def through(lane, batch, frames, **kw):
    with tile(lane, batch, **kw) as (st, prod, cons):
        out = run(st, prod, cons, frames)
        return check(st, out, frames), out, dict(st.metrics.counters)


# -- cases ----------------------------------------------------------------------

GRID = [(lane, b) for lane in LANES for b in BATCHES]


@pytest.mark.parametrize("lane,batch", GRID)
def test_live_slots_leave_whole_in_block_order(lane, batch, toy_verify_ok):
    frames = []
    for s in range(3):
        frames += slot_frames(s, txns_of(43, 50 * s))
    ref, out, c = through(lane, batch, frames)
    assert [s.verdict for s in ref.slots] == ["live"] * 3
    assert c["entry_txn_out"] == 129 == c["txn_in"]
    assert c["poh_hashes"] == 3 * (9 + 4 * 8)
    assert c["entries_in"] == 3 * 13


@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("lane,batch", GRID)
def test_a_bad_signature_kills_the_slot_at_its_entry_batch(
        lane, batch, where, toy_verify_ok):
    txns = txns_of(60)
    n_batches = len(slot_frames(1, txns))
    at = {"first": 0, "middle": n_batches // 2, "last": n_batches - 2}[where]
    # the transaction's entry batch: 10 transactions a batch but for
    # the ticks, so find it by looking
    for k in range(len(txns)):
        bad = txns[:k] + [corrupt(txns[k])] + txns[k + 1:]
        frames = slot_frames(1, bad)
        if any(bad[k] in f and j == at for j, f in enumerate(frames)):
            break
    frames = slot_frames(0, txns_of(12, 100)) + frames \
        + slot_frames(2, txns_of(12, 200))
    ref, out, c = through(lane, batch, frames)
    assert [(s.verdict, s.reason, s.at) for s in ref.slots] == [
        ("live", None, None), ("dead", "sig", at), ("live", None, None)]
    assert c["dead_slot_txn_skipped"] > 0 or where == "last"


@pytest.mark.parametrize("sig_i", range(8))
@pytest.mark.parametrize("lane,batch", GRID)
def test_any_signature_of_a_transaction_across_the_fit_rule(
        lane, batch, sig_i, toy_verify_ok):
    """An 8-signer transaction that does not fit what is left of the
    open device batch seals it short (the fit rule), lands whole in the
    next, and fails whole by any one of its signatures."""
    lead = txns_of(batch - 4)       # leaves 4 lanes: 8 do not fit
    frames = slot_frames(
        0, lead + [corrupt(txn(900, 8), sig_i)] + txns_of(6, 950),
        txns_per_entry=batch)
    ref, out, c = through(lane, batch, frames, batch_deadline_s=10.0)
    assert [(s.verdict, s.reason) for s in ref.slots] == [("dead", "sig")]
    assert c["batch_fit_pad_lanes"] == 4
    assert c["verify_fail_elems"] == 8


@pytest.mark.parametrize("how", ["hash", "num_hashes_0", "parse",
                                 "out_of_sequence"])
@pytest.mark.parametrize("lane,batch", GRID)
def test_a_slot_dies_at_the_door(lane, batch, how, toy_verify_ok):
    """By an entry whose hash does not follow, by a transaction entry
    with num_hashes 0, by a transaction that does not parse, by a frag
    that is not the one that follows: nothing of the entry batch goes
    to the device."""
    txns = txns_of(30)
    frames = slot_frames(5, txns)
    f = frames[2]
    body_at = rr.HDR.size
    e0 = body_at + 4
    if how == "hash":
        f = f[:e0 + 10] + bytes([f[e0 + 10] ^ 0x40]) + f[e0 + 11:]
    elif how == "num_hashes_0":
        f = f[:e0] + bytes(4) + f[e0 + 4:]
    elif how == "parse":
        # the first transaction's signature count says 3: it is short
        t0 = e0 + 38 + 2
        f = f[:t0] + b"\x03" + f[t0 + 1:]
    else:
        f = frames[3]
    frames = slot_frames(4, txns_of(8, 300)) + frames[:2] + [f] \
        + frames[3:] + slot_frames(6, txns_of(8, 400))
    ref, out, c = through(lane, batch, frames)
    want = "poh" if how in ("hash", "num_hashes_0") else "parse"
    assert [(s.verdict, s.reason, s.at) for s in ref.slots] == [
        ("live", None, None), ("dead", want, 2), ("live", None, None)]
    # what died at the door never took a lane
    assert c["dead_slot_lanes_spent"] == 0
    assert c["txn_in"] == c["entry_txn_out"]


@pytest.mark.parametrize("lane,batch", GRID)
def test_a_repeated_transaction_is_verified_and_passed(lane, batch,
                                                       toy_verify_ok):
    """No tag cache on this path: the same transaction twice in an
    entry, again in the next entry batch and again in the next slot
    takes a lane each time and leaves each time."""
    t = txn(7)
    a = [t, t] + txns_of(8, 20) + [t] + txns_of(9, 40)
    frames = slot_frames(0, a) + slot_frames(1, [t] + txns_of(5, 60))
    ref, out, c = through(lane, batch, frames)
    assert [s.verdict for s in ref.slots] == ["live", "live"]
    assert c["txn_in"] == len(a) + 6 == c["entry_txn_out"]


@pytest.mark.parametrize("lane,batch", GRID)
def test_two_batches_in_flight_and_the_failing_one_reaped_second(
        lane, batch, toy_verify_ok):
    """Nothing is reaped until two device batches are in flight, the
    bad signature in the second: the entry batches the first completes
    leave first, then the rejection, in block order."""
    n = 2 * batch
    txns = txns_of(n)
    txns[batch + batch // 2] = corrupt(txns[batch + batch // 2])
    frames = slot_frames(0, txns) + slot_frames(1, txns_of(6, 500))
    with tile(lane, batch, batch_deadline_s=10.0) as (st, prod, cons):
        ready = st._mask_ready
        seen = []

        def hold(result):
            seen.append(len(st._flying()))
            return max(seen) >= 2 and ready(result)

        st._mask_ready = hold
        out = run(st, prod, cons, frames)
        assert max(seen) == 2
        ref = check(st, out, frames)
    (s0, s1) = ref.slots
    assert (s0.verdict, s0.reason) == ("dead", "sig") and s1.verdict == "live"
    # the second device batch's first lane is transaction `batch`
    assert s0.at >= batch // 10


@pytest.mark.parametrize("lane,batch", GRID)
def test_a_slots_tail_leaves_when_the_input_runs_dry(lane, batch,
                                                     toy_verify_ok):
    """A slot whose last entry batches have not come: what has come
    leaves on the deadline (no flush), and there is no verdict yet."""
    frames = slot_frames(0, txns_of(37))[:-2]
    with tile(lane, batch) as (st, prod, cons):
        out = run(st, prod, cons, frames, flush=False)
        ref = check(st, out, frames)
        assert [s.verdict for s in ref.slots] == ["open"]
        assert len(out) == len(frames) and not st.held()


@pytest.mark.parametrize("batch", BATCHES)
def test_the_native_intake_and_the_python_lane_give_the_same_frames(
        batch, toy_verify_ok):
    txns = txns_of(70)
    txns[33] = corrupt(txns[33])
    frames = slot_frames(0, txns_of(25, 100) + [txn(800, 3), txn(801, 8)]) \
        + slot_frames(1, txns) + slot_frames(2, txns_of(11, 200))
    got = {}
    for lane in LANES:
        _, out, c = through(lane, batch, frames)
        got[lane] = (out, {k: c[k] for k in (
            "entry_batches_in", "entries_in", "txn_in", "elems_in",
            "slots_live", "slots_dead_sig", "poh_hashes",
            "entry_batches_out", "entry_txn_out", "entry_txn_rejected",
            "verify_fail", "verify_fail_elems", "txn_verified")})
    assert got["native"][0] == got["python"][0]
    # what went to the device before the slot was known dead is timing
    assert got["native"][1] == got["python"][1] or \
        {k for k in got["native"][1]
         if got["native"][1][k] != got["python"][1][k]} <= {"txn_in",
                                                            "elems_in"}


def test_frames_round_trip():
    f = rr.frame(7, 0, b"xyz", last=True, seed=bytes(range(32)))
    assert rr.unframe(f) == (7, 0, rr.F_LAST | rr.F_SEED, bytes(range(32)),
                             b"xyz")
    assert rr.parse_verdict(f) is None
    v = rr.verdict_frame(7, 3, rr.DEAD_POH)
    assert rr.parse_verdict(v) == (7, 3, "poh")
    assert rr.frag_sig(7, 3, True) == (1 << 63) | (7 << 32) | 3
    e = build_entry(64, bytes(32), [])
    assert rr.ReplayVerifyStage._claimed_txns(
        len(e).to_bytes(4, "little") + e) == 0
