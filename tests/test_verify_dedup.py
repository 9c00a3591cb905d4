"""The verify tile under one to eight signatures a transaction with the
dedup tile behind it (BASELINE configs[2]), at a small size on the CPU:
a seeded stream from the benchmark's own shape through VerifyStage ->
DedupStage -> a sink, on every lane, held to the plain reference
(ops/ref/verify_dedup.py) under the toy verdict; the three counters the
deployment is read by; the reference against ed25519_ref and against the
harness's own composition of the pair's rule."""

from __future__ import annotations

import contextlib
import os
import sys
import time

import numpy as np
import pytest

from firedancer_tpu.ops.ref import verify_dedup as ref
from firedancer_tpu.runtime import verify_native as vn
from firedancer_tpu.runtime.dedup import DedupStage, trailer_sig_cnt
from firedancer_tpu.runtime.verify import VERIFY_TCACHE_DEPTH, VerifyStage
from firedancer_tpu.tango import shm
from firedancer_tpu.utils import metrics as fm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
ACCOUNTS = {"n_payers": 64, "n_dests": 16}
SEED = 2**31 + 733
N_ROWS, BATCH, MML, DEDUP_DEPTH = 2400, 64, 384, 256

# native: the C intake and the dedup stage's sweep intake over native
# rings; python: the Python intake (drain table) over native rings;
# python_rings: both stages a frag at a time over the Python rings
LANES = ["native", "python", "python_rings"]
_ENV = {"native": ("1", "1"), "python": ("0", "1"),
        "python_rings": ("0", "0")}


@contextlib.contextmanager
def _bench_on_path():
    sys.path.insert(0, BENCH)
    try:
        yield
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def shape():
    """The benchmark's mixed-sigs shape, with the far repeats brought
    close enough for a stream of a few thousand offers to hold repeats
    both inside and beyond a dedup cache of 256."""
    with _bench_on_path():
        from harness.manifest import load_module

        mod = load_module(os.path.join(BENCH, "shapes", "mixed-sigs.py"),
                          "shape_mixed_sigs_t1")
    mod.FAR = (64, 1024)
    return mod


def _lane_bytes(buf, off: int, k: int, j: int):
    """The bytes the toy verdict reads of signature j of the k-signature
    transaction at `off`, and where its last signature byte lies."""
    msg = off + 1 + 64 * k
    sig = off + 1 + 64 * j
    pk = msg + 4 + 32 * j
    return (int(buf[msg]) + int(buf[sig]) + int(buf[sig + 63])
            + int(buf[pk]) + int(buf[pk + 31])), sig + 63


@pytest.fixture(scope="module")
def stream(shape):
    """-> (pool, order, bad rows -> their bad signature).  2,400 rows, 1
    in 16 corrupted in one signature, a tenth of the offers repeats; the
    last byte of every signature is then set so that the TOY verdict
    (tests/conftest.py: the parity of six bytes and the length) says of
    each signature what the construction says: every one passes but the
    one the shape corrupted."""
    pool = shape.build(SEED, N_ROWS, ACCOUNTS, {})
    clean = pool.buf.copy()
    pool.bad = shape.corrupt(pool, 16, SEED)
    at = np.flatnonzero(pool.buf != clean)
    assert len(at) == len(pool.bad) == N_ROWS // 16
    bad_sig = dict(zip(pool.bad.tolist(),
                       ((at - pool.off[pool.bad] - 1) // 64).tolist()))
    for i in range(pool.n):
        off, k = int(pool.off[i]), int(pool.sigs[i])
        msg_len = int(pool.len[i]) - 1 - 64 * k
        for j in range(k):
            total, last = _lane_bytes(pool.buf, off, k, j)
            if ((total + msg_len) & 1 == 0) == (bad_sig.get(i) == j):
                pool.buf[last] ^= 1
    return pool, shape.order(pool, SEED, {}), bad_sig


def _toy(lane_ok):
    """conftest's toy lane verdict as the reference's per-signature
    verdict function."""
    return lambda sig, pk, msg: bool(lane_ok(len(msg), msg[0], sig[0],
                                             sig[63], pk[0], pk[31]))


@contextlib.contextmanager
def _pair(lane: str, *, deadline_s: float = 3600.0, batch: int = BATCH,
          precomputed_ok: bool = False):
    """generator ring -> VerifyStage -> ring -> DedupStage -> ring, on
    one lane -> (verify, dedup, producer in, consumer out)."""
    if lane == "native" and not vn.available():
        pytest.skip("native verify client unavailable")
    keys = (vn.ENV_SWITCH, "FDTPU_NATIVE_RING")
    prev = [os.environ.get(k) for k in keys]
    for k, v in zip(keys, _ENV[lane]):
        os.environ[k] = v
    uid = shm.fresh_uid()
    links = [shm.ShmLink.create(f"tvd_{n}_{uid}", depth=256, mtu=mtu,
                                n_fseq=1)
             for n, mtu in (("i", 1232), ("m", 4096), ("o", 4096))]
    stages = []
    try:
        prod = shm.make_producer(links[0])
        verify = VerifyStage(
            "verify0", ins=[shm.make_consumer(links[0], lazy=8)],
            outs=[shm.make_producer(links[1])], batch=batch,
            max_msg_len=MML, batch_deadline_s=deadline_s,
            precomputed_ok=precomputed_ok)
        dedup = DedupStage("dedup", ins=[shm.make_consumer(links[1], lazy=8)],
                           outs=[shm.make_producer(links[2])],
                           tcache_depth=DEDUP_DEPTH)
        stages = [verify, dedup]
        assert (verify._sweep_client is not None) == (lane == "native")
        assert (dedup.sweep_frags is not None) \
            == (type(dedup.tcache).__name__ == "NativeTCache")
        yield verify, dedup, prod, shm.make_consumer(links[2], lazy=4)
    finally:
        for k, v in zip(keys, prev):
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        for s in stages:
            s.ins, s.outs = [], []
            s.drop_native_views()
        for link in links:
            link.close()


def _collect(cons, out: list) -> bool:
    got = False
    while True:
        res = cons.poll()
        if res in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
            return got
        frag = bytes(res[1])
        out.append(frag[:int.from_bytes(frag[-2:], "little")])
        got = True


def _drive(lane: str, txns: list[bytes], **kw):
    """The stream through the pair, every batch sealed by filling, for
    want of room, or by the flush at the end.  -> (what came out,
    verify's counters, dedup's)."""
    out: list = []
    with _pair(lane, **kw) as (verify, dedup, prod, cons):
        fed = 0
        t_end = time.monotonic() + 120
        while time.monotonic() < t_end:
            while fed < len(txns) and prod.try_publish(
                    txns[fed], sig=fed, tsorig=1 + fed):
                fed += 1
            moved = bool(verify.run_once()) | bool(dedup.run_once())
            moved |= _collect(cons, out)
            # the flush only once verify has taken the whole stream in
            if fed == len(txns) and not moved \
                    and prod.seq == verify.ins[0].seq:
                verify.flush()
                if not (dedup.run_once() or _collect(cons, out)
                        or verify.run_once()):
                    break
        assert fed == len(txns) and time.monotonic() < t_end
        verify.during_housekeeping()
        return (out, dict(verify.metrics.counters),
                dict(dedup.metrics.counters))


@pytest.mark.parametrize("lane", LANES)
def test_the_pair_equals_the_reference_under_the_toy_verdict(
        lane, stream, toy_verify_ok):
    pool, order, bad_sig = stream
    txns = [pool.row(int(r)) for r in order]
    assert len(txns) >= 2000
    want = ref.run(txns, verdict=_toy(toy_verify_ok), batch=BATCH,
                   max_msg_len=MML, dedup_depth=DEDUP_DEPTH)
    out, v, d = _drive(lane, txns)
    # the same transactions out, in the same order
    assert out == [txns[i] for i in want.out]
    assert v["verify_fail"] == want.verify_fail > 100
    assert v["dedup_dup"] == want.verify_dup > 50
    assert d["dedup_dup"] == want.dedup_dup > 20
    # a repeat beyond dedup's 256 tags came out again
    assert len(out) > len(set(out))
    assert v["txn_verified"] == d["frags_in"] == len(want.out) + want.dedup_dup
    # the three counters the deployment is read by, on every lane
    assert v["batch_fit_pad_lanes"] == want.fit_pad_lanes > 0
    assert v["verify_fail_elems"] == want.verify_fail_elems \
        > want.verify_fail
    assert d["dedup_dup_sigs"] == want.dedup_dup_sigs > want.dedup_dup
    assert v["batch_elems"] == want.lanes
    assert v["batch_close_full"] == want.full_batches
    assert v["batches"] - v["batch_close_full"] <= 1      # the flush's
    assert v["batch_fit_pad_lanes"] <= 7 * v["batch_close_full"]
    if lane == "native":
        assert (v["txn_in"], v["elems_in"]) == (want.txn_in, want.lanes)
    assert v.get("msg_too_long", 0) == v.get("parse_fail", 0) == 0
    # every signature count passed, failed and was dropped late
    ks = {t[0] for t in out}
    assert ks == set(range(1, 9))
    # the toy verdict is the construction's: what failed is what the
    # shape corrupted, once a time it reached verification
    valid = pool.valid
    assert all(valid[order[i]] for i in want.out)
    assert len(set(bad_sig.values())) >= 6             # not only the first


@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("bad", [7, 0, 3])
def test_one_bad_signature_in_eight_fails_the_transaction_whole(
        lane, bad, stream, toy_verify_ok):
    """An 8-signature transaction with only one signature bad (the last,
    the first, one in the middle) never leaves; its 8 lanes count as
    failed lanes; the transactions around it pass."""
    pool, _order, _bad = stream
    eight = [i for i in np.flatnonzero(pool.sigs == 8) if pool.valid[i]][:3]
    txns = [pool.row(int(i)) for i in eight]
    t = bytearray(txns[1])
    t[1 + 64 * bad + 63] ^= 1
    txns[1] = bytes(t)
    verdict = _toy(toy_verify_ok)
    sigs, pks, msg = ref.split(txns[1])
    assert [verdict(s, pk, msg) for s, pk in zip(sigs, pks)] \
        == [j != bad for j in range(8)]
    out, v, d = _drive(lane, txns)
    assert out == [txns[0], txns[2]]
    assert (v["verify_fail"], v["verify_fail_elems"]) == (1, 8)
    assert v["batch_elems"] == 24 and d["frags_in"] == 2
    want = ref.run(txns, verdict=verdict, batch=BATCH, max_msg_len=MML)
    assert want.out == [0, 2] and want.verify_fail_elems == 8


@pytest.mark.parametrize("waited", [True, False])
@pytest.mark.parametrize("lane", LANES)
def test_a_batch_sealed_for_want_of_room_still_counts_as_full(
        lane, waited, stream):
    """Nine 7-signature transactions fill 63 of 64 lanes; the next does
    not fit, so the batch is sealed CLOSE_FULL one lane short, and the
    clause of the close rule that rests on "full" holds for it on the
    evidence of ISSUE 40: when it had to WAIT for its place in the
    window (two were in flight) it says that the thread leads the chip,
    and the batch behind it, past its deadline, is queued behind it
    once there is room; dispatched at once behind a running batch it
    says nothing, and the batch behind it stays open."""
    pool, _order, _bad = stream
    n7 = 18 if waited else 9
    seven = [i for i in np.flatnonzero(pool.sigs == 7)
             if pool.valid[i]][:n7]
    two = [i for i in np.flatnonzero(pool.sigs == 2) if pool.valid[i]][:2]
    assert len(seven) == n7 and len(two) == 2
    with _pair(lane, deadline_s=0.001, precomputed_ok=True) \
            as (verify, dedup, prod, cons):
        reaped: list = []          # the results the test lets come back
        verify._mask_ready = lambda result: any(result is r for r in reaped)
        m = verify.metrics

        def reap_head():
            head = verify._flying()[0]
            reaped.append(head.result if lane != "native" else head[3])
            verify.run_once()

        # a batch of two lanes goes out alone at its deadline first
        assert prod.try_publish(pool.row(int(two[1])), sig=99, tsorig=1)
        for _ in range(5):
            verify.run_once()
        time.sleep(0.005)
        for _ in range(5):
            verify.run_once()
        assert m.get("batches") == 1 and len(verify._flying()) == 1
        # 63 lanes sealed for want of room go out behind it at once;
        # with `waited`, 63 more find the window full and wait sealed
        for fed, i in enumerate(list(seven) + two[:1]):
            assert prod.try_publish(pool.row(int(i)), sig=fed, tsorig=1)
        for _ in range(40):
            verify.run_once()
        assert m.get("batches") == 2 and len(verify._flying()) == 2
        assert m.get("batch_close_full") == 1
        verify.during_housekeeping()
        assert m.get(fm.BATCH_FIT_PAD_LANES) == 1 + waited
        assert m.get(fm.BATCH_QUEUED_BEHIND) == 1
        assert verify._full_waited == waited
        time.sleep(0.005)
        for _ in range(20):
            verify.run_once()
        # past its deadline the two-lane batch is held by the full window
        assert m.get("batches") == 2 and not verify._window_open()
        reap_head()
        if waited:
            # the sealed 63 lanes take the freed place, and at the next
            # reap the two-lane batch goes behind them in the same pass
            assert m.get("batches") == 3 and len(verify._flying()) == 2
            assert m.get("batch_close_full") == 2
            reap_head()
            assert len(verify._flying()) == 2
            assert m.get("batch_close_window") == 1
            assert m.get(fm.BATCH_QUEUED_BEHIND) == 3
            assert not verify._full_waited      # the slack is taken
        else:
            # room behind the 63 lanes, which found their place at once:
            # a thread that trails the chip sends such batches too
            assert len(verify._flying()) == 1 and verify._window_has_room()
            assert m.get("batches") == 2 and not verify._window_open()
            assert m.get(fm.BATCH_QUEUED_BEHIND) == 1
        assert m.get(fm.BATCH_HELD_BACKLOGGED) == 0
        del verify._mask_ready
        out: list = []
        verify.flush()
        assert m.get("batch_elems") == 2 + 63 * (1 + waited) + 2
        for _ in range(20):
            dedup.run_once()
            _collect(cons, out)
        assert len(out) == 1 + n7 + 1


def _one_bad_in_each_position(shape, seed: int) -> list[bytes]:
    """Signed transactions of k = 1..8 signatures: for each k one whose
    signature j is bad, for every j, then a good one; every one a row
    of its own (the tag cache would take a copy for a repeat).  36 bad,
    8 good."""
    pool = shape.build(seed, 800, ACCOUNTS, {})
    txns = []
    for k in range(1, 9):
        rows = np.flatnonzero(pool.sigs == k)[:k + 1]
        assert len(rows) == k + 1
        for j in range(k):
            t = bytearray(pool.row(int(rows[j])))
            t[1 + 64 * j + 40] ^= 0x04
            txns.append(bytes(t))
        txns.append(pool.row(int(rows[k])))
    return txns


def test_the_reference_agrees_with_ed25519_ref_and_with_openssl(shape):
    """Real signatures, k = 1..8, one bad in each position: the plain
    reference under its default verdict (OpenSSL) and under the
    repository's pure-Python ed25519_ref."""
    from firedancer_tpu.ops.ref import ed25519_ref

    txns = _one_bad_in_each_position(shape, SEED + 1)
    by_openssl = ref.run(txns, batch=BATCH, max_msg_len=MML)
    by_ref = ref.run(
        txns, verdict=lambda s, pk, m: ed25519_ref.verify(m, s, pk),
        batch=BATCH, max_msg_len=MML)
    assert by_openssl == by_ref
    assert by_ref.verify_fail == 36 and len(by_ref.out) == 8
    assert [txns[i][0] for i in by_ref.out] == list(range(1, 9))
    assert by_ref.verify_fail_elems == sum(k * k for k in range(1, 9))
    assert by_ref.verify_dup == by_ref.dedup_dup == 0


def test_the_reference_equals_the_harness_composition(shape):
    """`System.due` of the benchmark's verify_dedup topology is composed
    of harness/check.py's `through_verify` and `tcache_keeps`: the two
    say the same of a seeded stream with corrupted rows, near and far
    repeats, and repeats of corrupted rows."""
    with _bench_on_path():
        from harness import check

    pool = shape.build(SEED + 2, 4000, ACCOUNTS, {})
    pool.bad = shape.corrupt(pool, 16, SEED + 2)
    order = shape.order(pool, SEED + 2, {})
    offers = np.bincount(order, minlength=pool.n)
    assert (offers[pool.bad] > 1).any()      # a corrupted row repeats
    txns = [pool.row(int(r)) for r in order]
    passed, fail, dups = check.through_verify(order, pool.valid,
                                              VERIFY_TCACHE_DEPTH)
    keep = check.tcache_keeps(passed, DEDUP_DEPTH)
    # the verdict is the construction's, told by the message, which is
    # a row's own (transfer i moves 1 + i lamports); the test above
    # holds the verdicts themselves
    bad_msgs = {ref.split(pool.row(int(i)))[2] for i in pool.bad}
    got = ref.run(txns, verdict=lambda sig, pk, msg: msg not in bad_msgs,
                  batch=BATCH, max_msg_len=MML, dedup_depth=DEDUP_DEPTH)
    assert [int(order[i]) for i in got.out] == passed[keep].tolist()
    assert got.verify_fail == fail > 0
    assert got.verify_dup == dups > 0
    assert got.dedup_dup == int((~keep).sum()) > 0
    assert got.lanes == int(pool.sigs[order[check.tcache_keeps(
        order, VERIFY_TCACHE_DEPTH)]].sum())


def test_the_reference_imports_nothing_of_the_code_under_test():
    with open(ref.__file__, encoding="utf-8") as f:
        body = f.read().split('"""', 2)[2]
    for word in ("firedancer_tpu", "runtime", "tango", "native", "jax",
                 "numpy"):
        assert word not in body, word


def test_tag_cache_rule_of_the_reference():
    """fd_tcache's rule: a tag among the last `depth` let through is
    dropped, a dropped one is not inserted again, tag 0 never dedups."""
    c = ref.TagCache(2)
    assert [c.seen(t) for t in (5, 6, 5, 7, 5, 6, 0, 0)] \
        == [False, False, True, False, False, False, False, False]


def test_trailer_sig_cnt_reads_the_descriptor_and_survives_short_frags():
    from firedancer_tpu.protocol import txn as ft
    from firedancer_tpu.runtime.benchg import gen_transfer_pool
    from firedancer_tpu.runtime.verify import encode_verified

    payload = gen_transfer_pool(1, n_payers=1, n_dests=1)[0]
    assert trailer_sig_cnt(encode_verified(payload, ft.txn_parse(payload))) \
        == 1
    for frag in (b"", b"\x00", b"\x01\x02\x03", b"flood-00001-" + bytes(80)):
        assert trailer_sig_cnt(frag) in range(256)
    assert trailer_sig_cnt(b"\xff\xff") == 0


def test_the_counters_where_an_operator_looks(stream):
    """batch_fit_pad_lanes and verify_fail_elems beside the close
    counters, dedup_dup_sigs on dedup's line: the registry a scraper
    reads (schema -> Prometheus), the monitor's lines, slotreport."""
    from firedancer_tpu.runtime import monitor as mon
    from firedancer_tpu.runtime import slot_report

    pool, order, _bad = stream
    txns = [pool.row(int(r)) for r in order[:600]]
    with _pair("python", precomputed_ok=True) as (verify, dedup, prod, cons):
        fed, out = 0, []
        for _ in range(4000):
            while fed < len(txns) and prod.try_publish(
                    txns[fed], sig=fed, tsorig=1):
                fed += 1
            verify.run_once()
            dedup.run_once()
            _collect(cons, out)
        verify.flush()
        dedup.run_once()
        regs = {}
        for s in (verify, dedup):
            s.metrics.attach(fm.MetricsRegistry(s.metrics.schema))
            s.metrics.flush()
            regs[s.name] = s.metrics.registry
        pad = verify.metrics.get(fm.BATCH_FIT_PAD_LANES)
        dup, dup_sigs = (dedup.metrics.get(k) for k in fm.DEDUP_COUNTERS)
        assert pad > 0 and dup_sigs > dup > 0
        text = fm.render_prometheus(regs)
        assert f'batch_fit_pad_lanes{{stage="verify0"}} {pad}' in text
        assert 'verify_fail_elems{stage="verify0"} 0' in text
        assert f'dedup_dup_sigs{{stage="dedup"}} {dup_sigs}' in text
        row = fm.batch_close_row([regs["verify0"]])
        assert (row["fit_pad_lanes"], row["fail_elems"]) == (pad, 0)
        assert fm.dedup_row(regs["dedup"]) == {"dup": dup,
                                               "dup_sigs": dup_sigs}
        assert fm.dedup_row(regs["verify0"]) is None   # counts dedup_dup too
        rendered = mon.MonitorSession.render(
            [{"stage": n, "signal": 1, "heartbeat_age_ms": 1.0, "in": 0,
              "out": 0, "overrun": 0, "backpressure": 0, "iters": 1,
              "batch_closes": fm.batch_close_row([r]),
              "dedup": fm.dedup_row(r)} for n, r in regs.items()], None, 1.0)
        assert f"batch_stalls=0  fit_pad_lanes={pad:,}  " \
               f"verify_fail_elems=0" in rendered
        assert f"dedup: dropped dup={dup:,} dup_sigs={dup_sigs:,}" in rendered
        dump = fm.flight_dump_obj("t", {s.name: (regs[s.name], s.recorder)
                                        for s in (verify, dedup)})
        report = slot_report.build_report(dump)["stages"]
        assert report["verify0"][fm.BATCH_FIT_PAD_LANES] == pad
        assert report["verify0"][fm.VERIFY_FAIL_ELEMS] == 0
        assert report["dedup"]["dedup"] == {"dup": dup, "dup_sigs": dup_sigs}
        assert "dedup" not in report["verify0"]


@pytest.mark.slow   # compiles the real program (16 x 384) for the CPU
def test_real_signatures_one_to_eight_one_bad_in_each_position(shape):
    """OpenSSL-signed transactions of 1..8 signatures, one bad in each
    position, through the real batch program behind the stage: what
    leaves is what the plain reference says under OpenSSL."""
    txns = _one_bad_in_each_position(shape, SEED + 3)
    want = ref.run(txns, batch=16, max_msg_len=MML)
    out, v, _d = _drive("native" if vn.available() else "python", txns,
                        batch=16)
    assert out == [txns[i] for i in want.out] and len(out) == 8
    assert v["verify_fail"] == want.verify_fail == 36
    assert v["verify_fail_elems"] == want.verify_fail_elems
    assert v["batch_fit_pad_lanes"] == want.fit_pad_lanes
