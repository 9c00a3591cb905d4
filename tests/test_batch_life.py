"""The life of a verify batch as counters (ISSUE 24): seven cumulative-ns
phase counters stamped through one helper on both lanes, a flight event
when a thread-blocking phase stalls, every hop's wait as a counter beside
the latency histogram, and the time inside the native crossings.

Everything runs on the CPU with the all-pass mask or a stubbed dispatch:
the lanes under test are the host's, and nothing compiles.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import time

import numpy as np
import pytest

from firedancer_tpu.protocol import txn as ft
from firedancer_tpu.runtime import slot_report
from firedancer_tpu.runtime import verify as rv
from firedancer_tpu.runtime import verify_native as vn
from firedancer_tpu.runtime.benchg import gen_transfer_pool
from firedancer_tpu.runtime.stage import Stage
from firedancer_tpu.runtime.verify import VerifyStage
from firedancer_tpu.tango import shm
from firedancer_tpu.utils import metrics as fm

# two intakes x {1, n} devices: "mesh" is the native intake in front of
# four (virtual) devices (ISSUE 26): VerifyStage(devices=...), 16 lanes as
# 4 x 4; "python-mesh" the Python intake in front of the same four (what
# a four-chip host without a toolchain runs)
LANES = ["native", "python", "mesh", "python-mesh"]
NATIVE_LANES = ("native", "mesh")
MESH_LANES = ("mesh", "python-mesh")
MESH_DEVICES = 4
PHASE_COUNTERS = [f"batch_{p}_ns" for p in fm.BATCH_PHASES]


@pytest.fixture(scope="module")
def pool():
    return gen_transfer_pool(96, n_payers=12, n_dests=64)


@contextlib.contextmanager
def _tile(lane: str, **stage_kw):
    """One VerifyStage over real (native) rings -> (stage, producer into
    it, consumer behind it)."""
    if lane in NATIVE_LANES and not vn.available():
        pytest.skip("native verify client unavailable")
    prev = os.environ.get(vn.ENV_SWITCH)
    os.environ[vn.ENV_SWITCH] = "1" if lane in NATIVE_LANES else "0"
    uid = shm.fresh_uid()
    lin = shm.ShmLink.create(f"tbl_i_{uid}", depth=256, mtu=1232, n_fseq=1)
    lout = shm.ShmLink.create(f"tbl_o_{uid}", depth=256, mtu=4096, n_fseq=1)
    st = None
    try:
        kw = dict(batch=16, max_msg_len=256, batch_deadline_s=0.001,
                  precomputed_ok=True)
        kw.update(stage_kw)
        if lane in MESH_LANES:
            kw["devices"] = MESH_DEVICES
        st = VerifyStage("v0", ins=[shm.make_consumer(lin, lazy=8)],
                         outs=[shm.make_producer(lout)], **kw)
        assert (st._sweep_client is not None) == (lane in NATIVE_LANES)
        assert st.mesh_devices == (MESH_DEVICES if lane in MESH_LANES else 1)
        yield st, shm.make_producer(lin), shm.make_consumer(lout, lazy=4)
    finally:
        if prev is None:
            os.environ.pop(vn.ENV_SWITCH, None)
        else:
            os.environ[vn.ENV_SWITCH] = prev
        if st is not None:
            st.ins, st.outs = [], []
            st.drop_native_views()
        lin.close()
        lout.close()


def _drain(cons) -> int:
    n = 0
    while cons.poll() not in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
        n += 1
    return n


def _record_lives(st) -> list:
    """Every batch whose life completes, in order."""
    lives = []
    inner = st._phase_end

    def record(life, phase, now=None):
        inner(life, phase, now)
        if phase == rv.PH_PUBLISH:
            lives.append(life)

    st._phase_end = record
    return lives


def _trickle(st, prod, cons, pool, *, per_loop=3, every=64, loops=20000):
    """Offer a few transactions every few loops, so batches close on the
    deadline one at a time.  -> (frames out, counter samples)."""
    fed = out = 0
    samples = []
    for it in range(loops):
        if it % every == 0:
            for _ in range(per_loop):
                if fed < len(pool) and prod.try_publish(
                        pool[fed], sig=fed, tsorig=0):
                    fed += 1
        st.run_once()
        out += _drain(cons)
        if it % 64 == 0:
            samples.append([st.metrics.get(k) for k in PHASE_COUNTERS])
        if fed == len(pool) and out == fed:
            break
    st.flush()
    out += _drain(cons)
    samples.append([st.metrics.get(k) for k in PHASE_COUNTERS])
    return out, samples


@pytest.mark.parametrize("lane", LANES)
def test_phase_counters_present_before_any_batch(lane):
    with _tile(lane) as (st, _prod, _cons):
        for k in PHASE_COUNTERS + ["batch_stalls"]:
            assert st.metrics.counters[k] == 0
        assert set(PHASE_COUNTERS) <= st.metrics_schema().names()
        assert {"frag_wait_ns", "frag_wait_n"} <= st.metrics_schema().names()


@pytest.mark.parametrize("lane", LANES)
def test_phase_counters_monotone_ordered_and_exact(lane, pool):
    with _tile(lane) as (st, prod, cons):
        lives = _record_lives(st)
        t0 = time.monotonic_ns()
        out, samples = _trickle(st, prod, cons, pool)
        wall = time.monotonic_ns() - t0
        assert out == len(pool)
        n_batches = st.metrics.get("batches")
        assert n_batches >= 6 and len(lives) == n_batches
        # monotone, sample to sample
        for a, b in zip(samples, samples[1:]):
            assert all(y >= x for x, y in zip(a, b))
        # a batch's stamps are ordered: open <= sealed <= dispatch begin
        # <= copies done <= dispatch end <= ready <= reaped <= published
        for life in lives:
            assert len(life.t) == len(fm.BATCH_PHASES) + 1
            assert life.t == sorted(life.t)
            assert t0 <= life.t[0] and life.t[-1] <= t0 + wall
        assert [life.seq for life in lives] == list(range(1, n_batches + 1))
        # the counters are the sums of the stamps' differences, exactly
        for k, name in enumerate(PHASE_COUNTERS):
            assert st.metrics.get(name) == sum(
                life.t[k + 1] - life.t[k] for life in lives)
        # batches went one at a time, so what blocked the thread fits in
        # the loop's wall time
        blocking = sum(st.metrics.get(PHASE_COUNTERS[k])
                       for k in fm.BATCH_BLOCKING_PHASES)
        assert 0 < blocking <= wall
        # every batch was open for about its deadline, none for a loop's age
        open_ms = st.metrics.get("batch_open_ns") / n_batches / 1e6
        assert 0.5 <= open_ms < 50
        assert st.metrics.get("batch_stalls") == 0


def test_native_open_and_seal_stamps_are_on_the_python_clock(pool):
    """The C side stamps open and seal with CLOCK_MONOTONIC, which is
    time.monotonic_ns(): no offset between the crossing and the loop."""
    with _tile("native") as (st, prod, _cons):
        c = st._sweep_client
        t0 = time.monotonic_ns()
        for i in range(5):
            assert prod.try_publish(pool[i], sig=i, tsorig=0)
        st.run_once()
        assert c.open_elems() == 5
        t1 = time.monotonic_ns()
        c.seal(rv.CLOSE_DEADLINE)
        t2 = time.monotonic_ns()
        slot, n_elems, n_txn, opened, sealed, why = c.take_sealed()
        assert (n_elems, n_txn) == (5, 5)
        assert t0 <= opened <= t1 <= sealed <= t2
        c.release(slot)


@pytest.mark.parametrize("lane", LANES)
def test_a_full_batch_closes_without_the_deadline(lane, pool):
    """Sealed by filling (in C on the native lane): open is short."""
    with _tile(lane, batch_deadline_s=10.0) as (st, prod, cons):
        lives = _record_lives(st)
        for i in range(16):
            assert prod.try_publish(pool[i], sig=i, tsorig=0)
        out = 0
        for _ in range(200):
            st.run_once()
            out += _drain(cons)
        assert out == 16 and len(lives) == 1
        assert lives[0].t[1] - lives[0].t[0] < 1e9  # not the 10 s deadline


class _SlowResult:
    """A device future's surface, for a stubbed dispatch: the mask of
    the whole fixed-shape batch."""

    def __init__(self, lanes):
        self.mask = np.ones((lanes,), dtype=bool)

    def is_ready(self):
        return True

    def __array__(self, dtype=None, copy=None):
        return self.mask


@pytest.mark.parametrize("lane", LANES)
def test_a_slow_dispatch_is_one_stall_event(lane, pool):
    import jax.profiler  # noqa: F401  (the span's import, off the clock)

    with _tile(lane, precomputed_ok=False) as (st, prod, cons):
        def slow(life, rows):
            st._phase_end(life, rv.PH_H2D)
            time.sleep(0.12)
            return _SlowResult(len(rows))

        st._device_verify = slow
        for i in range(5):
            assert prod.try_publish(pool[i], sig=i, tsorig=0)
        out = 0
        for _ in range(400):
            st.run_once()
            out += _drain(cons)
            if out == 5:
                break
        assert out == 5
        assert st.metrics.get("batches") == 1
        assert st.metrics.get("batch_stalls") == 1
        assert st.metrics.get("batch_launch_ns") >= 120e6
        stalls = [(ev, arg) for _ts, ev, arg in st.recorder.records()
                  if ev == fm.EV_BATCH_STALL]
        assert len(stalls) == 1
        got = fm.batch_stall_fields(stalls[0][1])
        assert got["phase"] == "launch" and 120 <= got["ms"] < 1000
        # submit and complete keep their rate: two events for the batch
        evs = [ev for _ts, ev, _arg in st.recorder.records()]
        assert evs.count(fm.EV_BATCH_SUBMIT) == 1
        assert evs.count(fm.EV_BATCH_COMPLETE) == 1
        # the flight dump's readers name the phase
        dump = fm.flight_dump_obj("t", {"v0": (None, st.recorder)})
        chrome = fm.flight_to_chrome_trace(dump)["traceEvents"]
        hit = [e for e in chrome if e["name"] == "batch_stall"]
        assert len(hit) == 1 and hit[0]["args"] == got
        block = slot_report.build_report(dump)["stages"]["v0"]
        assert [s["phase"] for s in block["batch_stalls"]] == ["launch"]


def _toy_ok(t: bytes, toy_lane_ok) -> bool:
    """conftest's toy verdict on a transaction: every element's."""
    d = ft.txn_parse(t)
    msg = d.message(t)
    return all(toy_lane_ok(len(msg), msg[0], sig[0], sig[63], pk[0], pk[31])
               for sig, pk in zip(d.signatures(t), d.signers(t)))


@pytest.mark.parametrize("lane", LANES)
def test_a_batch_crosses_the_boundary_once_each_way(lane, pool, exchange,
                                                    toy_verify_ok):
    """One packed array in, one mask out (ISSUE 29), counted where the
    crossings are made: per dispatched batch one host->device array —
    one `device_put` on one device; over a mesh of d one array of d
    callbacks — one program, and one device->host fetch, at the reap."""
    import jax.profiler  # noqa: F401  (the span's import, off the clock)

    with _tile(lane, precomputed_ok=False) as (st, prod, cons):
        out, _samples = _trickle(st, prod, cons, pool)
        n = st.metrics.get("batches")
        assert n >= 6
        want = [t for t in pool if _toy_ok(t, toy_verify_ok)]
        assert 0 < len(want) < len(pool)
        assert out == len(want) == st.metrics.get("txn_verified")
        assert st.metrics.get("verify_fail") == len(pool) - len(want)
        assert exchange.programs == n
        assert len(exchange.h2d) == n and len(exchange.fetches) == n
        w = vn.row_width(256)
        assert {(a.shape, str(a.dtype)) for a in exchange.h2d} \
            == {((16, w), "uint8")}
        assert {f.shape for f in exchange.fetches} == {(16,)}
        if lane in MESH_LANES:
            assert not exchange.puts
            assert len(exchange.callbacks) == MESH_DEVICES * n
            assert all(len(a.sharding.device_set) == MESH_DEVICES
                       for a in exchange.made)
        else:
            assert not exchange.made and not exchange.callbacks
            assert all(len(a.sharding.device_set) == 1
                       for a in exchange.puts)
        if lane == "native":
            # the array is the slot's own memory as it lies: no copy of
            # the stage's between the intake's fill and the device_put
            c = st._sweep_client
            assert all(v.rows.flags.c_contiguous for v in c.slots)


def test_pad_rows_stale_from_an_earlier_batch_change_no_answer(
        pool, toy_verify_ok):
    """A reused slot's pad rows hold an earlier batch's elements, which
    the program verifies like any row (no pad mask on the device): the
    reap reads the real lanes only, so the same transactions leave the
    stage whatever the pad rows say."""
    import jax.profiler  # noqa: F401

    passing = [t for t in pool if _toy_ok(t, toy_verify_ok)]
    failing = [t for t in pool if not _toy_ok(t, toy_verify_ok)]
    assert len(passing) >= 14 and len(failing) >= 14
    with _tile("native", precomputed_ok=False, max_inflight=1,
               batch_deadline_s=10.0) as (st, prod, cons):
        c = st._sweep_client
        n_slots = c.n_slots

        def batch(txns) -> int:
            for i, t in enumerate(txns):
                assert prod.try_publish(t, sig=i, tsorig=0)
            for _ in range(4):
                st.run_once()
            st.flush()
            return _drain(cons)

        # fill every slot of the ring with rows that pass, then with
        # rows that fail, and send a short batch each time: its pad
        # rows are the earlier batch's
        for filler, rest in ((passing, failing), (failing, passing)):
            for k in range(n_slots):
                elems = st.metrics.get("batch_elems")
                batch(filler[4 * k:4 * k + 4])
                assert st.metrics.get("batch_elems") == elems + 4
            before = st.metrics.get("batches")
            assert batch(rest[-2:]) == (2 if rest is passing else 0)
            assert st.metrics.get("batches") == before + 1
        # the stale rows were there: some slot's pad rows are not zero
        assert any(v.rows[2:4].any() for v in c.slots)


def test_stall_event_wire_value_and_arg():
    assert fm.EV_BATCH_SUBMIT == 8 and fm.EV_BATCH_COMPLETE == 9
    assert fm.EV_BATCH_STALL == 20
    assert fm.EVENT_NAMES[fm.EV_BATCH_STALL] == "batch_stall"
    arg = fm.batch_stall_arg(rv.PH_REAP, 1_910_000_000)
    assert fm.batch_stall_fields(arg) == {"phase": "reap", "ms": 1910}
    assert fm.BATCH_STALL_NS == 100_000_000


# -- every hop's wait ----------------------------------------------------------


class _Count(Stage):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.got = 0

    def after_frag(self, in_idx, meta, payload):
        self.got += 1


class _CountTable(_Count):
    def sweep_frags(self, rows, buf):
        self.got += len(rows)
        return len(rows), [r[5] for r in rows]


def _wait_stage(path: str, lin):
    """-> a stage that observes frag latency on the named path."""
    if path == "sweep":
        if not vn.available():
            pytest.skip("native verify client unavailable")
        lout = shm.ShmLink.create(f"tbl_w_{shm.fresh_uid()}", depth=256,
                                  mtu=4096, n_fseq=1)
        st = VerifyStage("w", ins=[shm.make_consumer(lin, lazy=8)],
                         outs=[shm.make_producer(lout)], batch=16,
                         max_msg_len=256, precomputed_ok=True)
        assert st._sweep_client is not None
        return st, lout
    cons = shm.make_consumer(lin, lazy=8)
    if path == "poll":
        from firedancer_tpu.tango.lossy import LossyConsumer
        from firedancer_tpu.utils.rng import Rng

        return _Count("w", ins=[LossyConsumer(cons, Rng(7))]), None
    cls = _CountTable if path == "burst_table" else _Count
    return cls("w", ins=[cons]), None


@pytest.mark.parametrize("path", ["poll", "sweep", "burst_table",
                                  "burst_frag"])
def test_frag_wait_counters_are_the_histograms_sum_and_count(path, pool):
    lin = shm.ShmLink.create(f"tbl_q_{shm.fresh_uid()}", depth=256,
                             mtu=1232, n_fseq=1)
    st = lout = None
    try:
        prod = shm.make_producer(lin)
        st, lout = _wait_stage(path, lin)
        drainer = st._native_drainer()
        assert (drainer is None) == (path == "poll")
        due = time.monotonic_ns() - 5_000_000
        for i in range(40):
            assert prod.try_publish(pool[i], sig=i, tsorig=due + i)
        for _ in range(50):
            st.run_once()
        h = st.metrics.hist("frag_latency_ns")
        assert h["count"] == 40
        assert st.metrics.get("frag_wait_n") == 40
        assert st.metrics.get("frag_wait_ns") == int(h["sum"])
        mean_ms = st.metrics.get("frag_wait_ns") / 40 / 1e6
        assert 5.0 <= mean_ms < 5000
    finally:
        if st is not None:
            st.ins, st.outs = [], []
            st.drop_native_views()
        lin.close()
        if lout is not None:
            lout.close()


# -- time inside the crossings ---------------------------------------------------


def test_sweep_busy_is_the_sum_of_the_registrys_phase_sums(pool):
    with _tile("native") as (st, prod, cons):
        assert "sweep_busy_ns" not in st.metrics.counters
        _trickle(st, prod, cons, pool[:40])
        st.during_housekeeping()
        reg = st.metrics.registry
        want = sum(reg.hist(f"nsweep_{ph}_ns")["sum"]
                   for ph in fm.NSWEEP_PHASES)
        assert want > 0
        assert st.metrics.get("sweep_busy_ns") == int(want)
        assert st.metrics.get("sweep_crossings") \
            == reg.get("nsweep_crossings") > 0
        # local-only: the registry holds the originals
        assert "sweep_busy_ns" not in st.metrics_schema().names()


def test_sweep_counters_absent_where_the_stage_does_not_sweep_natively(pool):
    with _tile("python") as (st, prod, cons):
        _trickle(st, prod, cons, pool[:20])
        st.during_housekeeping()
        assert "sweep_busy_ns" not in st.metrics.counters
    assert "sweep_busy_ns" not in Stage("s").metrics.counters
    Stage("s")._copy_sweep_counters()  # no plane: nothing to copy


# -- a batch that overflows opens a new one ---------------------------------------


def _three_sig_txn(i: int) -> bytes:
    keys = [hashlib.sha256(b"k%d-%d" % (i, j)).digest() for j in range(3)]
    msg = ft.message_build(
        version=ft.VLEGACY, signature_cnt=3, readonly_signed_cnt=1,
        readonly_unsigned_cnt=1, acct_addrs=keys + [ft.SYSTEM_PROGRAM],
        recent_blockhash=bytes(32),
        instrs=[ft.InstrSpec(program_id=3, accounts=bytes([0, 1, 2]),
                             data=b"hi")])
    sigs = [hashlib.sha512(b"s%d-%d" % (i, j)).digest() for j in range(3)]
    return ft.txn_assemble(sigs, msg)


@pytest.mark.parametrize("lane", LANES)
def test_a_txn_that_does_not_fit_opens_the_next_batch(lane):
    """3 + 3 signatures into 4 lanes: the second transaction's elements
    belong to a batch of their own, with stamps of its own."""
    with _tile(lane, batch=4) as (st, prod, cons):
        lives = _record_lives(st)
        for i in range(2):
            assert prod.try_publish(_three_sig_txn(i), sig=i, tsorig=0)
        out = 0
        for _ in range(400):
            st.run_once()
            out += _drain(cons)
        st.flush()
        out += _drain(cons)
        assert out == 2
        assert st.metrics.get("batches") == 2
        assert st.metrics.get("batch_elems") == 6
        assert len(lives) == 2 and lives[0] is not lives[1]


# -- when a batch closes (ISSUE 25, ISSUE 32, ISSUE 40) ------------------------------
#
# Full (and then it may be dispatched behind a running batch), or past its
# deadline AND the window open to it (nothing in flight, or room in it
# after a full batch had to wait for its place), or flush(): the same rule
# on every lane, driven with a result that is not ready until the test
# says so.

CLOSE_COUNTERS = list(rv._CLOSE_COUNTERS)
QUEUED_BEHIND = fm.BATCH_QUEUED_BEHIND
HELD_BACKLOGGED = fm.BATCH_HELD_BACKLOGGED


class _Gated:
    """A device future that is ready when the test says."""

    def __init__(self, lanes):
        # from the stage's own books (_count_dispatch): the batch's
        # fill, what closed it, how many were in flight ahead of it
        self.n = self.close = self.behind = None
        self.mask = np.ones((lanes,), dtype=bool)
        self.done = False

    def is_ready(self):
        return self.done

    def __array__(self, dtype=None, copy=None):
        return self.mask


@contextlib.contextmanager
def _gated_tile(lane: str, **kw):
    """A tile whose dispatches hand back _Gated results, in `sent`."""
    import jax.profiler  # noqa: F401  (the span's import, off the clock)

    with _tile(lane, precomputed_ok=False, **kw) as (st, prod, cons):
        sent: list = []

        def dispatch(life, rows):
            st._phase_end(life, rv.PH_H2D)
            sent.append(_Gated(len(rows)))
            return sent[-1]

        books = st._count_dispatch

        def count(n, close, occupancy):
            sent[-1].n, sent[-1].close = n, close
            sent[-1].behind = occupancy - 1
            books(n, close, occupancy)

        st._device_verify = dispatch
        st._count_dispatch = count
        yield st, prod, cons, sent


def _open_elems(st) -> int:
    c = st._sweep_client
    if c is not None:
        return c.open_elems()
    return len(st._gen.elems) + len(st._comb.elems)


def _sealed_waiting(st) -> bool:
    c = st._sweep_client
    return bool(c.sealed_waiting() if c is not None else st._submit_queue)


def _in_flight(st) -> int:
    return len(st._flying())


def _feed(prod, pool, lo: int, hi: int) -> None:
    for i in range(lo, hi):
        assert prod.try_publish(pool[i], sig=i, tsorig=0)


def _collect(cons, got: list) -> None:
    """The transaction bytes that came out."""
    while True:
        res = cons.poll()
        if res in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
            return
        payload = bytes(res[1])
        got.append(payload[:int.from_bytes(payload[-2:], "little")])


def _spin(st, cons, got: list, loops: int = 60, until=None) -> None:
    """Run the stage, collecting the transaction bytes that come out."""
    for _ in range(loops):
        st.run_once()
        _collect(cons, got)
        if until is not None and until():
            return


def _past_deadline(st, cons, got) -> None:
    time.sleep(st.batch_deadline_s * 3)
    _spin(st, cons, got, loops=20)


def _closes(st) -> list[int]:
    return [st.metrics.get(k) for k in CLOSE_COUNTERS]


def _deadline_batch(st, prod, cons, pool, got, lo: int, n: int = 2) -> int:
    """n transactions, sealed at their deadline with nothing in flight."""
    before = st.metrics.get("batches")
    assert _in_flight(st) == 0
    _feed(prod, pool, lo, lo + n)
    _spin(st, cons, got)
    _past_deadline(st, cons, got)
    assert st.metrics.get("batches") == before + 1
    return lo + n


def _full_batch(st, prod, cons, pool, got, lo: int) -> int:
    """One batch's worth (16), sealed by filling and dispatched at once,
    behind whatever is in flight: the window has room."""
    before = st.metrics.get("batches"), st.metrics.get(QUEUED_BEHIND)
    flying = _in_flight(st)
    assert flying < st.max_inflight
    _feed(prod, pool, lo, lo + 16)
    _spin(st, cons, got)
    assert st.metrics.get("batches") == before[0] + 1
    assert st.metrics.get(QUEUED_BEHIND) == before[1] + (flying > 0)
    assert _in_flight(st) == flying + 1 and _open_elems(st) == 0
    return lo + 16


def _held_batch(st, prod, cons, pool, got, lo: int, n: int = 3,
                parked: bool = False) -> int:
    """n transactions, held open past their deadline by the window: a
    batch is in flight and no full one has had to wait for a place, or
    the window is full (with a sealed batch `parked` behind it or not)."""
    before = st.metrics.get("batches")
    assert _in_flight(st) > 0
    _feed(prod, pool, lo, lo + n)
    _spin(st, cons, got)
    _past_deadline(st, cons, got)
    assert st.metrics.get("batches") == before and _open_elems(st) == n
    assert _sealed_waiting(st) == parked
    return lo + n


def _parked_batch(st, prod, cons, pool, got, lo: int) -> int:
    """One batch's worth (16) that fills while the window is full: it
    waits sealed for its place, which is the evidence that the thread
    leads the chip (ISSUE 40)."""
    before = st.metrics.get("batches")
    assert not st._window_has_room() and not _sealed_waiting(st)
    _feed(prod, pool, lo, lo + 16)
    _spin(st, cons, got)
    assert st.metrics.get("batches") == before and _open_elems(st) == 0
    assert _sealed_waiting(st) and st._full_waited
    return lo + 16


def _fill_window(st, prod, cons, pool, got) -> int:
    """A small batch sealed on its deadline with nothing in flight, and a
    full one dispatched behind it: the window (2) is then full.
    -> transactions fed."""
    n = _deadline_batch(st, prod, cons, pool, got, 0, 3)
    n = _full_batch(st, prod, cons, pool, got, n)
    assert _closes(st) == [1, 1, 0] and _in_flight(st) == 2
    return n


def _deepest(st) -> int:
    """The most batches that were in flight at any dispatch."""
    h = st.metrics.hist("inflight_occupancy")
    assert h["count"] == st.metrics.get("batches")
    return max(int(edge) for edge, n in zip(h["buckets"], h["counts"]) if n)


@pytest.mark.parametrize("lane", LANES)
def test_close_counters_are_in_the_schema_and_start_at_zero(lane):
    with _tile(lane) as (st, _prod, _cons):
        for k in CLOSE_COUNTERS + [QUEUED_BEHIND]:
            assert st.metrics.counters[k] == 0
        assert set(CLOSE_COUNTERS + [QUEUED_BEHIND]) \
            <= st.metrics_schema().names()


@pytest.mark.parametrize("lane", LANES)
def test_nothing_in_flight_seals_at_the_deadline_as_before(lane, pool):
    with _gated_tile(lane, batch_deadline_s=0.05) as (st, prod, cons, sent):
        got: list = []
        _feed(prod, pool, 0, 5)
        t0 = time.monotonic()
        _spin(st, cons, got, loops=40)
        if time.monotonic() - t0 < 0.04:   # a slow machine proves nothing
            assert st.metrics.get("batches") == 0 and _open_elems(st) == 5
        _spin(st, cons, got, loops=100000,
              until=lambda: st.metrics.get("batches") == 1)
        assert 0.05 <= time.monotonic() - t0 < 5
        assert _closes(st) == [0, 1, 0] and [g.n for g in sent] == [5]
        assert st.metrics.get(QUEUED_BEHIND) == 0 and _deepest(st) == 1
        open_ms = st.metrics.get("batch_open_ns") / 1e6
        assert 50 <= open_ms < 1000


@pytest.mark.parametrize("lane", LANES)
def test_a_batch_in_flight_holds_the_open_batch_until_the_pump_that_reaps_it(
        lane, pool):
    """A batch that is not full does not queue behind a running one
    that was not full either (ISSUE 32): the window has room for it,
    and it stays open."""
    with _gated_tile(lane) as (st, prod, cons, sent):
        got: list = []
        n = _deadline_batch(st, prod, cons, pool, got, 0, 3)
        assert st._window_has_room() and not st._window_open()
        # deadline passed, one batch in flight: the batch stays open ...
        _feed(prod, pool, n, n + 3)
        _spin(st, cons, got)
        _past_deadline(st, cons, got)
        assert st.metrics.get("batches") == 1 and len(sent) == 1
        assert _open_elems(st) == 3 and not _sealed_waiting(st)
        # ... and takes later frags
        _feed(prod, pool, n + 3, n + 5)
        _spin(st, cons, got)
        assert _open_elems(st) == 5 and not _sealed_waiting(st)
        assert st.metrics.get("batches") == 1 and got == []
        # the pump that reaps the running batch seals and dispatches it
        sent[0].done = True
        st.after_credit()
        assert st.metrics.get("batches") == 2
        assert [g.n for g in sent] == [3, 5]
        assert _open_elems(st) == 0 and not _sealed_waiting(st)
        assert _closes(st) == [0, 1, 1]
        # nothing lost, nothing reordered across the hold
        sent[1].done = True
        _spin(st, cons, got)
        assert got == list(pool[:n + 5])
        assert sum(_closes(st)) == st.metrics.get("batches") == 2
        assert st.metrics.get("txn_verified") == n + 5
        # and nothing was ever queued behind anything
        assert st.metrics.get(QUEUED_BEHIND) == 0 and _deepest(st) == 1


@pytest.mark.parametrize("lane", LANES)
def test_a_full_batch_takes_the_second_place_and_never_a_third(lane, pool):
    with _gated_tile(lane) as (st, prod, cons, sent):
        got: list = []
        n = _fill_window(st, prod, cons, pool, got)   # 3 running, 16 behind
        assert st.metrics.get(QUEUED_BEHIND) == 1
        _feed(prod, pool, n, n + 16)           # one more batch's worth
        _spin(st, cons, got)
        assert _sealed_waiting(st) and st.metrics.get("batches") == 2
        assert _open_elems(st) == 0
        # a batch behind a sealed one is held too, even past its deadline
        _feed(prod, pool, n + 16, n + 20)
        _spin(st, cons, got)
        _past_deadline(st, cons, got)
        assert _open_elems(st) == 4 and st.metrics.get("batches") == 2
        # one freed slot goes to the sealed batch; the open one stays
        sent[0].done = True
        st.after_credit()
        assert [g.n for g in sent] == [3, 16, 16]
        assert _open_elems(st) == 4 and not _sealed_waiting(st)
        # the next reap leaves room behind a batch that had to wait for
        # its place: the thread leads the chip, and the open batch goes
        # behind it
        sent[1].done = True
        st.after_credit()
        assert [g.n for g in sent] == [3, 16, 16, 4]
        assert _in_flight(st) == 2 and _open_elems(st) == 0
        assert _closes(st) == [2, 1, 1]
        assert st.metrics.get(QUEUED_BEHIND) == 3
        assert [g.behind for g in sent] == [0, 1, 1, 1]
        for g in sent:
            g.done = True
        _spin(st, cons, got)
        assert got == list(pool[:n + 20])
        assert sum(_closes(st)) == st.metrics.get("batches") == 4
        assert _deepest(st) == 2


# -- a backlogged intake fills its batch (ISSUE 36) ------------------------------
#
# The one thing the close rule reads that is not the window: whether the
# last intake sweep took its whole burst (Stage.backlogged).  Driven with a
# burst of 4 under a batch of 16, so that a sweep can be full and the batch
# not; a pass through the rule without a sweep is after_credit().

BURST = 4


def _full_sweeps(st, prod, pool, lo: int, k: int) -> int:
    """k sweeps that each take their whole burst: the ring in front never
    runs dry.  -> transactions fed."""
    assert st.burst == BURST
    _feed(prod, pool, lo, lo + k * BURST)
    for _ in range(k):
        st.run_once()
        assert st.backlogged
    return lo + k * BURST


def _short_sweep(st, prod, pool, lo: int, n: int = 0) -> int:
    """One sweep that takes n < burst frags: the ring ran dry."""
    assert n < st.burst
    _feed(prod, pool, lo, lo + n)
    st.run_once()
    assert not st.backlogged
    return lo + n


def _overdue(st) -> None:
    """Let the open batch's deadline pass, and go through the close rule
    once, without a sweep."""
    st.before_credit()         # the Python lane stamps the open batch here
    time.sleep(st.batch_deadline_s * 3)
    st.after_credit()


def _held(st) -> int:
    return st.metrics.get(HELD_BACKLOGGED)


@pytest.mark.parametrize("lane", LANES)
def test_a_backlogged_intake_keeps_the_open_batch_filling_past_the_reap(
        lane, pool):
    """With a batch that was not full in flight and every sweep full, the
    pump that reaps it does not seal the open batch: it would run now,
    part empty, at a whole dispatch's cost.  It goes when it fills, as a
    full batch, and is counted once as held."""
    with _gated_tile(lane) as (st, prod, cons, sent):
        st.burst = BURST
        got: list = []
        n = _deadline_batch(st, prod, cons, pool, got, 0, 3)
        assert not st.backlogged and _held(st) == 0
        n = _full_sweeps(st, prod, pool, n, 2)
        _overdue(st)           # held by the window: 3 lanes are in flight
        assert st.metrics.get("batches") == 1 and _open_elems(st) == 8
        assert _held(st) == 0
        sent[0].done = True
        st.after_credit()      # the reap: nothing in flight, but backlogged
        _collect(cons, got)
        assert _in_flight(st) == 0 and got == list(pool[:3])
        assert st.metrics.get("batches") == 1 and _open_elems(st) == 8
        assert not _sealed_waiting(st) and _held(st) == 1
        n = _full_sweeps(st, prod, pool, n, 2)      # it fills ...
        st.after_credit()
        assert [(g.n, g.close, g.behind) for g in sent] \
            == [(3, rv.CLOSE_DEADLINE, 0), (16, rv.CLOSE_FULL, 0)]
        assert _closes(st) == [1, 1, 0] and _open_elems(st) == 0
        assert _held(st) == 1                   # ... counted once, not a pass
        # a full batch that went out alone is no evidence that the
        # device limits: the next one is held the same way
        n = _full_sweeps(st, prod, pool, n, 1)
        _overdue(st)
        assert not st._full_waited and _open_elems(st) == 4
        assert st.metrics.get("batches") == 2 and _held(st) == 1
        sent[1].done = True
        st.after_credit()
        assert st.metrics.get("batches") == 2 and _held(st) == 2
        for _ in range(3):
            st.after_credit()
        assert _held(st) == 2 and _open_elems(st) == 4
        st.flush()
        _spin(st, cons, got)
        assert got == list(pool[:n])
        assert sum(_closes(st)) == st.metrics.get("batches") == 3
        assert st.metrics.get(QUEUED_BEHIND) == 0


@pytest.mark.parametrize("flying", [True, False])
@pytest.mark.parametrize("lane", LANES)
def test_the_first_short_sweep_ends_the_backlog(lane, flying, pool):
    """On/off feed: the batch a backlog held goes out at the first pass
    through the rule after a sweep came back short, closed by the
    deadline, or by the window if a batch in flight held it first."""
    with _gated_tile(lane) as (st, prod, cons, sent):
        st.burst = BURST
        got: list = []
        n = 0
        if flying:
            n = _deadline_batch(st, prod, cons, pool, got, 0, 3)
        n = _full_sweeps(st, prod, pool, n, 2)
        _overdue(st)
        if flying:
            assert _held(st) == 0
            sent[0].done = True
            st.after_credit()
        assert _held(st) == 1 and _in_flight(st) == 0
        assert st.metrics.get("batches") == flying and _open_elems(st) == 8
        st.run_once()          # the rule (still held), then a short sweep
        assert not st.backlogged
        assert st.metrics.get("batches") == flying and _open_elems(st) == 8
        st.run_once()          # the next pass: the rule as it was
        assert st.metrics.get("batches") == flying + 1
        assert (sent[-1].n, sent[-1].behind) == (8, 0)
        assert _closes(st) == ([0, 1, 1] if flying else [0, 1, 0])
        assert _held(st) == 1 and _open_elems(st) == 0
        # and a batch that opens with the ring dry is not held at all
        sent[-1].done = True
        n = _short_sweep(st, prod, pool, n, 2)
        _overdue(st)
        assert st.metrics.get("batches") == flying + 2 and _held(st) == 1
        sent[-1].done = True
        _spin(st, cons, got)
        assert got == list(pool[:n])


@pytest.mark.parametrize("backlogged", [True, False])
@pytest.mark.parametrize("waited", [True, False])
@pytest.mark.parametrize("lane", LANES)
def test_only_a_full_batch_that_waited_for_its_place_is_evidence(
        lane, waited, backlogged, pool):
    """Clause (b) of the close rule (ISSUE 32) on the evidence of ISSUE
    40.  After a full batch had to wait sealed for a place in the
    window the deadline seal queues a batch that is not full behind the
    running one, backlogged or not: the thread leads the chip, and a
    queued partial batch is slack.  Behind a full batch that found room
    at once behind a running one it does not — that is all the evidence
    ISSUE 36 asked for, and a thread that trails the chip makes it too:
    the batch stays open, and at the reap that empties the window the
    backlog holds it."""
    with _gated_tile(lane) as (st, prod, cons, sent):
        st.burst = BURST
        got: list = []
        n = _fill_window(st, prod, cons, pool, got)   # 3, 16 behind it
        assert sent[-1].behind == 1 and not st._full_waited
        if waited:
            n = _parked_batch(st, prod, cons, pool, got, n)
            sent[0].done = True
            st.after_credit()   # the one that waited takes the place
            assert [g.n for g in sent] == [3, 16, 16]
        sent[len(sent) - 2].done = True
        st.after_credit()
        first = len(sent)
        assert _in_flight(st) == 1 and sent[-1].close == rv.CLOSE_FULL
        assert st._full_waited == waited and st._window_has_room()
        if backlogged:
            n = _full_sweeps(st, prod, pool, n, 1)
        else:
            n = _short_sweep(st, prod, pool, n, 3)
        k = _open_elems(st)
        assert k == (4 if backlogged else 3)
        _overdue(st)
        if waited:
            assert st._window_open() is False     # both places taken now
            assert len(sent) == first + 1 and _open_elems(st) == 0
            assert (sent[-1].n, sent[-1].close, sent[-1].behind) \
                == (k, rv.CLOSE_DEADLINE, 1)
            assert _held(st) == 0
            assert not st._full_waited            # and the slack with them
        else:
            # held by the window, not by the backlog: not counted
            assert len(sent) == first and _open_elems(st) == k
            assert not _sealed_waiting(st) and _held(st) == 0
            sent[first - 1].done = True
            st.after_credit()      # the reap: nothing in flight
            if backlogged:
                assert len(sent) == first and _held(st) == 1
                n = _short_sweep(st, prod, pool, n)
                st.after_credit()
            assert len(sent) == first + 1 and _open_elems(st) == 0
            assert (sent[-1].n, sent[-1].close, sent[-1].behind) \
                == (k, rv.CLOSE_WINDOW, 0)
        for g in sent:
            g.done = True
        _spin(st, cons, got)
        assert got == list(pool[:n])
        assert sum(_closes(st)) == st.metrics.get("batches") == len(sent)


# -- the second place is for a thread that leads the chip (ISSUE 40) -----------
#
# What the stage reads as "the device limits": a full batch found the
# window full and waited sealed for its place.  A full batch that went out
# at once behind a running one says nothing: a thread that trails the chip
# sends those whenever a part-empty batch is still running, and under the
# rule of ISSUE 36 each of them made the next part-empty batch.  Driven
# with a batch of 8 under a burst of 4 where many batches have to fill.


def _trailing_thread(st, prod, cons, pool, got, sent) -> int:
    """A part-empty batch (3) running at its deadline and a full one
    dispatched at once behind it: where a stage whose thread trails the
    chip stands after any partial dispatch.  -> transactions fed."""
    st.burst = BURST
    n = _deadline_batch(st, prod, cons, pool, got, 0, 3)
    n = _full_sweeps(st, prod, pool, n, st.batch // BURST)
    st.after_credit()
    assert [(g.n, g.close, g.behind) for g in sent] \
        == [(3, rv.CLOSE_DEADLINE, 0), (st.batch, rv.CLOSE_FULL, 1)]
    assert not st._full_waited
    return n


@pytest.mark.parametrize("lane", LANES)
def test_a_thread_that_trails_the_chip_sends_full_batches_only(lane, pool):
    """(i) A full batch dispatched at once behind a running partial one
    is no evidence.  The next batch, past its deadline, stays open
    behind whatever runs, is counted as held for the backlog once the
    window empties, and goes full; and so for every batch after it,
    whether the one before is still running when it fills or not: the
    alternation of full and part-empty batches does not arise."""
    with _gated_tile(lane, batch=8) as (st, prod, cons, sent):
        got: list = []
        n = _trailing_thread(st, prod, cons, pool, got, sent)
        for k in range(9):
            n = _full_sweeps(st, prod, pool, n, 1)      # half a batch
            _overdue(st)                # held by the window, whatever flies
            assert len(sent) == k + 2 and _open_elems(st) == 4
            assert not st._full_waited and _held(st) == (k + 2) // 3
            if k % 3 == 0:
                # two in flight, and the chip outruns the thread: both
                # come back before the batch has filled, and the backlog
                # holds it from there
                assert _in_flight(st) == 2
                for g in sent:
                    g.done = True
                st.after_credit()
                assert _in_flight(st) == 0 and len(sent) == k + 2
                assert _held(st) == k // 3 + 1 and _open_elems(st) == 4
            elif k % 3 == 2:
                # two in flight, the head comes back: room behind a full
                # batch that went out behind a running one, where ISSUE
                # 36's rule queued this one part empty.  No full batch
                # waited: it stays open
                assert _in_flight(st) == 2
                sent[-2].done = True
                st.after_credit()
                assert _in_flight(st) == 1 and st._window_has_room()
                assert len(sent) == k + 2 and _open_elems(st) == 4
            behind = _in_flight(st)
            assert behind == (0, 1, 1)[k % 3]
            n = _full_sweeps(st, prod, pool, n, 1)      # it fills
            st.after_credit()
            assert (sent[-1].n, sent[-1].close, sent[-1].behind) \
                == (8, rv.CLOSE_FULL, behind)
        assert _closes(st) == [10, 1, 0] and _held(st) == 3
        assert not st._full_waited and _deepest(st) == 2
        for g in sent:
            g.done = True
        st.flush()
        _spin(st, cons, got)
        assert got == list(pool[:n])
        assert st.metrics.get("batch_elems") == n == 83


@pytest.mark.parametrize("backlogged", [True, False])
@pytest.mark.parametrize("lane", LANES)
def test_a_thread_that_leads_the_chip_keeps_one_partial_batch_of_slack(
        lane, backlogged, pool):
    """(ii) A full batch that waited sealed with two in flight is the
    evidence: the batch behind it is queued at the reap that leaves
    room, backlogged or not, as under ISSUE 32.  That spends the slack:
    the batch after it stays open until a full batch waits again."""
    with _gated_tile(lane) as (st, prod, cons, sent):
        st.burst = BURST
        got: list = []
        n = _fill_window(st, prod, cons, pool, got)      # 3, 16 behind it
        n = _parked_batch(st, prod, cons, pool, got, n)
        if backlogged:
            n = _full_sweeps(st, prod, pool, n, 1)
        else:
            n = _short_sweep(st, prod, pool, n, 3)
        k = _open_elems(st)
        _overdue(st)            # a sealed batch waits ahead of it: held
        assert len(sent) == 2 and _open_elems(st) == k
        sent[0].done = True
        st.after_credit()       # its place goes to the batch that waited
        assert [g.n for g in sent] == [3, 16, 16] and _open_elems(st) == k
        sent[1].done = True
        st.after_credit()       # room behind it: the open batch is queued
        assert [(g.n, g.close, g.behind) for g in sent[3:]] \
            == [(k, rv.CLOSE_WINDOW, 1)]
        assert _held(st) == 0 and not st._full_waited
        # the next one is held behind the same running batch ...
        n = _short_sweep(st, prod, pool, n, 2)
        _overdue(st)
        assert len(sent) == 4 and _open_elems(st) == 2
        sent[2].done = True
        st.after_credit()
        assert len(sent) == 4 and _in_flight(st) == 1
        # ... until the thread is a whole batch ahead again
        _feed(prod, pool, n, n + 14)        # it fills: behind at once
        _spin(st, cons, got)
        n = _parked_batch(st, prod, cons, pool, got, n + 14)
        assert [(g.n, g.behind) for g in sent[4:]] == [(16, 1)]
        for g in sent:
            g.done = True
        st.flush()
        _spin(st, cons, got)
        assert got == list(pool[:n])
        assert sum(_closes(st)) == st.metrics.get("batches") == len(sent)
@pytest.mark.parametrize("lane", LANES)
def test_a_hiccup_clears_the_evidence_and_the_next_wait_restores_it(
        lane, pool):
    """(iii) The chip runs dry (a reap leaves nothing in flight): the
    evidence is gone, one full batch goes out alone and a batch past its
    deadline stays open behind it; the next full batch that has to wait
    for a place restores the evidence."""
    with _gated_tile(lane) as (st, prod, cons, sent):
        got: list = []
        n = _fill_window(st, prod, cons, pool, got)      # 3, 16 behind it
        n = _parked_batch(st, prod, cons, pool, got, n)
        sent[0].done = True
        st.after_credit()
        assert _in_flight(st) == 2 and st._full_waited
        for g in sent:          # the hiccup: the thread looks late
            g.done = True
        _spin(st, cons, got)
        assert _in_flight(st) == 0 and not st._full_waited
        n = _full_batch(st, prod, cons, pool, got, n)    # alone
        n = _held_batch(st, prod, cons, pool, got, n)    # room, no evidence
        assert st._window_has_room() and len(sent) == 4
        _feed(prod, pool, n, n + 13)        # it fills: behind at once
        _spin(st, cons, got)
        assert [g.behind for g in sent[3:]] == [0, 1]
        assert not st._full_waited
        n = _parked_batch(st, prod, cons, pool, got, n + 13)
        sent[3].done = True
        st.after_credit()       # the one that waited takes the place
        n = _held_batch(st, prod, cons, pool, got, n)    # a full window
        sent[4].done = True
        st.after_credit()       # room, on the evidence: taken
        assert [(g.n, g.close, g.behind) for g in sent[5:]] \
            == [(16, rv.CLOSE_FULL, 1), (3, rv.CLOSE_WINDOW, 1)]
        for g in sent:
            g.done = True
        _spin(st, cons, got)
        assert got == list(pool[:n])
        assert sum(_closes(st)) == st.metrics.get("batches") == 7


@pytest.mark.parametrize("how", ["flush", "short_sweep"])
@pytest.mark.parametrize("lane", LANES)
def test_what_a_trailing_thread_holds_is_released_as_before(
        lane, how, pool):
    """(iv) The batch that stays open behind a running full one for
    want of evidence is still sent by flush(), behind whatever runs, and
    by the first short sweep once the window has emptied."""
    with _gated_tile(lane, batch=8) as (st, prod, cons, sent):
        got: list = []
        n = _trailing_thread(st, prod, cons, pool, got, sent)
        n = _full_sweeps(st, prod, pool, n, 1)
        _overdue(st)
        sent[0].done = True
        st.after_credit()       # room behind the full batch: not taken
        assert len(sent) == 2 and _open_elems(st) == 4 and _held(st) == 0
        if how == "flush":
            st.flush()          # blocks on the heads: no gate needed
            want = (4, rv.CLOSE_DEADLINE, 1)
        else:
            sent[1].done = True
            st.after_credit()   # the window empties: the backlog holds it
            assert len(sent) == 2 and _held(st) == 1
            n = _short_sweep(st, prod, pool, n)
            st.after_credit()
            want = (4, rv.CLOSE_WINDOW, 0)
        assert [(g.n, g.close, g.behind) for g in sent[2:]] == [want]
        for g in sent:
            g.done = True
        _spin(st, cons, got)
        assert got == list(pool[:n])
        assert sum(_closes(st)) == st.metrics.get("batches") == 3


@pytest.mark.parametrize("lane", LANES)
def test_flush_sends_a_batch_the_backlog_held(lane, pool):
    with _gated_tile(lane) as (st, prod, cons, sent):
        st.burst = BURST
        got: list = []
        n = _full_sweeps(st, prod, pool, 0, 3)
        _overdue(st)
        assert _held(st) == 1 and sent == [] and _open_elems(st) == 12
        st.flush()             # blocks on the head: no gate needed
        _spin(st, cons, got)
        assert got == list(pool[:n])
        assert [(g.n, g.behind) for g in sent] == [(12, 0)]
        assert _closes(st) == [0, 1, 0] and _held(st) == 1


# What the rule of PR 32 (the parent of ISSUE 36) dispatches under a paced
# feed, every sweep short of its burst, as (lanes, close reason, batches
# in flight ahead) per batch: recorded from the parent commit with the
# scripts below, the same on every lane — but for the two cases that
# ISSUE 40 re-aimed: behind a full batch that found room at once the
# batch past its deadline now stays open until the reap; behind one that
# had to wait for its place it is queued as it was.  "feed" k offers and sweeps them
# (k < 16 = the burst: a full batch is fed as 15 + 1); "late" lets the
# deadline pass; "reap" lets the oldest batch in flight come back.
_F, _D, _W = rv.CLOSE_FULL, rv.CLOSE_DEADLINE, rv.CLOSE_WINDOW
PACED_CASES = {
    "alone_at_the_deadline": (
        [("feed", 5), ("late",), ("reap",)],
        [(5, _D, 0)]),
    "held_by_one_in_flight": (
        [("feed", 3), ("late",), ("feed", 3), ("late",), ("feed", 2),
         ("reap",)],
        [(3, _D, 0), (5, _W, 0)]),
    "one_after_another": (
        [("feed", 2), ("late",), ("reap",), ("feed", 2), ("late",)],
        [(2, _D, 0), (2, _D, 0)]),
    "fills_behind_a_running_one": (
        [("feed", 3), ("late",), ("feed", 15), ("feed", 1)],
        [(3, _D, 0), (16, _F, 1)]),
    "held_behind_a_full_one_that_found_room_at_once": (
        [("feed", 3), ("late",), ("feed", 15), ("feed", 1), ("reap",),
         ("feed", 3), ("late",), ("reap",)],
        [(3, _D, 0), (16, _F, 1), (3, _W, 0)]),
    "queued_behind_a_full_one_that_waited_for_its_place": (
        [("feed", 3), ("late",), ("feed", 15), ("feed", 1), ("feed", 15),
         ("feed", 1), ("reap",), ("reap",), ("feed", 3), ("late",)],
        [(3, _D, 0), (16, _F, 1), (16, _F, 1), (3, _D, 1)]),
    "a_full_window_holds_the_sealed_and_the_open": (
        [("feed", 3), ("late",), ("feed", 15), ("feed", 1), ("feed", 15),
         ("feed", 1), ("feed", 4), ("late",), ("reap",), ("reap",)],
        [(3, _D, 0), (16, _F, 1), (16, _F, 1), (4, _W, 1)]),
}


@pytest.mark.parametrize("case", sorted(PACED_CASES))
@pytest.mark.parametrize("lane", LANES)
def test_a_paced_feed_closes_its_batches_as_before(lane, case, pool):
    """Short sweeps throughout: the backlog never holds, and the seals
    and dispatches are the parent's, case by case."""
    script, want = PACED_CASES[case]
    with _gated_tile(lane) as (st, prod, cons, sent):
        assert st.burst == 16
        got: list = []
        n = reaped = 0
        for op, *arg in script:
            if op == "feed":
                _feed(prod, pool, n, n + arg[0])
                n += arg[0]
                _spin(st, cons, got, loops=8)
            elif op == "late":
                _past_deadline(st, cons, got)
            else:
                sent[reaped].done = True
                reaped += 1
                st.after_credit()
            assert not st.backlogged
        assert [(g.n, g.close, g.behind) for g in sent] == want
        assert _held(st) == 0
        for g in sent:
            g.done = True
        st.flush()
        _spin(st, cons, got)
        assert got == list(pool[:n])


@pytest.mark.parametrize("held", [False, True])
@pytest.mark.parametrize("lane", LANES)
def test_flush_seals_whatever_is_in_flight(lane, held, pool):
    with _gated_tile(lane, batch_deadline_s=0.001 if held else 10.0) \
            as (st, prod, cons, sent):
        got: list = []
        if held:
            n = _fill_window(st, prod, cons, pool, got)
            n = _held_batch(st, prod, cons, pool, got, n, 4)
        else:
            n = 4
            _feed(prod, pool, 0, n)
            _spin(st, cons, got)
        assert _open_elems(st) == 4 and len(sent) == (2 if held else 0)
        st.flush()                  # blocks on the heads: no gate needed
        _spin(st, cons, got)
        assert got == list(pool[:n])
        assert [g.n for g in sent] == ([3, 16, 4] if held else [4])
        # what flush() seals counts as a deadline close and may be
        # dispatched behind whatever runs
        assert _closes(st) == ([1, 2, 0] if held else [0, 1, 0])
        assert sum(_closes(st)) == st.metrics.get("batches")
        assert st.metrics.get(QUEUED_BEHIND) == (2 if held else 0)


def test_the_native_seal_hands_its_reason_back(pool):
    with _tile("native") as (st, prod, _cons):
        c = st._sweep_client
        assert c.open_since_ns() == 0 and not c.sealed_waiting()
        t0 = time.monotonic_ns()
        _feed(prod, pool, 0, 20)               # 16 fill a slot, 4 open
        for _ in range(4):
            st._native_sweep(st._native_drainer())
        assert c.sealed_waiting() and c.open_elems() == 4
        assert t0 <= c.open_since_ns() <= time.monotonic_ns()
        c.seal(rv.CLOSE_WINDOW)
        assert c.open_since_ns() == 0
        first, second = c.take_sealed(), c.take_sealed()
        assert (first[1], first[5]) == (16, rv.CLOSE_FULL)
        assert (second[1], second[5]) == (4, rv.CLOSE_WINDOW)
        assert second[3] >= first[3]
        c.release(first[0])
        c.release(second[0])


@pytest.mark.parametrize("lane", LANES)
def test_slotreport_and_monitor_show_the_close_counters(lane, pool):
    """Beside the stalls: the three close counters and
    batch_queued_behind, through the registry a scraper reads (schema ->
    Prometheus), the monitor's table and slotreport's stage block."""
    from firedancer_tpu.runtime import monitor as mon

    with _gated_tile(lane) as (st, prod, cons, sent):
        got: list = []
        n = _fill_window(st, prod, cons, pool, got)   # one queued behind
        for g in sent:
            g.done = True
        _spin(st, cons, got)
        n = _deadline_batch(st, prod, cons, pool, got, n, 1)
        sent[-1].done = True
        st.flush()
        st.metrics.flush()
        reg = st.metrics.registry
        assert reg is not None
        row = fm.batch_close_row([reg])
        assert row == {"full": 1, "deadline": 2, "window": 0,
                       "queued_behind": 1, "held_backlogged": 0,
                       "fit_pad_lanes": 0, "fail_elems": 0, "stalls": 0,
                       "fold_lanes": 0}     # a batch of 16: one axis
        assert sum(row[c] for c in fm.BATCH_CLOSES) \
            == st.metrics.get("batches")
        text = fm.render_prometheus({"v0": reg})
        for k in CLOSE_COUNTERS:
            assert f"{k}{{" in text or f"{k} " in text
        assert f'{QUEUED_BEHIND}{{stage="v0"}} 1' in text
        rendered = mon.MonitorSession.render(
            [{"stage": "v0", "signal": 1, "heartbeat_age_ms": 1.0, "in": 0,
              "out": 0, "overrun": 0, "backpressure": 0, "iters": 1,
              "batch_closes": row,
              "mesh": fm.mesh_row(reg)}], None, 1.0)
        assert "v0: batches closed full=1 deadline=2 window=0" \
               "  queued_behind=1  held_backlogged=0  batch_stalls=0" \
               in rendered
        dump = fm.flight_dump_obj("t", {"v0": (reg, st.recorder)})
        block = slot_report.build_report(dump)["stages"]["v0"]
        assert block["batch_closes"] == {c: row[c] for c in fm.BATCH_CLOSES}
        assert block[QUEUED_BEHIND] == 1 and block[HELD_BACKLOGGED] == 0
        # how the program lays its batch, in the same three places
        assert f'{fm.KERNEL_FOLD_LANES}{{stage="v0"}} 0' in text
        assert "  kernel_fold_lanes=0" in rendered
        assert block[fm.KERNEL_FOLD_LANES] == 0
        # over a mesh: how many chips and the useful lanes of each, in
        # the same three places; with one device, in none
        if lane in MESH_LANES:
            shards = [st.metrics.get(f"shard_elems_s{i}")
                      for i in range(MESH_DEVICES)]
            assert sum(shards) == st.metrics.get("batch_elems") == 20
            assert block["mesh"] == {"devices": MESH_DEVICES,
                                     "shard_elems": shards}
            assert f"v0: mesh of {MESH_DEVICES} chips, useful lanes " \
                + " ".join(f"s{i}={v:,}" for i, v in enumerate(shards)) \
                in rendered
            assert 'mesh_devices{stage="v0"} 4' in text
            assert f'shard_elems_s0{{stage="v0"}} {shards[0]}' in text
        else:
            assert "mesh" not in block and "mesh of" not in rendered
            assert 'mesh_devices{stage="v0"} 1' in text
    assert fm.batch_close_row([Stage("s").metrics.registry]) is None
    assert QUEUED_BEHIND not in slot_report.build_report(
        fm.flight_dump_obj("t", {"s": (Stage("s").metrics.registry, None)})
    )["stages"].get("s", {})


# -- how deep the in-flight window is (ISSUE 27, ISSUE 32) ---------------------------
#
# Two: one batch running and, if it is full, one queued behind it,
# whatever the caller asked for above that.  One test of the depth
# (_window_has_room) on every lane, and nothing in a lane rests on the two.


@pytest.mark.parametrize("asked", [None, 8])
@pytest.mark.parametrize("lane", LANES)
def test_the_window_is_two_deep_whatever_was_asked_above_that(
        lane, asked, pool):
    """A full batch is dispatched behind a running one, nothing behind
    the two however dry the device has run and however often, and a
    batch that is not full behind a full one that had to wait, once
    there is room."""
    with _gated_tile(lane, max_inflight=asked) as (st, prod, cons, sent):
        assert st.max_inflight == rv.WINDOW_DEPTH == 2
        got: list = []
        n = 0
        for k in range(2):
            # the window fills: a batch at its deadline, a full one
            # behind it ...
            n = _deadline_batch(st, prod, cons, pool, got, n)
            n = _full_batch(st, prod, cons, pool, got, n)
            # ... a second full one waits for its place, and a batch is
            # held open behind them; never a third in flight
            n = _parked_batch(st, prod, cons, pool, got, n)
            n = _held_batch(st, prod, cons, pool, got, n, parked=True)
            assert _in_flight(st) == 2 and len(sent) == 4 * k + 2
            # the head is reaped: the sealed batch takes its place
            sent[-2].done = True
            _spin(st, cons, got)
            assert len(sent) == 4 * k + 3 and _in_flight(st) == 2
            assert _open_elems(st) == 3 and st._full_waited
            # the next is reaped: the full batch that waited runs, there
            # is room behind it, and the held batch takes it
            sent[-2].done = True
            _spin(st, cons, got)
            assert len(sent) == 4 * k + 4 and _in_flight(st) == 2
            assert _open_elems(st) == 0
            assert [g.behind for g in sent[-4:]] == [0, 1, 1, 1]
            for g in sent:
                g.done = True
            _spin(st, cons, got)
            assert got == list(pool[:n]) and _in_flight(st) == 0
            assert _closes(st) == [2 * (k + 1), k + 1, k + 1]
            assert st.metrics.get(QUEUED_BEHIND) == 3 * (k + 1)
        assert _deepest(st) == 2
        assert sum(_closes(st)) == st.metrics.get("batches") == 8
        assert st.metrics.get("txn_verified") == n


@pytest.mark.parametrize("lane", LANES)
def test_a_window_of_one_still_runs(lane, pool):
    with _gated_tile(lane, max_inflight=1) as (st, prod, cons, sent):
        got: list = []
        n = _deadline_batch(st, prod, cons, pool, got, 0, 3)
        n = _held_batch(st, prod, cons, pool, got, n)
        sent[0].done = True
        st.after_credit()
        assert [g.n for g in sent] == [3, 3] and _closes(st) == [0, 1, 1]
        sent[1].done = True
        _spin(st, cons, got)
        assert got == list(pool[:n]) and _deepest(st) == 1
        assert st.metrics.get(QUEUED_BEHIND) == 0


@pytest.mark.parametrize("depth", [3, 5])
@pytest.mark.parametrize("lane", LANES)
def test_a_deeper_window_reaps_and_publishes_in_dispatch_order(
        lane, depth, pool, monkeypatch):
    """Nothing in a lane rests on the depth being two: with `depth` real
    batches in flight (full ones behind the first) and the later ones
    finished first, nothing leaves past the head, and what leaves is in
    dispatch order."""
    monkeypatch.setattr(rv, "WINDOW_DEPTH", depth)
    with _gated_tile(lane) as (st, prod, cons, sent):
        assert st.max_inflight == depth
        got: list = []
        n = _deadline_batch(st, prod, cons, pool, got, 0)
        for _ in range(depth - 1):
            n = _full_batch(st, prod, cons, pool, got, n)
        flown = n
        n = _held_batch(st, prod, cons, pool, got, n)
        assert len(sent) == depth and _deepest(st) == depth
        # the later batches finish ahead of the head: nothing comes out,
        # no slot is freed
        for g in sent[1:]:
            g.done = True
        _spin(st, cons, got)
        assert got == [] and len(sent) == depth
        assert st.metrics.get("txn_verified") == 0
        # the head finishes: the whole window leaves in dispatch order,
        # and the held batch goes at a freed slot (behind the full
        # batches still in flight, where the lane reaps them one by one)
        sent[0].done = True
        _spin(st, cons, got)
        assert got == list(pool[:flown])
        assert [g.n for g in sent] == [2] + [16] * (depth - 1) + [3]
        assert _closes(st) == [depth - 1, 1, 1]
        assert depth - 1 <= st.metrics.get(QUEUED_BEHIND) <= depth
        sent[-1].done = True
        _spin(st, cons, got)
        assert got == list(pool[:n])
        assert sum(_closes(st)) == st.metrics.get("batches") == depth + 1
        assert _deepest(st) == depth


# -- the books of the window under mixed traffic (ISSUE 32) --------------------------


@pytest.mark.parametrize("lane", LANES)
def test_the_windows_books_hold_under_bursts_of_every_size(lane, pool):
    """Bursts from one transaction to more than a batch's worth, results
    that come ready a round late: never more than two in flight, a
    batch that is not full behind a full one only, the close counters
    add up, and what leaves is what entered, in order."""
    with _gated_tile(lane) as (st, prod, cons, sent):
        got: list = []
        fed = 0
        bursts = [1, 16, 3, 20, 2, 16, 16, 5, 17]
        assert sum(bursts) == len(pool)
        for burst in bursts:
            earlier = len(sent)    # still in flight when the burst lands
            _feed(prod, pool, fed, fed + burst)
            fed += burst
            for it in range(80):
                if it == 20:       # the open batch's deadline passes
                    time.sleep(st.batch_deadline_s * 3)
                if it in (40, 60):  # an earlier round's head comes ready
                    late = [g for g in sent[:earlier] if not g.done]
                    if late:
                        late[0].done = True
                st.run_once()
                _collect(cons, got)
                assert _in_flight(st) <= 2
        before_flush = len(sent)
        for g in sent:
            g.done = True
        st.flush()
        _spin(st, cons, got)
        assert got == list(pool)
        # behind another batch went only a full one, or the batch right
        # behind a full one
        for ahead, g in zip(sent, sent[1:before_flush]):
            assert not g.behind or rv.CLOSE_FULL in (g.close, ahead.close)
        assert all(g.behind in (0, 1) for g in sent)
        c = st.metrics.get
        assert c(QUEUED_BEHIND) == sum(g.behind for g in sent)
        assert sum(_closes(st)) == c("batches") == len(sent)
        assert sum(g.n for g in sent) == c("batch_elems") == len(pool)
        assert _deepest(st) == 2
        assert c("batch_close_full") >= 4 and c("batch_close_window") >= 2
        # a batch held behind one that was not full (the 2 behind the 4)
        assert any(g.close == rv.CLOSE_WINDOW and not g.behind for g in sent)
