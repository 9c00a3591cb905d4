"""The thread's ledger (ISSUE 37): `Stage.run_once` charges every call
whole to one regime (work, an empty poll, housekeeping), and the verify
stage stamps when the chip had nothing of its to run and whose time that
was, through `_phase_end` alone.

Everything runs on the CPU on a scripted clock: each read of the
patched `time.monotonic_ns()` moves it one step on, and the test moves
it further between calls (the other stages' time).  The clock starts
far ahead of the real one and outruns it, so the native lane's own open
and seal stamps (CLOCK_MONOTONIC, in C) always lie behind it.  Nothing
compiles: the lanes under test are the host's.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np
import pytest

from firedancer_tpu.runtime import slot_report
from firedancer_tpu.runtime import stage as rs
from firedancer_tpu.runtime import verify as rv
from firedancer_tpu.runtime import verify_native as vn
from firedancer_tpu.runtime.benchg import gen_transfer_pool
from firedancer_tpu.runtime.poh_stage import PohStage
from firedancer_tpu.runtime.stage import Stage
from firedancer_tpu.runtime.verify import VerifyStage
from firedancer_tpu.tango import shm
from firedancer_tpu.utils import metrics as fm

STEP = 1_000_000        # one clock read: 1 ms of scripted time
REGIMES = ("loop_work_ns", "loop_poll_ns", "loop_backp_ns", "loop_hk_ns")
CHIP = list(fm.CHIP_EMPTY_COUNTERS)
# the verify lanes: "mesh" is the native lane in front of two (virtual)
# devices
LANES = ["native", "python", "mesh"]
NATIVE_LANES = ("native", "mesh")
# the three intake paths of Stage.run_once
INTAKES = ["python_burst", "native_burst", "native_sweep"]


class Clock:
    """time.monotonic_ns() on a script: every read is one STEP later
    than the last, `away()` moves it on between calls."""

    def __init__(self):
        self.t = time.monotonic_ns() + 3_600 * 10**9
        self.reads = 0

    def __call__(self) -> int:
        self.t += STEP
        self.reads += 1
        return self.t

    def away(self, ns: int) -> None:
        self.t += ns


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(rs, "_now_ns", c)
    monkeypatch.setattr(rv, "_now_ns", c)
    return c


@pytest.fixture(scope="module")
def pool():
    return gen_transfer_pool(96, n_payers=12, n_dests=64)


def _ledger(st) -> dict:
    return {k: st.metrics.get(k) for k in fm.LOOP_COUNTERS}


def _call(st, trail: list, clock=None, away_ns: int = 0) -> dict:
    """One run_once after `away_ns` elsewhere -> what it added to the
    ledger; its (entry, exit) goes on `trail`."""
    if away_ns:
        clock.away(away_ns)
    before = _ledger(st)
    st.run_once()
    trail.append((st._loop_entry_ns, st._loop_exit_ns))
    after = _ledger(st)
    return {k: after[k] - before[k] for k in after}


def _no_housekeeping(st) -> None:
    st._next_housekeeping = 1 << 62


class _Relay(Stage):
    """Forwards every frag; counts what it saw."""

    def after_frag(self, in_idx, meta, payload):
        self.publish(0, bytes(payload), sig=int(meta[1]))


@contextlib.contextmanager
def _staged(intake: str, *, out_depth: int = 64):
    """A stage whose run_once takes `intake`'s path, over real rings ->
    (stage, producer into it, consumer behind it, frames to feed)."""
    python = intake == "python_burst"
    if not python and not shm.native_ring_enabled():
        pytest.skip("native ring lane unavailable")
    if intake == "native_sweep" and not vn.available():
        pytest.skip("native verify client unavailable")
    uid = shm.fresh_uid()
    lin = shm.ShmLink.create(f"tl_i_{uid}", depth=64, mtu=1232, n_fseq=1)
    lout = shm.ShmLink.create(f"tl_o_{uid}", depth=out_depth, mtu=4096,
                              n_fseq=1)
    st = None
    try:
        with pytest.MonkeyPatch.context() as env:
            env.setenv("FDTPU_NATIVE_RING", "0" if python else "1")
            env.setenv(vn.ENV_SWITCH, "1")
            ins = [shm.make_consumer(lin, lazy=8)]
            outs = [shm.make_producer(lout)]
            prod = shm.make_producer(lin)
            cons = shm.make_consumer(lout, lazy=4)
            if intake == "native_sweep":
                # the verify stage's C client: the whole sweep in one
                # crossing; the all-pass mask keeps JAX out of it
                st = VerifyStage("s", ins=ins, outs=outs, batch=16,
                                 max_msg_len=256, batch_deadline_s=1e7,
                                 precomputed_ok=True)
                assert st._sweep_client is not None
                frames = gen_transfer_pool(8, n_payers=4, n_dests=4)
            else:
                st = _Relay("s", ins=ins, outs=outs)
                frames = [bytes([i]) * 40 for i in range(8)]
        want = {"python_burst": None, "native_burst": "BurstDrainer",
                "native_sweep": "SweepDrainer"}[intake]
        got = st._native_drainer()
        assert (type(got).__name__ if got is not None else None) == want
        yield st, prod, cons, frames
    finally:
        if st is not None:
            st.ins, st.outs = [], []
            st.drop_native_views()
        lin.close()
        lout.close()


# -- A. every call of the loop is charged to one regime -----------------------


def test_the_ledger_is_in_every_stages_schema_and_starts_at_zero():
    st = Stage("s")
    assert set(fm.LOOP_COUNTERS) <= st.metrics_schema().names()
    assert set(fm.LOOP_COUNTERS) <= VerifyStage.metrics_schema().names()
    assert all(st.metrics.counters[k] == 0 for k in fm.LOOP_COUNTERS)
    text = fm.render_prometheus(
        {"s": fm.MetricsRegistry(st.metrics_schema())})
    assert all(f'{k}{{stage="s"}} 0' in text for k in fm.LOOP_COUNTERS)


@pytest.mark.parametrize("intake", INTAKES)
def test_a_call_that_consumes_is_work_and_an_empty_one_a_poll(intake, clock):
    with _staged(intake) as (st, prod, _cons, frames):
        _no_housekeeping(st)
        trail: list = []
        d = _call(st, trail)                            # nothing there
        assert (d["loop_poll_n"], d["loop_work_n"]) == (1, 0)
        assert d["loop_poll_ns"] == trail[-1][1] - trail[-1][0] > 0
        assert d["loop_work_ns"] == d["loop_hk_ns"] == 0
        for i, f in enumerate(frames[:3]):
            assert prod.try_publish(f, sig=i, tsorig=0)
        d = _call(st, trail)                            # three frags
        assert st.metrics.get("frags_in") == 3
        assert (d["loop_work_n"], d["loop_poll_n"]) == (1, 0)
        assert d["loop_work_ns"] == trail[-1][1] - trail[-1][0] > 0
        assert d["loop_poll_ns"] == d["loop_hk_ns"] == 0
        d = _call(st, trail)                            # dry again
        assert (d["loop_poll_n"], d["loop_work_n"]) == (1, 0)
        assert not st._loop_worked


@pytest.mark.parametrize("intake", INTAKES[:2])
def test_a_credit_gated_return_with_a_frag_in_front_is_backpressure(
        intake, clock):
    """A stage that may not consume what it cannot forward, with no
    credit downstream: the frags stay in the ring in front, and the
    call is charged to backpressure, not to the polls —
    `backpressure_stall` counts it — on the two clock reads a call
    always makes."""
    with _staged(intake, out_depth=4) as (st, prod, cons, frames):
        _no_housekeeping(st)
        st.require_credit = True
        trail: list = []
        for i, f in enumerate(frames):
            assert prod.try_publish(f, sig=i, tsorig=0)
        for _ in range(4):
            _call(st, trail)
        assert st.metrics.get("frags_in") == 4      # the out ring is full
        stalls = st.metrics.get("backpressure_stall")
        reads = clock.reads
        d = _call(st, trail)
        assert clock.reads - reads == 2             # no clock read added
        assert st.metrics.get("frags_in") == 4
        assert (d["loop_backp_n"], d["loop_poll_n"], d["loop_work_n"]) \
            == (1, 0, 0)
        assert d["loop_backp_ns"] == trail[-1][1] - trail[-1][0] > 0
        assert d["loop_poll_ns"] == d["loop_work_ns"] == 0
        if intake == "python_burst":    # the native path is cut to 0 frags
            assert st.metrics.get("backpressure_stall") == stalls + 1
        _drained(cons)                                  # credits again
        cons.publish_progress()
        d = _call(st, trail)
        assert st.metrics.get("frags_in") > 4
        assert (d["loop_work_n"], d["loop_backp_n"]) == (1, 0)


@pytest.mark.parametrize("intake", INTAKES[:2])
def test_a_blocked_stage_with_nothing_in_front_polls(intake, clock):
    """The same stage, out ring full, once the ring in front has run
    dry: it is starved as much as blocked, and that is a poll."""
    with _staged(intake, out_depth=4) as (st, prod, cons, frames):
        _no_housekeeping(st)
        st.require_credit = True
        trail: list = []
        for i, f in enumerate(frames[:4]):
            assert prod.try_publish(f, sig=i, tsorig=0)
        for _ in range(4):
            _call(st, trail)
        assert st.metrics.get("frags_in") == 4      # out full, in empty
        d = _call(st, trail)
        assert (d["loop_poll_n"], d["loop_backp_n"], d["loop_work_n"]) \
            == (1, 0, 0)
        assert d["loop_poll_ns"] == trail[-1][1] - trail[-1][0] > 0
        # an idle stage with credits: a poll too
        _drained(cons)
        cons.publish_progress()
        d = _call(st, trail)
        assert (d["loop_poll_n"], d["loop_backp_n"]) == (1, 0)


@pytest.mark.parametrize("intake", INTAKES[:2])
def test_a_stage_without_room_is_held_like_one_without_credits(intake,
                                                               clock):
    """`intake_room`, a stage's own bound (pack's pool): 0 leaves the
    ring in front unpolled and charges backpressure; a number under
    the burst caps the sweep; None is the burst."""
    with _staged(intake) as (st, prod, cons, frames):
        _no_housekeeping(st)
        trail: list = []
        for i, f in enumerate(frames):
            assert prod.try_publish(f, sig=i, tsorig=0)
        st.intake_room = 0
        d = _call(st, trail)
        assert st.metrics.get("frags_in") == 0
        assert (d["loop_backp_n"], d["loop_poll_n"]) == (1, 0)
        st.intake_room = 3
        d = _call(st, trail)
        assert st.metrics.get("frags_in") == 3 and d["loop_work_n"] == 1
        st.intake_room = None
        _call(st, trail)
        assert st.metrics.get("frags_in") == len(frames)
        assert _drained(cons) == len(frames)


@pytest.mark.parametrize("intake", INTAKES[:2])
def test_the_four_regimes_add_up_to_the_call_times(intake, clock):
    """Work, backpressure, polls and housekeeping: every call whole in
    one of them (housekeeping taken out of the call it ran in), so the
    four sums are the calls' entry-to-exit times."""
    with _staged(intake, out_depth=4) as (st, prod, cons, frames):
        st.require_credit = True
        st._next_housekeeping = 3       # a pass falls among the calls
        trail: list = []
        total = {k: 0 for k in fm.LOOP_COUNTERS}
        for step in range(12):
            if step == 1:
                for i, f in enumerate(frames):
                    assert prod.try_publish(f, sig=i, tsorig=0)
            if step == 8:
                _drained(cons)
                cons.publish_progress()
            for k, v in _call(st, trail, clock, away_ns=7 * STEP).items():
                total[k] += v
        assert all(total[k] > 0 for k in (
            "loop_work_ns", "loop_backp_ns", "loop_poll_ns", "loop_hk_ns"))
        assert sum(total[k] for k in total if k.endswith("_ns")) \
            == sum(b - a for a, b in trail)
        assert total["loop_work_n"] + total["loop_backp_n"] \
            + total["loop_poll_n"] == len(trail)


@pytest.mark.parametrize("intake", INTAKES)
def test_housekeeping_is_taken_out_of_the_call_it_ran_in(intake, clock):
    with _staged(intake) as (st, prod, _cons, frames):
        trail: list = []
        for fed in (0, 2):              # in a poll, then in a working call
            for i in range(fed):
                assert prod.try_publish(frames[i], sig=i, tsorig=0)
            st._next_housekeeping = st._iter + 1
            reads = clock.reads
            d = _call(st, trail)
            entry, exit_ = trail[-1]
            assert st._next_housekeeping > st._iter     # the pass ran
            assert d["loop_hk_ns"] > 0
            rest = d["loop_work_ns"] + d["loop_poll_ns"]
            assert d["loop_hk_ns"] + rest == exit_ - entry
            assert (d["loop_work_n"], d["loop_poll_n"]) == \
                ((1, 0) if fed else (0, 1))
            assert (d["loop_work_ns"] > 0) == bool(fed)
            # entry, the pass's end, exit: the ledger's three reads
            assert clock.reads - reads >= 3


def test_a_publish_from_a_hook_is_work_by_the_stages_own_frags_out(clock):
    """The benchmark's generator offers from after_credit and counts
    `frags_out` itself: run_once sees the count move."""

    class Gen(Stage):
        offers = 0

        def after_credit(self):
            if self.offers:
                self.offers -= 1
                self.metrics.inc("frags_out", 5)

    st = Gen("gen")
    _no_housekeeping(st)
    trail: list = []
    assert _call(st, trail)["loop_poll_n"] == 1
    st.offers = 2
    assert _call(st, trail)["loop_work_n"] == 1
    d = _call(st, trail)
    assert (d["loop_work_n"], d["loop_poll_n"]) == (1, 0)
    assert _call(st, trail)["loop_poll_n"] == 1


def test_a_hook_that_works_without_a_frag_says_so_through_one_flag(clock):
    class Ticker(Stage):
        due = False

        def before_credit(self):
            if self.due:
                self.due = False
                self._loop_worked = True

    st = Ticker("t")
    _no_housekeeping(st)
    trail: list = []
    assert _call(st, trail)["loop_poll_n"] == 1
    st.due = True
    d = _call(st, trail)
    assert (d["loop_work_n"], d["loop_poll_n"]) == (1, 0)
    assert st._loop_worked is False                     # read and cleared
    assert _call(st, trail)["loop_poll_n"] == 1


def test_pohs_hashes_and_ticks_are_work_and_a_stopped_clock_a_poll(clock):
    uid = shm.fresh_uid()
    link = shm.ShmLink.create(f"tl_p_{uid}", depth=64, mtu=4096, n_fseq=1)
    try:
        poh = PohStage("poh", outs=[shm.make_producer(link)],
                       hashes_per_tick=64, hashes_per_iter=16)
        _no_housekeeping(poh)
        trail: list = []
        for k in range(4):              # 3 x 16 hashes, then the tick
            d = _call(poh, trail)
            assert (d["loop_work_n"], d["loop_poll_n"]) == (1, 0)
        assert poh.metrics.get("ticks") == 1
        assert poh.metrics.get("frags_out") == 1
        poh.hashes_per_iter = 0         # drain mode: the clock stopped
        d = _call(poh, trail)
        assert (d["loop_work_n"], d["loop_poll_n"]) == (0, 1)
        poh.ins, poh.outs = [], []
    finally:
        link.close()


@pytest.mark.parametrize("intake", INTAKES)
def test_the_regimes_add_up_to_the_loop_less_the_time_outside(intake, clock):
    with _staged(intake) as (st, prod, cons, frames):
        st.lazy = 4                     # a housekeeping pass every few
        st._next_housekeeping = 0
        trail: list = []
        outside = 0
        for k in range(40):
            if k % 5 == 0:
                assert prod.try_publish(frames[k % len(frames)], sig=k,
                                        tsorig=0)
            gap = (k % 3) * 7 * STEP
            _call(st, trail, clock, away_ns=gap)
            _drained(cons)
        led = _ledger(st)
        assert led["loop_work_n"] + led["loop_poll_n"] == 40
        assert led["loop_work_n"] >= 8 and led["loop_hk_ns"] > 0
        outside = sum(b[0] - a[1] for a, b in zip(trail, trail[1:]))
        assert outside >= sum((k % 3) * 7 * STEP for k in range(1, 40))
        assert sum(led[k] for k in REGIMES) \
            == trail[-1][1] - trail[0][0] - outside
        # per stage, the ledger never exceeds the time there was
        assert sum(led[k] for k in REGIMES) <= trail[-1][1] - trail[0][0]


# -- B. when the chip had nothing of the stage's to run ----------------------


class _Gated:
    """A device future that is ready when the test says."""

    def __init__(self, lanes):
        self.mask = np.ones((lanes,), dtype=bool)
        self.done = False

    def is_ready(self):
        return self.done

    def __array__(self, dtype=None, copy=None):
        return self.mask


@contextlib.contextmanager
def _verify_tile(lane: str, **stage_kw):
    """A VerifyStage over native rings whose dispatches hand back
    _Gated futures -> (stage, producer, consumer, futures sent, lives
    in dispatch order)."""
    if lane in NATIVE_LANES and not vn.available():
        pytest.skip("native verify client unavailable")
    import jax.profiler  # noqa: F401  (the span's import, off the clock)

    uid = shm.fresh_uid()
    lin = shm.ShmLink.create(f"tl_i_{uid}", depth=256, mtu=1232, n_fseq=1)
    lout = shm.ShmLink.create(f"tl_o_{uid}", depth=256, mtu=4096, n_fseq=1)
    st = None
    try:
        kw = dict(batch=16, max_msg_len=256, batch_deadline_s=1e7,
                  precomputed_ok=False)
        if lane == "mesh":
            kw["devices"] = 2
        kw.update(stage_kw)
        with pytest.MonkeyPatch.context() as env:
            env.setenv(vn.ENV_SWITCH, "1" if lane in NATIVE_LANES else "0")
            st = VerifyStage("v0", ins=[shm.make_consumer(lin, lazy=8)],
                             outs=[shm.make_producer(lout)], **kw)
        assert (st._sweep_client is not None) == (lane in NATIVE_LANES)
        assert st.mesh_devices == (2 if lane == "mesh" else 1)
        _no_housekeeping(st)
        sent: list = []
        lives: list = []

        def dispatch(life, rows):
            st._phase_end(life, rv.PH_H2D)
            sent.append(_Gated(len(rows)))
            lives.append(life)
            return sent[-1]

        if not st.precomputed_ok:
            st._device_verify = dispatch
        yield st, shm.make_producer(lin), shm.make_consumer(lout, lazy=4), \
            sent, lives
    finally:
        if st is not None:
            st.ins, st.outs = [], []
            st.drop_native_views()
        lin.close()
        lout.close()


def _feed(prod, pool, lo: int, n: int) -> int:
    for i in range(lo, lo + n):
        assert prod.try_publish(pool[i], sig=i, tsorig=0)
    return lo + n


def _drained(cons) -> int:
    n = 0
    while cons.poll() not in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
        n += 1
    return n


def _chip(st) -> dict:
    return {k[len("chip_empty_"):]: st.metrics.get(k) for k in CHIP}


def _end_of(life, phase: int) -> int:
    return life.t[phase + 1]


@pytest.mark.parametrize("lane", LANES)
def test_chip_counters_are_in_the_schema_and_start_at_zero(lane):
    with _verify_tile(lane) as (st, _p, _c, _sent, _lives):
        assert set(CHIP) <= st.metrics.schema.names()
        assert all(st.metrics.counters[k] == 0 for k in CHIP)


@pytest.mark.parametrize("lane", LANES)
def test_one_batch_at_a_time_from_the_reaps_sight_to_the_next_launch(
        lane, pool, clock):
    with _verify_tile(lane) as (st, prod, cons, sent, lives):
        trail: list = []
        fed = _feed(prod, pool, 0, 16)          # a full batch: goes at once
        for _ in range(3):
            _call(st, trail, clock, away_ns=5 * STEP)
        assert len(sent) == 1 and len(st._flying()) == 1
        assert _chip(st) == {"ns": 0, "n": 0, "call_ns": 0, "away_ns": 0}
        # the chip finishes; the loop sees it, reaps and publishes: the
        # chip has nothing to run from here
        sent[0].done = True
        _call(st, trail, clock, away_ns=5 * STEP)
        assert not st._flying() and _drained(cons) == 16
        since = _end_of(lives[0], rv.PH_INFLIGHT)
        assert st._chip_empty_since == since
        first_inside = len(trail)               # the calls that begin in it
        # ... while the next batch fills, the thread elsewhere in between
        gaps = [11 * STEP, 3 * STEP, 29 * STEP, 7 * STEP]
        for k, gap in enumerate(gaps):
            if k < 2:
                fed = _feed(prod, pool, fed, 8)
            _call(st, trail, clock, away_ns=gap)
        assert len(sent) == 2                   # full again: dispatched
        assert _chip(st)["n"] == 1 and st._chip_empty_since == 0
        got = _chip(st)
        # from the reap's sight to the end of the next launch
        assert got["ns"] == _end_of(lives[1], rv.PH_LAUNCH) - since
        # the other stages' time: exit -> entry of the calls that began
        # while the chip was empty, up to the one that dispatched
        launched = _end_of(lives[1], rv.PH_LAUNCH)
        inside = [k for k in range(first_inside, len(trail))
                  if trail[k][0] < launched]
        away = sum(trail[k][0] - trail[k - 1][1] for k in inside)
        assert got["away_ns"] == away >= sum(gaps[:len(inside)])
        # this stage's own calls in it: the reap and publish of the batch
        # that left, and what it took to send the next one
        assert got["call_ns"] >= (
            _end_of(lives[0], rv.PH_PUBLISH) - since
            + launched - _end_of(lives[1], rv.PH_SEALED_WAIT))
        assert 0 < got["call_ns"] and got["away_ns"] + got["call_ns"] \
            <= got["ns"]
        # and a second interval adds to the first
        sent[1].done = True
        _call(st, trail, clock, away_ns=STEP)
        _feed(prod, pool, fed, 16)
        for _ in range(3):
            _call(st, trail, clock, away_ns=2 * STEP)
        assert len(sent) == 3
        more = _chip(st)
        assert more["n"] == 2 and more["ns"] > got["ns"]
        assert more["away_ns"] + more["call_ns"] <= more["ns"]


@pytest.mark.parametrize("lane", LANES)
def test_two_in_flight_add_nothing_until_the_last_one_lands(lane, pool,
                                                            clock):
    with _verify_tile(lane) as (st, prod, cons, sent, lives):
        trail: list = []
        fed = _feed(prod, pool, 0, 16)
        for _ in range(3):
            _call(st, trail, clock, away_ns=STEP)
        fed = _feed(prod, pool, fed, 16)        # full: queues behind it
        for _ in range(3):
            _call(st, trail, clock, away_ns=STEP)
        assert len(sent) == 2 and len(st._flying()) == 2
        sent[0].done = True                     # the chip runs the second
        for _ in range(2):
            _call(st, trail, clock, away_ns=9 * STEP)
        assert len(st._flying()) == 1 and _drained(cons) == 16
        assert st._chip_empty_since == 0
        fed = _feed(prod, pool, fed, 16)        # a third, behind the second
        for _ in range(3):
            _call(st, trail, clock, away_ns=STEP)
        assert len(sent) == 3 and len(st._flying()) == 2
        assert _chip(st) == {"ns": 0, "n": 0, "call_ns": 0, "away_ns": 0}
        sent[1].done = sent[2].done = True      # both land in one pass
        _call(st, trail, clock, away_ns=STEP)
        assert not st._flying()
        # the chip ran dry when the LAST one was seen done, not before
        assert st._chip_empty_since == _end_of(lives[2], rv.PH_INFLIGHT)
        assert _chip(st)["n"] == 0              # added when it ends


@pytest.mark.parametrize("lane", LANES)
def test_flush_ends_an_interval(lane, pool, clock):
    with _verify_tile(lane) as (st, prod, cons, sent, lives):
        trail: list = []
        fed = _feed(prod, pool, 0, 16)
        for _ in range(3):
            _call(st, trail, clock, away_ns=STEP)
        sent[0].done = True
        _call(st, trail, clock, away_ns=STEP)
        since = st._chip_empty_since
        assert since == _end_of(lives[0], rv.PH_INFLIGHT)
        _feed(prod, pool, fed, 5)               # a batch that will not fill
        _call(st, trail, clock, away_ns=13 * STEP)
        assert len(sent) == 1 and _chip(st)["n"] == 0
        # flush() seals and sends it, then waits for it: make it ready
        # as soon as it is sent

        class Ready(_Gated):
            done = True

            def __init__(self, lanes):
                super().__init__(lanes)
                self.done = True

        def dispatch(life, rows):
            st._phase_end(life, rv.PH_H2D)
            sent.append(Ready(len(rows)))
            lives.append(life)
            return sent[-1]

        st._device_verify = dispatch
        st.flush()
        assert len(sent) == 2 and not st._flying()
        got = _chip(st)
        assert got["n"] == 1
        assert got["ns"] == _end_of(lives[1], rv.PH_LAUNCH) - since
        assert got["away_ns"] + got["call_ns"] <= got["ns"]
        assert got["away_ns"] >= 13 * STEP
        # flush() reaped it too: the chip is empty again, not yet added
        assert st._chip_empty_since == _end_of(lives[1], rv.PH_INFLIGHT)
        assert _drained(cons) == 21


@pytest.mark.parametrize("lane", LANES)
def test_the_all_pass_mask_stamps_nothing(lane, pool, clock):
    with _verify_tile(lane, precomputed_ok=True) as (st, prod, cons, _s, _l):
        trail: list = []
        fed = 0
        for _ in range(3):
            fed = _feed(prod, pool, fed, 16)
            for _ in range(4):
                _call(st, trail, clock, away_ns=3 * STEP)
        assert st.metrics.get("batches") == 3 and _drained(cons) == 48
        assert st.metrics.get("batch_reap_ns") >= 0
        assert _chip(st) == {"ns": 0, "n": 0, "call_ns": 0, "away_ns": 0}
        assert st._chip_empty_since == 0
        # the batches are still work to the thread's ledger
        assert st.metrics.get("loop_work_n") >= 3


@pytest.mark.parametrize("lane", LANES)
def test_the_pump_that_dispatches_or_reaps_is_a_working_call(lane, pool,
                                                             clock):
    """A call in which verify's hooks moved a batch and no frag came in
    or went out is work, through the stage's one flag."""
    with _verify_tile(lane) as (st, prod, cons, sent, _lives):
        trail: list = []
        _feed(prod, pool, 0, 5)
        d = _call(st, trail)                    # intake: work by the frags
        assert d["loop_work_n"] == 1
        assert _call(st, trail)["loop_poll_n"] == 1     # open, not due
        st.batch_deadline_s = 0.0               # due at the next pass
        if st._sweep_client is None:
            time.sleep(0.002)
        d = _call(st, trail)                    # seal + dispatch, no frag
        assert len(sent) == 1
        assert (d["loop_work_n"], d["loop_poll_n"]) == (1, 0)
        assert _call(st, trail)["loop_poll_n"] == 1     # in flight: polls
        sent[0].mask[:] = False                 # every transaction fails:
        sent[0].done = True                     # a reap that publishes none
        d = _call(st, trail)
        assert not st._flying() and _drained(cons) == 0
        assert st.metrics.get("verify_fail") == 5
        assert (d["loop_work_n"], d["loop_poll_n"]) == (1, 0)
        assert _call(st, trail)["loop_poll_n"] == 1


# -- the operator's view -------------------------------------------------------


def test_the_monitors_busy_is_time_and_its_chip_line(clock):
    from firedancer_tpu.runtime import monitor as mon

    ns = dict(work_ns=30, poll_ns=40, backp_ns=20, hk_ns=10)
    assert fm.loop_shares(ns) == {"busy_pct": pytest.approx(30.0),
                                  "backp_pct": pytest.approx(20.0),
                                  "poll_pct": pytest.approx(40.0)}
    assert fm.loop_shares(ns, ns) is None
    st = VerifyStage("v0", batch=16, max_msg_len=256, native_client=False)
    st.metrics.attach(fm.MetricsRegistry(st.metrics.schema))

    def row(work, poll, hk, empty, call, away, backp=0):
        c = st.metrics.counters
        c.update(loop_work_ns=work, loop_poll_ns=poll, loop_hk_ns=hk,
                 loop_backp_ns=backp,
                 chip_empty_ns=empty, chip_empty_n=empty // 10**6,
                 chip_empty_call_ns=call, chip_empty_away_ns=away)
        st.metrics.flush()
        reg = st.metrics.registry
        return {"stage": "v0", "signal": 1, "heartbeat_age_ms": 1.0,
                "in": 0, "out": 0, "overrun": 0, "backpressure": 0,
                "iters": 1, "loop": fm.loop_row([reg]),
                "batch_closes": fm.batch_close_row([reg]),
                "chip_empty": fm.chip_empty_row(reg)}

    a = row(10**8, 10**8, 0, 10**8, 10**7, 10**7)
    b = row(4 * 10**8, 2 * 10**8, 2 * 10**8, 8 * 10**8, 8 * 10**7,
            36 * 10**7, backp=3 * 10**8)
    assert b["loop"] == {"work_ns": 4 * 10**8, "poll_ns": 2 * 10**8,
                         "backp_ns": 3 * 10**8, "hk_ns": 2 * 10**8}
    text = mon.MonitorSession.render([b], [a], 1.0)
    assert text.splitlines()[0].split()[5:7] == ["busy%", "backp%"]
    line = text.splitlines()[2]
    # work 3e8 and backpressure 3e8 of (3 + 1 + 3 + 2)e8 between the
    # samples: 33 % each, time and not frags a pass
    assert line.split()[5:7] == ["33", "33"]
    assert "  chip_empty=70.0% (away=50% call=10%)" in text
    first = mon.MonitorSession.render([a], None, 1.0)
    assert "chip_empty=- (away=- call=-)" in first
    # a stage whose metrics plane is not joined shows no busy share
    bare = dict(b, loop=None)
    assert mon.MonitorSession.render([bare], [a], 1.0) \
        .splitlines()[2].split()[5] == "-"
    assert fm.loop_row([None]) is None
    assert fm.chip_empty_row(Stage("s").metrics.registry) is None
    # slotreport: the same three under the verify stage
    dump = fm.flight_dump_obj("t", {"v0": (st.metrics.registry, st.recorder)})
    block = slot_report.build_report(dump)["stages"]["v0"]
    assert block["chip_empty"] == {
        "ns": 8 * 10**8, "n": 800, "call_ns": 8 * 10**7,
        "away_ns": 36 * 10**7, "away_pct": pytest.approx(45.0),
        "call_pct": pytest.approx(10.0)}
    # and the ledger's shares since boot, backpressure among them
    assert block["loop"]["backp_ns"] == 3 * 10**8
    assert block["loop"]["busy_pct"] == pytest.approx(400 / 11)
    assert block["loop"]["backp_pct"] == pytest.approx(300 / 11)
    assert block["loop"]["poll_pct"] == pytest.approx(200 / 11)
    plain = fm.flight_dump_obj("t", {"s": (Stage("s").metrics.registry,
                                           None)})
    assert "chip_empty" not in slot_report.build_report(plain)["stages"] \
        .get("s", {})


def test_msg_len_is_observed_only_where_the_tuner_is_armed(pool):
    """The native dispatch's msg_len histogram is the autotuner's
    evidence and nobody else's: off the thread unless it is armed."""
    if not vn.available():
        pytest.skip("native verify client unavailable")
    for armed in (0, 4):
        with _verify_tile("native", precomputed_ok=True,
                          autotune_after=armed) as (st, prod, cons, _s, _l):
            _feed(prod, pool, 0, 16)
            for _ in range(4):
                st.run_once()
            assert st.metrics.get("batches") == 1
            assert st.metrics.hist("msg_len")["count"] == (16 if armed else 0)
