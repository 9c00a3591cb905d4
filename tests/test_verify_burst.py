"""How much one intake sweep of the verify stage takes (ISSUE 42): a
quarter of the shallowest ring in front, at most a batch, at least
Stage's 16 — worked out in the constructor from the rings and the batch,
so that a 1,024-lane batch is gathered in 4 sweeps, and so that "the
sweep took its whole burst" stays the backlog's evidence (a whole burst
is less than the ring can hold).

Everything runs on the CPU with the all-pass mask or a gated stub of the
dispatch: the lanes under test are the host's, and nothing compiles.
"""

from __future__ import annotations

import contextlib
import os
import time

import numpy as np
import pytest

from firedancer_tpu.runtime import monitor as mon
from firedancer_tpu.runtime import slot_report
from firedancer_tpu.runtime import verify as rv
from firedancer_tpu.runtime import verify_native as vn
from firedancer_tpu.runtime.benchg import gen_transfer_pool
from firedancer_tpu.runtime.stage import Stage
from firedancer_tpu.runtime.verify import VerifyStage
from firedancer_tpu.tango import shm
from firedancer_tpu.utils import metrics as fm

CLOSE_COUNTERS = fm.BATCH_CLOSE_COUNTERS
HELD_BACKLOGGED = fm.BATCH_HELD_BACKLOGGED
MESH_DEVICES = 4


@pytest.fixture(scope="module")
def base_txn() -> bytes:
    return gen_transfer_pool(1)[0]


def _txn(base: bytes, i: int) -> bytes:
    """Transfer i: the one signed transfer with i written over the head
    of its signature (byte 0 is the signature count), so every one has
    a tag of its own; nothing here checks a signature."""
    return base[:1] + (i + 1).to_bytes(8, "little") + base[9:]


def _feed(prod, base: bytes, lo: int, hi: int) -> int:
    for i in range(lo, hi):
        assert prod.try_publish(_txn(base, i), sig=i, tsorig=0)
    return hi


def _collect(cons, got: list) -> None:
    """The transaction bytes that came out."""
    while True:
        res = cons.poll()
        if res in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
            return
        payload = bytes(res[1])
        got.append(payload[:int.from_bytes(payload[-2:], "little")])


def _closes(st) -> list[int]:
    return [st.metrics.get(k) for k in CLOSE_COUNTERS]


class _Gated:
    """A device future that is ready when the test says."""

    def __init__(self, lanes: int):
        self.n = self.close = None      # from _count_dispatch
        self.mask = np.ones((lanes,), dtype=bool)
        self.done = False

    def is_ready(self):
        return self.done

    def __array__(self, dtype=None, copy=None):
        return self.mask


@contextlib.contextmanager
def _tile(*, depths=(1024,), batch=1024, lane="native", gated=False, **kw):
    """One VerifyStage over native rings, a ring in front for each of
    `depths` -> (stage, the producers into it, the consumer behind it,
    the gated results it dispatched)."""
    if lane == "native" and not vn.available():
        pytest.skip("native verify client unavailable")
    prev = os.environ.get(vn.ENV_SWITCH)
    os.environ[vn.ENV_SWITCH] = "1" if lane == "native" else "0"
    uid = shm.fresh_uid()
    lins = [shm.ShmLink.create(f"tvb_i{k}_{uid}", depth=d, mtu=1232,
                               n_fseq=1) for k, d in enumerate(depths)]
    lout = shm.ShmLink.create(f"tvb_o_{uid}", depth=1024, mtu=4096, n_fseq=1)
    st = None
    try:
        st = VerifyStage(
            "v0", ins=[shm.make_consumer(l, lazy=8) for l in lins],
            outs=[shm.make_producer(lout)], batch=batch, max_msg_len=256,
            batch_deadline_s=0.0005, precomputed_ok=not gated, **kw)
        assert (st._sweep_client is not None) == (lane == "native")
        sent: list = []
        if gated:
            import jax.profiler  # noqa: F401  (the span's import)

            def dispatch(life, rows):
                st._phase_end(life, rv.PH_H2D)
                sent.append(_Gated(len(rows)))
                return sent[-1]

            books = st._count_dispatch

            def count(n, close, occupancy):
                sent[-1].n, sent[-1].close = n, close
                books(n, close, occupancy)

            st._device_verify = dispatch
            st._count_dispatch = count
        yield (st, [shm.make_producer(l) for l in lins],
               shm.make_consumer(lout, lazy=4), sent)
    finally:
        if prev is None:
            os.environ.pop(vn.ENV_SWITCH, None)
        else:
            os.environ[vn.ENV_SWITCH] = prev
        if st is not None:
            st.ins, st.outs = [], []
            st.drop_native_views()
        for l in lins:
            l.close()
        lout.close()


# -- the rule ----------------------------------------------------------------

GEOMETRY = {
    # the tile of six configurations: 1,024-deep rings, a batch of 1,024
    "tile": (dict(depths=(1024,), batch=1024), 256),
    # the fan-out: 4 x 1,024 lanes behind the same ring
    "fanout": (dict(depths=(1024,), batch=4096, devices=MESH_DEVICES), 256),
    # tests/test_batch_life.py's tiles, and a shallower ring: Stage's 16
    "batch_life": (dict(depths=(256,), batch=16), 16),
    "shallow": (dict(depths=(64,), batch=16), 16),
    # at most a batch; the shallowest ring in front decides
    "small_batch": (dict(depths=(1024,), batch=64), 64),
    "two_rings": (dict(depths=(1024, 512), batch=1024), 128),
    # the Python lane reads the same number (Stage._native_burst)
    "python_lane": (dict(depths=(1024,), batch=1024, lane="python"), 256),
}


@pytest.mark.parametrize("case", sorted(GEOMETRY))
def test_the_burst_comes_from_the_rings_in_front_and_the_batch(case):
    kw, burst = GEOMETRY[case]
    with _tile(**kw) as (st, prods, cons, sent):
        assert st.burst == burst
        # the backlog's evidence: a whole burst never empties a ring
        assert all(st.burst < c.link.depth for c in st.ins)
        # what the sweep's plan is built with (meta table and arena)
        assert st._native_drainer().max_frags == burst
    assert Stage("s").burst == 16       # every other stage's, as before


def test_a_stage_with_no_ring_in_front_keeps_the_default():
    assert VerifyStage("v0", batch=1024, precomputed_ok=True,
                       native_client=False).burst == 16


# -- a backlogged tile: 4 sweeps a batch ----------------------------------------


@pytest.mark.parametrize("lane", ["native", "python"])
def test_a_backlogged_tile_fills_its_batch_in_four_working_sweeps(
        lane, base_txn):
    """1,024 frags wait in a 1,024-deep ring: each call takes 256 and
    reads a backlog; the fourth fills the batch, which closes `full`
    (64 calls of 16 until ISSUE 42)."""
    with _tile(lane=lane) as (st, (prod,), cons, sent):
        _feed(prod, base_txn, 0, 1024)
        m = st.metrics
        for k in range(1, 5):
            assert st.run_once()
            assert m.get("frags_in") == 256 * k and st.backlogged
            assert m.get("loop_work_n") == k
        got: list = []
        for _ in range(4):      # the pump dispatches, reaps and publishes
            st.run_once()
            _collect(cons, got)
        assert _closes(st) == [1, 0, 0] and m.get("batch_elems") == 1024
        assert not st.backlogged            # a sweep came back empty
        assert got == [_txn(base_txn, i) for i in range(1024)]
        assert m.get("intake_dropped") == 0


def test_frags_a_crossing_reach_the_monitor_and_slotreport(base_txn):
    """Verify's line says how much one crossing took: 256 under a
    backlog, between two samples on the monitor and since boot in
    slotreport; a stage that is not swept natively has no such number."""
    with _tile() as (st, (prod,), cons, sent):
        st.run_once()                       # builds the sweep's plane
        reg = st.metrics.registry
        row0 = fm.intake_row(reg)
        assert row0 == {"frags": 0, "crossings": 0}
        assert fm.format_frags_per_crossing(row0, None) \
            == "frags/crossing=-"
        _feed(prod, base_txn, 0, 600)
        for _ in range(3):
            st.run_once()
        st.metrics.flush()
        row = fm.intake_row(reg)
        assert row == {"frags": 600, "crossings": 3}
        assert fm.format_frags_per_crossing(row, row0) \
            == "frags/crossing=200.0"
        base = {"stage": "v0", "signal": 1, "heartbeat_age_ms": 1.0,
                "in": 0, "out": 0, "overrun": 0, "backpressure": 0,
                "iters": 1, "batch_closes": fm.batch_close_row([reg])}
        rendered = mon.MonitorSession.render(
            [dict(base, intake=row)], [dict(base, intake=row0)], 1.0)
        assert "v0: batches closed full=0 deadline=0 window=0" in rendered
        assert "  frags/crossing=200.0" in rendered
        dump = fm.flight_dump_obj("t", {"v0": (reg, st.recorder)})
        block = slot_report.build_report(dump)["stages"]["v0"]
        assert block["frags_per_crossing"] == 200.0
    with _tile(lane="python") as (st, (prod,), cons, sent):
        st.run_once()
        reg = st.metrics.registry
        assert fm.intake_row(reg) is None
        dump = fm.flight_dump_obj("t", {"v0": (reg, st.recorder)})
        assert "frags_per_crossing" not in \
            slot_report.build_report(dump)["stages"]["v0"]


# -- the fan-out's guard (ISSUE 36) at the new burst --------------------------------


def test_a_wide_stage_behind_a_shallow_ring_fills_under_a_standing_backlog(
        base_txn):
    """4,096 lanes behind a 1,024-deep ring that is never full and never
    holds less than a burst: every sweep takes its whole burst, so each
    batch outlives its deadline many times over — with nothing in
    flight, with a batch in flight, at the reap — and still closes
    `full`.  (A burst of the ring's depth would take what is there, read
    "it ran dry", and seal a part-empty batch at the first deadline.)"""
    with _tile(batch=4096, devices=MESH_DEVICES, gated=True) \
            as (st, (prod,), cons, sent):
        assert st.burst == 256
        got: list = []
        fed = 0
        for it in range(200):
            # the stage in front offers 400 a pass against the ring's
            # credits: 656 to 1,024 are waiting at every sweep
            prod.refresh_credits()
            fed = _feed(prod, base_txn, fed,
                        fed + min(400 if fed else 1000, prod.cr_avail))
            time.sleep(st.batch_deadline_s * 2)     # overdue at every pass
            assert st.run_once() and st.backlogged
            _collect(cons, got)
            if it % 8 == 7:         # the chip finishes a batch now and then
                for g in sent:
                    g.done = True
            if len(sent) == 3:
                break
        assert [(g.n, g.close) for g in sent] == [(4096, rv.CLOSE_FULL)] * 3
        assert _closes(st) == [3, 0, 0]
        assert st.metrics.get(HELD_BACKLOGGED) >= 1
        assert st.metrics.get("frags_in") \
            == 256 * st.metrics.get("loop_work_n")
        shards = [st.metrics.get(f"shard_elems_s{i}")
                  for i in range(MESH_DEVICES)]
        assert shards == [3 * 1024] * MESH_DEVICES
        # nothing more is offered: the ring runs dry, the tail goes out
        for _ in range(20):
            for g in sent:
                g.done = True
            if not st.run_once():
                st.flush()
            _collect(cons, got)
        assert got == [_txn(base_txn, i) for i in range(fed)]
        assert st.metrics.get("intake_dropped") == 0


# -- a slot that fills mid-crossing with no slot free -----------------------------


def test_a_sweep_that_runs_out_of_slots_stops_and_loses_nothing(base_txn):
    """A burst of 48 over slots of 48: the crossing that fills the open
    slot opens the next one in the same crossing — unless every other
    slot is busy.  Then the frag that found no room is stashed (one, of
    the 8 the stash holds), the crossing stops there, the rest waits in
    the ring, the backlog's evidence stays as it was, and when a slot
    comes back every frag goes out, in ring order."""
    with _tile(depths=(256,), batch=48, gated=True) \
            as (st, (prod,), cons, sent):
        c = st._sweep_client
        assert st.burst == 48 and c.n_slots == 4
        got: list = []
        # one crossing fills a slot and starts the next (a free one)
        fed = _feed(prod, base_txn, 0, 68)
        st.run_once()
        assert st.metrics.get("frags_in") == 48 and c.sealed_waiting()
        st.run_once()
        assert st.metrics.get("frags_in") == 68 and c.open_elems() == 20
        # two fly, one waits sealed for its place, the fourth is open
        fed = _feed(prod, base_txn, fed, fed + 96)
        for _ in range(3):
            st.run_once()
        assert [g.n for g in sent] == [48, 48] and c.sealed_waiting()
        assert c.open_elems() == 20 and c.can_accept()
        # 28 fill the open slot; the 29th finds no slot
        fed = _feed(prod, base_txn, fed, fed + 60)
        was = st.backlogged
        assert st.run_once()
        assert st.metrics.get("frags_in") == 164 + 29
        assert c.stash_pending and not c.can_accept()
        assert st.backlogged == was
        for _ in range(5):      # nothing is swept while no slot is free
            assert not st.run_once()
        assert st.metrics.get("frags_in") == 164 + 29
        assert c.counters()["intake_dropped"] == 0
        # the chip gives the slots back
        for _ in range(40):
            for g in sent:
                g.done = True
            st.run_once()
            _collect(cons, got)
        st.flush()
        for _ in range(10):
            for g in sent:
                g.done = True
            st.run_once()
            _collect(cons, got)
        assert got == [_txn(base_txn, i) for i in range(fed)]
        assert c.counters()["intake_dropped"] == 0 and not c.stash_pending
        assert sum(g.n for g in sent) == fed == st.metrics.get("batch_elems")


# -- the one-thread leader pipeline keeps Stage's sweep ---------------------------
#
# Its stages take turns and its pack sheds what its pool cannot hold, so
# what the stage in front of pack takes in a turn is the only thing that
# keeps a closed-loop flood under what the banks land in a turn
# (models/leader._take_turns).  The builder says so; the stage's own rule
# would give 256 behind the same ring.


@pytest.mark.parametrize("sweep", [None, 256])
def test_the_one_thread_leader_pipeline_sheds_nothing_at_stages_sweep(
        sweep, base_txn):
    """A flood of 20,000 distinct transfers through generator -> verify
    (all-pass) -> pack -> banks -> poh -> shred -> store on one thread,
    the ring in front of verify topped up every turn (a generator that
    offers what the ring has credits for, as the benchmark's does).  As
    built, verify takes 16 a turn and pack drops nothing; handed its own
    rule's 256 (what it takes behind this ring anywhere else), pack's
    pool overflows and drops what verify had verified."""
    from firedancer_tpu.models.leader import build_leader_pipeline

    if not vn.available():
        pytest.skip("native verify client unavailable")
    n = 20_000
    pipe = build_leader_pipeline(
        pool_size=64, n_payers=64, batch=1024, depth=1024,
        verify_precomputed=True, keep_sets=False, gen_limit=n)
    try:
        v = pipe.verifies[0]
        assert v._sweep_client is not None and v.burst == 16
        assert min(c.link.depth for c in v.ins) == 1024
        if sweep:
            v.burst = sweep
        # distinct transfers: a tag of its own each (pack dedups on it)
        bases = pipe.benchg.pool
        pipe.benchg.pool = [_txn(bases[k % 64], k) for k in range(n)]
        dropped = landed = 0
        for _ in range(4000):
            for _ in range(1024 // pipe.benchg.burst):
                pipe.benchg.run_once()
            for s in pipe.stages:
                s.run_once()
            dropped = pipe.pack.metrics.get("txn_dropped")
            landed = sum(b.metrics.get("txn_exec") for b in pipe.banks)
            if landed + dropped == n:
                break
        assert landed + dropped == n
        assert (dropped == 0) if sweep is None else (dropped > n // 10)
    finally:
        pipe.close()
