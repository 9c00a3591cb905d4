"""The verify program's one dispatch + async window + autotuner (ISSUE 13).

Tier-1 here is structural and host-only: one compiled module a
dispatch, the in-flight window's in-order/backpressure semantics at its
depth of two (ISSUE 27) and, so that nothing rests on the two, at deeper
ones — full batches behind the running one, a batch that is not full
only behind a full one (ISSUE 32) — driven with fake device futures (no XLA), the autotuner's
determinism, and the packed row a batch goes to the device as (ISSUE
29): its layout held equal between native/fd_verify.cpp, the binding,
the Python lane's _assemble and the program's on-device unpack, which
compiles in no time.  The compile-heavy differential lanes (the packed
program vs ops/ref vs the four-array entry's masks on adversarial inputs,
cached interleave) live behind the `slow` marker — a single
sigverify-program compile costs ~3 min on one core.
"""

import hashlib

import numpy as np
import pytest

from firedancer_tpu.runtime import verify as rv
from firedancer_tpu.runtime import verify_tune as vt
from firedancer_tpu.runtime.benchg import gen_transfer_pool
from firedancer_tpu.runtime.stage import Stage
from firedancer_tpu.runtime.verify import VerifyStage
from firedancer_tpu.tango import shm


# -- one module a dispatch -----------------------------------------------------


def test_a_dispatch_enters_one_compiled_module(toy_verify_ok):
    """What is left of the ladder's books: after one batch shape has
    run, a dispatch enters ONE compiled module, unpack included — and
    the same one however often it is called."""
    import jax

    from firedancer_tpu.ops import sigverify as sv
    from firedancer_tpu.runtime import verify_native as vn

    prog = sv.ed25519_verify_batch_fused
    assert prog._cache_size() == 0
    dev = jax.device_put(np.zeros((16, vn.row_width(64)), dtype=np.uint8))
    for _ in range(3):
        mask = np.asarray(sv.verify_dispatch(dev, max_msg_len=64))
        assert mask.shape == (16,) and mask.all()   # the toy passes zeros
        assert prog._cache_size() == 1


def test_stage_window_defaults(monkeypatch):
    # the depth is a constant: the environment switch is gone
    monkeypatch.setenv("FDTPU_VERIFY_INFLIGHT", "5")
    st = VerifyStage("v", ins=[], outs=[], native_client=False)
    assert not hasattr(st, "kernel")    # and so is the ladder's
    # one running and, if it is full, one queued behind it
    assert st.max_inflight == rv.WINDOW_DEPTH == 2


@pytest.mark.parametrize("asked, held", [(None, 2), (1, 1), (2, 2), (3, 2),
                                         (8, 2)])
def test_max_inflight_can_only_narrow_the_window(asked, held):
    st = VerifyStage("v", ins=[], outs=[], native_client=False,
                     max_inflight=asked)
    assert st.max_inflight == held


# -- the async in-flight window (fake futures, no XLA) ------------------------


class _FakeResult:
    """A controllable device future: is_ready() flips on demand, and
    np.asarray() returns the prepared mask (the reap-point contract)."""

    def __init__(self, mask: np.ndarray):
        self.mask = mask
        self.ready = False

    def is_ready(self) -> bool:
        return self.ready

    def __array__(self, dtype=None, copy=None):
        return self.mask


class _WindowStage(VerifyStage):
    """VerifyStage with the device replaced by fake futures."""

    def __init__(self, *a, **kw):
        kw.setdefault("native_client", False)
        super().__init__(*a, **kw)
        self.fakes: list[_FakeResult] = []
        self.emitted: list = []

    def _dispatch(self, acc, cached):
        f = _FakeResult(np.ones((len(acc.elems),), dtype=bool))
        self.fakes.append(f)
        return f

    def _emit_burst(self, emits):
        self.emitted.extend(emits)
        if emits:
            self.metrics.inc("txn_verified", len(emits))


def _feed(st, pool, t0=1000):
    meta = np.zeros(7, dtype=np.uint64)
    for i, p in enumerate(pool):
        meta[5] = t0 + i
        st.after_frag(0, meta, p)


@pytest.fixture(scope="module")
def txn_pool():
    return gen_transfer_pool(48, n_payers=8, n_dests=64)


@pytest.fixture(params=[2, 3, 8])
def depth(request, monkeypatch):
    """The window's depth: the one the stage runs at, and deeper ones the
    same lane has to hold in order."""
    monkeypatch.setattr(rv, "WINDOW_DEPTH", request.param)
    return request.param


@pytest.mark.parametrize("max_inflight", [None, 1, 2, 8])
def test_window_fills_to_its_depth_with_full_batches_and_defers(
        txn_pool, depth, max_inflight):
    st = _WindowStage("v", ins=[], outs=[], batch=4, max_msg_len=256,
                      max_inflight=max_inflight)
    held = min(depth, max_inflight or depth)
    assert st.max_inflight == held
    n = 4 * (held + 3)
    _feed(st, txn_pool[:n])  # the window + 3 batches of 4, every one full
    # nothing reaped (no fake is ready): the window holds exactly its
    # depth — full batches go behind the running one — and the
    # remaining sealed batches parked in the submit queue: submit never
    # blocked on a device future
    assert len(st._inflight) == held
    assert len(st._submit_queue) == 3
    assert st.metrics.get("submit_deferred") > 0
    assert st.metrics.get("batches") == held  # only submitted ones
    assert st.metrics.get("batch_close_full") == held
    assert st.metrics.get("batch_queued_behind") == held - 1
    occ = st.metrics.hist("inflight_occupancy")
    assert occ["count"] == held and occ["sum"] == held * (held + 1) / 2
    # and it still runs to the end, in order
    for f in st.fakes:
        f.ready = True
    st.flush()
    assert [e[2] for e in st.emitted] == list(range(1000, 1000 + n))


def test_window_reaps_in_order_under_out_of_order_completion(txn_pool, depth):
    st = _WindowStage("v", ins=[], outs=[], batch=4, max_msg_len=256)
    _feed(st, txn_pool[:4 * depth + 8])  # the window + 2 parked
    assert len(st.fakes) == depth
    # complete LATER batches first: nothing may emit past the head
    for f in st.fakes[1:]:
        f.ready = True
    st.after_credit()
    assert st.emitted == []
    # head completes: everything in the window reaps, in submission
    # order, and the parked batches take the freed slots
    st.fakes[0].ready = True
    st.after_credit()
    assert len(st.emitted) == 4 * depth
    assert len(st._inflight) == 2 and not st._submit_queue
    st.flush()
    tsorigs = [e[2] for e in st.emitted]
    # global emit order = intake order
    assert tsorigs == list(range(1000, 1000 + 4 * depth + 8))
    assert st.metrics.get("batches") == depth + 2


def test_window_freed_slots_pull_deferred_full_batches(txn_pool, depth):
    st = _WindowStage("v", ins=[], outs=[], batch=4, max_msg_len=256,
                      batch_deadline_s=0.0)
    # the window + 3 parked, and a batch that is not full, past its
    # deadline from the start
    _feed(st, txn_pool[:4 * depth + 12 + 2])
    assert len(st._inflight) == depth and len(st._submit_queue) == 3
    st.before_credit()
    st.fakes[0].ready = True
    st.after_credit()
    # one reap -> one parked batch submitted into the freed slot, behind
    # the ones in flight; the open batch stays open behind them all
    assert len(st._inflight) == depth
    assert len(st._submit_queue) == 2
    assert len(st.fakes) == depth + 1
    assert len(st._gen.elems) == 2 and st._gen.held
    # while a sealed batch is parked ahead of it, it stays open
    for _ in range(2):
        assert st.metrics.get("batch_close_window") == 0
        assert len(st._gen.elems) == 2
        next(f for f in st.fakes if not f.ready).ready = True
        st.after_credit()
    assert not st._submit_queue and len(st._gen.elems) == 2
    # the next freed slot is behind full batches, and its own
    next(f for f in st.fakes if not f.ready).ready = True
    st.after_credit()
    assert not st._gen.elems and len(st._inflight) == depth
    assert st.metrics.get("batch_close_window") == 1
    assert st.metrics.get("batch_close_full") == depth + 3
    assert st.metrics.get("batch_queued_behind") == depth + 3
    for f in st.fakes:
        f.ready = True
    st.flush()
    assert [e[2] for e in st.emitted] \
        == list(range(1000, 1000 + 4 * depth + 14))


@pytest.mark.parametrize("max_inflight", [None, 1])
def test_a_batch_that_is_not_full_is_not_queued_behind_another_such(
        txn_pool, max_inflight):
    """The second place in the window is for full batches and the batch
    behind one (ISSUE 32): a batch past its deadline stays open while
    one that was not full is in flight, takes what arrives, and goes in
    the pump that reaps the running one; one that fills meanwhile goes
    behind it at once."""
    st = _WindowStage("v", ins=[], outs=[], batch=4, max_msg_len=256,
                      batch_deadline_s=0.0, max_inflight=max_inflight)
    _feed(st, txn_pool[:2])
    st.before_credit()
    st.after_credit()            # nothing in flight: goes at its deadline
    assert len(st._inflight) == 1
    assert st.metrics.get("batch_close_deadline") == 1
    _feed(st, txn_pool[2:4], t0=1002)
    for _ in range(3):           # one in flight: stays open, whatever room
        st.before_credit()
        st.after_credit()
    assert st._window_has_room() == (max_inflight is None)
    assert not st._window_open()
    assert len(st._inflight) == 1 and len(st._gen.elems) == 2
    assert st._gen.held and not st._submit_queue
    _feed(st, txn_pool[4:5], t0=1004)      # and takes what arrives
    assert len(st._gen.elems) == 3
    if max_inflight is None:
        # it fills: the second place is its
        _feed(st, txn_pool[5:6], t0=1005)
        assert len(st._inflight) == 2 and not st._gen.elems
        assert st.metrics.get("batch_close_full") == 1
        assert st.metrics.get("batch_queued_behind") == 1
        st.fakes[0].ready = True
    else:
        # the reap of the running batch sends it, in the same pass
        st.fakes[0].ready = True
        st.after_credit()
        assert len(st._inflight) == 1 and not st._gen.elems
        assert st.metrics.get("batch_close_window") == 1
        assert st.metrics.get("batch_queued_behind") == 0
    st.fakes[1].ready = True
    st.flush()
    n = 6 if max_inflight is None else 5
    assert [e[2] for e in st.emitted] == list(range(1000, 1000 + n))


@pytest.mark.parametrize("max_inflight", [None, 1])
def test_a_backlogged_intake_holds_the_batch_that_would_run_part_empty(
        txn_pool, max_inflight):
    """ISSUE 36, the Python lane with fake futures: while the intake's
    last sweep took its whole burst (Stage.backlogged, set here by hand:
    no ring feeds this stage) a batch past its deadline with nothing in
    flight stays open, is counted once, and goes when it fills, or at
    the first pass after the backlog ends."""
    st = _WindowStage("v", ins=[], outs=[], batch=4, max_msg_len=256,
                      batch_deadline_s=0.0, max_inflight=max_inflight)

    def held():
        return st.metrics.get("batch_held_backlogged")

    st.backlogged = True
    _feed(st, txn_pool[:2])
    for _ in range(3):
        st.before_credit()
        st.after_credit()
    assert not st._inflight and len(st._gen.elems) == 2
    assert st._gen.held == rv._HELD_BACKLOGGED and held() == 1
    _feed(st, txn_pool[2:4], t0=1002)       # it fills: out at once, alone
    assert len(st._inflight) == 1 and not st._gen.elems
    assert st.metrics.get("batch_close_full") == 1
    assert not st._full_waited
    # behind it the next one is held by the window, then by the backlog
    _feed(st, txn_pool[4:6], t0=1004)
    st.before_credit()
    st.after_credit()
    assert st._gen.held == rv._HELD_WINDOW and held() == 1
    st.fakes[0].ready = True
    st.after_credit()
    assert not st._inflight and len(st._gen.elems) == 2
    assert st._gen.held == rv._HELD_WINDOW | rv._HELD_BACKLOGGED
    assert held() == 2
    # the backlog ends: the rule as it was, at the next pass
    st.backlogged = False
    st.after_credit()
    assert len(st._inflight) == 1 and not st._gen.elems
    assert st.metrics.get("batch_close_window") == 1
    assert st.metrics.get("batch_queued_behind") == 0
    st.fakes[1].ready = True
    st.flush()
    assert [e[2] for e in st.emitted] == list(range(1000, 1006))
    assert st.metrics.get("batches") == 2 and held() == 2


@pytest.mark.parametrize("backlogged", [False, True])
def test_the_place_behind_a_full_batch_needs_one_to_have_waited_for_it(
        txn_pool, backlogged):
    """Clause (b), on the evidence of ISSUE 40: a batch that is not full
    goes behind a running one only after a full batch had to wait for
    its place in the window (the thread leads the chip), backlogged or
    not; a full batch that found room behind a running one at once says
    nothing, since a thread that trails the chip sends those too."""
    st = _WindowStage("v", ins=[], outs=[], batch=4, max_msg_len=256,
                      batch_deadline_s=0.0)
    st.backlogged = backlogged
    _feed(st, txn_pool[:4])                 # full, out alone
    _feed(st, txn_pool[4:6], t0=1004)
    st.before_credit()
    st.after_credit()
    assert len(st._inflight) == 1 and len(st._gen.elems) == 2
    assert not st._window_open() and st._window_has_room()
    _feed(st, txn_pool[6:8], t0=1006)       # full, behind the running one
    assert len(st._inflight) == 2 and not st._full_waited
    _feed(st, txn_pool[8:10], t0=1008)
    st.before_credit()
    st.fakes[0].ready = True
    st.after_credit()                       # room behind it: not taken
    assert len(st._inflight) == 1 and len(st._gen.elems) == 2
    assert st._window_has_room() and not st._window_open()
    _feed(st, txn_pool[10:12], t0=1010)     # it fills: behind at once
    _feed(st, txn_pool[12:16], t0=1012)     # the next finds no place
    assert len(st._inflight) == 2 and len(st._submit_queue) == 1
    assert st._full_waited
    _feed(st, txn_pool[16:18], t0=1016)
    st.before_credit()
    st.fakes[1].ready = True
    st.after_credit()           # the one that waited takes the place
    assert len(st._inflight) == 2 and len(st._gen.elems) == 2
    st.fakes[2].ready = True
    st.after_credit()           # room behind it, on its evidence: taken
    assert len(st._inflight) == 2 and not st._gen.elems
    assert st.metrics.get("batch_queued_behind") == 4
    assert st.metrics.get("batch_close_window") == 1
    assert st.metrics.get("batch_held_backlogged") == 0
    assert not st._full_waited              # the slack is spent
    for f in st.fakes:
        f.ready = True
    st.flush()
    assert [e[2] for e in st.emitted] == list(range(1000, 1018))


class _CountStage(Stage):
    """Forwards what it takes: the out ring's credits bound its sweeps."""

    def after_frag(self, in_idx, meta, payload):
        self.publish(0, payload, sig=int(meta[1]))


@pytest.mark.parametrize("rings", ["native", "python"])
def test_the_intake_says_whether_the_ring_in_front_ran_dry(
        rings, monkeypatch):
    """Stage.backlogged on the poll loop and on the native drain: a sweep
    that took its whole burst sets it, one that came back short clears
    it, and one that credits downstream cut short, or that was skipped
    for want of them, leaves it as it was."""
    monkeypatch.setenv("FDTPU_NATIVE_RING", "1" if rings == "native" else "0")
    uid = shm.fresh_uid()
    lin = shm.ShmLink.create(f"tvk_i_{uid}", depth=64, mtu=64, n_fseq=1)
    lout = shm.ShmLink.create(f"tvk_o_{uid}", depth=4, mtu=64, n_fseq=1)
    st = None
    try:
        prod = shm.make_producer(lin)
        cons = shm.make_consumer(lout, lazy=1)
        st = _CountStage("s", ins=[shm.make_consumer(lin, lazy=1)],
                         outs=[shm.make_producer(lout)])
        assert (type(st.ins[0]).__name__ == "NativeConsumer") \
            == (rings == "native")
        st.burst = 4
        st.require_credit = True
        fed = iter(range(64))

        def feed(n):
            for _ in range(n):
                assert prod.try_publish(b"x" * 8, sig=next(fed), tsorig=0)

        def drain():
            n = 0
            while cons.poll() not in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
                n += 1
            return n

        assert st.backlogged is False
        feed(5)
        st.run_once()                       # 4 of 5: the whole burst
        assert st.backlogged and st.metrics.get("frags_in") == 4
        st.run_once()                       # no credits: skipped
        assert st.backlogged and st.metrics.get("frags_in") == 4
        assert drain() == 4
        st.run_once()                       # 1 left: the ring ran dry
        assert not st.backlogged and st.metrics.get("frags_in") == 5
        assert drain() == 1
        st.run_once()                       # nothing there
        assert not st.backlogged
        # fewer credits than the burst, for a ring that holds more: the
        # sweep is cut short, and says nothing either way
        out = st.outs[0]
        feed(2)
        st.run_once()
        assert not st.backlogged and st.metrics.get("frags_in") == 7
        out.refresh_credits()
        left = out.cr_avail
        assert 0 < left < st.burst
        feed(12)
        st.run_once()
        assert st.metrics.get("frags_in") == 7 + left and not st.backlogged
        drain()
        st.run_once()                       # credits for the whole burst
        assert st.metrics.get("frags_in") == 11 + left and st.backlogged
    finally:
        if st is not None:
            st.ins, st.outs = [], []
            st.drop_native_views()
        lin.close()
        lout.close()


def test_flush_drains_window_and_queue(txn_pool):
    st = _WindowStage("v", ins=[], outs=[], batch=4, max_msg_len=256)
    _feed(st, txn_pool[:30])  # 7 full batches + a partial
    for f in st.fakes:
        f.ready = True
    # flush must close the partial, pump the queue, and reap everything
    # (fakes created during flush are ready=False but the blocking drain
    # materializes them via __array__ regardless — the jax contract)
    st.flush()
    assert len(st.emitted) == 30
    assert not st._inflight and not st._submit_queue


def test_deep_submit_queue_falls_back_to_blocking_drain(txn_pool):
    st = _WindowStage("v", ins=[], outs=[], batch=4, max_msg_len=256)
    st._submit_queue_max = 2
    _feed(st, txn_pool[:40])  # 10 batches >> window + queue bound
    # the memory bound engaged: the blocking drain consumed heads, so
    # the queue never exceeds its bound + the one being closed
    assert len(st._submit_queue) <= st._submit_queue_max + 1
    assert len(st.emitted) > 0  # heads were reaped to make room


# -- autotuner ----------------------------------------------------------------


def _hist(values, buckets):
    """Build a Metrics-shaped histogram dict from raw observations."""
    from bisect import bisect_left

    counts = [0] * (len(buckets) + 1)
    for v in values:
        counts[bisect_left(buckets, v)] += 1
    return {"buckets": list(buckets), "counts": counts,
            "sum": float(sum(values)), "count": len(values)}


def test_autotune_recommend_deterministic():
    from firedancer_tpu.utils import metrics as fm

    fills = _hist([300, 310, 290, 305] * 8, fm.exp_buckets(1, 4096, 13))
    msgs = _hist([180, 200, 150] * 10, fm.exp_buckets(32, 2048, 13))
    a = vt.recommend(fills, msgs, batch_elems=1000, comb_elems=100)
    b = vt.recommend(fills, msgs, batch_elems=1000, comb_elems=100)
    assert a == b  # same histograms -> same geometry, always
    assert a.batch in vt.BATCH_LADDER
    assert a.max_msg_len in vt.MSG_LEN_LADDER
    # p95 fill ~512-bucket -> batch rung must cover it
    assert a.batch >= 300
    assert a.max_msg_len >= 200
    assert a.comb_split is False  # 10% comb share < the split threshold


def test_autotune_comb_split_threshold():
    assert vt.recommend({}, None, batch_elems=100,
                        comb_elems=50).comb_split is True
    assert vt.recommend({}, None, batch_elems=100,
                        comb_elems=10).comb_split is False
    # no evidence: keep the current choice
    cur = vt.Geometry(128, 256, False)
    assert vt.recommend({}, None, current=cur) == cur


def test_autotune_overflow_takes_top_rung():
    from firedancer_tpu.utils import metrics as fm

    buckets = fm.exp_buckets(1, 4096, 13)
    fills = _hist([5000] * 16, buckets)  # above the top edge
    rec = vt.recommend(fills, None, batch_elems=1, comb_elems=0)
    assert rec.batch == vt.BATCH_LADDER[-1]


def test_stage_autotune_applies_at_quiet_housekeeping(txn_pool):
    def run(stage):
        _feed(stage, txn_pool)
        stage.flush()
        stage.during_housekeeping()
        return stage.batch, stage.max_msg_len

    a = VerifyStage("a", ins=[], outs=[], batch=2048, max_msg_len=1232,
                    precomputed_ok=True, autotune_after=1,
                    native_client=False)
    b = VerifyStage("b", ins=[], outs=[], batch=2048, max_msg_len=1232,
                    precomputed_ok=True, autotune_after=1,
                    native_client=False)
    ga, gb = run(a), run(b)
    assert ga == gb  # deterministic per identical input stream
    # 48 txns of ~150-byte transfers against a 2048/1232 shape: the
    # evidence must shrink both axes
    assert ga[0] < 2048 and ga[1] < 1232
    assert a.metrics.get("retunes") == 1


def test_stage_autotune_waits_for_quiet_point(txn_pool):
    st = _WindowStage("v", ins=[], outs=[], batch=4, max_msg_len=1232,
                      max_inflight=8, autotune_after=1)
    _feed(st, txn_pool[:32])
    assert st._inflight  # batches outstanding
    st._maybe_retune()
    assert st.batch == 4  # never retunes with work in flight


# -- the packed row (ISSUE 29) --------------------------------------------------
#
# msg[max_msg_len] | sig[64] | pk[32] | msg_len u32 LE, one element a row:
# written by native/fd_verify.cpp at intake, mirrored by the binding
# (fdlint FD305), built by the Python lane's _assemble, read back by the
# program's on-device unpack.

ROW_NAMES = ("ROW_SIG_OFF", "ROW_PK_OFF", "ROW_LEN_OFF", "ROW_TAIL")


def _repo(*parts):
    import os

    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), *parts)


def test_row_layout_is_one_layout_in_c_the_binding_and_the_program():
    from firedancer_tpu.analysis import abi_check
    from firedancer_tpu.ops import sigverify as sv
    from firedancer_tpu.runtime import verify_native as vn

    c = abi_check.extract_c(_repo("native", "fd_verify.cpp")).consts
    py = abi_check.extract_py(
        _repo("firedancer_tpu", "runtime", "verify_native.py")).consts
    for name in ROW_NAMES:
        # both sides of FD305 see the constant, so the lint holds it
        assert c[name] == py[name][0] == getattr(vn, name) \
            == getattr(sv, name), name
    assert (vn.ROW_SIG_OFF, vn.ROW_PK_OFF, vn.ROW_LEN_OFF, vn.ROW_TAIL) \
        == (0, 64, 96, 100)
    assert vn.row_width(256) == 356 and vn.row_width(1232) == 1332


def test_fdlint_fd305_catches_a_drifted_row_offset(tmp_path):
    from firedancer_tpu.analysis import abi_check

    with open(_repo("firedancer_tpu", "runtime", "verify_native.py")) as f:
        src = f.read()
    assert "ROW_PK_OFF = 64\n" in src
    drifted = tmp_path / "verify_native.py"
    drifted.write_text(src.replace("ROW_PK_OFF = 64\n", "ROW_PK_OFF = 60\n"))
    cpp = _repo("native", "fd_verify.cpp")
    hits = [f for f in abi_check.check_pair(str(drifted), cpp)
            if f.rule == "FD305"]
    assert len(hits) == 1 and "ROW_PK_OFF" in hits[0].msg
    clean = tmp_path / "clean" / "verify_native.py"
    clean.parent.mkdir()
    clean.write_text(src)
    assert not [f for f in abi_check.check_pair(str(clean), cpp)
                if f.rule == "FD305"]


def _txn_with_msg_len(target: int, seed: int) -> bytes:
    """A one-signature transaction whose message is `target` bytes."""
    from firedancer_tpu.protocol import txn as ft

    keys = [hashlib.sha256(b"row%d-%d" % (seed, j)).digest()
            for j in range(2)]
    for pad in range(target):
        msg = ft.message_build(
            version=ft.VLEGACY, signature_cnt=1, readonly_signed_cnt=0,
            readonly_unsigned_cnt=1, acct_addrs=keys + [ft.SYSTEM_PROGRAM],
            recent_blockhash=hashlib.sha256(b"bh%d" % seed).digest(),
            instrs=[ft.InstrSpec(program_id=2, accounts=bytes([0, 1]),
                                 data=bytes([seed & 0xFF]) * pad)])
        if len(msg) == target:
            sig = hashlib.sha512(b"sig%d" % seed).digest()
            return ft.txn_assemble([sig], msg)
    raise AssertionError(f"no message of {target} bytes")


ROW_MML = 256


def _row_txns():
    """Messages short, across the 255/256 byte boundary (msg_len's byte
    order) and at max_msg_len; a multi-signature one; the pool's."""
    from tests.test_batch_life import _three_sig_txn

    return ([_txn_with_msg_len(n, n) for n in (160, 255, ROW_MML)]
            + [_three_sig_txn(1)]
            + gen_transfer_pool(6, n_payers=3, n_dests=8))


def test_native_slot_rows_equal_the_python_lanes_assemble():
    """The same transactions through the C intake and through the Python
    lane's intake + _assemble give the same packed rows, byte for byte;
    the layout read off them is the documented one."""
    from firedancer_tpu.protocol import txn as ft
    from firedancer_tpu.runtime import verify_native as vn

    if not vn.available():
        pytest.skip("native verify client unavailable")
    txns = _row_txns()
    batch = 16
    c = vn.StageClient(shard_idx=0, shard_cnt=1, batch=batch,
                       max_msg_len=ROW_MML, n_slots=2)
    st = VerifyStage("v", ins=[], outs=[], batch=batch, max_msg_len=ROW_MML,
                     native_client=False)
    try:
        for i, t in enumerate(txns):
            assert c.append(t, 1000 + i)
            got = st._intake(t)
            assert got is not None
            st._accumulate(got, t, 1000 + i)
        n = 3 + 3 + 6
        assert c.open_elems() == n == len(st._gen.elems)
        c.seal(vn.CLOSE_DEADLINE)
        slot, n_elems, n_txn, *_ = c.take_sealed()
        assert (n_elems, n_txn) == (n, len(txns))
        native = c.slots[slot].rows
        python = st._assemble(st._gen)
        w = vn.row_width(ROW_MML)
        assert native.shape == python.shape == (batch, w) == (16, 356)
        assert native.dtype == python.dtype == np.uint8
        assert native.flags.c_contiguous and python.flags.c_contiguous
        assert native.tobytes() == python.tobytes()
        assert not native[n:].any()          # a fresh slot's pad rows
        # the layout, read off the bytes with no help from the code
        # under test: element e's message, signature, signer, length
        e = 0
        for t in txns:
            d = ft.txn_parse(t)
            msg = d.message(t)
            for sig, pk in zip(d.signatures(t), d.signers(t)):
                row = native[e].tobytes()
                assert row[:len(msg)] == msg
                assert row[len(msg):ROW_MML] == bytes(ROW_MML - len(msg))
                assert row[ROW_MML:ROW_MML + 64] == sig
                assert row[ROW_MML + 64:ROW_MML + 96] == pk
                assert row[ROW_MML + 96:w] == len(msg).to_bytes(4, "little")
                e += 1
        assert e == n
        # 256 = 00 01 00 00: the order of msg_len's bytes is little-endian
        assert native[2, ROW_MML + 96:].tolist() == [0, 1, 0, 0]
        assert c.slots[slot].ln[:n].tolist() \
            == vn.row_lens(python, ROW_MML)[:n].tolist() \
            == [160, 255, 256] + [len(ft.txn_parse(t).message(t))
                                  for t in txns[3:4] for _ in range(3)] \
            + [len(ft.txn_parse(t).message(t)) for t in txns[4:]]
        c.release(slot)
    finally:
        c.close()


@pytest.mark.parametrize("mml", [256, 96, 33, 1232])
def test_the_programs_unpack_inverts_pack_rows(mml):
    """ops/sigverify.unpack_rows (traced into the program; here jitted
    alone, which compiles in no time) hands _verify_ok exactly the four
    arrays the rows were packed from: any max_msg_len (33: the length
    column unaligned), msg_len 0, 255, 256 and max_msg_len."""
    import jax

    from firedancer_tpu.ops import sigverify as sv
    from firedancer_tpu.runtime import verify_native as vn

    rng = np.random.default_rng(mml)
    b = 24
    ln = rng.integers(0, mml + 1, (b,)).astype(np.int32)
    ln[:4] = [0, min(255, mml), min(256, mml), mml]
    msg = rng.integers(0, 256, (b, mml), dtype=np.uint8)
    msg[np.arange(mml)[None, :] >= ln[:, None]] = 0
    sig = rng.integers(0, 256, (b, 64), dtype=np.uint8)
    pk = rng.integers(0, 256, (b, 32), dtype=np.uint8)
    rows = vn.pack_rows(msg, ln, sig, pk, batch=b + 8)
    assert rows.shape == (b + 8, mml + sv.ROW_TAIL)
    assert vn.row_lens(rows, mml).tolist() == ln.tolist() + [0] * 8
    got = sv.unpack_rows(jax.device_put(rows), max_msg_len=mml)
    want = (msg.T, ln, sig.T, pk.T)
    # the host's own unpack (strided views, for the comb lane) reads
    # the same layout
    for h, x in zip(vn.byte_rows(rows, mml), want):
        assert np.shares_memory(h, rows) and (h[..., :b] == x).all()
    for g, x, dt in zip(got, want, (np.uint8, np.int32, np.uint8, np.uint8)):
        g = np.asarray(g)
        assert g.dtype == dt and g.shape == (*x.shape[:-1], b + 8)
        assert (g[..., :b] == x).all() and not g[..., b:].any()


# -- the layout of the batch inside the program (ISSUE 38) ---------------------


@pytest.mark.parametrize("entry", ["fused", "four_arrays"])
@pytest.mark.parametrize("batch, lanes", [
    (96, (96,)), (128, (1, 128)), (200, (200,)), (1024, (8, 128))])
def test_the_program_folds_its_batch_by_the_shape_alone(
        entry, batch, lanes, toy_verify_ok, monkeypatch):
    """A batch that is a multiple of 128 lanes is laid on both tiled
    axes inside the program, (batch // 128, 128); any other keeps its
    one axis.  The packed rows in and the (batch,) bool mask out are
    the same either way, lane for lane, and the stage's gauge says
    which program it dispatches.  The four-array entry (the tests'
    reference, ed25519_verify_batch over the same rows unpacked) folds
    by the same rule."""
    import jax

    from firedancer_tpu.ops import sigverify as sv
    from firedancer_tpu.runtime import verify_native as vn
    from firedancer_tpu.utils import metrics as fm

    mml = 64
    seen = []
    toy = sv._verify_ok

    def spy(msg, msg_len, sig, pubkey, *, max_msg_len):
        seen.append((msg.shape, msg_len.shape, sig.shape, pubkey.shape))
        ok = toy(msg, msg_len, sig, pubkey, max_msg_len=max_msg_len)
        assert ok.shape == msg_len.shape
        return ok

    monkeypatch.setattr(sv, "_verify_ok", spy)
    folded = len(lanes) == 2
    assert sv.fold_lanes(batch) == (128 if folded else 0) == \
        (sv.FOLD_LANES if folded else 0)
    rng = np.random.default_rng(batch)
    rows = rng.integers(0, 256, (batch, vn.row_width(mml)), dtype=np.uint8)
    ln = vn.row_lens(rows, mml)
    ln[:] = rng.integers(0, mml + 1, (batch,))
    tail = rows[:, mml:].astype(np.int64)
    want = toy_verify_ok(ln, rows[:, 0], tail[:, 0], tail[:, 63],
                         tail[:, 64], tail[:, 95])
    dev = jax.device_put(rows)
    if entry == "fused":
        prog = sv.ed25519_verify_batch_fused

        def call():
            return sv.verify_dispatch(dev, max_msg_len=mml)
    else:
        prog = sv.ed25519_verify_batch
        prog.clear_cache()      # the fixture clears the stage's program

        def call():
            return prog(*sv.unpack_rows(dev, max_msg_len=mml),
                        max_msg_len=mml)
    try:
        for _ in range(2):          # the second call traces nothing
            mask = np.asarray(call())
            assert mask.dtype == np.bool_ and mask.shape == (batch,)
            assert (mask == want).all() and want.any() and not want.all()
        assert seen == [((mml,) + lanes, lanes, (64,) + lanes, (32,) + lanes)]
        assert prog._cache_size() == 1
    finally:
        prog.clear_cache()      # nothing traced on the toy answers later
    shape = jax.eval_shape(
        lambda r: sv.ed25519_verify_batch_fused(r, max_msg_len=mml), dev)
    assert (shape.shape, shape.dtype) == ((batch,), np.bool_)
    st = VerifyStage("v", ins=[], outs=[], batch=batch, max_msg_len=mml,
                     native_client=False)
    assert st.metrics.get(fm.KERNEL_FOLD_LANES) == (128 if folded else 0)
    assert fm.KERNEL_FOLD_LANES in VerifyStage.metrics_schema().names()


def test_fold_batch_is_a_reshape_of_the_last_axis():
    from firedancer_tpu.ops import sigverify as sv

    x = np.arange(3 * 256).reshape(3, 256)
    ln = np.arange(256)
    fx, fl_ = sv.fold_batch(x, ln)
    assert fx.shape == (3, 2, 128) and fl_.shape == (2, 128)
    assert (fx.reshape(3, 256) == x).all() and fl_[1, 5] == 128 + 5
    assert sv.fold_batch(x[:, :200], ln[:200])[0].shape == (3, 200)
    assert [sv.fold_lanes(b) for b in (0, 64, 200, 4096)] == [0, 0, 0, 128]


# -- differential lanes (compile-heavy: slow tier) ----------------------------


MAX_MSG = 96


def _cases(rng):
    """Adversarial (msg, sig, pubkey) triples + expected mask."""
    from firedancer_tpu.ops.ref import ed25519_ref as ref

    L = (1 << 252) + 27742317777372353535851937790883648493
    cases, expect = [], []
    for i in range(6):  # honest: seeded lengths, then empty and max_msg_len
        secret = hashlib.sha256(b"k%d" % i).digest()
        pub = ref.public_key(secret)
        m = rng.bytes((int(rng.integers(0, MAX_MSG + 1)), 0, MAX_MSG)[i % 3])
        cases.append((m, ref.sign(secret, m), pub))
        expect.append(True)
    secret = hashlib.sha256(b"adv").digest()
    pub = ref.public_key(secret)
    m = b"the quick brown fox"
    s = ref.sign(secret, m)
    # truncated message
    cases.append((m[:-1], s, pub))
    expect.append(False)
    # non-canonical s (s + L re-encoding of a valid sig)
    s_val = int.from_bytes(s[32:], "little")
    bad_s = s[:32] + (s_val + L).to_bytes(32, "little")
    cases.append((m, bad_s, pub))
    expect.append(False)
    # small-order A (torsion point: the identity, y=1)
    torsion = b"\x01" + b"\x00" * 31
    cases.append((m, s, torsion))
    expect.append(False)
    # small-order R
    bad_r = torsion + s[32:]
    cases.append((m, bad_r, pub))
    expect.append(False)
    # corrupted sig bits
    flip = bytearray(s)
    flip[2] ^= 4
    cases.append((m, bytes(flip), pub))
    expect.append(False)
    # one flipped bit of the message
    cases.append((m[:5] + bytes([m[5] ^ 0x20]) + m[6:], s, pub))
    expect.append(False)
    # a non-canonical y (>= p: accepted as an encoding, reduced mod p)
    # in A's place and in R's
    noncanon = next(e for e in (int.to_bytes(y, 32, "little")
                                for y in range(ref.P, 1 << 255))
                    if ref.point_decompress(e))
    cases.append((m, s, noncanon))
    expect.append(False)
    cases.append((m, noncanon + s[32:], pub))
    expect.append(False)
    # corrupted signatures of the empty and the max_msg_len message
    for msg_c, sig_c, pub_c in (cases[1], cases[2]):
        flip = bytearray(sig_c)
        flip[40] ^= 1
        cases.append((msg_c, bytes(flip), pub_c))
        expect.append(False)
    assert [ref.verify(*c) for c in cases] == expect
    return cases, expect


def _arrays(cases, batch=None):
    b = batch or len(cases)
    msg = np.zeros((MAX_MSG, b), dtype=np.uint8)
    ln = np.zeros(b, dtype=np.int32)
    sig = np.zeros((64, b), dtype=np.uint8)
    pk = np.zeros((32, b), dtype=np.uint8)
    for i, (m, s, p) in enumerate(cases):
        msg[: len(m), i] = np.frombuffer(m, dtype=np.uint8)
        ln[i] = len(m)
        sig[:, i] = np.frombuffer(s, dtype=np.uint8)
        pk[:, i] = np.frombuffer(p, dtype=np.uint8)
    return msg, ln, sig, pk


def _rows(cases, batch=None):
    from firedancer_tpu.runtime import verify_native as vn

    msg, ln, sig, pk = _arrays(cases)
    return vn.pack_rows(msg.T, ln, sig.T, pk.T, batch)


@pytest.mark.slow  # two sigverify-program compiles (~3 min each)
@pytest.mark.parametrize("batch", [None, 128])
def test_program_masks_byte_identical_to_the_reference(batch, rng):
    """The packed program's mask equals ops/ref's verdicts and the
    four-array entry's (ed25519_verify_batch over the same rows
    unpacked), on corrupted signatures, msg_len 0 and max_msg_len; and
    at a partial fill the real lanes' verdicts do not depend on the pad
    rows — zero, or stale from an earlier batch.  At a batch of 128
    both entries fold their lanes to (1, 128) (sv.fold_batch): the
    masks are those of the one-axis program too."""
    import jax

    from firedancer_tpu.ops import sigverify as sv

    cases, expect = _cases(rng)
    n = batch or len(cases)
    assert bool(sv.fold_lanes(n)) == (batch is not None)
    expect = expect + [False] * (n - len(cases))    # zero pad rows
    rows = jax.device_put(_rows(cases, batch))
    masks = {
        "fused": sv.verify_dispatch(rows, max_msg_len=MAX_MSG),
        "four_arrays": sv.ed25519_verify_batch(
            *sv.unpack_rows(rows, max_msg_len=MAX_MSG),
            max_msg_len=MAX_MSG),
    }
    for entry, mask in masks.items():
        masks[entry] = mask = np.asarray(mask)
        assert mask.dtype == np.bool_ and mask.shape == (n,)
    assert masks["fused"].tolist() == expect
    assert masks["fused"].tolist() == masks["four_arrays"].tolist()
    if batch:
        flat = jax.jit(lambda r: sv._verify_ok(
            *sv.unpack_rows(r, max_msg_len=MAX_MSG), max_msg_len=MAX_MSG))
        assert np.asarray(flat(rows)).tolist() == expect
    # a partial fill of the same fixed shape: the first k rows real, the
    # pad rows zero, then stale (the rows of the batch above, as a reused
    # slot holds them).  What the program says of pad lanes is nobody's
    # answer; the real lanes' is the same either way.
    k = len(cases) - 5
    zero = _rows(cases[:k], batch=n)
    stale = _rows(cases, batch)
    stale[:k] = zero[:k]
    assert not zero[k:].any() and stale[k:].any()
    got_zero = np.asarray(sv.verify_dispatch(
        jax.device_put(zero), max_msg_len=MAX_MSG))
    got_stale = np.asarray(sv.verify_dispatch(
        jax.device_put(stale), max_msg_len=MAX_MSG))
    assert got_zero[:k].tolist() == got_stale[:k].tolist() == expect[:k]
    assert not got_zero[k:].any()            # an all-zero row never verifies
    assert got_stale[k:].tolist() == expect[k:]


@pytest.mark.slow  # fused + cached kernel compiles
@pytest.mark.parametrize("batch", [None, 128])
def test_cached_lane_interleave_matches_generic(batch, rng):
    """Cached-signer (comb) verifies agree with the generic fused lane
    on an interleaved honest/adversarial batch; at 128 lanes both fold
    their batch and the bank gather takes the two-axis slots."""
    import jax.numpy as jnp

    from firedancer_tpu.ops import sigverify as sv
    from firedancer_tpu.ops.ref import ed25519_ref as ref

    signers = [hashlib.sha256(b"c%d" % i).digest() for i in range(3)]
    pubs = [ref.public_key(s) for s in signers]
    cases = []
    for i in range(8):
        sec, pub = signers[i % 3], pubs[i % 3]
        m = rng.bytes(int(rng.integers(1, MAX_MSG)))
        s = ref.sign(sec, m)
        if i == 5:
            m = m[:-1] + b"\xff"  # one corrupted element mid-batch
        cases.append((m, s, pub))
    msg, ln, sig, pk = _arrays(cases, batch)
    n = len(cases)
    gen_mask = sv.verify_dispatch(jnp.asarray(_rows(cases, batch)),
                                  max_msg_len=MAX_MSG)
    fill = np.zeros((32, len(pubs)), dtype=np.uint8)
    for i, p in enumerate(pubs):
        fill[:, i] = np.frombuffer(p, dtype=np.uint8)
    tables, ok = sv.comb_fill(jnp.asarray(fill))
    assert bool(np.asarray(ok).all())
    bank = sv.bank_alloc(len(pubs))
    bank = sv.bank_install(
        bank, tables, jnp.asarray(np.arange(len(pubs), dtype=np.int32)))
    slots = jnp.asarray(
        np.asarray([i % 3 for i in range(batch or n)], dtype=np.int32))
    cached = sv.ed25519_verify_batch_cached(
        jnp.asarray(msg), jnp.asarray(ln), jnp.asarray(sig),
        jnp.asarray(pk), bank, slots, max_msg_len=MAX_MSG)
    assert np.asarray(cached).shape == (batch or n,)
    assert np.asarray(cached)[:n].tolist() == \
        np.asarray(gen_mask)[:n].tolist() == [i != 5 for i in range(n)]
