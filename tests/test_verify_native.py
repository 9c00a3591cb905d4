"""Differential suite for the native verify sweep client (ISSUE 13,
native/fd_verify.cpp + runtime/verify_native.py).

Lane parity is the contract: the same txn stream through the native
sweep lane (fdr_sweep: C-side parse/guards/dedup/batch assembly, one
crossing per sweep) and through the Python intake path must publish
byte-identical verified frames in the same order, with the same
metrics.  Everything here runs with precomputed masks — the lanes under
test are the HOST orchestration, not the device kernel — so no XLA
compile is paid.

The module SKIPS (never fails) without the .so or with
FDTPU_NATIVE_VERIFY=0.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from firedancer_tpu.runtime import verify_native as vn
from firedancer_tpu.runtime.benchg import gen_transfer_pool
from firedancer_tpu.runtime.verify import VerifyStage
from firedancer_tpu.tango import shm

if not vn.available():
    pytest.skip(
        "native verify client unavailable (no toolchain or"
        " FDTPU_NATIVE_VERIFY=0)",
        allow_module_level=True,
    )


@pytest.fixture(scope="module")
def pool():
    return gen_transfer_pool(96, n_payers=12, n_dests=64)


def _drive(stream, *, native: bool, batch=16, max_msg_len=256,
           out_depth=256, drain=True, iters=30000, lossy=False,
           max_inflight=None):
    """One VerifyStage over real rings; returns (stage armed?, frames
    [(payload, sig, tsorig)...], metrics dict, undelivered count)."""
    prev = os.environ.get(vn.ENV_SWITCH)
    os.environ[vn.ENV_SWITCH] = "1" if native else "0"
    uid = shm.fresh_uid()
    lin = shm.ShmLink.create(f"tvn_i_{uid}", depth=256, mtu=1232, n_fseq=1)
    lout = shm.ShmLink.create(f"tvn_o_{uid}", depth=out_depth, mtu=4096,
                              n_fseq=1)
    try:
        prod = shm.make_producer(lin)
        st = VerifyStage(
            "v0", ins=[shm.make_consumer(lin, lazy=8)],
            outs=[shm.make_producer(lout)], batch=batch,
            max_msg_len=max_msg_len, batch_deadline_s=0.001,
            precomputed_ok=True,
            **({"max_inflight": max_inflight} if max_inflight else {}),
        )
        if lossy:
            from firedancer_tpu.tango.lossy import LossyConsumer
            from firedancer_tpu.utils.rng import Rng

            # a fault-free splice: forces the per-frag fallback path
            st.ins[0] = LossyConsumer(st.ins[0], Rng(7))
        armed = st._sweep_client is not None
        cons = shm.make_consumer(lout, lazy=4)
        outs, fed = [], 0
        for _ in range(iters):
            while fed < len(stream) and prod.try_publish(
                    stream[fed], sig=fed, tsorig=1000 + fed):
                fed += 1
            st.run_once()
            if drain:
                while True:
                    r = cons.poll()
                    if r in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
                        break
                    meta, payload = r
                    outs.append((bytes(payload), int(meta[1]),
                                 int(meta[5])))
            if fed == len(stream) and not drain:
                break
        st.flush()
        while True:
            r = cons.poll()
            if r in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
                break
            meta, payload = r
            outs.append((bytes(payload), int(meta[1]), int(meta[5])))
        rep = {k: st.metrics.get(k) for k in (
            "frags_in", "filtered", "txn_verified", "parse_fail",
            "dedup_dup", "msg_too_long", "too_many_sigs", "batches",
            "batch_elems", "intake_dropped", "emit_dropped")}
        return armed, outs, rep, len(stream) - fed
    finally:
        if prev is None:
            os.environ.pop(vn.ENV_SWITCH, None)
        else:
            os.environ[vn.ENV_SWITCH] = prev
        lin.close()
        lout.close()


def _adversarial(pool):
    """Honest txns + a tcache-window duplicate + malformed bytes."""
    stream = list(pool[:40])
    stream.insert(10, pool[9])  # duplicate inside the 16-deep tcache
    stream.append(b"\x01" + b"garbage" * 12)  # malformed
    stream.append(b"")  # empty frag
    return stream


def test_stream_diff_native_vs_python(pool):
    stream = _adversarial(pool)
    a_n, out_n, rep_n, und_n = _drive(stream, native=True)
    a_p, out_p, rep_p, und_p = _drive(stream, native=False)
    assert a_n and not a_p
    assert und_n == und_p == 0
    assert rep_n["dedup_dup"] == rep_p["dedup_dup"] == 1
    assert rep_n["parse_fail"] == rep_p["parse_fail"] == 2
    assert rep_n == rep_p
    assert out_n == out_p  # byte-identical frames, sigs, tsorigs, order


def test_msg_len_guard_parity(pool):
    # a max_msg_len below the txn message size: both lanes drop all
    stream = list(pool[:8])
    a_n, out_n, rep_n, _ = _drive(stream, native=True, max_msg_len=64)
    a_p, out_p, rep_p, _ = _drive(stream, native=False, max_msg_len=64)
    assert a_n
    assert rep_n["msg_too_long"] == rep_p["msg_too_long"] == 8
    assert out_n == out_p == []


def test_mixed_lane_splice_matches_sweep(pool):
    """A LossyConsumer splice (chaos shape) drops the stage to the
    per-frag path, which forwards into the SAME C-side state — frames
    must still match the pure-sweep run."""
    stream = list(pool[:32])
    a_s, out_s, rep_s, _ = _drive(stream, native=True)
    a_m, out_m, rep_m, _ = _drive(stream, native=True, lossy=True)
    assert a_s and a_m
    assert out_s == out_m
    assert rep_s["txn_verified"] == rep_m["txn_verified"]


def test_backpressure_retries_without_loss_or_reorder(pool):
    """An out ring far smaller than the stream: emits stall on credits,
    the frame tables retry next credit window, nothing drops, order
    holds."""
    stream = list(pool)
    armed, outs, rep, und = _drive(stream, native=True, out_depth=16,
                                   batch=8)
    assert armed
    assert und == 0
    assert rep["intake_dropped"] == 0 and rep["emit_dropped"] == 0
    assert len(outs) == len(stream)
    assert [o[2] for o in outs] == sorted(o[2] for o in outs)
    # frames byte-identical to the python lane under the same pressure
    _, outs_p, _, _ = _drive(stream, native=False, out_depth=16, batch=8)
    assert outs == outs_p


def test_stalled_consumer_backpressures_intake(pool):
    """No consumer progress at all: slots fill, the sweep gate closes,
    the INPUT ring backpressures the producer — verified work is never
    dropped — and everything flows once draining resumes."""
    stream = list(pool)
    uid = shm.fresh_uid()
    # input ring much smaller than the stream: a stalled verify must
    # push the pressure back to the producer, not absorb-and-drop
    lin = shm.ShmLink.create(f"tvb_i_{uid}", depth=32, mtu=1232, n_fseq=1)
    lout = shm.ShmLink.create(f"tvb_o_{uid}", depth=8, mtu=4096, n_fseq=1)
    try:
        prod = shm.make_producer(lin)
        st = VerifyStage(
            "v2", ins=[shm.make_consumer(lin, lazy=8)],
            outs=[shm.make_producer(lout)], batch=4, max_msg_len=256,
            batch_deadline_s=0.0005, precomputed_ok=True, max_inflight=2)
        assert st._sweep_client is not None
        fed = 0
        for _ in range(4000):  # consumer never drains
            while fed < len(stream) and prod.try_publish(
                    stream[fed], sig=fed, tsorig=1000 + fed):
                fed += 1
            st.run_once()
        assert fed < len(stream)  # the producer felt the stall
        assert st.metrics.get("intake_dropped") == 0
        # resume draining: every fed txn arrives, in order, then the
        # rest of the stream flows through cleanly
        cons = shm.make_consumer(lout, lazy=4)
        outs = []
        for _ in range(30000):
            while fed < len(stream) and prod.try_publish(
                    stream[fed], sig=fed, tsorig=1000 + fed):
                fed += 1
            st.run_once()
            while True:
                r = cons.poll()
                if r in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
                    break
                meta, payload = r
                outs.append((bytes(payload), int(meta[1]), int(meta[5])))
            if fed == len(stream) and len(outs) >= len(stream):
                break
        st.flush()
        while True:
            r = cons.poll()
            if r in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
                break
            meta, payload = r
            outs.append((bytes(payload), int(meta[1]), int(meta[5])))
        assert len(outs) == len(stream)
        assert [o[2] for o in outs] == [1000 + i
                                        for i in range(len(stream))]
    finally:
        lin.close()
        lout.close()


def test_client_counters_surface_in_metrics(pool):
    stream = list(pool[:24])
    _, _, rep, _ = _drive(stream, native=True)
    assert rep["frags_in"] == 24
    assert rep["txn_verified"] == 24
    assert rep["batches"] >= 1 and rep["batch_elems"] == 24


def test_env_switch_disarms():
    os.environ[vn.ENV_SWITCH] = "0"
    try:
        assert not vn.available()
    finally:
        os.environ[vn.ENV_SWITCH] = "1"
    assert vn.available()


def test_shard_filter_in_sweep(pool):
    """shard_cnt=2: the C callback filters by seq parity exactly like
    before_frag, and the filtered count matches."""
    uid = shm.fresh_uid()
    lin = shm.ShmLink.create(f"tvs_i_{uid}", depth=128, mtu=1232, n_fseq=1)
    lout = shm.ShmLink.create(f"tvs_o_{uid}", depth=128, mtu=4096,
                              n_fseq=1)
    try:
        prod = shm.make_producer(lin)
        st = VerifyStage(
            "v1", ins=[shm.make_consumer(lin, lazy=8)],
            outs=[shm.make_producer(lout)], batch=8, max_msg_len=256,
            batch_deadline_s=0.001, precomputed_ok=True,
            shard_idx=1, shard_cnt=2)
        assert st._sweep_client is not None
        for i, p in enumerate(pool[:20]):
            prod.publish(p, sig=i)
        for _ in range(200):
            st.run_once()
        st.flush()
        st.during_housekeeping()  # copy the C counters
        assert st.metrics.get("filtered") == 10
        assert st.metrics.get("txn_verified") == 10
    finally:
        lin.close()
        lout.close()


def test_a_slots_rows_keep_the_c_stage_alive_past_its_client(monkeypatch):
    """A sealed slot's rows go to the device as they lie, by an
    asynchronous copy that holds the numpy view and not the memory
    under it: the C stage is freed with the last view, not with the
    client (ISSUE 29)."""
    import gc

    if not vn.available():
        pytest.skip("native verify client unavailable")
    freed = []
    delete = vn._Owner.__del__

    def counted(self):
        freed.append(self.h)
        delete(self)

    monkeypatch.setattr(vn._Owner, "__del__", counted)
    c = vn.StageClient(shard_idx=0, shard_cnt=1, batch=8, max_msg_len=64,
                       n_slots=2)
    h = c._h
    rows, ln = c.slots[1].rows, c.slots[0].ln
    assert rows.shape == (8, vn.row_width(64)) and not rows.any()
    c.close()
    del c
    gc.collect()
    assert freed == []
    rows[:] = 7                  # still the slot's memory, still there
    assert int(rows.sum()) == 7 * rows.size
    del rows
    gc.collect()
    assert freed == []           # the other slot's length column holds it
    assert ln.tolist() == [0] * 8
    del ln
    gc.collect()
    assert freed == [h]
