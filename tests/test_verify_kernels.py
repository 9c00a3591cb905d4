"""Verify kernel ladder + async window + autotuner (ISSUE 13).

Tier-1 here is structural and host-only: ladder introspection (dispatch
counts), the in-flight window's in-order/backpressure semantics at its
depth of two (ISSUE 27) and, so that nothing rests on the two, at deeper
ones, driven with fake device futures (no XLA), and the autotuner's
determinism.  The compile-heavy differential lanes (fused vs split vs
baseline masks on adversarial inputs, cached interleave) live behind
the `slow` marker — a single sigverify-program compile costs ~3 min on
one core.
"""

import hashlib

import numpy as np
import pytest

from firedancer_tpu.runtime import verify as rv
from firedancer_tpu.runtime import verify_tune as vt
from firedancer_tpu.runtime.benchg import gen_transfer_pool
from firedancer_tpu.runtime.verify import VerifyStage


# -- ladder structure (no device) ---------------------------------------------


def test_kernel_ladder_dispatch_counts():
    from firedancer_tpu.ops import sigverify as sv

    assert set(sv.KERNEL_LADDER) == {"fused", "baseline", "split"}
    assert sv.kernel_dispatch_count("fused") == 1
    assert sv.kernel_dispatch_count("baseline") == 1
    assert sv.kernel_dispatch_count("split") == 4
    with pytest.raises(KeyError):
        sv.kernel_dispatch_count("nope")


def test_stage_rejects_unknown_kernel():
    with pytest.raises(ValueError, match="unknown verify kernel"):
        VerifyStage("v", ins=[], outs=[], kernel="warp")


def test_stage_kernel_and_window_defaults(monkeypatch):
    # the depth is a constant: the environment switch is gone
    monkeypatch.setenv("FDTPU_VERIFY_INFLIGHT", "5")
    st = VerifyStage("v", ins=[], outs=[], native_client=False)
    assert st.kernel == "fused"
    assert st.max_inflight == rv.WINDOW_DEPTH == 2  # one running, one queued


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
        return f, None

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
def test_window_fills_to_its_depth_and_defers(txn_pool, depth, max_inflight):
    st = _WindowStage("v", ins=[], outs=[], batch=4, max_msg_len=256,
                      max_inflight=max_inflight)
    held = min(depth, max_inflight or depth)
    assert st.max_inflight == held
    n = 4 * (held + 3)
    _feed(st, txn_pool[:n])  # the window + 3 batches of 4
    # nothing reaped (no fake is ready): the window holds exactly its
    # depth and the remaining sealed batches parked in the submit queue
    # — submit never blocked on a device future
    assert len(st._inflight) == held
    assert len(st._submit_queue) == 3
    assert st.metrics.get("submit_deferred") > 0
    assert st.metrics.get("batches") == held  # only submitted ones
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


def test_window_freed_slots_pull_deferred_submits(txn_pool, depth):
    st = _WindowStage("v", ins=[], outs=[], batch=4, max_msg_len=256)
    _feed(st, txn_pool[:4 * depth + 12])  # the window + 3 parked
    assert len(st._inflight) == depth and len(st._submit_queue) == 3
    st.fakes[0].ready = True
    st.after_credit()
    # one reap -> one parked batch submitted into the freed slot
    assert len(st._inflight) == depth
    assert len(st._submit_queue) == 2
    assert len(st.fakes) == depth + 1


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


# -- differential lanes (compile-heavy: slow tier) ----------------------------


MAX_MSG = 96


def _cases(rng):
    """Adversarial (msg, sig, pubkey) triples + expected mask."""
    from firedancer_tpu.ops.ref import ed25519_ref as ref

    L = (1 << 252) + 27742317777372353535851937790883648493
    cases, expect = [], []
    for i in range(4):  # honest, varied lengths incl. empty
        secret = hashlib.sha256(b"k%d" % i).digest()
        pub = ref.public_key(secret)
        m = rng.bytes(int(rng.integers(0, MAX_MSG + 1)))
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
    return cases, expect


def _arrays(cases):
    b = len(cases)
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


@pytest.mark.slow  # three sigverify-program compiles (~3 min each)
def test_ladder_lanes_byte_identical_masks(rng):
    import jax.numpy as jnp

    from firedancer_tpu.ops import sigverify as sv

    cases, expect = _cases(rng)
    msg, ln, sig, pk = _arrays(cases)
    args = (jnp.asarray(msg), jnp.asarray(ln), jnp.asarray(sig),
            jnp.asarray(pk))
    n = len(cases)
    masks = {}
    for kernel in sv.KERNEL_LADDER:
        mask, n_ok = sv.verify_dispatch(kernel, *args, n,
                                        max_msg_len=MAX_MSG)
        masks[kernel] = np.asarray(mask)[:n]
        if n_ok is not None:
            assert int(np.asarray(n_ok)) == int(masks[kernel].sum())
    assert masks["fused"].tolist() == expect
    assert masks["fused"].tolist() == masks["baseline"].tolist()
    assert masks["fused"].tolist() == masks["split"].tolist()
    # the fused program masks pad lanes ON DEVICE
    mask, n_ok = sv.verify_dispatch("fused", *args, n - 2,
                                    max_msg_len=MAX_MSG)
    got = np.asarray(mask)
    assert not got[n - 2:].any()
    assert int(np.asarray(n_ok)) == int(got[: n - 2].sum())


@pytest.mark.slow  # fused + cached kernel compiles
def test_cached_lane_interleave_matches_generic(rng):
    """Cached-signer (comb) verifies agree with the generic fused lane
    on an interleaved honest/adversarial batch."""
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
    msg, ln, sig, pk = _arrays(cases)
    n = len(cases)
    gen_mask, _ = sv.verify_dispatch(
        "fused", jnp.asarray(msg), jnp.asarray(ln), jnp.asarray(sig),
        jnp.asarray(pk), n, max_msg_len=MAX_MSG)
    fill = np.zeros((32, len(pubs)), dtype=np.uint8)
    for i, p in enumerate(pubs):
        fill[:, i] = np.frombuffer(p, dtype=np.uint8)
    tables, ok = sv.comb_fill(jnp.asarray(fill))
    assert bool(np.asarray(ok).all())
    bank = sv.bank_alloc(len(pubs))
    bank = sv.bank_install(
        bank, tables, jnp.asarray(np.arange(len(pubs), dtype=np.int32)))
    slots = jnp.asarray(
        np.asarray([i % 3 for i in range(n)], dtype=np.int32))
    cached = sv.ed25519_verify_batch_cached(
        jnp.asarray(msg), jnp.asarray(ln), jnp.asarray(sig),
        jnp.asarray(pk), bank, slots, max_msg_len=MAX_MSG)
    assert np.asarray(cached)[:n].tolist() == \
        np.asarray(gen_mask)[:n].tolist()
