"""Differential suite for the native shredder (native/fd_shred.cpp).

Byte parity across lanes is the lane's entire contract: seeded entry
batches through runtime/shredder.Shredder (the Python ground truth,
itself a port of the reference's fd_shredder.c) and
runtime/shred_native.NativeShredder must produce identical data shreds,
parity shreds, merkle roots, and leader signatures — including the
d=32 normal shape, small/odd final FEC sets, the boundary sizes of the
odd-set payload table, and index continuity across batches in a slot.

The stage-level stream diff runs a real leader pipeline with the lane
toggled on/off (and in mixed-lane form) and compares the shreds that
arrive at the store byte for byte.

The module SKIPS (never fails) without the .so or with
FDTPU_NATIVE_SHRED=0 — toolchain-less hosts run the Python lane only.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import random

import pytest

from firedancer_tpu.ops.ref import ed25519_ref as ref
from firedancer_tpu.runtime import shred_native as sn
from firedancer_tpu.runtime.shredder import EntryBatchMeta, Shredder

if not sn.available():
    pytest.skip(
        "native shredder unavailable (no toolchain or FDTPU_NATIVE_SHRED=0)",
        allow_module_level=True,
    )

SECRET = hashlib.sha256(b"shred-native-test").digest()


def _pair(shred_version: int = 2):
    py = Shredder(signer=lambda root: ref.sign(SECRET, root),
                  shred_version=shred_version)
    nat = sn.NativeShredder(secret=SECRET, shred_version=shred_version)
    return py, nat


def _assert_sets_equal(a, b, ctx=""):
    assert len(a) == len(b), ctx
    for s1, s2 in zip(a, b):
        assert s1.fec_set_idx == s2.fec_set_idx, ctx
        assert s1.slot == s2.slot, ctx
        assert s1.merkle_root == s2.merkle_root, ctx
        assert s1.data_shreds == s2.data_shreds, ctx
        assert s1.parity_shreds == s2.parity_shreds, ctx


# batch sizes hitting every branch of the chunking + odd-set payload
# table: single tiny set, the 9135/31840/62400 per-shred boundaries,
# the d=32 normal shape, a normal+odd multi-set batch, and a batch
# whose final odd set exceeds one normal set (d up to 67)
SIZES = [1, 17, 954, 955, 9135, 9136, 16384, 31840, 31841,
         62400, 62401, 63679, 63680, 70000, 200001]


def test_differential_batch_shapes():
    py, nat = _pair()
    rng = random.Random(0xF1D0)
    for sz in SIZES:
        batch = rng.randbytes(sz)
        for bc in (False, True):
            meta = EntryBatchMeta(parent_offset=2, reference_tick=9,
                                  block_complete=bc)
            a = py.entry_batch_to_fec_sets(batch, slot=7, meta=meta)
            b = nat.entry_batch_to_fec_sets(batch, slot=7, meta=meta)
            _assert_sets_equal(a, b, ctx=f"sz={sz} bc={bc}")


def test_mega_batch_over_256_sets():
    """A deferred-flush-sized batch (>256 FEC sets, ~8.4MB) must shred,
    not crash or drop: the plan tables grow with the batch (the Python
    lane has no size ceiling, so this lane must not invent one)."""
    from firedancer_tpu.runtime.shredder import count_fec_sets

    _, nat = _pair()
    batch = random.Random(0x818).randbytes(270 * 31_840)
    expect = count_fec_sets(len(batch))
    assert expect > 256
    sets = nat.entry_batch_to_fec_sets(batch, slot=3)
    assert len(sets) == expect
    # index continuity across the whole run of sets, and a verifiable
    # leader signature on a set past the old 256 cap
    assert sets[0].fec_set_idx == 0
    assert [st.fec_set_idx for st in sets] == sorted(
        st.fec_set_idx for st in sets)
    probe = sets[260]
    from firedancer_tpu.protocol import shred as fs

    sh = fs.parse(probe.data_shreds[0])
    pub = ref.public_key(SECRET)
    assert ref.verify(probe.merkle_root, sh.signature(probe.data_shreds[0]),
                      pub)


def test_differential_index_continuity_and_slot_reset():
    """Shred indices continue across batches within a slot and reset on
    a slot change — in lockstep across lanes."""
    py, nat = _pair()
    rng = random.Random(7)
    for slot in (3, 3, 4, 3):  # includes a slot REUSE after a change
        batch = rng.randbytes(rng.randrange(1, 40_000))
        a = py.entry_batch_to_fec_sets(batch, slot=slot)
        b = nat.entry_batch_to_fec_sets(batch, slot=slot)
        _assert_sets_equal(a, b, ctx=f"slot={slot}")
        assert py.data_idx_offset == nat.data_idx_offset
        assert py.parity_idx_offset == nat.parity_idx_offset


def test_signatures_verify_and_match_reference():
    """The comb-signed roots verify under the strict reference verifier
    AND equal ed25519_ref.sign byte for byte (the key-cache expansion)."""
    _, nat = _pair()
    pub = ref.public_key(SECRET)
    sets = nat.entry_batch_to_fec_sets(b"\xab" * 5000, slot=1)
    for st in sets:
        sig = st.data_shreds[0][:64]
        assert sig == ref.sign(SECRET, st.merkle_root)
        assert ref.verify(st.merkle_root, sig, pub)
        # every shred of the set carries the same signature
        for buf in st.data_shreds + st.parity_shreds:
            assert buf[:64] == sig


def test_resolver_accepts_native_sets():
    """The receive path (FEC resolver with full signature verification)
    reassembles a native-shredded batch."""
    from firedancer_tpu.protocol import shred as fs
    from firedancer_tpu.runtime.fec_resolver import FecResolver

    _, nat = _pair(shred_version=1)
    pub = ref.public_key(SECRET)
    batch = random.Random(11).randbytes(40_000)
    sets = nat.entry_batch_to_fec_sets(batch, slot=1)
    resolver = FecResolver(
        verify_sig=lambda root, sig: ref.verify(root, sig, pub)
    )
    done = {}
    for st in sets:
        for buf in st.data_shreds + st.parity_shreds:
            out = resolver.add_shred(buf)
            if out is not None:
                done[out.fec_set_idx] = out
    assert len(done) == len(sets)
    # reassemble the entry batch from the resolved data shreds
    rebuilt = bytearray()
    for st in sets:
        for buf in done[st.fec_set_idx].data_shreds:
            sh = fs.parse(bytes(buf))
            rebuilt += sh.payload(bytes(buf))
    assert bytes(rebuilt) == batch


ENTRIES = [random.Random(0xBEEF).randbytes(40 + (i * 37) % 900)
           for i in range(64)]


def _drive_ring_stage(native_shred: bool, *, native_ring: bool = True,
                      splice_lossy: bool = False, sigs=None,
                      entries=ENTRIES):
    """Feed a FIXED entry stream through real rings into a ShredStage
    and collect every published shred — deterministic across lanes, so
    the outputs byte-compare.  `sigs`: the frag sig of each entry (the
    slot-clocked poh's poh_sig; default the entry's index)."""
    import time as _t

    from firedancer_tpu.runtime.shred_stage import ShredStage
    from firedancer_tpu.tango import shm

    prev = {k: os.environ.get(k)
            for k in (sn.ENV_SWITCH, "FDTPU_NATIVE_RING")}
    os.environ[sn.ENV_SWITCH] = "1" if native_shred else "0"
    if not native_ring:
        os.environ["FDTPU_NATIVE_RING"] = "0"
    uid = f"{os.getpid()}_{int(_t.monotonic_ns() % 1_000_000)}"
    try:
        link_in = shm.ShmLink.create(f"fdtpu_tsn_in_{uid}", depth=512,
                                     mtu=2048, n_fseq=1)
        link_out = shm.ShmLink.create(f"fdtpu_tsn_out_{uid}", depth=4096,
                                      mtu=1232, n_fseq=1)
        feeder = shm.make_producer(link_in)
        sink = shm.make_consumer(link_out, lazy=0)
        stage = ShredStage(
            "shred",
            ins=[shm.make_consumer(link_in, lazy=8)],
            outs=[shm.make_producer(link_out)],
            signer=lambda root: ref.sign(SECRET, root),
            secret=SECRET if native_shred else None,
            slot=2, batch_target_sz=4096, keep_sets=False,
        )
        if splice_lossy:
            # a chaos-style consumer splice drops the stage off the
            # sweep path: the per-frag fallback must feed the SAME
            # C-side buffer (byte-identical output)
            from firedancer_tpu.tango.lossy import LossyConsumer
            from firedancer_tpu.utils.rng import Rng

            stage.ins[0] = LossyConsumer(stage.ins[0], Rng(1))
        mode = ("sweep" if stage._sweep_client is not None
                else ("nbatch" if stage.native_shred else "python"))
        shreds: list[bytes] = []

        def drain():
            while True:
                res = sink.poll()
                if not isinstance(res, tuple):
                    break
                shreds.append(res[1])

        for i, e in enumerate(entries):
            assert feeder.try_publish(e, sig=sigs[i] if sigs else i,
                                      tsorig=1000 + i)
            stage.run_once()
            drain()
        for _ in range(200):
            stage.run_once()
            drain()
        stage.flush(block_complete=True)
        for _ in range(200):
            stage.run_once()
            drain()
        drain()
        counters = {k: stage.metrics.get(k) for k in
                    ("entries_in", "entry_batches", "fec_sets",
                     "data_shreds_out", "parity_shreds_out")}
        return shreds, counters, mode
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        try:
            del feeder, sink, stage
        except UnboundLocalError:
            pass
        import gc

        # gen-0 only: the just-deleted endpoints' buffer pins are young,
        # and a full collect over the whole suite's heap costs ~10s here
        gc.collect(0)
        for link in (link_in, link_out):
            link.close()
            link.unlink()


def test_stream_diff_sweep_vs_python():
    """The acceptance diff: the zero-Python sweep lane and the pure
    Python lane produce byte-identical shred streams from the same
    entry stream over real rings."""
    on, on_c, on_mode = _drive_ring_stage(True)
    off, off_c, off_mode = _drive_ring_stage(False)
    assert off_mode == "python"
    # on native-ring machines the armed stage must actually sweep
    from firedancer_tpu.tango import shm as tshm

    if tshm.native_ring_enabled():
        assert on_mode == "sweep"
    assert len(on) == len(off) > 0
    assert on == off
    assert on_c == off_c


def test_stream_diff_mixed_lane():
    """Mixed lanes: native shredder over PYTHON rings (no sweep client)
    and a lossy-spliced input (sweep armed, per-frag fallback into the
    same C buffer) both match the Python stream byte for byte."""
    off, _, _ = _drive_ring_stage(False)
    mixed, _, mixed_mode = _drive_ring_stage(True, native_ring=False)
    assert mixed_mode in ("nbatch", "python")
    assert mixed == off
    spliced, _, spliced_mode = _drive_ring_stage(True, splice_lossy=True)
    assert spliced == off


def test_stage_batch_mode_byte_diff():
    """keep_sets mode (NativeShredder behind the Python frag path):
    drive the stage callbacks directly, both lanes, and byte-compare
    every produced shred."""
    from firedancer_tpu.runtime.shred_stage import ShredStage

    rng = random.Random(99)
    entries = [rng.randbytes(rng.randrange(40, 900)) for _ in range(64)]

    def drive(secret):
        stage = ShredStage(
            "shred", ins=[], outs=[],
            signer=lambda root: ref.sign(SECRET, root),
            secret=secret, slot=5, batch_target_sz=4096, keep_sets=True,
        )
        meta = [0, 0, 0, 0, 0, 123456, 0]
        for e in entries:
            stage.after_frag(0, meta, e)
        stage.flush(block_complete=True)
        return stage

    a = drive(None)         # pure Python lane
    b = drive(SECRET)       # NativeShredder batch lane
    assert b.native_shred
    assert not a.native_shred
    assert len(a.sets) == len(b.sets) > 0
    for s1, s2 in zip(a.sets, b.sets):
        assert s1.data_shreds == s2.data_shreds
        assert s1.parity_shreds == s2.parity_shreds
        assert s1.merkle_root == s2.merkle_root


def test_env_toggle_restores_python_lane(monkeypatch):
    """FDTPU_NATIVE_SHRED=0 must build a pure-Python stage even with a
    secret provided (the fallback-intact acceptance criterion)."""
    from firedancer_tpu.runtime.shred_stage import ShredStage

    monkeypatch.setenv(sn.ENV_SWITCH, "0")
    assert not sn.available()
    stage = ShredStage(
        "shred", ins=[], outs=[],
        signer=lambda root: ref.sign(SECRET, root),
        secret=SECRET, slot=1,
    )
    assert not stage.native_shred
    assert stage._sweep_client is None
    assert isinstance(stage.shredder, Shredder)


# -- the stage follows poh's slot (ISSUE 25) -----------------------------------


def _slot_sigs():
    """ENTRIES as a slot-clocked poh would tag them: slot 2 sealed (its
    last tick at 19), slot 3 sealed (at 39), slot 4 cut short (missed,
    no last tick), slot 7 open until the final flush."""
    from firedancer_tpu.runtime.poh_stage import poh_sig

    slot_of = [2] * 20 + [3] * 20 + [4] * 10 + [7] * 14
    return slot_of, [poh_sig(sl, 1000 + i, block_complete=i in (19, 39))
                     for i, sl in enumerate(slot_of)]


def _blocks(shreds):
    """{slot: [parsed data shreds in wire order]}."""
    from firedancer_tpu.protocol import shred as fs

    out: dict = {}
    for buf in shreds:
        sh = fs.parse(bytes(buf))
        if sh.is_data:
            out.setdefault(sh.slot, []).append((sh, bytes(buf)))
    return out


@pytest.mark.parametrize("lane", ["sweep", "spliced", "nbatch"])
def test_stream_diff_slot_follow(lane):
    """Slot-tagged sigs: each lane moves to poh's slot at the same entry,
    ends the old block there, restarts the shred index, and the streams
    stay byte-identical to the Python lane's."""
    from firedancer_tpu.protocol import shred as fs

    slot_of, sigs = _slot_sigs()
    off, off_c, off_mode = _drive_ring_stage(False, sigs=sigs)
    assert off_mode == "python"
    on, on_c, _mode = _drive_ring_stage(
        True, sigs=sigs, native_ring=lane != "nbatch",
        splice_lossy=lane == "spliced")
    assert on == off and on_c == off_c
    blocks = _blocks(on)
    assert sorted(blocks) == [2, 3, 4, 7]
    parent = {2: 1, 3: 1, 4: 1, 7: 3}
    for slot, data in blocks.items():
        assert [sh.idx for sh, _ in data] == list(range(len(data)))
        done = [bool(sh.flags & fs.DATA_FLAG_SLOT_COMPLETE) for sh, _ in data]
        assert done == [False] * (len(data) - 1) + [True]
        assert {sh.parent_off for sh, _ in data} == {parent[slot]}
        want = b"".join(len(e).to_bytes(4, "little") + e
                        for e, sl in zip(ENTRIES, slot_of) if sl == slot)
        assert b"".join(sh.payload(buf) for sh, buf in data) == want


@contextlib.contextmanager
def _tight_stage(native_shred: bool):
    """A ShredStage (slot 2, 4096-byte batches) whose out ring holds 512
    frags, so an undrained sink takes it under the 256 credits a burst
    wants.  -> (stage, feeder, shreds, drain, waiting): drain() empties
    the sink into shreds and so gives the credits back; waiting() says a
    close is deferred."""
    import time as _t

    from firedancer_tpu.runtime.shred_stage import ShredStage
    from firedancer_tpu.tango import shm

    prev = os.environ.get(sn.ENV_SWITCH)
    os.environ[sn.ENV_SWITCH] = "1" if native_shred else "0"
    uid = f"{os.getpid()}_{int(_t.monotonic_ns() % 1_000_000)}"
    link_in = shm.ShmLink.create(f"fdtpu_tsc_in_{uid}", depth=512,
                                 mtu=2048, n_fseq=1)
    link_out = shm.ShmLink.create(f"fdtpu_tsc_out_{uid}", depth=512,
                                  mtu=1232, n_fseq=1)
    try:
        feeder = shm.make_producer(link_in)
        sink = shm.make_consumer(link_out, lazy=0)
        stage = ShredStage(
            "shred", ins=[shm.make_consumer(link_in, lazy=8)],
            outs=[shm.make_producer(link_out)],
            signer=lambda root: ref.sign(SECRET, root),
            secret=SECRET if native_shred else None,
            slot=2, batch_target_sz=4096, keep_sets=False)
        assert (stage._sweep_client is not None) == native_shred
        shreds: list[bytes] = []

        def drain():
            while isinstance(res := sink.poll(), tuple):
                shreds.append(bytes(res[1]))

        def waiting() -> bool:
            c = stage._sweep_client
            if c is not None:
                return c.pending_flush
            return stage._pending_bc or (
                len(stage._buf) >= stage.batch_target_sz)

        yield stage, feeder, shreds, drain, waiting
    finally:
        if prev is None:
            os.environ.pop(sn.ENV_SWITCH, None)
        else:
            os.environ[sn.ENV_SWITCH] = prev
        try:
            del feeder, sink, stage
        except UnboundLocalError:
            pass
        import gc

        gc.collect(0)
        for link in (link_in, link_out):
            link.close()
            link.unlink()


def _slot_done_flags(data):
    from firedancer_tpu.protocol import shred as fs

    return [bool(sh.flags & fs.DATA_FLAG_SLOT_COMPLETE) for sh, _ in data]


@pytest.mark.parametrize("native_shred", [True, False],
                         ids=["sweep", "python"])
def test_last_tick_flush_waits_for_credits_and_keeps_its_flag(native_shred):
    """A slot's last tick closes its block only when the out ring can
    take the burst; meanwhile it keeps block-complete, and the retry from
    after_credit sends it under the old slot before anything of the
    next."""
    from firedancer_tpu.runtime.poh_stage import poh_sig

    with _tight_stage(native_shred) as (stage, feeder, shreds, drain,
                                        waiting):
        # slot 2's entries, undrained: the out ring (512) falls under the
        # 256 credits a burst wants
        i = 0
        out = stage.outs[0]
        out.refresh_credits()
        while out.cr_avail >= 256:
            assert feeder.try_publish(ENTRIES[i % 64], sig=poh_sig(2, i),
                                      tsorig=1)
            stage.run_once()
            out.refresh_credits()
            i += 1
            assert i < 400
        assert i > 20
        n_before = i
        # the last tick: deferred, flag kept; the next slot does not start
        assert feeder.try_publish(
            b"tick" * 10, sig=poh_sig(2, i, block_complete=True), tsorig=1)
        for _ in range(20):
            stage.run_once()
        assert waiting() and stage.slot == 2
        # credits come back: the retry closes slot 2's block
        drain()
        for _ in range(20):
            stage.run_once()
            drain()
        assert not waiting()
        blocks = _blocks(shreds)
        assert sorted(blocks) == [2]
        done = _slot_done_flags(blocks[2])
        assert done == [False] * (len(done) - 1) + [True]
        assert [sh.idx for sh, _ in blocks[2]] == list(range(len(done)))
        want = b"".join(len(e).to_bytes(4, "little") + e for e in
                        [ENTRIES[k % 64] for k in range(n_before)]
                        + [b"tick" * 10])
        assert b"".join(sh.payload(buf) for sh, buf in blocks[2]) == want
        # slot 3 then starts at index 0
        assert feeder.try_publish(ENTRIES[0], sig=poh_sig(3, i + 1), tsorig=1)
        for _ in range(5):
            stage.run_once()
        assert stage.slot == 3
        stage.flush(block_complete=True)
        drain()
        b3 = _blocks(shreds)[3]
        assert [sh.idx for sh, _ in b3] == list(range(len(b3)))
        assert stage.metrics.get("backpressure") == 0


# The last tick's own bytes can be what closes the batch: its append
# crosses batch_target, or it arrives on a size close that waited for
# credits and releases it.  Either way the close that takes the tick is
# the block's last and carries block-complete (REVIEW of PR 25: the
# native lane closed for size with the flag clear, then found nothing
# left to flag).

_TICK = b"\x07" * 40
_FILL = [random.Random(0xF00D + k).randbytes(1016) for k in range(4)]


@pytest.mark.parametrize("lane", ["sweep", "spliced", "nbatch"])
def test_last_tick_that_crosses_batch_target_ends_the_block(lane):
    """4 x (4 + 1016) = 4080 bytes buffered, under the 4096 target; the
    40-byte last tick takes it over.  One close, flagged, on every lane,
    and the next slot's block starts clean."""
    from firedancer_tpu.runtime.poh_stage import poh_sig

    entries = _FILL + [_TICK] + ENTRIES[:3]
    slot_of = [2] * 5 + [3] * 3
    sigs = [poh_sig(sl, 500 + i, block_complete=i == 4)
            for i, sl in enumerate(slot_of)]
    off, off_c, off_mode = _drive_ring_stage(False, sigs=sigs,
                                             entries=entries)
    assert off_mode == "python"
    on, on_c, _mode = _drive_ring_stage(
        True, sigs=sigs, entries=entries, native_ring=lane != "nbatch",
        splice_lossy=lane == "spliced")
    assert on == off and on_c == off_c
    assert on_c["entry_batches"] == 2      # one close a slot
    blocks = _blocks(on)
    assert sorted(blocks) == [2, 3]
    for slot, data in blocks.items():
        done = _slot_done_flags(data)
        assert done == [False] * (len(done) - 1) + [True], (slot, done)
        assert [sh.idx for sh, _ in data] == list(range(len(data)))
        want = b"".join(len(e).to_bytes(4, "little") + e
                        for e, sl in zip(entries, slot_of) if sl == slot)
        assert b"".join(sh.payload(buf) for sh, buf in data) == want


def test_last_tick_that_releases_a_deferred_size_close_flags_it():
    """A size close waits for credits; they come back just as the last
    tick arrives (no after_credit in between: a later frag of the same
    sweep, or the fused stage's in-process hop).  The tick's append
    releases the close, which takes the tick and is the block's end.
    The native and the Python lane agree byte for byte."""
    from firedancer_tpu.runtime.poh_stage import poh_sig
    from firedancer_tpu.tango.rings import MCache

    def frag(stage, payload, sig):
        meta = [0] * 8
        meta[MCache.COL_SIG] = sig
        meta[MCache.COL_TSORIG] = 1
        stage.after_frag(0, meta, payload)

    streams = []
    for native_shred in (True, False):
        with _tight_stage(native_shred) as (stage, _feeder, shreds, drain,
                                            waiting):
            out = stage.outs[0]
            i = 0
            while not waiting():       # undrained: a size close defers
                frag(stage, ENTRIES[i % 64], poh_sig(2, i))
                i += 1
                assert i < 400
            out.refresh_credits()
            assert out.cr_avail < 256
            n_batches = stage._sweep_client.counters()["entry_batches"] \
                if native_shred else stage.metrics.get("entry_batches")
            drain()                    # the credits come back ...
            frag(stage, _TICK, poh_sig(2, i, block_complete=True))
            assert not waiting()       # ... and the tick's append closed
            drain()
            frag(stage, ENTRIES[0], poh_sig(3, i + 1))
            assert stage.slot == 3
            stage.flush(block_complete=True)
            drain()
            after = stage.metrics.get("entry_batches")
            assert after == n_batches + 2  # slot 2's one last close, slot 3
            blocks = _blocks(shreds)
            assert sorted(blocks) == [2, 3]
            for slot, data in blocks.items():
                done = _slot_done_flags(data)
                assert done == [False] * (len(done) - 1) + [True], slot
                assert [sh.idx for sh, _ in data] == list(range(len(data)))
            want = b"".join(len(e).to_bytes(4, "little") + e for e in
                            [ENTRIES[k % 64] for k in range(i)] + [_TICK])
            assert b"".join(sh.payload(b) for sh, b in blocks[2]) == want
            streams.append((i, shreds))
    assert streams[0] == streams[1]
