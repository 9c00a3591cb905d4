"""The verify stage: txn parse + dedup guard + batched TPU sigverify.

Pipeline position and semantics mirror the reference's verify tile
(/root/reference/src/app/fdctl/run/tiles/fd_verify.c):

  - round-robin shard by input seq across N verify stages (fd_verify.c:46);
  - parse the txn (drop on malformed, fd_verify.c:117);
  - small per-stage tcache keyed on the first signature, guarding duplicate
    spam racing across round-robin peers (fd_verify.h:6-7 — real dedup is
    the downstream dedup stage's big tcache; keep both);
  - ed25519-verify EVERY signature; a txn passes only if all pass
    (fd_verify.h:45-89);
  - publish payload + parsed descriptor to the output, so downstream never
    reparses (the parsed-txn trailer convention, fd_verify.c:93-100).

TPU-native twist (the wiredancer async-offload shape, SURVEY §7.1): txns
accumulate into fixed-shape device batches, and a window of them stays
in flight so host streaming overlaps device compute.  Fixed shapes mean
partial batches are padded and the pad lanes' results ignored.

A batch crosses the host-device boundary once each way: going in it is
ONE (batch, row_width) uint8 array of packed rows — element e's message,
signature, signer and message length in row e (ops/sigverify.unpack_rows
has the layout; the native intake fills slots of exactly these rows, the
Python lane's _assemble builds the same), one `device_put` of the slot's
own memory — and coming back it is the program's one output, the (batch,)
bool mask, fetched once at the reap, which counts the passes itself.

How deep the window is (one test, `_window_has_room`, on the native and
the Python lane alike): WINDOW_DEPTH, two — one
batch running and one queued behind it.  The second place is for FULL
batches, and for a batch that is not full only while the thread LEADS
the chip: it exists to keep the device back to back, which matters only
when the device is what limits, and then the batches fill faster than
the device runs them.  A batch dispatched behind another waits a whole
program length on the device's queue, so a batch that is not full is
not queued behind any other batch unless the stage has seen that the
device limits (next paragraph): it would run no sooner than if it had
stayed open, and what arrived meanwhile would wait for the batch after.
`max_inflight` can only narrow the window (1: one batch at a time).

When a batch closes (one rule, `_past_deadline` over `_window_open`,
asked in `_deadline_close` on the native and the Python lane alike):

  - when it is full (in C, inside the crossing, on the native lane): it
    is dispatched at once if the window has room, behind a running
    batch if there is one, and is parked until a reap otherwise;
  - when its deadline (`batch_deadline_s`) has passed, no sealed batch
    waits ahead of it, AND either
      (a) nothing is in flight (it would run now) and the intake is not
          backlogged, or
      (b) the window has room and, since the chip last ran dry, a full
          batch had to wait for its place in it (the thread leads the
          chip: the queued program length is the slack that rides out
          the thread's hiccups), and no batch that was not full has
          taken that slack since.
    While any other batch is in flight an open batch past its deadline
    stays open and keeps taking frags, until it fills (the line above)
    or the pump that reaps the running batch comes through the rule
    again in the same pass (reap -> publish -> seal -> dispatch);
  - on `flush()`, whatever is in flight.

The one thing the rule reads that is not the window is the intake's
`backlogged` (runtime/stage.py `_note_sweep`): the last intake sweep
took its whole burst from the ring in front, so more is waiting there.
A burst, for this stage, is a quarter of the shallowest ring in front
(at most a batch, at least Stage's 16; the constructor works it out
from `ins` and `batch`): everything around the C crossing — the loop's
stamps, the credit checks, the pump's poll of the device, the wait
books — is paid once a sweep whatever the sweep takes, and so is a call
of the stage in front, which offers what the ring has credits for, so
a 1,024-lane batch is gathered in 4 sweeps where a burst of 16 made it
64.  It stays under the ring's depth because the evidence needs it to:
a sweep that may take all the ring can hold empties it every time and
says nothing about what is waiting behind it; a quarter leaves a
standing backlog three quarters of a ring to show in.  (The one-thread
leader pipeline, whose pack sheds what its pool cannot hold instead of
pushing back, sets its verify stages back to Stage's 16: what the
stage in front of such a pack takes in a turn is that pipeline's only
flow control — models/leader._take_turns.)
While that holds, a batch that would run now but part empty stays open
and fills — (a) waits — and goes the way full batches go; the first
sweep that comes back short ends the backlog, and the batch goes at
the next pass.  Why (a) and (b) differ: a dispatch costs the thread the
same blocking calls (copy, launch, reap, publish) at any fill.  Where
the thread limits — a full batch takes longer to gather than the
program takes to run, so nothing is in flight when the deadline passes
— a partial dispatch is a whole dispatch's cost for part of its lanes,
and under a backlog the ring in front is where the latency is anyway.
Where the device limits — the thread fills 1,024 lanes in under a
program length, so a batch fills while two are still in flight, finds no
place and waits sealed for the next reap, which is the evidence (b)
asks for (`_waits_for_place`; `batch_sealed_wait_ns` is the same fact
on the clock) — a queued partial batch costs nothing the device was not
going to wait for, and is slack.  A full batch that went out at once
behind a running one is no such evidence: a thread that TRAILS the chip
does that too, whenever a part-empty batch is still running, and a rule
that took it for evidence would make that part-empty batch itself, every
other dispatch, for ever.  Nor is a full batch that went out alone:
under a backlog every batch of a thread-bound stage closes full.  The
evidence lasts until the chip runs dry (a reap leaves nothing in
flight, where `chip_empty_ns` starts: after a hiccup one full batch
goes out alone, and the next that has to wait restores it) or the slack
is spent (a batch that is not full is dispatched): the stage keeps at
most one part-empty batch queued for each full batch that waited, so a
thread that falls behind the chip finds its way back to one dispatch
per full batch.

So a stage whose thread leads the chip keeps two in flight, a paced one
keeps one and seals at the reap, and one whose thread trails under a
backlog dispatches once per full batch — with the chip running or not
when the batch fills — from what the stage itself observes: whether a
full batch had to wait for a place, whether the device has work,
whether the ring in front ran dry.  No setting, no clock, no estimate
of the program's length.
`batch_close_full + batch_close_deadline + batch_close_window == batches`
says which of the three closed each dispatched batch,
`batch_queued_behind` how many were dispatched while another was in
flight (the second place used: full batches, the batch sealed under
(b), and what flush() sends; over `batches` ~1 where the thread leads
the chip, between 0 and ~1 where it trails — a full batch goes out
behind the running one or after it by how the fill time compares with
the program's — and 0 where the stage is paced or shares its thread
with slower stages), and `batch_held_backlogged` how many
were kept open past their deadline for the backlog alone (over
`batches`: ~1 in a thread-bound flood, the chip's own speed
notwithstanding; ~0 where the stage is paced or the thread leads).
Two counters say which lanes carried
no verdict anyone used: `batch_fit_pad_lanes`, the lanes left empty by
batches sealed because the next transaction's signatures did not fit
(such a batch closed full: it is as full as its transactions allow), and
`verify_fail_elems`, the lanes of the transactions that failed whole.

When the chip had nothing of this stage's to run, and whose time that
was, is stamped too (`_phase_end`, with the clock reads it makes
anyway): `chip_empty_ns` / `chip_empty_n` run from the loop's first
sight of a finished batch with no other in flight to the end of the
next dispatch's launch; of that, `chip_empty_away_ns` is the time the
thread spent outside this stage's run_once (the other stages on its
thread) and `chip_empty_call_ns` the time inside this stage's own
blocking phases; the rest is the stage in its own loop, filling the
next batch.  What the stage cannot see is how late the ready flag
turns: the device's own idle share is that much larger.

More than one chip behind one intake (`devices=n`): the stage builds a
one-axis mesh over the first n local devices, the native intake seals
slots of the whole fixed shape (n x batch // n lanes), and the dispatch
deals the slot's elements round-robin — element e of a step goes to
chip e % n, as the reference's N verify tiles each take seq % N — by
placing rows i, i + n, ... of the slot's packed array straight onto chip
i, and runs the same program over the mesh: one module a step, no
collective in it.  Reap, publish, the close rule, the stamps and
the counters are the one-device lane's; `shard_elems_s{i}` counts the
useful lanes chip i was given.

Repeated-signer fast path (round 4): real ingress repeats signers heavily
(one vote key per validator), so the stage keeps a device-resident comb
bank (ops/sigverify.py comb_fill / ed25519_verify_batch_cached).  A pubkey
seen >= promote_threshold times gets its comb built (a batched device call
costing ~3 verifies of work) and installed; txns whose signers are ALL
cached accumulate into a separate batch dispatched to the cached kernel —
128 cached adds per sig instead of 256 doublings + 142 adds + A decompress.
The reference's analog is its precomputed base-point table
(src/ballet/ed25519/table/) — extended here to runtime-filled per-signer
tables, which only a batch-oriented accelerator with GBs of HBM can afford.

One kernel element = one (signature, signer pubkey, message) triple; a
multi-sig txn contributes sig_cnt elements and passes iff all its elements
pass (reference batch rejects the whole batch on any failure and the tile
then drops the txn — element-level masks give us the same txn-level rule
without the retry).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import numpy as np

from firedancer_tpu.protocol import txn as ft
from firedancer_tpu.tango.rings import MCache, TCache
from firedancer_tpu.utils import metrics as fm
from .stage import Stage
from . import verify_native as vn
from .verify_native import CLOSE_DEADLINE, CLOSE_FULL, CLOSE_WINDOW

# the per-packet parse is this stage's host hot path: prefer the native
# (C++) parser — differentially proven byte-identical — and fall back to
# the python parser where no toolchain exists
try:
    from firedancer_tpu.protocol.txn_native import txn_parse_packed as _txn_packed

    _txn_packed(b"")  # force the .so build/load now, not mid-stream
    PARSER = "native"
except Exception:  # pragma: no cover - toolchain-less environment
    _txn_packed = None
    PARSER = "python"


def _parse_pair(payload: bytes):
    """-> (Txn | None, packed-descriptor bytes | None); (None, None) on
    reject.  The native parser emits the packed trailer directly, and
    the stage reads the few fields it needs (signatures, message,
    signers) straight from the packed offsets — no Txn object is ever
    built on the native-parse path (the txn_unpack construction cost ~7
    us/frag of the verify host path), and _emit never re-serializes the
    descriptor (zero-copy through to pack and the bank lane)."""
    if _txn_packed is not None:
        packed = _txn_packed(payload)
        if packed is None:
            return None, None
        # structural sanity without unpacking: the trailer must be
        # exactly the declared fixed-layout length (instr/lut counts at
        # bytes 16/13; the layout has ONE owner — protocol/txn.py)
        if len(packed) != ft.txn_packed_sz(packed[16], packed[13]):
            return None, None
        return None, packed
    t = ft.txn_parse(payload)
    return t, None


def _packed_fields(payload: bytes, packed: bytes):
    """(signatures, message, signers) read straight off the packed
    descriptor — the zero-object fast path for the per-frag loop."""
    sig_cnt = packed[1]
    sig_off = packed[2] | (packed[3] << 8)
    msg_off = packed[4] | (packed[5] << 8)
    acct_off = packed[9] | (packed[10] << 8)
    sigs = [payload[sig_off + 64 * i : sig_off + 64 * (i + 1)]
            for i in range(sig_cnt)]
    signers = [payload[acct_off + 32 * i : acct_off + 32 * (i + 1)]
               for i in range(sig_cnt)]
    return sigs, payload[msg_off:], signers


def _packed_first_sig(payload: bytes, packed: bytes) -> bytes:
    sig_off = packed[2] | (packed[3] << 8)
    return payload[sig_off : sig_off + 64]

MCACHE_COL_TSORIG = MCache.COL_TSORIG

VERIFY_TCACHE_DEPTH = 16  # tiny by design (fd_verify.h:6-7)

COMB_FILL_BATCH = 32  # pubkeys per comb_fill dispatch (fixed jit shape)

# the async in-flight window (wiredancer shape): how many device batches
# a stage keeps outstanding — one running and, if it or the running one
# is full, one queued behind it (module docstring).  Reaping is strictly
# in submission order at any depth.
WINDOW_DEPTH = 2

# native sweep-client frames are payload + packed descriptor + u16; the
# out link must carry them (fd_verify.cpp FRAME_CAP)
_NATIVE_FRAME_MTU = 1232 + 2048 + 2


# a batch's life (utils/metrics.BATCH_PHASES), phase ids
(PH_OPEN, PH_SEALED_WAIT, PH_H2D, PH_LAUNCH, PH_INFLIGHT, PH_REAP,
 PH_PUBLISH) = range(len(fm.BATCH_PHASES))
_PHASE_COUNTERS = tuple(f"batch_{p}_ns" for p in fm.BATCH_PHASES)
# the phases the stage's thread spends getting the chip its next batch:
# the blocking calls, and a sealed batch's wait for the thread
_CHIP_CALL_PHASES = fm.BATCH_BLOCKING_PHASES | {PH_SEALED_WAIT}

# what closed a batch (the ids are the binding's, held to
# native/fd_verify.cpp by fdlint FD305): it filled; its deadline passed
# with the window open to it (_window_open) and no backlog in front
# (_past_deadline); or it was held past its deadline by the window and
# sealed at a reap.  Counted at dispatch, so
# the three add up to `batches`.
_CLOSE_COUNTERS = fm.BATCH_CLOSE_COUNTERS

# why an open batch was kept open past its deadline (_past_deadline): the
# window was shut to it; or the window was open and the intake backlogged
_HELD_WINDOW, _HELD_BACKLOGGED = 1, 2

_now_ns = time.monotonic_ns

_trace_annotation = None
_NO_SPAN = contextlib.nullcontext()


class _Life:
    """One batch's stamps on time.monotonic_ns(): t[0] is when its first
    element entered the slot, t[k + 1] when phase k ended (BATCH_PHASES
    order).  `seq` is its dispatch order, from 1."""

    __slots__ = ("seq", "t")

    def __init__(self, opened_ns: int):
        self.seq = 0
        self.t = [opened_ns]


def sig_tag(sig: bytes) -> int:
    """64-bit dedup tag: low 8 bytes of the (uniformly distributed) sig."""
    return int.from_bytes(sig[:8], "little") or 1


# -- the program's one argument: where a batch's rows go ----------------------
#
# What a stage dispatches is fixed by three numbers — batch, max_msg_len,
# devices — so the stage and `python -m firedancer_tpu warmup`, which
# builds no stage, share these three functions and compile one program.


def mesh_row_sharding(batch: int, devices: int | None):
    """How a (batch, row_width) array of packed rows lies over `devices`
    chips: None for the default device (devices None or 1), else rows
    sharded P(axis, None) over a one-axis mesh of the first `devices`
    local devices (parallel/mesh.AXIS; the mask comes back P(axis)).
    Raises ValueError where the batch, or the (batch // 128, 128) rows
    the program folds it to, do not divide over the chips."""
    if devices is None or devices == 1:
        return None
    from firedancer_tpu.ops.sigverify import FOLD_LANES, fold_lanes

    if batch % devices:
        raise ValueError(
            f"verify batch {batch} does not divide over"
            f" {devices} devices")
    if fold_lanes(batch) and batch % (FOLD_LANES * devices):
        # the program sees the global shape and folds it to
        # (batch // 128, 128) rows: they have to divide too
        raise ValueError(
            f"verify batch {batch} is folded by the program to"
            f" {batch // FOLD_LANES} rows of {FOLD_LANES} lanes,"
            f" which do not divide over {devices} devices")
    from jax.sharding import NamedSharding, PartitionSpec

    from firedancer_tpu.parallel import mesh as pm

    return NamedSharding(pm.make_mesh(devices),
                         PartitionSpec(pm.AXIS, None))


def place_rows(rows: np.ndarray, sharding):
    """One batch's packed rows (element e in row e) onto the device(s)
    -> the program's one argument.  One device (`sharding` None): one
    `device_put` of the contiguous array as it lies (the native lane's
    is the slot's own memory, not written again until the reap releases
    the slot) — no transpose, no host copy of ours.  A mesh of d
    devices: chip i is dealt rows i, i + d, ... — one row-strided view
    per chip straight onto its shard (wrapping in jnp.asarray first
    would commit the whole batch to device 0 and then reshard it).
    uint8: 4x less transfer; the program widens to int32 on the
    device."""
    import jax

    if sharding is None:
        return jax.device_put(rows)
    d = sharding.mesh.size
    per = rows.shape[0] // d
    return jax.make_array_from_callback(
        rows.shape, sharding, lambda idx: rows[idx[0].start // per::d])


def warm_program(batch: int, max_msg_len: int, sharding) -> float:
    """Compile (or load from the persistent cache) the program a stage
    of this geometry dispatches, at its exact shape, dtype and
    placement: one all-pad batch through the call the dispatch makes.
    -> seconds."""
    from firedancer_tpu.ops import sigverify as sv

    t0 = time.monotonic()
    rows = np.zeros((batch, vn.row_width(max_msg_len)), dtype=np.uint8)
    sv.verify_dispatch(place_rows(rows, sharding),
                       max_msg_len=max_msg_len).block_until_ready()
    return time.monotonic() - t0


@dataclass
class _Pending:
    """A device batch in flight: txns + their element ranges + the future."""

    payloads: list[bytes]
    descs: list  # [(Txn, packed-desc | None)]
    elem_ranges: list[tuple[int, int]]
    tsorigs: list[int]
    n_elems: int
    result: object  # jax array future
    life: _Life  # the batch's stamps


@dataclass
class _Acc:
    """One accumulating fixed-shape batch (generic or cached-signer)."""

    payloads: list[bytes] = field(default_factory=list)
    descs: list = field(default_factory=list)  # [(Txn, packed | None)]
    elems: list[tuple[bytes, bytes, bytes]] = field(default_factory=list)
    ranges: list[tuple[int, int]] = field(default_factory=list)
    tsorigs: list[int] = field(default_factory=list)
    slots: list[int] = field(default_factory=list)  # cached path only
    opened_at: float = 0.0
    life: _Life | None = None  # stamped when the first element enters
    held: int = 0  # _HELD_* marks: seen past its deadline and kept open
    close: int = CLOSE_FULL  # what sealed it (CLOSE_*)

    def clear(self) -> None:
        self.payloads, self.descs = [], []
        self.elems, self.ranges, self.tsorigs, self.slots = [], [], [], []
        self.opened_at = 0.0  # re-stamped by before_credit when reopened
        self.held = 0


class VerifyStage(Stage):
    def __init__(
        self,
        *args,
        shard_idx: int = 0,
        shard_cnt: int = 1,
        batch: int = 256,
        max_msg_len: int = 1232,
        batch_deadline_s: float = 0.002,
        max_inflight: int | None = None,
        autotune_after: int = 0,
        native_client: bool | None = None,
        devices: int | None = None,
        precomputed_ok: bool = False,
        comb_slots: int = 0,
        promote_threshold: int = 2,
        **kwargs,
    ):
        super().__init__(*args, **kwargs)
        # precomputed_ok: bench instrument — skip the device dispatch and
        # mark every element valid, so the HOST pipeline machinery (rings,
        # parse, dedup, pack, bank, poh, shred) is measured net of
        # accelerator round trips.  Never use outside bench.
        self.precomputed_ok = precomputed_ok
        # devices: how many chips are behind this stage.  None or 1 is
        # the default device.  n > 1 is a one-axis mesh over the first n
        # local devices: device i owns lanes [i, i + 1) * batch // n of
        # the fixed-shape batch and is dealt elements i, i + n, i + 2n,
        # ... of every batch (the reference's N verify tiles each take
        # seq % N), so the chips fill evenly at any fill; every generic
        # batch is placed straight onto its shards and verified by the
        # SAME program (ops/sigverify.verify_dispatch), one compiled
        # module a step over the mesh (mesh_row_sharding, place_rows).
        from firedancer_tpu.ops.sigverify import fold_lanes

        self._row_sharding = mesh_row_sharding(batch, devices)
        self.mesh_devices = 1
        if self._row_sharding is not None:
            if comb_slots:
                raise ValueError(
                    "devices= places generic batches itself: a comb bank"
                    " dispatches elsewhere")
            self.mesh_devices = devices
            self._use_shard_schema(devices)
        self.shard_idx = shard_idx
        self.shard_cnt = shard_cnt
        self.batch = batch
        # what one intake sweep may take, from the stage's own
        # geometry: a quarter of the shallowest ring in front, at most
        # a batch, at least Stage's default (module docstring: a
        # sweep's cost is paid once a sweep, and the backlog's evidence
        # needs a whole burst to be less than the ring can hold)
        depth = min((c.link.depth for c in self.ins), default=0)
        self.burst = max(self.burst, min(batch, depth // 4))
        self.max_msg_len = max_msg_len
        self.batch_deadline_s = batch_deadline_s
        # the most batches in flight: WINDOW_DEPTH, or the fewer a caller
        # holds the stage to
        self.max_inflight = (WINDOW_DEPTH if max_inflight is None
                             else min(WINDOW_DEPTH, max_inflight))
        # autotune_after: re-derive (batch, max_msg_len, comb split) from
        # this stage's own batch-fill/msg-len histograms every N closed
        # batches (runtime/verify_tune.py); 0 = off (retuning recompiles)
        self.autotune_after = autotune_after
        self._last_tune_batches = 0
        self._comb_lane_on = True
        self.tcache = TCache(VERIFY_TCACHE_DEPTH)
        # comb bank (0 slots = fast path disabled)
        self.comb_slots = comb_slots
        self.promote_threshold = promote_threshold
        self._bank = None  # device (NWIN,16,4,NLIMB,N) int16, lazy alloc
        self._slot_of: dict[bytes, int] = {}
        self._seen_cnt: dict[bytes, int] = {}
        self._fill_queue: list[bytes] = []
        self._free_slots: list[int] = list(range(comb_slots))
        # accumulating batch state: generic and cached-signer lanes
        self._gen = _Acc()
        self._comb = _Acc()
        self._inflight: list[_Pending] = []
        # sealed batches waiting for an in-flight window slot: submit is
        # backpressure-aware — a full window parks the sealed batch here
        # instead of blocking the loop on the oldest device future; a
        # deep queue (memory bound) falls back to the blocking drain
        self._submit_queue: list = []
        self._submit_queue_max = 4
        # verified frames awaiting output-ring credits: a whole batch can
        # complete while the out ring holds fewer credits than the burst,
        # and dropping the tail (the old per-frag posture) loses verified
        # work — queue and retry, bounded so a dead consumer cannot grow
        # the queue without limit
        self._emit_queue: list = []
        self._emit_queue_max = 8192
        # whose frames the emit queue holds, in order: [life, frames
        # still queued] per reaped batch — a batch's publish phase ends
        # when its last frame has left the queue
        self._emit_marks: list = []
        for name in (_PHASE_COUNTERS + _CLOSE_COUNTERS
                     + (fm.BATCH_QUEUED_BEHIND, fm.BATCH_HELD_BACKLOGGED,
                        fm.BATCH_FIT_PAD_LANES, fm.VERIFY_FAIL_ELEMS)):
            self.metrics.counters[name] = 0
        self.metrics.counters["batch_stalls"] = 0
        self.metrics.counters["mesh_devices"] = self.mesh_devices
        # the layout of the batch inside the program this stage
        # dispatches, from the program's own predicate on the shape
        self.metrics.counters[fm.KERNEL_FOLD_LANES] = fold_lanes(batch)
        for name in fm.CHIP_EMPTY_COUNTERS:
            self.metrics.counters[name] = 0
        # the open interval in which the chip has nothing of this
        # stage's to run (_phase_end): when the loop first saw the chip
        # done (0 = the chip has work); up to when the interval's time
        # is charged to a call of this stage's; and the two sums so far
        self._chip_empty_since = 0
        self._chip_mark = 0
        self._chip_call_ns = 0
        self._chip_away_ns = 0
        # sweep-granularity parser (drain-table path), built on first use
        self._burst_parser = None
        # -- native sweep client (ISSUE 13) -----------------------------------
        # the whole intake sweep (drain -> parse -> guards -> batch
        # assembly) in ONE fdr_sweep crossing with zero Python per frag;
        # armed only on the generic lane (no comb bank; one device or a
        # mesh of them: the slot is the whole fixed-shape batch either
        # way) over all-native rings whose out link carries
        # the preassembled frame size.  native_client: None = auto-arm
        # for exact VerifyStage instances, False = never, True =
        # required (raises, naming what blocked it).
        self._sweep_client = None
        # (slot, n_elems, n_txn, result, life)
        self._nv_inflight: list = []
        # [slot, arena address, frame table, rows published, life]
        self._nv_emit: list = []
        # the open batch (named by its C-side open stamp) that was kept
        # open past its deadline, and its _HELD_* marks
        self._nv_held = (0, 0)
        # since the chip last ran dry (a reap left nothing in flight) a
        # full batch had to wait for its place in the window, and no
        # batch that was not full has been dispatched since: the stage's
        # evidence that its thread leads the chip (_waits_for_place sets
        # it, the reap and _count_dispatch end it; _window_open reads it)
        self._full_waited = False
        # unasked, the C intake arms for a class that brings one
        # (VerifyStage's own; a subclass with an intake of its own); a
        # test's subclass stays on the Python lane unless it asks
        want_native = (native_client if native_client is not None
                       else "_new_sweep_client" in vars(type(self)))
        if want_native:
            # structural preconditions, each named so native_client=True
            # (the "required" contract) can say exactly what blocked it
            blocker = None
            if comb_slots != 0:
                blocker = ("the comb bank's signer tracking dispatches"
                           " from the Python lane")
            elif not self.ins or not self.outs:
                blocker = "stage has no rings"
            elif not all(type(c).__name__ == "NativeConsumer"
                         for c in self.ins):
                blocker = "not every input is a native-ring consumer"
            elif type(self.outs[0]).__name__ != "NativeProducer":
                blocker = "the output is not a native-ring producer"
            elif self.outs[0].link.mtu < self._native_frame_mtu():
                blocker = (f"out link mtu {self.outs[0].link.mtu} <"
                           f" {self._native_frame_mtu()} (frame headroom)")
            if blocker is None:
                try:
                    if not vn.available():
                        raise vn.NativeUnavailable(
                            "toolchain missing or FDTPU_NATIVE_VERIFY=0")
                    self._sweep_client = self._new_sweep_client(
                        self.max_inflight + 2)
                except vn.NativeUnavailable as e:
                    if native_client:
                        raise RuntimeError(
                            f"native_client=True but the verify sweep"
                            f" client is unavailable: {e}") from e
            elif native_client:
                raise RuntimeError(
                    f"native_client=True but the stage cannot arm the"
                    f" sweep client: {blocker}")

    # -- which C intake (a subclass with one of its own overrides both) -------

    def _native_frame_mtu(self) -> int:
        """The least mtu of the out link the C intake's frames need."""
        return _NATIVE_FRAME_MTU

    def _new_sweep_client(self, n_slots: int):
        return vn.StageClient(
            shard_idx=self.shard_idx, shard_cnt=self.shard_cnt,
            batch=self.batch, max_msg_len=self.max_msg_len, n_slots=n_slots)

    # -- observability ------------------------------------------------------

    @classmethod
    def extra_schema(cls) -> fm.MetricsSchema:
        return (
            fm.MetricsSchema()
            .counter("txn_verified", "txns whose every signature verified")
            .counter("verify_fail", "txns failing signature verification")
            .counter("parse_fail", "malformed txns dropped at parse")
            .counter("dedup_dup", "duplicates caught by the stage tcache")
            .counter("msg_too_long", "txns over max_msg_len")
            .counter("too_many_sigs", "txns that can never fit a batch")
            .counter("batches", "device batches dispatched")
            .counter("batch_elems", "signature elements dispatched")
            .counter("comb_elems", "elements on the cached-signer lane")
            .counter("comb_filled", "comb tables installed in the bank")
            .counter("emit_dropped",
                     "verified frames dropped after the bounded emit"
                     " retry queue overflowed (dead/wedged consumer)")
            .counter("submit_deferred",
                     "batches sealed while the in-flight window was full"
                     " (backpressure-aware submit parked them)")
            .counter("intake_dropped",
                     "frags dropped after the native intake stash"
                     " overflowed (dead/wedged consumer)")
            .counter("retunes", "autotuner geometry changes applied")
            .gauge("mesh_devices",
                   "chips behind this stage: 1 = the default device, n > 1"
                   " = a mesh whose chip i takes elements i, i + n, ... of"
                   " every batch (shard_elems_s{i} counts them)")
            .gauge(fm.KERNEL_FOLD_LANES,
                   "128 when the program this stage dispatches folds its"
                   " batch onto both tiled axes, (batch // 128, 128): a"
                   " limb is whole vregs (ops/sigverify.fold_batch; the"
                   " batch is a multiple of 128); 0 = one trailing axis")
            # the life of a batch, summed over batches as each phase
            # ends (ns; divide a window's delta by its delta of batches)
            .counter("batch_open_ns",
                     "first element in the slot -> the slot sealed")
            .counter("batch_sealed_wait_ns",
                     "sealed -> the dispatch call begins (waiting for a"
                     " window slot, or for the thread to come back)")
            .counter("batch_h2d_ns",
                     "the host->device copy of the dispatch (on the"
                     " Python lane the packed-row assembly before it too)")
            .counter("batch_launch_ns",
                     "the kernel dispatch call, copies excluded")
            .counter("batch_inflight_ns",
                     "dispatch returned -> the loop first sees the mask"
                     " ready (device queue + execution + how late the"
                     " thread looked)")
            .counter("batch_reap_ns",
                     "mask fetched, per-txn reduction")
            .counter("batch_publish_ns",
                     "reaped -> the batch's last frame out and its slot"
                     " released (credit waits included)")
            .counter("batch_stalls",
                     "thread-blocking batch phases (h2d, launch, reap,"
                     " publish) of 100 ms or more; each is an"
                     " EV_BATCH_STALL flight event")
            # what closed each dispatched batch: the three add up to
            # `batches`
            .counter("batch_close_full",
                     "batches sealed because they filled (or the next"
                     " txn's signatures did not fit)")
            .counter("batch_close_deadline",
                     "batches sealed past their deadline with nothing in"
                     " flight (and no backlog in front, or none any more),"
                     " or with room in the window after a full batch had"
                     " to wait for its place (flush() counts here)")
            .counter("batch_close_window",
                     "batches held open past their deadline by a batch in"
                     " flight while no full batch had had to wait for a"
                     " place (or by a full window), sealed at a reap")
            .counter(fm.BATCH_QUEUED_BEHIND,
                     "batches dispatched while another was in flight (the"
                     " window's second place: full batches, the batch"
                     " sealed past its deadline after a full one had to"
                     " wait for its place, and flush()): over `batches`,"
                     " ~1 where the thread leads the chip, 0 where the"
                     " stage is paced")
            .counter(fm.BATCH_HELD_BACKLOGGED,
                     "batches kept open past their deadline with nothing"
                     " in flight because the intake was backlogged (the"
                     " last sweep took its whole burst), once a batch:"
                     " over `batches`, ~1 in a thread-bound flood (with"
                     " the chip faster than the thread or not), ~0 where"
                     " the stage is paced or the thread leads the chip")
            # lanes that carried no verdict anyone used: those a batch
            # sealed for want of room left empty, and those of the
            # transactions that failed whole (one bad signature fails
            # all of a transaction's lanes)
            .counter(fm.BATCH_FIT_PAD_LANES,
                     "lanes left empty by batches sealed because the next"
                     " txn's signatures did not fit (a txn's elements land"
                     " in one batch)")
            .counter(fm.VERIFY_FAIL_ELEMS,
                     "signature elements of the txns counted in"
                     " verify_fail")
            # when the chip had nothing of this stage's to run, and
            # whose time that was (added as each interval ends)
            .counter("chip_empty_ns",
                     "the loop first saw a batch done with no other in"
                     " flight -> the next dispatch's launch returned")
            .counter("chip_empty_n", "such intervals ended")
            .counter("chip_empty_call_ns",
                     "of chip_empty_ns, inside this stage's blocking"
                     " phases (reap and publish of the batch that left;"
                     " sealed_wait, h2d and launch of the one that ends"
                     " it), each charged its part in the call that ends"
                     " it")
            .counter("chip_empty_away_ns",
                     "of chip_empty_ns, the thread was outside this"
                     " stage's run_once: the other stages' time")
            .histogram(
                "batch_fill",
                fm.exp_buckets(1, 4096, 13),
                "elements per closed device batch (fill vs the fixed shape)",
            )
            .histogram(
                "msg_len",
                fm.exp_buckets(32, 2048, 13),
                "per-txn message bytes (autotuner evidence)",
            )
            .histogram(
                "inflight_occupancy",
                tuple(float(i) for i in range(1, 17)),
                "in-flight batches at submit (async window fill)",
            )
        )

    @classmethod
    def metrics_schema_n(cls, n_shards: int) -> fm.MetricsSchema:
        """The class schema + per-shard element counters (the per-shard
        metrics the scrape surface labels by shard): what a stage over
        a mesh of `n_shards` devices publishes, and what a process
        topology sizes its shm segment from."""
        s = cls.metrics_schema()
        for i in range(n_shards):
            s.counter(f"shard_elems_s{i}",
                      f"signature elements dispatched on shard {i}")
        return s

    def _use_shard_schema(self, n_shards: int) -> None:
        """Swap the stage's metrics for ones over metrics_schema_n,
        keeping what was counted so far; the shard counters start at 0."""
        self._use_schema(self.metrics_schema_n(n_shards))
        for i in range(n_shards):
            self.metrics.counters.setdefault(f"shard_elems_s{i}", 0)

    # -- mux callbacks ------------------------------------------------------

    def before_frag(self, in_idx: int, seq: int, sig: int) -> bool:
        return (seq % self.shard_cnt) == self.shard_idx

    def _intake(self, payload: bytes):
        """Parse + guard one ingress frag; (sigs, msg, signers, t,
        packed) or None after counting the drop.  The Python lane's
        frag-intake rules (after_frag and the python-parser sweep; the
        native intake holds the same guards in native/fd_verify.cpp)."""
        t, packed = _parse_pair(payload)
        if packed is not None:
            sigs, msg, signers = _packed_fields(payload, packed)
        elif t is not None:
            sigs = t.signatures(payload)
            msg = t.message(payload)
            signers = t.signers(payload)
        else:
            self.metrics.inc("parse_fail")
            return None
        if self.tcache.insert(sig_tag(sigs[0])):
            self.metrics.inc("dedup_dup")
            return None
        if len(msg) > self.max_msg_len:
            self.metrics.inc("msg_too_long")
            return None
        # a txn's elements must land in ONE device batch (the txn-level
        # pass-iff-all-pass rule is evaluated per batch): drop txns that
        # can never fit
        if len(sigs) > self.batch:
            self.metrics.inc("too_many_sigs")
            return None
        return sigs, msg, signers, t, packed

    def _accumulate(self, got, payload: bytes, tsorig: int) -> _Life:
        """Batch one intaken txn (the ONE accumulation implementation —
        after_frag and the drain-table sweep_frags path both land here).
        -> the stamps of the batch that took it (they name the batch)."""
        sigs, msg, signers, t, packed = got
        self.metrics.observe("msg_len", len(msg))
        slots = self._signer_slots(signers)
        acc = self._comb if slots is not None else self._gen
        if acc.elems and len(acc.elems) + len(sigs) > self.batch:
            # sealed for want of room (fd_verify.cpp counts the same)
            self.metrics.inc(fm.BATCH_FIT_PAD_LANES,
                             self.batch - len(acc.elems))
            self._close_batch(acc)
            acc = self._comb if slots is not None else self._gen
        if not acc.elems:
            # the batch opens here: one clock read a batch (not the
            # deadline's clock, which before_credit stamps)
            acc.life = _Life(_now_ns())
        start = len(acc.elems)
        for i, (s, pk) in enumerate(zip(sigs, signers)):
            acc.elems.append((msg, s, pk))
            if slots is not None:
                acc.slots.append(slots[i])
        acc.ranges.append((start, len(acc.elems)))
        acc.payloads.append(payload)
        acc.descs.append((t, packed))
        acc.tsorigs.append(tsorig)
        life = acc.life
        if len(acc.elems) >= self.batch:
            self._close_batch(acc)
        return life

    def after_frag(self, in_idx: int, meta, payload: bytes) -> None:
        c = self._sweep_client
        if c is not None:
            # fallback surface (mixed-lane / lossy splice): forward into
            # the SAME C-side batch state the sweep callback fills; the
            # deadline stamp happens in before_credit off the C-side
            # open_elems word (the FD202 discipline)
            c.append(payload, int(meta[MCACHE_COL_TSORIG]))
            return
        got = self._intake(payload)
        if got is None:
            return
        self._accumulate(got, payload, int(meta[MCACHE_COL_TSORIG]))

    def sweep_frags(self, rows, buf: bytes):
        """Drain-table batch intake (ISSUE 11): one call consumes a whole
        native-ring sweep off the meta table + joined payload buffer —
        the shard filter reads the seq column directly, the per-packet
        parse collapses into ONE fd_txn_parse_burst crossing over the
        table's (off, sz) columns, and the 3-call per-frag dispatch
        (before/during/after) disappears.  Counting parity with the
        per-frag path: shard-filtered frags are `filtered` (not
        frags_in); intake drops count frags_in."""
        shard_cnt = self.shard_cnt
        shard_idx = self.shard_idx
        accumulate = self._accumulate
        m = self.metrics
        n_done = 0
        ts_done: list[int] = []
        if shard_cnt > 1:
            kept = []
            for row in rows:
                if (row[0] % shard_cnt) != shard_idx:
                    m.inc("filtered")
                else:
                    kept.append(row)
            rows = kept
        if not rows:
            return 0, ts_done
        if _txn_packed is None:
            # python-parser fallback: per-frag intake, still one sweep
            for row in rows:
                off = row[2]
                payload = buf[off : off + row[3]]
                n_done += 1
                ts_done.append(row[5])
                got = self._intake(payload)
                if got is not None:
                    accumulate(got, payload, row[5])
            return n_done, ts_done
        bp = self._burst_parser
        if bp is None:
            from firedancer_tpu.protocol.txn_native import BurstParser

            bp = self._burst_parser = BurstParser(max(64, self.burst))
        descs = bp.parse(buf, rows)
        tcache = self.tcache
        max_msg = self.max_msg_len
        batch = self.batch
        for row, packed in zip(rows, descs):
            n_done += 1
            ts_done.append(row[5])
            if packed is None or len(packed) != ft.txn_packed_sz(
                packed[16], packed[13]
            ):
                m.inc("parse_fail")
                continue
            off = row[2]
            payload = buf[off : off + row[3]]
            sigs, msg, signers = _packed_fields(payload, packed)
            if tcache.insert(sig_tag(sigs[0])):
                m.inc("dedup_dup")
                continue
            if len(msg) > max_msg:
                m.inc("msg_too_long")
                continue
            if len(sigs) > batch:
                m.inc("too_many_sigs")
                continue
            accumulate((sigs, msg, signers, None, packed), payload, row[5])
        return n_done, ts_done

    def native_lanes(self) -> dict[str, bool]:
        return dict(super().native_lanes(),
                    verify=self._sweep_client is not None)

    def before_credit(self) -> None:
        # The batch-deadline clock is stamped HERE, not in after_frag
        # (the per-frag path must stay free of wall-clock syscalls,
        # fdlint FD202) and not in after_credit (run_once skips that
        # hook entirely while any output is backpressured): before_credit
        # runs unconditionally every iteration, so a fresh batch is
        # stamped within one iteration of opening even under
        # backpressure.  The clock is only read when a batch newly
        # opened — idle spins stay syscall-free.  (clear() resets
        # opened_at, so a stale stamp can never survive a close.)
        since = self._chip_empty_since
        if since and since < self._loop_entry_ns:
            # this call began with the chip empty: the thread was in
            # the other stages since the last one ended (run_once's two
            # stamps; no clock read here)
            self._chip_away_ns += self._loop_entry_ns - self._loop_exit_ns
        if self._sweep_client is not None:
            # native lane: the C side stamps a batch as it opens, in
            # the crossing (one clock read a batch; open_since_ns)
            return
        for acc in (self._gen, self._comb):
            if acc.elems and acc.opened_at == 0.0:
                acc.opened_at = time.monotonic()

    def after_credit(self) -> None:
        if self._sweep_client is not None:
            # reap, deadline-based batch close, dispatch, publish
            self._nv_pump()
            return
        # credits are available again: retry frames a full out ring
        # parked on the emit queue before touching new work
        if self._emit_queue:
            self._emit_reaped([])
        self._deadline_close()
        self._pump_submits()
        self._drain(block=False)

    # -- when a batch closes -------------------------------------------------

    def _flying(self) -> list:
        """The batches in flight, in dispatch order (the lane's own)."""
        return (self._nv_inflight if self._sweep_client is not None
                else self._inflight)

    def _window_has_room(self) -> bool:
        """Fewer batches are in flight than the window is held to.  The
        ONE test of the depth, asked for batches that are sealed
        already (full ones, and what flush() seals): both lanes' submit
        loops ask here."""
        return len(self._flying()) < self.max_inflight

    def _waits_for_place(self, close: int) -> None:
        """A lane's submit loop left a sealed batch behind for want of
        room (the native pump and the Python lane's).  A FULL one is
        the evidence clause (b) reads (module docstring); what flush()
        seals and parks says nothing."""
        if close == CLOSE_FULL:
            self._full_waited = True

    def _window_freed(self) -> None:
        """A reap took its batch out of the window (both lanes' reaps
        call here): one that leaves nothing in flight is the chip
        running dry, which ends the evidence — on the window's own
        state, so under the all-pass mask too, which stamps no
        chip_empty."""
        if not self._flying():
            self._full_waited = False

    def _window_open(self) -> bool:
        """The window's half of the close rule (module docstring): a
        batch that is not full may take a place in it now.  No sealed
        batch waits ahead of it, and either nothing is in flight (it
        would run now) or the window has room and the evidence of
        clause (b) stands: _waits_for_place set it, and neither
        _window_freed nor a dispatch that was not full
        (_count_dispatch) has ended it since.  It reads nothing but
        the stage's own window."""
        c = self._sweep_client
        if c.sealed_waiting() if c is not None else self._submit_queue:
            return False
        return not self._flying() or (self._full_waited
                                      and self._window_has_room())

    def _past_deadline(self, held: int) -> tuple[int | None, int]:
        """The close rule for one open batch past its deadline, asked
        here alone on every lane: -> (the CLOSE_* reason to seal it
        with, or None to keep it open; its _HELD_* marks, `held` and
        what this pass adds).  It seals if the window is open to it
        (_window_open) — unless nothing is in flight and the intake is
        backlogged (Stage.backlogged).  Then the batch would run now,
        but part empty, at a whole dispatch's cost to a thread that
        limits: it stays open until it fills, or until the first short
        sweep ends the backlog and it goes at the next pass through
        here.  With a batch in flight, the place behind it is taken
        backlogged or not, on the evidence _window_open asks for and on
        nothing else (module docstring, "Why (a) and (b) differ").
        `batch_held_backlogged` counts a batch the first time it is
        kept open for the backlog alone."""
        if not self._window_open():
            return None, held | _HELD_WINDOW
        if self.backlogged and not self._flying():
            if not held & _HELD_BACKLOGGED:
                self.metrics.inc(fm.BATCH_HELD_BACKLOGGED)
            return None, held | _HELD_BACKLOGGED
        return (CLOSE_WINDOW if held & _HELD_WINDOW else CLOSE_DEADLINE), held

    def _deadline_close(self) -> None:
        """The deadline's half of the close rule (p99 latency at low
        occupancy): an open batch past its deadline seals if
        _past_deadline says so, and otherwise stays open, taking
        frags, until it fills or a pass comes through here again (every
        pump, and the reap).  Sealing it behind a running batch that
        was not full would only move its wait from the batch (where
        lanes fill) to the device's queue (where the frags behind it
        wait a program length more)."""
        c = self._sweep_client
        if c is not None:
            t = c.open_since_ns()  # ONE u64 read; 0 = nothing open
            if not t or _now_ns() - t < self.batch_deadline_s * 1e9:
                return
            was, held = self._nv_held
            why, held = self._past_deadline(held if was == t else 0)
            if why is None:
                self._nv_held = (t, held)
            else:
                c.seal(why)
            return
        now = time.monotonic()
        for acc in (self._gen, self._comb):
            if not (acc.elems and acc.opened_at
                    and now - acc.opened_at >= self.batch_deadline_s):
                continue
            why, acc.held = self._past_deadline(acc.held)
            if why is not None:
                self._close_batch(acc, why)

    def during_housekeeping(self) -> None:
        c = self._sweep_client
        if c is not None:
            self._nv_pump()
            # C-side intake counters are authoritative in sweep mode
            # (the shred-client discipline): absolute values copied at
            # the same lazy cadence every other stage metric has
            self.metrics.counters.update(c.counters())
            self._copy_sweep_counters()
            return
        self._pump_submits()
        self._drain(block=False)
        self._fill_bank()
        self._maybe_retune()

    # -- autotuner (runtime/verify_tune.py) ---------------------------------

    def _maybe_retune(self) -> None:
        """Re-derive batch geometry from this stage's own histograms at
        housekeeping cadence, applying only at a quiet point (nothing
        accumulated, nothing in flight) — a retune is a recompile, so
        the evidence bar (autotune_after batches) is deliberately
        high."""
        if not self.autotune_after:
            return
        if self.metrics.get("batches") - self._last_tune_batches \
                < self.autotune_after:
            return
        if (self._inflight or self._submit_queue or self._gen.elems
                or self._comb.elems):
            return
        from . import verify_tune as vt

        self._last_tune_batches = self.metrics.get("batches")
        rec = vt.recommend_for_stage(self)
        changed = (rec.batch != self.batch
                   or rec.max_msg_len != self.max_msg_len
                   or rec.comb_split != self._comb_lane_on)
        if not changed:
            return
        self.batch = rec.batch
        self.max_msg_len = rec.max_msg_len
        self._comb_lane_on = rec.comb_split
        self.metrics.inc("retunes")

    # -- device readiness ----------------------------------------------------

    def warmup(self) -> float:
        """Compile (or load from the persistent cache) the generic-lane
        program at this stage's exact dispatch shape and dtypes, before
        traffic (warm_program).  Returns seconds; 0.0 when the stage
        dispatches nothing (the precomputed mask)."""
        if self.precomputed_ok:
            return 0.0
        return warm_program(self.batch, self.max_msg_len,
                            self._row_sharding)

    def _mask_ready(self, result) -> bool:
        """Whether a dispatched batch's mask can be fetched without
        blocking.  The precomputed bench bypass is the ONE case that
        hands back a host array; anything else must be a device future
        with is_ready(), so a device result arriving as the wrong type
        fails here instead of passing as ready."""
        if self.precomputed_ok and isinstance(result, np.ndarray):
            return True
        return result.is_ready()

    # -- the life of a batch -------------------------------------------------

    def _span(self, name: str, life: _Life | None):
        """A blocking point of one batch as a span on the profiler's
        host plane, so on the device trace's clock:
        jax.profiler.TraceAnnotation, imported on first use like the
        stage's other JAX imports.  With no profiler session it costs a
        flag test.  The all-pass mask has nothing on the device to line
        up with, and stays free of JAX."""
        if self.precomputed_ok:
            return _NO_SPAN
        global _trace_annotation
        if _trace_annotation is None:
            from jax.profiler import TraceAnnotation

            _trace_annotation = TraceAnnotation
        return _trace_annotation(name, batch=life.seq if life else 0)

    def _phase_end(self, life: _Life, phase: int,
                   now: int | None = None) -> None:
        """End `phase` of one batch's life (it began where the phase
        before it ended): add its nanoseconds to the phase's counter.
        The ONE place a batch is stamped — the native lane and the
        Python lane both come through here, so they cannot stamp
        differently.  A thread-blocking phase of BATCH_STALL_NS or more
        is a flight event that names the phase.

        The chip's ledger is kept here too, with the same `now`: the
        chip has nothing of this stage's to run from the PH_INFLIGHT
        end of a batch with no other in flight (the loop first saw the
        chip done) to the PH_LAUNCH end of the next dispatch.  When
        that interval ends `chip_empty_ns` takes its length,
        `chip_empty_away_ns` the part the thread spent outside this
        stage's run_once (before_credit sums it from run_once's
        stamps), and `chip_empty_call_ns` the part inside the phases of
        _CHIP_CALL_PHASES that ended in it — each from where it began,
        or where the last such phase ended, or where this call began,
        whichever is latest, so that no instant is charged twice: a
        phase that spans calls (a publish waiting on credits, a sealed
        batch waiting for the thread) is charged its part in the call
        that ends it.  The rest of the interval is the stage in its
        own loop with no blocking call: intake, polls, the close rule.
        Not under the all-pass mask, which has no chip (like _span)."""
        self._loop_worked = True    # a batch moved: run_once's regime
        if now is None:
            now = _now_ns()
        t = life.t
        while len(t) <= phase:  # a phase that was skipped reads 0 (the
            t.append(t[-1])     # all-pass mask copies and launches nothing)
        ns = now - t[phase]
        t.append(now)
        c = self.metrics.counters
        c[_PHASE_COUNTERS[phase]] += ns
        if ns >= fm.BATCH_STALL_NS and phase in fm.BATCH_BLOCKING_PHASES:
            c["batch_stalls"] += 1
            self.trace(fm.EV_BATCH_STALL, fm.batch_stall_arg(phase, ns))
        since = self._chip_empty_since
        if phase == PH_INFLIGHT:
            if len(self._flying()) == 1 and not self.precomputed_ok:
                self._chip_empty_since = self._chip_mark = now
        elif since and phase in _CHIP_CALL_PHASES:
            self._chip_call_ns += now - max(t[phase], self._chip_mark,
                                            self._loop_entry_ns)
            self._chip_mark = now
            if phase == PH_LAUNCH:
                c["chip_empty_ns"] += now - since
                c["chip_empty_n"] += 1
                c["chip_empty_call_ns"] += self._chip_call_ns
                c["chip_empty_away_ns"] += self._chip_away_ns
                self._chip_empty_since = 0
                self._chip_call_ns = self._chip_away_ns = 0

    def _dispatch_begins(self, life: _Life) -> None:
        """The sealed batch's wait is over: it takes its place in
        dispatch order (the annotations' `batch`)."""
        self._phase_end(life, PH_SEALED_WAIT)
        life.seq = self.metrics.get("batches") + 1

    def _mask_of(self, result) -> np.ndarray:
        """A dispatched batch's mask on the host, element e at index e
        (over a mesh the lanes come back chip by chip: dealt back; the
        all-pass mask never left the host)."""
        mask = np.asarray(result)
        if self._row_sharding is None or mask is result:
            return mask
        return mask.reshape(self.mesh_devices, -1).T.reshape(-1)

    def _device_verify(self, life: _Life, rows: np.ndarray):
        """The dispatch of one batch's packed rows, the ONE signature
        of both lanes: the host->device copy (place_rows: to the default
        device, or to each mesh device the rows it is dealt; the end of
        the batch's h2d phase), then the program's call
        (ops/sigverify.verify_dispatch: one compiled module per batch —
        over a mesh one module a step, the same program partitioned by
        its argument's sharding, no collective in it); the caller ends
        the launch phase.  -> the mask future, read through _mask_of;
        pad lanes say nothing, the reap reads the real ones."""
        from firedancer_tpu.ops import sigverify as sv

        dev = place_rows(rows, self._row_sharding)
        self._phase_end(life, PH_H2D)
        return sv.verify_dispatch(dev, max_msg_len=self.max_msg_len)

    def _count_dispatch(self, n: int, close: int, occupancy: int) -> None:
        """The books of one dispatched batch of `n` elements, on both
        lanes.  Over a mesh of d devices,
        device i was dealt elements i, i + d, ... below `n`: from the
        fill alone, no per-element work.

        A batch that was not full spends the evidence of clause (b):
        it is the slack the evidence stood for, and only a full batch
        that has to wait again (_waits_for_place) buys the next one."""
        m = self.metrics
        m.inc("batches", 1)
        m.inc(_CLOSE_COUNTERS[close])
        m.inc("batch_elems", n)
        m.observe("batch_fill", n)
        m.observe("inflight_occupancy", occupancy)
        if occupancy > 1:
            m.inc(fm.BATCH_QUEUED_BEHIND)
        if close != CLOSE_FULL:
            self._full_waited = False
        self.trace(fm.EV_BATCH_SUBMIT, n)
        d = self.mesh_devices
        for i in range(min(d, n) if d > 1 else 0):
            m.inc(f"shard_elems_s{i}", (n - i + d - 1) // d)

    # -- native sweep-client plumbing ---------------------------------------

    def _native_sweep(self, drainer) -> bool:
        c = self._sweep_client
        if c is not None and not c.can_accept():
            # every slot busy: sweeping now would only stash — reap and
            # publish first so the intake window reopens
            self._nv_pump()
            return False
        was = self.backlogged
        progressed = super()._native_sweep(drainer)
        if c is not None and c.stash_pending:
            # cut short for want of a slot, not of frags: it says
            # nothing about the ring in front (Stage._note_sweep)
            self.backlogged = was
        return progressed

    def _nv_pump(self) -> None:
        """The native lane's batch-granular loop: reap completed heads
        (in order), publish reaped frames from the slot arenas, seal
        the open batch if its deadline has passed and the close rule
        lets it go (_deadline_close),
        submit sealed slots into the in-flight window (in seal order);
        a sealed slot left behind for want of room is what the close
        rule's clause (b) reads (_waits_for_place).
        Reap -> seal -> dispatch, so the batch the window held open
        goes in the pass that reaped its head; the publish goes before the
        dispatch because it is a tenth of a ms and the dispatch nearly
        two, and the reaped transactions have nowhere else to wait."""
        c = self._sweep_client
        self._nv_drain(block=False)
        self._nv_publish()
        self._deadline_close()
        while self._window_has_room():
            got = c.take_sealed()
            if got is None:
                return
            self._nv_dispatch(*got)
        if c.sealed_waiting():      # and no room for it
            self._waits_for_place(c.sealed_close())

    def _nv_dispatch(self, slot: int, n_elems: int, n_txn: int,
                     opened_ns: int, sealed_ns: int, close: int) -> None:
        c = self._sweep_client
        views = c.slots[slot]
        if self.autotune_after:
            # per-txn msg lengths, the autotuner's evidence and nobody
            # else's: one vectorized observe off the ln column at the
            # txns' first elements, only where the tuner is armed
            starts = views.ranges[:n_txn, 0].astype(np.int64)
            self.metrics.observe_batch("msg_len", views.ln[starts])
        life = _Life(opened_ns)
        self._phase_end(life, PH_OPEN, sealed_ns)
        self._dispatch_begins(life)
        if self.precomputed_ok:
            result = np.ones((n_elems,), dtype=bool)
        else:
            with self._span("verify.dispatch", life):
                result = self._device_verify(life, views.rows)
        self._phase_end(life, PH_LAUNCH)
        self._nv_inflight.append((slot, n_elems, n_txn, result, life))
        self._count_dispatch(n_elems, close, len(self._nv_inflight))

    def _nv_drain(self, block: bool) -> None:
        while self._nv_inflight:
            slot, n_elems, n_txn, result, life = self._nv_inflight[0]
            if not block and not self._mask_ready(result):
                return
            self._phase_end(life, PH_INFLIGHT)
            with self._span("verify.reap", life):
                mask = self._mask_of(result)
                self._nv_inflight.pop(0)
                self._window_freed()
                self.trace(fm.EV_BATCH_COMPLETE, n_elems)
                ent = self._nv_reaped(slot, n_elems, n_txn, mask, life)
            self._phase_end(life, PH_REAP)
            if ent is not None:
                self._nv_emit.append(ent)
            else:
                self._phase_end(life, PH_PUBLISH)
            if block:
                break

    @staticmethod
    def _passed_txns(mask: np.ndarray, n_elems: int, ranges: np.ndarray):
        """-> None when every real lane passed, else bool per txn: does
        every element of its range ([start, end) rows of `ranges`)
        pass (a txn fails whole)."""
        if mask[:n_elems].all():
            return None
        return np.minimum.reduceat(
            mask[:n_elems].astype(np.uint8),
            ranges[:, 0].astype(np.int64)).astype(bool)

    def _nv_reaped(self, slot: int, n_elems: int, n_txn: int,
                   mask: np.ndarray, life: _Life):
        """What a reaped batch's mask means, on the native lane: the
        frames of the transactions that passed, as an entry of the emit
        queue — [the slot whose arena holds them (released when they
        are out), the arena's address, the frame table, rows published,
        the batch's stamps] — or None when nothing leaves (the slot is
        released here)."""
        c = self._sweep_client
        views = c.slots[slot]
        tbl = views.frames[:n_txn]
        kept = n_txn
        ok_txn = self._passed_txns(mask, n_elems, views.ranges[:n_txn])
        if ok_txn is not None:
            tbl = np.ascontiguousarray(tbl[ok_txn])
            kept = int(ok_txn.sum())
            self.metrics.inc("verify_fail", n_txn - kept)
            lanes = views.ranges[:n_txn][~ok_txn].astype(np.int64)
            self.metrics.inc(fm.VERIFY_FAIL_ELEMS,
                             int((lanes[:, 1] - lanes[:, 0]).sum()))
        if not kept:
            c.release(slot)
            return None
        self.metrics.inc("txn_verified", kept)
        return [slot, views.arena_ptr, tbl, 0, life]

    def _nv_published(self, slot) -> None:
        """An emit-queue entry's last frame is out: its slot returns
        to the intake ring."""
        self._sweep_client.release(slot)

    def _nv_publish(self) -> None:
        """Publish reaped frame tables head-first (global emit order is
        reap order), straight from the slot arenas: one
        fdr_publish_burst crossing per table, credit-gated, the
        unpublished tail retried next credit window.  A slot returns to
        the intake ring only when its frames are fully out."""
        if not self._nv_emit or not self.outs:
            return
        p = self.outs[0]
        # the reap publishes OUTSIDE the sweep crossing: route the burst
        # through the metrics plane so its duration still lands in the
        # stage's publish-phase histogram (ISSUE 20)
        plane = self._native_plane()
        while self._nv_emit:
            ent = self._nv_emit[0]
            slot, arena_ptr, tbl, pos, life = ent
            sub = tbl[pos:]
            with self._span("verify.publish", life):
                done = p.publish_burst_raw(arena_ptr, sub, len(sub), plane)
            if done:
                self.metrics.inc("frags_out", done)
            ent[3] = pos + done
            if ent[3] == len(tbl):
                self._nv_emit.pop(0)
                self._nv_published(slot)
                if life is not None:
                    self._phase_end(life, PH_PUBLISH)
            else:
                self.metrics.inc("backpressure", len(sub) - done)
                break

    # -- comb bank ----------------------------------------------------------

    def _signer_slots(self, signers: list[bytes]) -> list[int] | None:
        """Bank slots if EVERY signer is cached, else None; bumps repeat
        counters and queues promotions on the way."""
        if not self.comb_slots or self.precomputed_ok \
                or not self._comb_lane_on:
            return None
        slots = []
        all_cached = True
        for pk in signers:
            slot = self._slot_of.get(pk)
            if slot is None:
                all_cached = False
                cnt = self._seen_cnt.get(pk, 0) + 1
                self._seen_cnt[pk] = cnt
                # >= not ==: a hot signer whose threshold crossing races a
                # full fill queue must still promote on a later sighting
                if (
                    cnt >= self.promote_threshold
                    and self._free_slots
                    and len(self._fill_queue) < self.comb_slots
                    and pk not in self._fill_queue
                ):
                    self._fill_queue.append(pk)
                # spam guard: random one-shot pubkeys must not grow the
                # counter map without bound
                if len(self._seen_cnt) > 16 * max(self.comb_slots, 256):
                    self._seen_cnt.clear()
            else:
                slots.append(slot)
        return slots if all_cached else None

    def _fill_bank(self) -> None:
        """Build + install combs for queued pubkeys (one fixed-shape
        dispatch of up to COMB_FILL_BATCH keys)."""
        if not self._fill_queue or not self._free_slots:
            return
        import jax.numpy as jnp

        from firedancer_tpu.ops import sigverify as sv

        take = min(len(self._fill_queue), len(self._free_slots),
                   COMB_FILL_BATCH)
        keys = self._fill_queue[:take]
        del self._fill_queue[:take]
        pk = np.zeros((32, COMB_FILL_BATCH), dtype=np.uint8)
        for i, k in enumerate(keys):
            pk[:, i] = np.frombuffer(k, dtype=np.uint8)
        tables, ok = sv.comb_fill(jnp.asarray(pk))
        ok = np.asarray(ok)
        if self._bank is None:
            # slot comb_slots is a scratch lane: pad/invalid columns of a
            # fill land there so every install is one FIXED-shape dispatch
            # (a ragged len(good) trailing dim would recompile the donated
            # scatter per distinct count, stalling housekeeping mid-ingress)
            self._bank = sv.bank_alloc(self.comb_slots + 1)
        good = [i for i in range(take) if ok[i]]
        slot_col = np.full((COMB_FILL_BATCH,), self.comb_slots,
                           dtype=np.int32)
        slots = [self._free_slots.pop() for _ in good]
        slot_col[np.asarray(good, dtype=np.int64)] = slots
        if good:
            self._bank = sv.bank_install(
                self._bank, tables, jnp.asarray(slot_col),
            )
            for i, s in zip(good, slots):
                self._slot_of[keys[i]] = s
                self._seen_cnt.pop(keys[i], None)
            self.metrics.inc("comb_filled", len(good))
        # invalid pubkeys never verify anyway; don't re-queue them

    # -- device batching ----------------------------------------------------

    def _close_batch(self, acc: _Acc, why: int = CLOSE_FULL) -> None:
        """Seal the accumulating batch and submit it if the in-flight
        window has room; a full window PARKS the sealed batch (submit is
        backpressure-aware — the loop never blocks on the oldest device
        future just to close a batch) until reaping frees a slot.  Only
        a deep submit queue (the memory bound) falls back to the
        blocking drain.  `why` is what closed it (CLOSE_*): a batch that
        filled, the default, seals whatever is in flight and may take
        the window's second place; the deadline comes through
        _deadline_close, which asks first."""
        if not acc.elems:
            return
        acc.close = why
        cached = acc is self._comb
        # take the accumulator object itself as the sealed snapshot and
        # open a fresh one (clear() would free the lists we still need)
        if cached:
            self._comb = _Acc()
        else:
            self._gen = _Acc()
        self._phase_end(acc.life, PH_OPEN)
        self._submit_queue.append((acc, cached))
        self._pump_submits()
        if self._submit_queue:
            self.metrics.inc("submit_deferred")
            if len(self._submit_queue) > self._submit_queue_max:
                self._drain(block=True)
                self._pump_submits()

    def _pump_submits(self) -> None:
        """Move sealed batches into the device window, in seal order,
        while the window has room; one left behind for want of room is
        what the close rule's clause (b) reads (_waits_for_place)."""
        q = self._submit_queue
        while q and self._window_has_room():
            acc, cached = q.pop(0)
            self._submit(acc, cached)
        if q:
            self._waits_for_place(q[0][0].close)

    def _submit(self, acc: _Acc, cached: bool) -> None:
        n = len(acc.elems)
        life = acc.life
        self._dispatch_begins(life)
        if self.precomputed_ok:
            result = np.ones((n,), dtype=bool)
        else:
            with self._span("verify.dispatch", life):
                result = self._dispatch(acc, cached)
        self._phase_end(life, PH_LAUNCH)
        self._inflight.append(
            _Pending(
                payloads=acc.payloads,
                descs=acc.descs,
                elem_ranges=acc.ranges,
                tsorigs=acc.tsorigs,
                n_elems=n,
                result=result,
                life=life,
            )
        )
        self._count_dispatch(n, acc.close, len(self._inflight))
        if cached:
            self.metrics.inc("comb_elems", n)

    def _assemble(self, acc: _Acc) -> np.ndarray:
        """elems -> the batch's packed rows, (batch, row_width) uint8,
        byte for byte what the native intake writes into a slot
        (verify_native has the layout); pad rows are zero.

        Batched assembly: one bytes-join + frombuffer + reshape per
        field instead of 4 numpy calls per ELEMENT — the joined form is
        C-speed throughout.
        """
        n = len(acc.elems)
        mm = self.max_msg_len
        msgs, sigs, pks = zip(*acc.elems)
        joined = b"".join(m if len(m) == mm else m.ljust(mm, b"\x00")
                          for m in msgs)
        return vn.pack_rows(
            np.frombuffer(joined, dtype=np.uint8).reshape(n, mm),
            np.fromiter(map(len, msgs), dtype=np.int32, count=n),
            np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(n, 64),
            np.frombuffer(b"".join(pks), dtype=np.uint8).reshape(n, 32),
            self.batch)

    def _dispatch(self, acc: _Acc, cached: bool):
        """-> the mask future.  Ends the batch's h2d phase once its
        rows are on the device; the packed-row assembly counts into it
        on this lane (the native lane assembles in C, at intake)."""
        n = len(acc.elems)
        life = acc.life
        rows = self._assemble(acc)
        if not cached:
            return self._device_verify(life, rows)
        # the comb lane's kernel takes the four byte-row arrays (len,
        # batch) and makes its own copies
        msg, ln, sig, pk = vn.byte_rows(rows, self.max_msg_len)
        import jax.numpy as jnp

        from firedancer_tpu.ops import sigverify as sv

        slots = np.zeros((self.batch,), dtype=np.int32)
        slots[:n] = acc.slots
        dev = (jnp.asarray(msg), jnp.asarray(ln), jnp.asarray(sig),
               jnp.asarray(pk), self._bank, jnp.asarray(slots))
        self._phase_end(life, PH_H2D)
        return sv.ed25519_verify_batch_cached(
            *dev, max_msg_len=self.max_msg_len)

    def _drain(self, block: bool) -> None:
        while self._inflight:
            head = self._inflight[0]
            if not block and not self._mask_ready(head.result):
                return
            life = head.life
            self._phase_end(life, PH_INFLIGHT)
            with self._span("verify.reap", life):
                emits = self._reap(head)
            self._phase_end(life, PH_REAP)
            self._emit_reaped(emits, life)
            if block:
                break

    def _reap(self, head) -> list:
        """Fetch the head batch's mask, free its window slot, and encode
        the frames of the transactions that passed."""
        mask = self._mask_of(head.result)
        self._inflight.pop(0)
        self._window_freed()
        # a window slot freed: if the window is open to it now, seal
        # the batch that was held past its deadline, and submit it or
        # any parked ones before walking the mask (keeps the device fed
        # meanwhile); their dispatch falls inside this batch's reap phase
        self._deadline_close()
        self._pump_submits()
        self.trace(fm.EV_BATCH_COMPLETE, head.n_elems)
        return self._reaped_txns(head, mask)

    def _reaped_txns(self, head, mask: np.ndarray) -> list:
        """What a reaped batch's mask means, on the Python lane: the
        frames of the transactions that passed."""
        # honest traffic overwhelmingly passes whole batches: one
        # reduction over the fetched mask's real lanes decides the
        # common case instead of a numpy slice + reduction per txn
        # (~1.5us/txn of the host path)
        all_ok = bool(mask[: head.n_elems].all())
        emits = []
        for payload, desc, (a, b), tsorig in zip(
            head.payloads, head.descs, head.elem_ranges, head.tsorigs
        ):
            if all_ok or bool(mask[a:b].all()):
                emits.append(self._encode_emit(payload, desc, tsorig))
            else:
                self.metrics.inc("verify_fail")
                self.metrics.inc(fm.VERIFY_FAIL_ELEMS, b - a)
        return emits

    def _encode_emit(self, payload: bytes, desc_pair, tsorig: int):
        desc, packed = desc_pair
        if packed is None:
            packed = ft.txn_pack(desc)
        out = encode_verified_packed(payload, packed)
        # first signature's tag rides in the frag sig for cheap dedup
        return out, sig_tag(_packed_first_sig(payload, packed)), tsorig

    def _emit_burst(self, emits: list) -> None:
        """Publish a completed batch's verified frags downstream — ONE
        ring crossing on the native lane (fdr_publish_burst), in-order
        per-frag on the Python lane.  Frames past credit exhaustion stay
        queued and retry next credit window (after_credit), so a full
        out ring backpressures verify instead of losing verified txns."""
        if emits:
            self.metrics.inc("txn_verified", len(emits))
        if not self.outs:
            return
        q = self._emit_queue
        q.extend(emits)
        if not q:
            return
        n = self.publish_burst_out(0, q)
        if n == len(q):
            q.clear()
        else:
            del q[:n]
            if len(q) > self._emit_queue_max:
                drop = len(q) - self._emit_queue_max
                del q[:drop]
                self.metrics.inc("emit_dropped", drop)

    def _emit_reaped(self, emits: list, life: _Life | None = None) -> None:
        """_emit_burst with the books of whose frames the queue holds:
        `life` is the reaped batch `emits` are of (none on a retry).  A
        batch's publish phase ends when its last frame has left the
        queue, published or dropped."""
        marks = self._emit_marks
        if life is not None:
            marks.append([life, len(emits)])
        with self._span("verify.publish", marks[0][0] if marks else None):
            self._emit_burst(emits)
        left = len(self._emit_queue) if self.outs else 0
        gone = sum(m[1] for m in marks) - left
        while marks and marks[0][1] <= gone:
            gone -= marks[0][1]
            self._phase_end(marks.pop(0)[0], PH_PUBLISH)
        if marks:
            marks[0][1] -= gone

    def flush(self) -> None:
        """Close and drain everything (test/shutdown path)."""
        c = self._sweep_client
        if c is not None:
            # bounded: the emit side may be stuck on credits (the same
            # posture the Python lane's emit queue keeps at shutdown)
            for _ in range(4 * c.n_slots):
                c.pump()
                c.seal(CLOSE_DEADLINE)
                self._nv_pump()
                if self._nv_inflight:
                    self._nv_drain(block=True)
                    self._nv_publish()
                if (not self._nv_inflight and not self._nv_emit
                        and not c.stash_pending and not c.open_elems()
                        and not (c.meta[:, 0] == 2).any()):
                    break
            return
        self._fill_bank()
        for acc in (self._gen, self._comb):
            if acc.elems:
                self._close_batch(acc, CLOSE_DEADLINE)
        self._pump_submits()
        while self._inflight or self._submit_queue:
            self._drain(block=True)
            self._pump_submits()
        if self._emit_queue:
            self._emit_reaped([])


def encode_verified_packed(payload: bytes, packed: bytes) -> bytes:
    """The verified-frag framing, ONE place: payload || packed-descriptor
    trailer || u16 payload_sz.  Every producer (encode_verified, _emit's
    native-parser fast path) and consumer (decode_verified, the bank
    stage's zero-copy reader) speaks this layout."""
    return payload + packed + len(payload).to_bytes(2, "little")


def encode_verified(payload: bytes, desc: ft.Txn) -> bytes:
    """payload || packed-descriptor trailer || u16 payload_sz.

    The parsed-txn trailer convention (fd_disco_base.h:33-45): downstream
    stages get payload + descriptor in one frag and never reparse.  The
    descriptor uses the packed fixed-offset binary layout (txn.txn_pack) —
    a real wire format, safe across trust/process boundaries and readable
    by the native runtime.
    """
    return encode_verified_packed(payload, ft.txn_pack(desc))


def decode_verified(frag: bytes) -> tuple[bytes, ft.Txn]:
    payload_sz = int.from_bytes(frag[-2:], "little")
    payload = frag[:payload_sz]
    desc, end = ft.txn_unpack(frag, payload_sz)
    if end != len(frag) - 2:
        raise ValueError("verified-frag trailer size mismatch")
    if not ft.txn_desc_valid(desc, payload_sz):
        raise ValueError("verified-frag descriptor fails validation")
    return payload, desc
