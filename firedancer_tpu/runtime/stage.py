"""The stage run loop — this framework's fd_mux_tile.

A Stage owns zero or more input links (as Consumers) and zero or more output
links (as Producers) and exposes the reference mux's callback set
(/root/reference/src/disco/mux/fd_mux.h:105-200):

    during_housekeeping()  — lazy out-of-band work (credits, fseq, heartbeat)
    before_credit()        — called every iteration before credit check
    after_credit()         — called when there is room to publish (batch
                             close / drain point for async device work)
    before_frag(in_idx, seq, sig) -> bool   — cheap filter (False = skip)
    during_frag(in_idx, meta, payload)      — speculative payload handling
    after_frag(in_idx, meta, payload)       — commit: process and publish

Differences from the reference, by design: the loop is cooperative
(`run_once` does one iteration) so a single process can drive a whole
topology deterministically in tests, while the process runner just calls
`run()`; and "device work" (TPU batches) is naturally asynchronous via jax
dispatch, so stages overlap host streaming with device compute without
extra threads.  Housekeeping is scheduled by iteration count rather than
tsc ticks (same randomized-lazy idea, fd_mux.c:389-474).
"""

from __future__ import annotations

import time
import zlib
from bisect import bisect_left

import numpy as np

from firedancer_tpu.tango import shm
from firedancer_tpu.tango.rings import (
    CNC_SIG_HALT, CNC_SIG_RUN, CNC_SIG_SYNC, Cnc, MCache,
)
from firedancer_tpu.utils import metrics as fm
from .autotune import OCC_EDGES

_now_ns = time.monotonic_ns

# what one sweep takes from the rings in front unless the stage says
# otherwise (Stage.burst)
DEFAULT_BURST = 16


# tango.native, resolved lazily: stages must boot (and the Python lane
# must run) in toolchain-less environments where the import-time .so
# build would fail
_native_mod = None
_native_probe_done = False


def _native_ring():
    global _native_mod, _native_probe_done
    if not _native_probe_done:
        _native_probe_done = True
        # one probe source of truth (shm's build-and-load cache); the env
        # switch is NOT consulted here — the drainer engages whenever the
        # stage's consumers actually ARE native, however they were made
        if shm._native_ring_available():
            from firedancer_tpu.tango import native as fn

            _native_mod = fn
    return _native_mod


class Metrics:
    """Per-stage metrics over a declared schema (utils/metrics.py).

    Two-tier design, the same split the reference gets in C for free:
    the PER-FRAG update path is plain dict/int arithmetic (a numpy u64
    scalar store costs ~20x a dict bump in Python, and frag-rate work
    cannot afford it), and `flush()` — called from the housekeeping pass
    alongside the cnc diag stores — copies the local state into the
    shm-backed MetricsRegistry a monitor/scrape process reads.  Readers
    therefore see values at most one lazy interval stale, exactly the
    staleness contract the cnc diag words already have.

    Counter names outside the schema still work (they stay local-only,
    like the old plain-dict Metrics); `observe()` requires a declared
    histogram.  `counters` stays a public dict for existing callers.
    """

    def __init__(self, schema: fm.MetricsSchema | None = None):
        self.schema = schema if schema is not None else fm.stage_schema()
        # the loop's own counters start at 0: run_once adds to them
        # every call and reads `frags_out` beside them, without asking
        # whether they are there (a stage may swap in a wider Metrics)
        self.counters: dict[str, int] = dict.fromkeys(
            fm.LOOP_COUNTERS + ("frags_out",), 0)
        # histogram state: plain lists + float sums; bisect_left over a
        # tuple of precomputed edges is ~10x cheaper than np.searchsorted
        self._hedges: dict[str, tuple] = {}
        self._hedges_np: dict[str, np.ndarray] = {}  # observe_batch lane
        self._hcounts: dict[str, list[int]] = {}
        self._hsums: dict[str, float] = {}
        for d in self.schema.defs:
            if d.native:
                # native-owned words (written in-line by a C sweep
                # client): building local state for them would make
                # flush() overwrite the C increments with zeros — the
                # facade never tracks them (fdlint FD219's contract)
                continue
            if d.kind == fm.HISTOGRAM:
                self._hedges[d.name] = d.buckets
                self._hcounts[d.name] = [0] * (len(d.buckets) + 1)
                self._hsums[d.name] = 0.0
        self.registry: fm.MetricsRegistry | None = None

    def inc(self, name: str, v: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + v

    def get(self, name: str) -> int:
        return self.counters.get(name, 0)

    def observe(self, name: str, value: float) -> None:
        c = self._hcounts[name]
        c[bisect_left(self._hedges[name], value)] += 1
        if value > 0:
            self._hsums[name] += value

    def observe_batch(self, name: str, values):
        """Vectorized observe() over a 1-D ndarray — the native
        burst-drain path observes a whole sweep's frag latencies from the
        returned meta table in one searchsorted+bincount instead of a
        clock read + bisect per frag.  -> what it added to the sum."""
        edges = self._hedges_np.get(name)
        if edges is None:
            edges = self._hedges_np[name] = np.asarray(
                self._hedges[name], dtype=np.float64
            )
        c = self._hcounts[name]
        bc = np.bincount(
            np.searchsorted(edges, values, side="left"), minlength=len(c)
        )
        for j in np.flatnonzero(bc):
            c[j] += int(bc[j])
        total = values[values > 0].sum()
        self._hsums[name] += float(total)
        return total

    def hist(self, name: str) -> dict:
        return {
            "buckets": list(self._hedges[name]),
            "counts": list(self._hcounts[name]),
            "sum": self._hsums[name],
            "count": sum(self._hcounts[name]),
        }

    def quantile(self, name: str, q: float) -> float:
        return fm.hist_quantile(self.hist(name), q)

    # -- shm publication ----------------------------------------------------

    def attach(self, registry: fm.MetricsRegistry) -> None:
        """Bind the shm-backed registry (child boot path) and publish the
        current local state immediately."""
        self.registry = registry
        self.flush()

    def flush(self) -> None:
        """Publish local counters/histograms into the attached registry
        (no-op unattached).  Called from the stage housekeeping pass."""
        reg = self.registry
        if reg is None:
            return
        for name, (d, _off) in reg._off.items():
            if d.native:
                continue  # C-owned words: never overwrite from Python
            if d.kind == fm.HISTOGRAM:
                if name in self._hcounts:
                    reg.store_hist(name, self._hcounts[name],
                                   self._hsums[name])
            else:
                v = self.counters.get(name)
                if v is not None:
                    reg.store(name, v)


class Stage:
    def __init__(
        self,
        name: str,
        ins: list[shm.Consumer] | None = None,
        outs: list[shm.Producer] | None = None,
        cnc: Cnc | None = None,
        lazy: int = 128,
        seed: int = 0,
    ):
        self.name = name
        self.ins = ins or []
        self.outs = outs or []
        self.cnc = cnc or Cnc()
        self.metrics = Metrics(type(self).metrics_schema())
        # flight recorder: local ring by default; attach_observability
        # swaps in the shm-backed ring (replaying boot-time records) so
        # the record survives this process crashing
        self.recorder = fm.FlightRecorder(fm.FLIGHT_DEPTH)
        self.recorder.record(fm.EV_BOOT)
        self._bp_since: int | None = None  # iteration backpressure began
        self._hk_cnt = 0  # housekeeping passes (trace decimation)
        self.lazy = lazy
        # Stages that publish from after_frag set this so they never consume
        # an input frag they couldn't forward (losing e.g. a lock-release
        # message would wedge upstream; the reference makes such links
        # reliable via credit flow, fd_topo.h:99-101).
        self.require_credit = False
        # the most frags one run_once sweep takes from the rings in
        # front, on all three intake paths.  A stage whose sweep costs
        # much more than its frags sets its own (pack, dedup; verify: a
        # quarter of the ring in front).  Where `backlogged` is read it
        # has to stay under that ring's depth: a sweep that can empty
        # the ring says nothing about what waits behind it (_note_sweep)
        self.burst = DEFAULT_BURST
        # the last intake sweep took its whole burst from the rings in
        # front: more is waiting there (_note_sweep; the verify stage's
        # close rule reads it)
        self.backlogged = False
        # native ring plane: when every input is a NativeConsumer the
        # sweep drains through ONE fdr_drain FFI crossing (cached plan,
        # rebuilt when the input list changes — e.g. a chaos LossyConsumer
        # splice drops the stage back to the per-frag poll path)
        self._drainer: tuple | None = None
        # sweep-harness client (ISSUE 11): a stage that registers one (an
        # object with .cb/.cb_ctx — e.g. shred_native.StageClient) runs
        # its ENTIRE sweep through fdr_sweep: drain -> C stage callback
        # -> publish, zero Python per frag.  The fallback surfaces
        # (after_frag on mixed/lossy lanes) must forward into the same
        # C-side state so the two paths never diverge.
        self._sweep_client = None
        # in-crossing metrics plane (ISSUE 20): built lazily alongside
        # the drainer and handed into fdr_sweep so C records phase
        # histograms / counters / flight events from INSIDE the
        # crossing.  (registry-or-local, plane-or-None) — rebuilt when
        # attach_observability rebinds the registry.
        self._nplane: tuple | None = None
        # stage-extra native histogram the plane should bind as its
        # xlat slot (bank sets "nbank_txn_lat_ns")
        self.native_xlat_metric: str | None = None
        # in-place restart (runtime/topo supervisor respawn): out_idx ->
        # the ring's published-sig set, armed by resume_from_rings; the
        # publish guard suppresses re-published replay frags until the
        # stream passes the crash point (exactly-once on the wire)
        self._resume_guards: dict[int, set[int]] = {}
        # transactional progress (StageSpec.restartable): fseq advances
        # ONLY at safe points — end of a completed sweep and housekeeping
        # — never mid-poll, so a SIGKILL can never mark a frag consumed
        # whose downstream effects were not yet published
        self.safe_progress = False
        # crc32, not builtin hash(): str hashing is salted per process
        # (PYTHONHASHSEED), and spawned children must derive the SAME
        # housekeeping phase for a given (name, seed) as the parent and
        # as any restart — fdlint FD204 guards this.
        from firedancer_tpu.utils.rng import Rng

        self._rng = Rng(seed, zlib.crc32(name.encode()))
        # per-out occupancy bucket counts (OCC_EDGES geometry), sampled
        # in _housekeeping — runtime/autotune's per-link evidence
        self.out_occupancy: list[list[int]] = []
        self._next_housekeeping = 0
        self._iter = 0
        self._in_rr = 0  # round-robin input cursor
        # the thread's ledger (run_once): every call is charged to one
        # regime.  A hook that worked without consuming or publishing a
        # frag says so here; run_once reads and clears it
        self._loop_worked = False
        # this call's sweep found a ring behind the stage without
        # credits (or the stage without room: `intake_room`): a call
        # that then did nothing while a ring in front held a frag is
        # charged to backpressure, not to the polls
        self._loop_blocked = False
        # frags the stage has room to take in one sweep, where it has a
        # bound of its own (pack's pool); None: the burst.  A hook sets
        # it before the intake (before_credit); the Python and the
        # native burst paths keep to it (a C sweep client gates itself)
        self.intake_room: int | None = None
        # the last call's entry and exit, on time.monotonic_ns(): what
        # lies between an exit and the next entry is the other stages'
        self._loop_entry_ns = 0
        self._loop_exit_ns = 0
        self.cnc.signal = CNC_SIG_RUN

    # -- observability ------------------------------------------------------

    @classmethod
    def metrics_schema(cls) -> fm.MetricsSchema:
        """The stage KIND's metric layout: the shared stage-loop block
        plus whatever `extra_schema` adds.  topo.launch sizes the shm
        segment from this (via the StageSpec), so override extra_schema
        in subclasses rather than this."""
        s = fm.stage_schema()
        for d in cls.extra_schema().defs:
            s.defs.append(d)
        return s

    @classmethod
    def extra_schema(cls) -> fm.MetricsSchema:
        """Per-kind metric extensions (the per-tile block of metrics.xml)."""
        return fm.MetricsSchema()

    def trace(self, event: int, arg: int = 0) -> None:
        """Flight-recorder append (rare events only — never per frag)."""
        self.recorder.record(event, arg)

    def attach_observability(self, registry, recorder) -> None:
        """Bind the shm-backed metrics registry + flight ring (child boot
        path, after the builder ran)."""
        self.metrics.attach(registry)
        self.recorder.replay_into(recorder)
        self.recorder = recorder
        # the native plane (if one was already built) pointed at the old
        # words — drop it so the next sweep rebinds against the shm
        # segment (and the drainer plan with it)
        self._nplane = None
        self._drainer = None

    def _native_plane(self):
        """The stage's in-crossing metrics plane (NativePlane), built
        lazily against the attached shm registry — or a private local
        registry when the stage runs cooperatively without one, so the
        profiler works in-process too (bench's A/B windows).  None when
        the plane is disabled (FDTPU_NATIVE_METRICS=0) or the schema
        lacks the native block."""
        cached = self._nplane
        if cached is not None and cached[0] is self.metrics.registry:
            return cached[1]
        from . import native_metrics as nm

        plane = None
        reg = self.metrics.registry
        if nm.enabled():
            if reg is None:
                reg = fm.MetricsRegistry(self.metrics.schema)
                self.metrics.attach(reg)
            try:
                plane = nm.NativePlane(
                    reg, self.recorder,
                    xlat=self.native_xlat_metric,
                )
            except (nm.PlaneUnavailable, KeyError):
                plane = None
        self._nplane = (self.metrics.registry, plane)
        return plane

    def drop_native_views(self) -> None:
        """Terminal: release every native-plane reference holding views
        over an shm metrics segment (the plane itself, the drainer plan
        that embeds it, and the sweep client's keepalive), so a caller
        that owns the segment can close it without BufferError.  The
        stage must not sweep again after this."""
        self._nplane = None
        self._drainer = None
        client = self._sweep_client
        if client is not None and getattr(client, "_plane", None) is not None:
            set_metrics = getattr(client, "set_metrics", None)
            if set_metrics is not None:
                set_metrics(None)  # C drops its raw pointer too

    def _use_schema(self, schema: fm.MetricsSchema) -> None:
        """Swap the stage's metrics for ones over `schema` (the class
        schema plus counters whose number an instance decides: a chip,
        a bank), keeping what was counted so far."""
        kept = self.metrics.counters
        self.metrics = type(self.metrics)(schema)
        self.metrics.counters.update(kept)

    def native_lanes(self) -> dict[str, bool]:
        """lane -> armed, of the native lanes this stage would run on:
        here the rings (every consumer and producer the native ring
        plane's); a stage kind with a C sweep client or a native store
        adds its own.  A benchmark holds a run to all of them; in a
        process topology each tile answers for its own process
        (`sync_counters`: the gauges native_lanes / native_lanes_off)."""
        fn = _native_ring()
        ends = self.ins + self.outs
        if not ends:
            return {}
        return {"rings": fn is not None and all(
            type(e) in (fn.NativeConsumer, fn.NativeProducer) for e in ends)}

    def sync_counters(self) -> None:
        """Everything a reader in another process is about to read,
        put out now: the C-side counters into the metrics, how many of
        the stage's native lanes are armed and how many are not, and
        the metrics into the registry."""
        self.during_housekeeping()
        lanes = self.native_lanes()
        c = self.metrics.counters
        c["native_lanes"] = sum(lanes.values())
        c["native_lanes_off"] = len(lanes) - c["native_lanes"]
        self.metrics.flush()

    def _copy_sweep_counters(self) -> None:
        """Time inside this stage's non-empty native crossings, for a
        reader of `metrics.counters`: the sums of the four nsweep_*_ns
        phase histograms and the nsweep_crossings count, which C writes
        into the registry, copied into the local-only counters
        `sweep_busy_ns` and `sweep_crossings` (not schema names: the
        registry holds the originals).  Natively swept stages call it
        from during_housekeeping, beside their C-side counter copy."""
        cached = self._nplane
        if cached is None or cached[1] is None:
            return
        self.metrics.counters.update(fm.sweep_counters(cached[1].registry))

    # -- in-place restart (supervisor respawn) -------------------------------

    def resume_from_rings(self) -> None:
        """Reattach this stage's cursors to its EXISTING shm rings after
        a supervisor respawn (runtime/topo supervise restart path):

          - every consumer resumes at the progress it last PUBLISHED to
            its fseq (frags consumed past that before the crash replay);
          - every producer resumes at the frontier recovered from its
            own mcache (never seq 0 — that would lap live consumers and
            clobber in-flight payloads), and its ring's published sigs
            arm the publish guard so replayed frags are suppressed
            rather than re-delivered.

        Exactly-once holds for stages whose output is a pure function of
        their input stream and whose frag sigs are unique within a ring
        depth (every pipeline link's are).  A SOURCE stage (no inputs)
        must derive its own progress from producer state — override this
        and read `self.outs[i].seq` (see chaos/scenario's gen stage)."""
        for c in self.ins:
            c.resume()
        self._resume_guards = {}
        for i, p in enumerate(self.outs):
            sigs = p.resume()
            if sigs:
                self._resume_guards[i] = sigs
        self.trace(fm.EV_RESTART, self._iter)

    def arm_safe_progress(self) -> None:
        """Make fseq publication TRANSACTIONAL for this stage (the
        restartable-stage contract, StageSpec.restartable): consumers
        stop auto-publishing progress mid-poll (their lazy interval is
        pushed out of reach) and run_once publishes it only after a
        sweep's frag effects are fully out.  A SIGKILL therefore leaves
        the fseq at a point where everything at or before it is on the
        wire — resume replays at-least-once and the publish guard dedups
        to exactly-once."""
        self.safe_progress = True
        for c in self.ins:
            c.set_lazy(1 << 62)

    def _commit_progress(self) -> None:
        for c in self.ins:
            c.publish_progress()

    def _guarded(self, out_idx: int, sig: int) -> bool:
        """True = this publish is a replay duplicate: swallow it.  The
        guard disarms at the first sig the pre-crash ring never carried
        (the replay has passed the crash point and everything after is
        new work)."""
        g = self._resume_guards.get(out_idx)
        if g is None:
            return False
        if sig in g:
            g.discard(sig)
            self.metrics.inc("restart_dedup")
            return True
        del self._resume_guards[out_idx]
        return False

    # -- callbacks (override in subclasses) ---------------------------------

    def during_housekeeping(self) -> None: ...

    def before_credit(self) -> None: ...

    def after_credit(self) -> None: ...

    def before_frag(self, in_idx: int, seq: int, sig: int) -> bool:
        return True

    def during_frag(self, in_idx: int, meta, payload: bytes) -> None: ...

    def after_frag(self, in_idx: int, meta, payload: bytes) -> None: ...

    # -- the loop -----------------------------------------------------------

    # cnc diagnostic word layout (read by the monitor, fd_cnc.h diag words)
    DIAG_FRAGS_IN = 0
    DIAG_FRAGS_OUT = 1
    DIAG_OVERRUN = 2
    DIAG_BACKPRESSURE = 3
    DIAG_ITER = 4

    def _housekeeping(self) -> None:
        for c in self.ins:
            c.publish_progress()
        for p in self.outs:
            p.refresh_credits()
        # per-link occupancy sample (1 - credits/depth) at housekeeping
        # cadence — the evidence the credit/depth autotuner
        # (runtime/autotune) sizes rings and laziness from.  Kept both
        # as the schema histogram (monitor/scrape) and as per-out bucket
        # counts (per-LINK resolution the aggregate hist can't give).
        if len(self.out_occupancy) != len(self.outs):
            self.out_occupancy = [
                [0] * (len(OCC_EDGES) + 1) for _ in self.outs
            ]
        for i, p in enumerate(self.outs):
            d = getattr(getattr(p, "link", None), "depth", 0)
            if d:
                occ = 1.0 - p.cr_avail / d
                self.metrics.observe("out_occupancy", occ)
                self.out_occupancy[i][bisect_left(OCC_EDGES, occ)] += 1
        self.cnc.heartbeat(time.monotonic_ns())
        m = self.metrics
        self.cnc.diag_set(self.DIAG_FRAGS_IN, m.get("frags_in"))
        self.cnc.diag_set(self.DIAG_FRAGS_OUT, m.get("frags_out"))
        self.cnc.diag_set(self.DIAG_OVERRUN, m.get("overrun"))
        self.cnc.diag_set(self.DIAG_BACKPRESSURE, m.get("backpressure"))
        self.cnc.diag_set(self.DIAG_ITER, self._iter)
        m.flush()  # publish schema metrics to the shm registry (if any)
        # decimated: one timeline tick per 32 passes, or the 512-slot
        # ring would hold nothing but housekeeping when a stage runs hot
        self._hk_cnt += 1
        if self._hk_cnt & 31 == 1:
            self.trace(fm.EV_HOUSEKEEPING, self._iter)
        self.during_housekeeping()
        # randomized lazy interval: [lazy/2, 3*lazy/2) iterations
        self._next_housekeeping = self._iter + self.lazy // 2 + self._rng.roll(
            max(self.lazy, 1)
        )

    def run_once(self) -> bool:
        """One loop iteration; returns True if any frag was processed.

        The ONE place a call is stamped (the thread's ledger, upstream's
        stem regimes): two clock reads a call, charged whole to one of
        four regimes.  `loop_hk_ns`: the housekeeping pass, taken out
        of the call it ran in.  `loop_work_ns` / `loop_work_n`: the
        call did work — it consumed a frag (the three intake paths say
        so), its own `frags_out` moved (a publish from any hook), or a
        hook that works with neither said so through `_loop_worked`
        (verify's pump dispatching or reaping, a tick's hashes, a slot
        close, a flush that publishes in C).  `loop_backp_ns` /
        `loop_backp_n`: it did nothing because a ring behind it had no
        credits, or the stage no room (`_sweep` says so through
        `_loop_blocked`), while a ring in front held a frag: the tile
        behind limits (fdctl monitor's % backp).  `loop_poll_ns` /
        `loop_poll_n`: it found nothing to do.  With a process a tile
        the two tell a starved tile from a blocked one.  The exit
        stamp is kept: what lies between it and the next entry is the
        other stages' time."""
        c = self.metrics.counters
        t0 = self._loop_entry_ns = _now_ns()
        self._iter += 1
        halted = False
        if self._iter >= self._next_housekeeping:
            self._housekeeping()
            t = _now_ns()
            c["loop_hk_ns"] += t - t0
            t0 = t
            halted = self.cnc.signal == CNC_SIG_HALT
        out0 = c["frags_out"]
        progressed = False if halted else self._sweep()
        t1 = self._loop_exit_ns = _now_ns()
        if progressed or self._loop_worked or c["frags_out"] != out0:
            self._loop_worked = False
            c["loop_work_ns"] += t1 - t0
            c["loop_work_n"] += 1
        elif self._loop_blocked and not halted and self._input_pending():
            c["loop_backp_ns"] += t1 - t0
            c["loop_backp_n"] += 1
        else:
            c["loop_poll_ns"] += t1 - t0
            c["loop_poll_n"] += 1
        return progressed

    def _input_pending(self) -> bool:
        """Something waits in front of the stage (asked only on a call
        that was blocked and did nothing): a ring in front holds a
        frag; a stage fed by a socket answers for its socket."""
        return any(cons.has_pending() for cons in self.ins)

    def _sweep(self) -> bool:
        """The body of one iteration, after housekeeping: credits, the
        hooks, one intake sweep.  -> whether a frag was processed."""
        self.before_credit()
        backpressured = any(p.cr_avail <= 0 for p in self.outs)
        if backpressured:
            for p in self.outs:  # stale credits? re-read consumer fseqs
                p.refresh_credits()
            backpressured = any(p.cr_avail <= 0 for p in self.outs)
        # backpressure onset/relief transitions ride the flight recorder
        # (a transition, not a per-frag event: two int compares per iter)
        if backpressured:
            if self._bp_since is None:
                self._bp_since = self._iter
                self.trace(fm.EV_BACKPRESSURE_ON, self._iter)
        elif self._bp_since is not None:
            self.trace(fm.EV_BACKPRESSURE_OFF, self._iter - self._bp_since)
            self._bp_since = None
        room = self.intake_room
        self._loop_blocked = backpressured or room == 0
        if not backpressured:
            self.after_credit()
        if self.require_credit and any(p.cr_avail <= 0 for p in self.outs):
            # Re-checked AFTER after_credit: it may have spent the last
            # credit (e.g. a poh tick entry), and consuming an input frag
            # we can't forward would silently drop it.
            self.metrics.inc("backpressure_stall")
            self._loop_blocked = True
            return False
        n_in = len(self.ins)
        if n_in:
            drainer = self._native_drainer()
            if drainer is not None:
                if self._sweep_client is not None:
                    progressed = self._native_sweep(drainer)
                else:
                    progressed = self._native_burst(drainer)
                if progressed and self.safe_progress:
                    # transactional commit: the drained sweep's effects
                    # are out, so the fseq may now cover it
                    self._commit_progress()
                return progressed
        progressed = False
        # burst-drain: up to `burst` frags per sweep.  One-frag sweeps
        # make the COOPERATIVE scheduler pay the whole loop overhead
        # (credits, housekeeping checks, empty polls of sibling inputs)
        # per frag — the dominant host-path cost at profile; the
        # reference's stem loop amortizes the same way in C.
        asked = max(1, self.burst)
        if room is not None and room < asked:
            asked = max(room, 0)
        taken = 0
        while taken < asked:
            if progressed and self.require_credit and any(
                p.cr_avail <= 0 for p in self.outs
            ):
                asked = taken  # mid-burst credit exhaustion: stop cleanly
                break
            got = False
            for k in range(n_in):
                idx = (self._in_rr + k) % n_in
                cons = self.ins[idx]
                seq = cons.seq
                res = cons.poll()
                if res == shm.POLL_EMPTY:
                    continue
                if res == shm.POLL_OVERRUN:
                    self.metrics.inc("overrun")
                    # decimated: a sustained lap overruns per poll and
                    # would flood the flight ring (arg = running total,
                    # so the dump still shows the loss magnitude)
                    n = self.metrics.get("overrun")
                    if n & 63 == 1:
                        self.trace(fm.EV_OVERRUN, n)
                    progressed = True
                    got = True
                    break
                meta, payload = res
                progressed = True
                got = True
                if not self.before_frag(idx, seq, int(meta[MCache.COL_SIG])):
                    self.metrics.inc("filtered")
                else:
                    self.during_frag(idx, meta, payload)
                    self.after_frag(idx, meta, payload)
                    self.metrics.inc("frags_in")
                    # per-hop + e2e latency: tsorig is stamped once at the
                    # origin stage and carried through every ring, so this
                    # observation at the LAST stage is the whole-pipeline
                    # figure.  Cheap by construction: one vDSO clock read
                    # (the same cost Producer.try_publish already pays per
                    # frag) + a bisect over precomputed edges.
                    ts = int(meta[MCache.COL_TSORIG])
                    if ts:
                        lat = shm.now_ns() - ts
                        if lat >= 0:
                            m = self.metrics
                            m.observe("frag_latency_ns", lat)
                            m.inc("frag_wait_ns", lat)
                            m.inc("frag_wait_n")
                self._in_rr = (idx + 1) % n_in
                break
            if not got:
                break
            taken += 1
        self._note_sweep(taken, asked)
        if progressed and self.safe_progress:
            self._commit_progress()
        return progressed

    def _note_sweep(self, n: int, asked: int) -> None:
        """One intake sweep took `n` frags where it was allowed `asked`
        (at most `burst`).  The whole burst: more is waiting in the
        rings in front, the stage is backlogged.  Fewer than it was
        allowed: they ran dry.  A sweep that was skipped, or that
        credits downstream held short of the burst, says nothing about
        the rings in front and leaves the observation as it was."""
        if n >= max(1, self.burst):
            self.backlogged = True
        elif n < asked:
            self.backlogged = False

    # -- native ring burst path ---------------------------------------------

    def _native_drainer(self):
        """The cached fdr_drain/fdr_sweep plan when EVERY input is a
        native-ring consumer, else None (per-frag poll path — Python
        consumers, LossyConsumer shims, mixed lanes).  Keyed on the
        input objects AND the sweep client so a spliced/replaced input
        (or a re-armed client) rebuilds the plan."""
        cached = self._drainer
        client = self._sweep_client
        # list == compares elements by identity here (consumers define no
        # __eq__), so revalidation costs no allocation per sweep; a chaos
        # LossyConsumer splice (stage.ins[i] = shim) breaks the equality
        # and rebuilds the plan
        if cached is not None and cached[0] == self.ins \
                and cached[2] is client:
            return cached[1]
        drainer = None
        fn = _native_ring()
        if fn is not None and all(
            type(c) is fn.NativeConsumer for c in self.ins
        ):
            if client is not None:
                plane = self._native_plane()
                drainer = fn.SweepDrainer(self.ins, max(1, self.burst),
                                          client, plane)
                if plane is not None:
                    set_metrics = getattr(client, "set_metrics", None)
                    if set_metrics is not None:
                        # hand the plane into the stage's own C context
                        # too: apply/publish phase attribution + stage
                        # extras (bank's per-txn latency) write through it
                        set_metrics(plane)
            else:
                drainer = fn.BurstDrainer(self.ins, max(1, self.burst))
        self._drainer = (list(self.ins), drainer, client)
        return drainer

    def _native_sweep(self, drainer) -> bool:
        """One run_once sweep through the generic sweep harness: ONE FFI
        crossing drains every input AND runs the stage's registered C
        callback per frag (fdr_sweep) — drain table -> stage compute ->
        publish with zero Python per frag.  Python's per-sweep work is
        bookkeeping only: frags_in and the batched frag_latency_ns
        observation off the returned meta table."""
        max_frags = self.burst if self.burst > 0 else 1
        room = self.intake_room
        if room is not None and room < max_frags:
            max_frags = room
        if max_frags <= 0:
            return False
        m = self.metrics
        n, self._in_rr, d_ovr = drainer.sweep(self._in_rr, max_frags)
        self._note_sweep(n, max_frags)
        if d_ovr:
            m.inc("overrun", d_ovr)
            tot = m.get("overrun")
            if (tot ^ (tot - d_ovr)) >> 6 or tot == d_ovr:
                self.trace(fm.EV_OVERRUN, tot)
        if n == 0:
            return d_ovr > 0
        m.inc("frags_in", n)
        self._observe_waits(drainer.meta[:n, 5].astype(np.int64))
        return True

    def _observe_waits(self, ts_col: np.ndarray) -> None:
        """One sweep's frag latencies off its tsorig column, one clock
        read for all of them: into the frag_latency_ns histogram, and
        the same values summed into frag_wait_ns / frag_wait_n (the
        histogram's sum and count as counters, which is the form a
        reader of `metrics.counters` can take a window's mean from)."""
        lat = shm.now_ns() - ts_col
        ok = lat[(ts_col > 0) & (lat >= 0)]
        if ok.size:
            m = self.metrics
            m.inc("frag_wait_ns", int(m.observe_batch("frag_latency_ns", ok)))
            m.inc("frag_wait_n", ok.size)

    # drain-table batch hook: a stage may process a whole drained sweep
    # from the meta table + joined payload buffer in ONE call instead of
    # per-frag before/during/after dispatch (3 dynamic calls per frag on
    # the hot path).  Return (frags consumed, [tsorig...]) with the same
    # counting rules the per-frag loop has.  None = use the per-frag loop.
    sweep_frags = None

    def _native_burst(self, drainer) -> bool:
        """One run_once sweep over the native ring plane: ONE FFI
        crossing pulls up to `burst` frags from all inputs round-robin
        into the drainer's arena; frag callbacks then run over the
        returned meta table (after_frag semantics unchanged), and
        frag_latency_ns is batch-observed from the tsorig column — no
        per-frag Python timestamping."""
        max_frags = self.burst if self.burst > 0 else 1
        if self.require_credit and self.outs:
            # never pull a frag we may not be able to forward: each input
            # frag spends at most one credit per output link in every
            # stage that sets require_credit (router/bank/poh)
            cap = min(p.cr_avail for p in self.outs)
            if cap < max_frags:
                max_frags = cap
        room = self.intake_room
        if room is not None and room < max_frags:
            max_frags = room
        if max_frags <= 0:
            return False
        m = self.metrics
        n, self._in_rr, d_ovr = drainer.drain(self._in_rr, max_frags)
        self._note_sweep(n, max_frags)
        if d_ovr:
            m.inc("overrun", d_ovr)
            tot = m.get("overrun")
            # decimated like the per-frag path: one timeline tick per
            # 64-overrun stride (arg = running total)
            if (tot ^ (tot - d_ovr)) >> 6 or tot == d_ovr:
                self.trace(fm.EV_OVERRUN, tot)
        if n == 0:
            return d_ovr > 0
        # one block conversion each: meta rows become plain-int lists
        # (python list indexing beats a numpy scalar read ~5x in the
        # per-frag loop below) and payloads one contiguous bytes copy
        # (frags land back-to-back in the arena, so the last frag's end
        # bounds them all; bytes slicing is then near-free per frag)
        rows = drainer.meta[:n].tolist()
        last = rows[n - 1]
        buf = drainer.arena[: last[2] + last[3]].tobytes()
        sweep_frags = self.sweep_frags
        if sweep_frags is not None:
            n_done, ts_done = sweep_frags(rows, buf)
            if n_done:
                m.inc("frags_in", n_done)
                self._observe_waits(np.asarray(ts_done, dtype=np.int64))
            return True
        before_frag = self.before_frag
        during_frag = self.during_frag
        after_frag = self.after_frag
        n_done = 0
        ts_done: list[int] = []
        for row in rows:
            idx = row[7]
            if not before_frag(idx, row[0], row[1]):
                m.inc("filtered")
                continue
            off = row[2]
            payload = buf[off : off + row[3]]
            during_frag(idx, row, payload)
            after_frag(idx, row, payload)
            n_done += 1
            ts_done.append(row[5])
        if n_done:
            m.inc("frags_in", n_done)
            # batch latency observation: one clock read for the sweep
            self._observe_waits(np.asarray(ts_done, dtype=np.int64))
        return True

    def run(
        self,
        max_iters: int | None = None,
        *,
        idle_spins: int = 256,
        idle_sleep_s: float = 0.001,
    ) -> None:
        """The process-runner loop.  The reference spins with PAUSE on a
        DEDICATED core; without core pinning a hot spin just steals CPU
        from busy sibling stages, so after `idle_spins` empty iterations
        the loop naps briefly (progress resets the counter)."""
        it = 0
        idle = 0
        self.trace(fm.EV_RUN)
        while True:
            sig = self.cnc.signal
            if sig != CNC_SIG_RUN:
                if sig == CNC_SIG_HALT:
                    break
                if sig == CNC_SIG_SYNC:
                    # the supervisor reads the counters next
                    self.sync_counters()
                    self.cnc.signal = CNC_SIG_RUN
            if self.run_once():
                idle = 0
            else:
                idle += 1
                if idle >= idle_spins:
                    time.sleep(idle_sleep_s)
            it += 1
            if max_iters is not None and it >= max_iters:
                break
        self.trace(fm.EV_HALT, self._iter)
        self.metrics.flush()  # final state visible to post-mortem readers

    def halt(self) -> None:
        self.cnc.signal = CNC_SIG_HALT

    # -- helpers ------------------------------------------------------------

    def publish(
        self, out_idx: int, payload: bytes, sig: int = 0, tsorig: int = 0
    ) -> bool:
        if self._resume_guards and self._guarded(out_idx, sig):
            return True  # replay duplicate: already on the wire pre-crash
        p = self.outs[out_idx]
        ok = p.try_publish(payload, sig=sig, tsorig=tsorig)
        if ok:
            self.metrics.inc("frags_out")
        else:
            self.metrics.inc("backpressure")
        return ok

    def publish_burst_out(self, out_idx: int, items: list) -> int:
        """Publish a frame list [(payload, sig, tsorig), ...] on one
        output — ONE ring crossing on the native lane
        (fdr_publish_burst), an in-order per-frame loop on the Python
        lane.  Both stop at credit exhaustion; the shortfall counts as
        backpressure and stays with the caller.  Returns frames
        published."""
        if not items:
            return 0
        if self._resume_guards and out_idx in self._resume_guards:
            # replay window after an in-place restart: route through the
            # per-frame path so the publish guard sees every sig (the
            # guard disarms within one ring depth — not a hot path)
            n = 0
            for payload, sig, tsorig in items:
                if self._guarded(out_idx, sig):
                    n += 1
                    continue
                if not self.publish(out_idx, payload, sig=sig,
                                    tsorig=tsorig):
                    break
                n += 1
            return n
        p = self.outs[out_idx]
        burst = getattr(p, "publish_burst", None)
        # the native burst publishes through the metrics plane (ISSUE
        # 20): the crossing's duration observes into the stage's
        # publish-phase histogram from INSIDE C
        plane = self._native_plane() if burst is not None else None
        n = self._publish_items(p, burst, items, plane)
        if n:
            self.metrics.inc("frags_out", n)
        if n < len(items):
            self.metrics.inc("backpressure", len(items) - n)
        return n

    @staticmethod
    def _publish_items(p, burst, items, plane=None) -> int:
        if burst is not None:
            return burst(items, plane)
        n = 0
        for payload, sig, tsorig in items:
            if not p.try_publish(payload, sig=sig, tsorig=tsorig):
                break
            n += 1
        return n
