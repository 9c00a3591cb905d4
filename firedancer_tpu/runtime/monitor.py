"""Operator surface: live monitor TUI + readiness gate.

Parity targets (no code shared): `fdctl monitor` — a terminal sampler
of every tile's cnc heartbeat, in/out sequence deltas and diag counters
(/root/reference/src/app/fdctl/monitor/monitor.c, workflow in
book/guide/tuning.md:212-238) — and `fdctl ready`, which blocks until
every tile heartbeats in the RUN state
(/root/reference/src/app/fdctl/ready.c).

A running topology advertises itself in a run descriptor
(`fdtpu_run_<uid>.json` under RUN_DIR, written by runtime/topo.launch): stage
names + cnc shared-memory names.  `attach()` joins those cnc regions
READ-ONLY from any process, so the monitor and `ready` work exactly
like the reference's: against a live validator they did not start.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass
from multiprocessing import shared_memory

import numpy as np

from firedancer_tpu.tango import rings
from firedancer_tpu.tango.rings import CNC_SIG_FAIL, CNC_SIG_RUN, Cnc
from firedancer_tpu.utils import metrics as fm

RUN_DIR = os.environ.get("FDTPU_RUN_DIR") or tempfile.gettempdir()
_SIG_NAMES = {0: "BOOT", 1: "RUN", 2: "HALT", 3: "FAIL", 4: "SYNC"}


def _attach_shm(name: str) -> shared_memory.SharedMemory:
    """Join a segment WITHOUT adopting ownership: CPython's resource
    tracker unlinks every tracked segment when its process exits, so a
    short-lived scraper (`fdtpu metrics --once`) would destroy the live
    topology's shm behind its back.  Observers must unregister — the
    segments belong to the launching supervisor (3.13's track=False,
    done by hand for this interpreter)."""
    s = shared_memory.SharedMemory(name=name)
    try:
        from multiprocessing import resource_tracker

        resource_tracker.unregister(s._name, "shared_memory")
    except Exception:
        pass  # tracker layout changed: worst case is the old behavior
    return s


def descriptor_path(uid: str) -> str:
    return os.path.join(RUN_DIR, f"fdtpu_run_{uid}.json")


# Mappings whose close() hit BufferError because the caller still held
# registry views (e.g. a MetricsServer scraping across a refresh()).
# Parked here so SharedMemory.__del__ never re-raises into the void;
# reaped on the next session close() once the views have died.
_ORPHANS: list = []


def _reap_orphans() -> None:
    for s in list(_ORPHANS):
        try:
            s.close()
        except BufferError:
            continue
        _ORPHANS.remove(s)


def flight_dump_path(uid: str) -> str:
    return os.path.join(RUN_DIR, f"fdtpu_flight_{uid}.json")


def list_flight_dumps() -> list[str]:
    """Flight-recorder dump paths, newest first (dumps outlive their
    runs deliberately — they are crash evidence)."""
    out = [
        os.path.join(RUN_DIR, fn)
        for fn in os.listdir(RUN_DIR)
        if fn.startswith("fdtpu_flight_") and fn.endswith(".json")
    ]
    return sorted(out, key=os.path.getmtime, reverse=True)


def write_descriptor(uid: str, stages: dict[str, str],
                     metrics: dict | None = None,
                     shards: dict | None = None) -> str:
    """stages: name -> cnc shm name; metrics: name -> {"shm": metrics
    segment shm name, "schema": schema_to_obj(...)}; shards: name ->
    {"shard": int, "logical": str} for sharded-serving stages (absent
    entries are unsharded).  Returns the path."""
    path = descriptor_path(uid)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"uid": uid, "pid": os.getpid(), "stages": stages,
                   "metrics": metrics or {}, "shards": shards or {}}, f)
    os.replace(tmp, path)
    return path


def remove_descriptor(uid: str) -> None:
    try:
        os.remove(descriptor_path(uid))
    except OSError:
        pass


def list_runs() -> list[str]:
    """Run descriptor paths, newest first, dead owners pruned."""
    out = []
    for fn in os.listdir(RUN_DIR):
        if not (fn.startswith("fdtpu_run_") and fn.endswith(".json")):
            continue
        p = os.path.join(RUN_DIR, fn)
        try:
            with open(p) as f:
                d = json.load(f)
            os.kill(int(d["pid"]), 0)  # owner alive?
        except (OSError, ValueError, KeyError):
            try:
                os.remove(p)
            except OSError:
                pass
            continue
        out.append(p)
    return sorted(out, key=os.path.getmtime, reverse=True)


@dataclass
class _Joined:
    name: str
    cnc: Cnc
    shm: shared_memory.SharedMemory
    # metrics-plane joins (None on descriptors that predate them or when
    # the segment failed to map — the cnc surface still works)
    registry: object = None  # fm.MetricsRegistry
    recorder: object = None  # fm.FlightRecorder
    met_shm: shared_memory.SharedMemory | None = None
    # sharded-serving labels (None/name on unsharded stages)
    shard: int | None = None
    logical: str | None = None


class MonitorSession:
    """Read-only join of a running topology's cnc + metrics regions."""

    def __init__(self, joined: list[_Joined], uid: str | None = None,
                 descriptor: str | None = None):
        self._joined = joined
        self.uid = uid
        # the path we attached through — refresh() re-reads it to detect
        # a replaced run or a metrics segment that failed to join
        self.descriptor = descriptor

    @classmethod
    def attach(cls, descriptor: str | None = None) -> "MonitorSession":
        """Join the given descriptor (path), or the newest live run."""
        if descriptor is None:
            runs = list_runs()
            if not runs:
                raise RuntimeError("no running fdtpu topology found")
            descriptor = runs[0]
        with open(descriptor) as f:
            d = json.load(f)
        joined = []
        met = d.get("metrics", {})
        shards = d.get("shards", {})
        for name, shm_name in d["stages"].items():
            s = _attach_shm(shm_name)
            cnc = Cnc(np.frombuffer(s.buf, dtype=rings.U64,
                                    count=2 + Cnc.NDIAG))
            j = _Joined(name, cnc, s)
            sh = shards.get(name)
            if sh:
                j.shard = sh.get("shard")
                j.logical = sh.get("logical", name)
            m = met.get(name)
            if m:
                ms = None
                try:
                    ms = _attach_shm(m["shm"])
                    schema = fm.schema_from_obj(m["schema"])
                    j.registry, j.recorder = fm.metrics_segment_attach(
                        ms.buf, schema
                    )
                    j.met_shm = ms
                except (OSError, ValueError, KeyError):
                    # metrics plane unavailable; cnc view still works —
                    # but never leak a mapping opened before the failure
                    if ms is not None and j.met_shm is None:
                        try:
                            ms.close()
                        except (OSError, BufferError):
                            pass
            joined.append(j)
        return cls(joined, uid=d.get("uid"), descriptor=descriptor)

    def close(self) -> None:
        for j in self._joined:
            # drop the numpy views before closing the mappings
            j.cnc.cells = np.zeros(2 + Cnc.NDIAG, dtype=rings.U64)
            j.shm.close()
            if j.met_shm is not None:
                j.registry = j.recorder = None
        import gc

        gc.collect()
        for j in self._joined:
            if j.met_shm is not None:
                try:
                    j.met_shm.close()
                except BufferError:
                    # a caller still holds registry views — park the
                    # mapping instead of orphaning it to a __del__ that
                    # would re-raise; reaped once the views die
                    _ORPHANS.append(j.met_shm)
                j.met_shm = None
        _reap_orphans()

    def refresh(self) -> bool:
        """Re-attach if the run behind our descriptor changed: a new uid
        (the run was replaced), a different stage set, or a metrics
        segment that failed to map at attach time and may exist now.

        An IN-PLACE restart (RestartPolicy respawn) reuses the same shm
        regions, so our mappings stay valid and this is a no-op — the
        stale case this guards is a scraper outliving the run it first
        joined (ISSUE 20 satellite 2).  Returns True when re-attached."""
        if self.descriptor is None:
            return False
        try:
            with open(self.descriptor) as f:
                d = json.load(f)
        except (OSError, ValueError):
            return False  # descriptor gone/torn — keep the old mappings
        joined_regs = {j.name for j in self._joined
                       if j.registry is not None}
        stale = (
            d.get("uid") != self.uid
            or set(d.get("stages", {})) != {j.name for j in self._joined}
            or bool(set(d.get("metrics", {})) - joined_regs)
        )
        if not stale:
            return False
        fresh = MonitorSession.attach(self.descriptor)
        self.close()
        self._joined = fresh._joined
        self.uid = fresh.uid
        return True

    # -- metrics plane ------------------------------------------------------

    def registries(self) -> dict:
        """{stage: MetricsRegistry} for every stage whose segment joined."""
        return {j.name: j.registry for j in self._joined
                if j.registry is not None}

    def shard_labels(self) -> dict:
        """{physical stage: {"stage": logical, "shard": i}} for sharded
        stages — the scrape relabeling that lets shards of one logical
        stage aggregate instead of fragmenting over physical names."""
        return {
            j.name: {"stage": j.logical or j.name, "shard": j.shard}
            for j in self._joined
            if j.shard is not None
        }

    def scrape(self) -> str:
        """The Prometheus text exposition over all joined stages (what
        `fdtpu metrics --once` prints and `--serve` serves); sharded
        stages carry {stage=<logical>,shard=<i>} labels."""
        return fm.render_prometheus(self.registries(),
                                    labels=self.shard_labels())

    def flight_records(self) -> dict:
        """{stage: [(ts_ns, event, arg), ...]} from the live rings."""
        return {j.name: j.recorder.records() for j in self._joined
                if j.recorder is not None}

    def flight_dump(self, reason: str = "live snapshot") -> dict:
        return fm.flight_dump_obj(
            self.uid or "?",
            {j.name: (j.registry, j.recorder) for j in self._joined
             if j.recorder is not None},
            failed=None, reason=reason,
        )

    # -- sampling -----------------------------------------------------------

    def sample(self, *, aggregate_shards: bool = False) -> list[dict]:
        """Per-stage liveness + counters.  aggregate_shards=True folds
        the N physical shards of each logical stage into ONE row (the
        monitor-TUI view): counters sum, heartbeat age is the WORST
        shard's, signal is FAIL if any shard failed (else the minimum —
        a still-BOOTing shard keeps the row at BOOT), and the latency
        percentiles come from the merged cross-shard histogram."""
        from firedancer_tpu.runtime.stage import Stage

        now = time.monotonic_ns()
        out = []
        groups: dict[str, list] = {}
        for j in self._joined:
            if aggregate_shards and j.shard is not None:
                groups.setdefault(j.logical or j.name, []).append(j)
                continue
            hb = j.cnc.last_heartbeat
            row = {
                "stage": j.name,
                "signal": j.cnc.signal,
                "heartbeat_age_ms": (now - hb) / 1e6 if hb else None,
                "in": j.cnc.diag(Stage.DIAG_FRAGS_IN),
                "out": j.cnc.diag(Stage.DIAG_FRAGS_OUT),
                "overrun": j.cnc.diag(Stage.DIAG_OVERRUN),
                "backpressure": j.cnc.diag(Stage.DIAG_BACKPRESSURE),
                "iters": j.cnc.diag(Stage.DIAG_ITER),
                "shard": j.shard,
            }
            row.update(fm.latency_row(j.registry))
            row["sweep_phases"] = fm.nsweep_phase_row([j.registry])
            row["loop"] = fm.loop_row([j.registry])
            row["batch_closes"] = fm.batch_close_row([j.registry])
            row["chip_empty"] = fm.chip_empty_row(j.registry)
            row["intake"] = fm.intake_row(j.registry)
            row["mesh"] = fm.mesh_row(j.registry)
            row["votes"] = fm.vote_row(j.registry)
            row["funk"] = fm.funk_row(j.registry)
            row["dedup"] = fm.dedup_row(j.registry)
            row["front"] = fm.front_row(j.registry)
            row["replay"] = fm.replay_row(j.registry)
            out.append(row)
        for logical, js in groups.items():
            sigs = [j.cnc.signal for j in js]
            ages = [
                (now - j.cnc.last_heartbeat) / 1e6
                for j in js if j.cnc.last_heartbeat
            ]
            row = {
                "stage": f"{logical} x{len(js)}",
                "signal": (CNC_SIG_FAIL if CNC_SIG_FAIL in sigs
                           else min(sigs)),
                "heartbeat_age_ms": max(ages) if ages else None,
                "in": sum(j.cnc.diag(Stage.DIAG_FRAGS_IN) for j in js),
                "out": sum(j.cnc.diag(Stage.DIAG_FRAGS_OUT) for j in js),
                "overrun": sum(j.cnc.diag(Stage.DIAG_OVERRUN) for j in js),
                "backpressure": sum(
                    j.cnc.diag(Stage.DIAG_BACKPRESSURE) for j in js
                ),
                "iters": sum(j.cnc.diag(Stage.DIAG_ITER) for j in js),
                "shards": len(js),
            }
            row.update(fm.latency_row_merged([j.registry for j in js]))
            row["sweep_phases"] = fm.nsweep_phase_row(
                [j.registry for j in js])
            row["loop"] = fm.loop_row([j.registry for j in js])
            row["batch_closes"] = fm.batch_close_row(
                [j.registry for j in js])
            out.append(row)
        return out

    def all_running(self, *, max_heartbeat_age_s: float = 5.0) -> bool:
        for r in self.sample():
            if r["signal"] != CNC_SIG_RUN:
                return False
            age = r["heartbeat_age_ms"]
            if age is None or age > max_heartbeat_age_s * 1e3:
                return False
        return True

    def any_failed(self) -> bool:
        return any(r["signal"] == CNC_SIG_FAIL for r in self.sample())

    def wait_ready(self, *, timeout_s: float = 60.0,
                   poll_s: float = 0.05) -> bool:
        """Block until every stage heartbeats in RUN (the `ready`
        command).  False on timeout or any FAIL."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.any_failed():
                return False
            if self.all_running():
                return True
            time.sleep(poll_s)
        return False

    # -- rendering ----------------------------------------------------------

    @staticmethod
    def render(rows: list[dict], prev: list[dict] | None,
               dt_s: float) -> str:
        hdr = (f"{'stage':<14}{'state':<6}{'hb_ms':>8}{'in/s':>11}"
               f"{'out/s':>11}{'busy%':>7}{'backp%':>7}{'ovrn':>7}{'bkp':>7}"
               f"{'p50 lat':>9}{'p99 lat':>9}{'sweep p50us':>16}")
        lines = [hdr, "-" * len(hdr)]
        prev_by = {r["stage"]: r for r in prev or []}
        for r in rows:
            p = prev_by.get(r["stage"])
            in_rate = out_rate = busy = backp = float("nan")
            if p and dt_s > 0:
                in_rate = (r["in"] - p["in"]) / dt_s
                out_rate = (r["out"] - p["out"]) / dt_s
                # busy% and backp% are time: the shares of the stage's
                # loop time, between the two samples, in calls that did
                # work and in calls that the tile behind it held up (the
                # thread's ledger; "-" where the metrics plane is not
                # joined).  With a process a tile the busiest tile
                # limits; the tiles in front of it show backp%
                lp, lp0 = r.get("loop"), p.get("loop")
                if lp and lp0:
                    sh = fm.loop_shares(lp, lp0)
                    busy, backp = ((sh["busy_pct"], sh["backp_pct"])
                                   if sh else (0.0, 0.0))
            hb = (f"{r['heartbeat_age_ms']:.1f}"
                  if r["heartbeat_age_ms"] is not None else "-")
            fmt = lambda v: "-" if v != v else f"{v:,.0f}"  # noqa: E731
            # cumulative per-stage latency percentiles from the shm
            # histogram (ms; "-" when the metrics plane is not joined)
            lines.append(
                f"{r['stage']:<14}{_SIG_NAMES.get(r['signal'], '?'):<6}"
                f"{hb:>8}{fmt(in_rate):>11}{fmt(out_rate):>11}"
                f"{fmt(busy):>7}{fmt(backp):>7}"
                f"{r['overrun']:>7}{r['backpressure']:>7}"
                f"{fm.format_latency_ms(r.get('lat_p50_ms')):>9}"
                f"{fm.format_latency_ms(r.get('lat_p99_ms')):>9}"
                f"{fm.format_phase_cell(r.get('sweep_phases') or {}):>16}"
            )
        # under the table: what closed each verify stage's batches, how
        # many were dispatched behind a running one, how many a backlog
        # in front kept open past their deadline, its stalls, and
        # the lanes nobody used: left empty because the next
        # transaction did not fit, spent on transactions that failed
        # whole (cumulative), how the program lays its batch (128: on
        # both tiled axes); then, between the two samples, the share
        # of the time the chip had nothing of the stage's to run, and
        # of that the thread's time in other stages and in the stage's
        # own blocking calls; and how many frags one intake crossing
        # took (the stage's whole burst under a backlog)
        for r in rows:
            bc = r.get("batch_closes")
            if bc:
                before = prev_by.get(r["stage"]) or {}
                lines.append(
                    f"{r['stage']}: batches closed "
                    + " ".join(f"{k}={bc[k]:,}" for k in fm.BATCH_CLOSES)
                    + f"  queued_behind={bc['queued_behind']:,}"
                    + f"  held_backlogged={bc['held_backlogged']:,}"
                    + f"  batch_stalls={bc['stalls']:,}"
                    + f"  fit_pad_lanes={bc['fit_pad_lanes']:,}"
                    + f"  verify_fail_elems={bc['fail_elems']:,}"
                    + f"  kernel_fold_lanes={bc['fold_lanes']}"
                    + ("  " + fm.format_frags_per_crossing(
                        r["intake"], before.get("intake"))
                       if r.get("intake") else "")
                    + ("  " + fm.format_chip_empty(
                        r["chip_empty"], before.get("chip_empty"), dt_s)
                       if r.get("chip_empty") else ""))
            mesh = r.get("mesh")
            if mesh:
                lines.append(
                    f"{r['stage']}: mesh of {mesh['devices']} chips,"
                    " useful lanes " + " ".join(
                        f"s{i}={v:,}"
                        for i, v in enumerate(mesh["shard_elems"])))
            votes = r.get("votes")
            if votes:
                # pack: votes scheduled / dropped, scan steps over a
                # locked account; a bank: votes landed / landed failed
                lines.append(f"{r['stage']}: votes " + " ".join(
                    f"{k}={v:,}" for k, v in votes.items()))
            dedup = r.get("dedup")
            if dedup:
                # the dedup stage: transactions its tag cache dropped,
                # and the signatures verify had checked for them
                lines.append(f"{r['stage']}: dropped " + " ".join(
                    f"{k}={v:,}" for k, v in dedup.items()))
            front = r.get("front")
            if front:
                # the quic tile: datagrams, punts to the Python lane,
                # what the reassembler made of the streams, whole
                # transactions that waited for verify's ring; a sender
                # tile: what it sent, sent again, and how often the
                # peer's credit held it (cumulative)
                lines.append(f"{r['stage']}: front " + " ".join(
                    f"{k}={v:,}" for k, v in front.items()))
            replay = r.get("replay")
            if replay:
                # the replay verify stage: entry batches in and out,
                # the slots' verdicts by reason, what was skipped of
                # dead slots and the lanes spent on it, the PoH check
                # and the unpack as cumulative ns
                lines.append(f"{r['stage']}: replay " + " ".join(
                    f"{k}={v:,}" for k, v in replay.items()))
        # the bank tiles' one account store: the lock they meet at,
        # what each took from the segment after another tile's write,
        # and what pack gave each (cumulative)
        funk = fm.format_funk({r["stage"]: r.get("funk") for r in rows})
        if funk:
            lines.append(funk)
        return "\n".join(lines)

    def run(self, *, interval_s: float = 1.0, iterations: int | None = None,
            out=sys.stdout) -> None:
        """The live TUI loop: redraw-in-place sampler (^C exits)."""
        prev, prev_t = None, time.monotonic()
        first = True
        n = 0
        try:
            while iterations is None or n < iterations:
                rows = self.sample(aggregate_shards=True)
                now = time.monotonic()
                text = self.render(rows, prev, now - prev_t)
                if not first:
                    # move cursor up over the previous frame
                    out.write(f"\x1b[{text.count(chr(10)) + 1}A")
                out.write("\x1b[J" + text + "\n")
                out.flush()
                prev, prev_t, first = rows, now, False
                n += 1
                if iterations is None or n < iterations:
                    time.sleep(interval_s)
        except KeyboardInterrupt:
            pass
