"""Process topology runner: spawn stages as processes, supervise, monitor.

The process-isolation model of the reference
(/root/reference/src/disco/topo/fd_topo_run.c:50-190 boots one tile per
process; src/app/fdctl/run/run.c:252-330 is the parent that watches the
brood and kills the whole topology when any tile dies): a Topology is a
declarative description of links and stages; `launch` creates every shm
link, spawns one OS process per stage (fork), hands each its Consumers /
Producers / a shared-memory cnc, and returns a handle whose supervisor
loop watches process liveness and cnc heartbeats.  One dead or wedged
stage takes the whole topology down — crash containment by process
boundary, not by try/except.

The monitor (`snapshot` / `format_monitor`) is the fdctl-monitor analog
(src/app/fdctl/monitor/monitor.c): per-stage heartbeat age and the diag
counters each stage exports during housekeeping (frags in/out, overruns,
backpressure).

Stage construction runs IN THE CHILD: specs carry a builder callable
invoked after the links are joined, so device handles / caches are never
shared across processes.  Children START FRESH (the multiprocessing
"spawn" method, not fork): a forked child inherits the parent's
initialized XLA runtime whose thread pools did not survive the fork, and
its first device dispatch deadlocks — so builders must be module-level
(picklable) functions, with per-stage parameters in StageSpec.kwargs.

These invariants (and the link-graph ones: single producer per link,
power-of-two depths, credit-cycle freedom) are CHECKED, not just
documented: stages declare their wiring via StageSpec.ins/outs, and
`launch()` runs the fdlint topology checker (firedancer_tpu/analysis,
the fd_topob analog) in the parent before creating any shm — see
docs/ANALYSIS.md.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import signal as _signal
import time
from dataclasses import dataclass, field, replace
from multiprocessing import shared_memory

import numpy as np

from firedancer_tpu.tango import rings, shm
from firedancer_tpu.tango.rings import CNC_SIG_FAIL, CNC_SIG_HALT, CNC_SIG_RUN, Cnc
from firedancer_tpu.utils import log as fl
from firedancer_tpu.utils import metrics as fm

_log = fl.get_logger("topo")


@dataclass
class LinkSpec:
    name: str
    depth: int = 1024
    mtu: int = 4096
    n_consumers: int = 1
    # optional data-region oversizing (burst headroom); None = the exact
    # DCache.footprint(mtu, depth).  Undersizing is refused at create and
    # reported pre-boot by the topology checker (analysis FD105).
    dcache_sz: int | None = None


@dataclass
class StageSpec:
    """builder(links: dict[str, ShmLink], cnc: Cnc) -> Stage; runs in child.

    sandbox: optional utils/sandbox.enter kwargs — the per-stage jail
    (rlimits/namespaces/seccomp) applied in the CHILD after the builder
    ran (privileged_init analog: open sockets/keys first, then drop) and
    before the run loop, mirroring fd_topo_run's boot ordering
    (src/disco/topo/fd_topo_run.c:50-190).

    ins / outs: DECLARATIVE wiring — the link names this stage's builder
    will consume / produce.  Purely descriptive (builders still wire the
    actual Consumers/Producers), but declaring lets the pre-boot
    topology checker (firedancer_tpu/analysis, the fd_topob analog)
    validate the whole graph in the parent before any shm exists.  None
    (default) means "hand-wired": graph rules skip this stage.

    credit_gated mirrors Stage.require_credit: the stage stops consuming
    inputs while any output is backpressured, which the checker uses to
    find credit-deadlock cycles (FD107).

    shard / logical: the sharded-serving labels.  A sharded topology runs
    N instances of one LOGICAL stage (e.g. "verify") as physically
    distinct stages ("verify_s0".."verify_s3") — `logical` names the
    stage kind and `shard` its index, and both ride the run descriptor so
    the scrape surface labels series {stage=<logical>,shard=<i>} and the
    monitor aggregates across shards instead of colliding on (or being
    fragmented by) the physical names.  None = unsharded (no label).

    schema: the stage KIND's metric layout (Stage.metrics_schema()).
    launch() sizes the per-stage metrics shm segment from it IN THE
    PARENT, and the child attaches with the same spec-resolved schema,
    so writer and reader can never disagree on the layout.  None means
    the shared base stage_schema()."""

    name: str
    builder: object
    kwargs: dict = field(default_factory=dict)
    sandbox: dict | None = None
    ins: tuple[str, ...] | None = None
    outs: tuple[str, ...] | None = None
    credit_gated: bool = False
    schema: fm.MetricsSchema | None = None
    shard: int | None = None
    logical: str | None = None
    # declarative restart eligibility: the child arms TRANSACTIONAL
    # progress (Stage.arm_safe_progress — fseq moves only after a
    # sweep's effects are published), the precondition for supervise's
    # in-place restart path to resume exactly-once.  Only mark stages
    # whose frag effects complete within the sweep (relay-shaped); a
    # stage holding cross-sweep in-memory state (verify's in-flight
    # batches, pack's pool, bank's funk) would lose it on respawn.
    restartable: bool = False


@dataclass
class Topology:
    links: list[LinkSpec] = field(default_factory=list)
    stages: list[StageSpec] = field(default_factory=list)
    # what the run's stages make beside the links, named per run: a
    # template with "{uid}" in it, a /dev/shm segment name or (with a
    # "/" in it) a directory.  launch() puts the run's uid into these
    # and into every string among the stages' kwargs, makes the
    # directories, and close() takes all of it away again, whether or
    # not the stage that made it lived to do so (`own`)
    owned: list[str] = field(default_factory=list)

    def own(self, template: str) -> str:
        self.owned.append(template)
        return template

    def link(self, name: str, **kw) -> "LinkSpec":
        spec = LinkSpec(name, **kw)
        self.links.append(spec)
        return spec

    def stage(self, name: str, builder, *, sandbox: dict | None = None,
              ins: list[str] | tuple[str, ...] | None = None,
              outs: list[str] | tuple[str, ...] | None = None,
              credit_gated: bool = False,
              schema: fm.MetricsSchema | None = None,
              shard: int | None = None,
              logical: str | None = None,
              restartable: bool = False,
              **kwargs) -> "StageSpec":
        spec = StageSpec(
            name, builder, kwargs, sandbox,
            ins=tuple(ins) if ins is not None else None,
            outs=tuple(outs) if outs is not None else None,
            credit_gated=credit_gated,
            schema=schema,
            shard=shard,
            logical=logical,
            restartable=restartable,
        )
        self.stages.append(spec)
        return spec

    def validate(self, label: str = "topology"):
        """Pre-boot check (fd_topob analog); raises analysis.TopologyError
        with the full readable report on any error-severity finding."""
        from firedancer_tpu.analysis.topo_check import validate_or_raise

        return validate_or_raise(self, label)


def _cnc_shm_name(uid: str, stage: str) -> str:
    return f"fdtpu_cnc_{uid}_{stage}"


def _met_shm_name(uid: str, stage: str) -> str:
    return f"fdtpu_met_{uid}_{stage}"


def _spec_schema(spec: StageSpec) -> fm.MetricsSchema:
    """The ONE schema resolution both parent (segment sizing, descriptor)
    and child (attach) use — never resolve this any other way."""
    if spec.schema is not None:
        return spec.schema
    from firedancer_tpu.runtime.stage import Stage

    return Stage.metrics_schema()


def _quiet_shm_close(s: shared_memory.SharedMemory) -> None:
    """Close a segment; if exported views still pin the mapping, detach
    the fd/mmap from the wrapper so interpreter-exit __del__ cannot spew
    'cannot close exported pointers exist' into the parent's stderr
    (refcounting frees the mapping when the last view dies)."""
    try:
        s.close()
    except BufferError:
        try:
            if getattr(s, "_fd", -1) >= 0:
                os.close(s._fd)
                s._fd = -1
            s._mmap = None
            s._buf = None
        except OSError:
            pass


def _die_with_parent(parent_pid: int) -> None:
    """A tile does not outlive its supervisor: SIGKILL when the parent
    goes, however it goes (prctl PR_SET_PDEATHSIG; Linux).  A parent
    that was gone before the call is caught by the pid check."""
    import ctypes

    try:
        ctypes.CDLL(None, use_errno=True).prctl(1, int(_signal.SIGKILL))
    except (OSError, AttributeError):
        return
    if os.getppid() != parent_pid:
        os._exit(1)


def _stage_main(spec: StageSpec, link_names: dict, uid: str,
                resume: bool = False, parent_pid: int | None = None) -> None:
    """Child entry: join links + cnc + metrics segment, build the stage,
    run until HALT.  On any raise the flight ring gets an EV_FAIL record
    BEFORE the cnc flips to FAIL — the ring lives in shm, so the record
    survives this process for the supervisor's dump.

    resume=True is the IN-PLACE RESTART path (supervise's restart
    policy): the stage reattaches to its existing shm rings — consumers
    at their published fseqs, producers at their recovered mcache
    frontiers with the replay-dedup guard armed — and its counters
    continue from the registry's last flushed values instead of zero."""
    if parent_pid is not None:
        _die_with_parent(parent_pid)
    cnc_shm = shared_memory.SharedMemory(name=_cnc_shm_name(uid, spec.name))
    cnc = Cnc(np.frombuffer(cnc_shm.buf, dtype=rings.U64, count=2 + Cnc.NDIAG))
    met_shm = shared_memory.SharedMemory(name=_met_shm_name(uid, spec.name))
    registry, recorder = fm.metrics_segment_attach(
        met_shm.buf, _spec_schema(spec)
    )
    links = {n: shm.ShmLink.join(sn) for n, sn in link_names.items()}
    stage = None
    try:
        stage = spec.builder(links, cnc, **spec.kwargs)
        from firedancer_tpu.utils.platform import process_jax_state

        # one process per chip: the run's log shows which children can
        # reach a device at all (leader_topo: only verify is "default")
        _log.notice(f"stage {spec.name} jax={process_jax_state()}")
        if resume:
            # counters continue monotonically across the respawn (a
            # fresh zeroed stage would go BACKWARD in the scrape the
            # instant its first flush landed); histograms restart —
            # their pre-crash state is already in the registry and the
            # stage only ever overwrites what it locally observed
            # native-owned words are never resume-copied: C bumps them
            # in the segment directly, and seeding the Python facade
            # would re-add them at the next flush (double count)
            for name, (d, _off) in registry._off.items():
                if d.kind != fm.HISTOGRAM and not d.native:
                    v = registry.get(name)
                    if v:
                        stage.metrics.counters[name] = v
        # schema-drift guard: a stage kind with extra_schema() whose spec
        # forgot schema=Kind.metrics_schema() would silently publish only
        # the base block — make the partial-metrics trap loud at boot
        missing = (type(stage).metrics_schema().names()
                   - _spec_schema(spec).names())
        if missing:
            _log.warning(
                f"stage '{spec.name}': metrics {sorted(missing)} are "
                f"declared by {type(stage).__name__}.extra_schema() but "
                f"absent from the StageSpec schema — they will not reach "
                f"the shm metrics plane (pass "
                f"schema={type(stage).__name__}.metrics_schema() to "
                f"Topology.stage)"
            )
        if spec.restartable:
            stage.arm_safe_progress()
        stage.attach_observability(registry, recorder)
        if resume:
            stage.resume_from_rings()
        if spec.sandbox is not None:
            from firedancer_tpu.utils import sandbox as sb

            sb.enter(**spec.sandbox)
        stage.run()
    except Exception:
        recorder.record(fm.EV_FAIL)
        if stage is not None:
            stage.metrics.flush()  # last state, for the post-mortem dump
        cnc.signal = CNC_SIG_FAIL
        raise
    finally:
        # clean-exit hygiene: drop the stage's views and close the
        # joined segments quietly, or every HALTing child sprays
        # BufferError __del__ noise onto the shared stderr (the
        # BENCH-tail pollution's process-topology sibling).  Crash paths
        # already flushed their evidence above; the supervisor owns the
        # segments, so closing here never unlinks anything.
        stage = None
        registry = recorder = None
        cnc.cells = np.zeros(2 + Cnc.NDIAG, dtype=rings.U64)
        import gc

        gc.collect()
        for _lnk in links.values():
            _lnk.close()
        _quiet_shm_close(cnc_shm)
        _quiet_shm_close(met_shm)


class TopologyHandle:
    def __init__(self, topo, uid, links, cncs, cnc_shms, procs,
                 met_shms=None, met_views=None, link_names=None,
                 owned=()):
        self.topo = topo
        self.uid = uid
        self.links = links  # name -> ShmLink (parent-side joins)
        self.cncs = cncs  # stage name -> Cnc
        self._cnc_shms = cnc_shms
        self.procs = procs  # stage name -> mp.Process (spawned stages)
        # stages the caller runs in its own process (launch(held=...)):
        # name -> the Stage once the caller has built it (`hold`)
        self.held: dict[str, object] = {}
        # Topology.owned with the uid in: segments and directories of
        # the run that close() takes away
        self.owned = list(owned)
        # stage name -> its StageSpec with the run's uid in its kwargs:
        # what a child is built from, and a held stage by its caller
        self.run_specs: dict[str, StageSpec] = {}
        self._closed = False
        self._met_shms = met_shms or {}
        # stage name -> (MetricsRegistry, FlightRecorder), parent views
        self.met_views = met_views or {}
        # segment names per link, for in-place respawns (same rings)
        self._link_names = link_names or {}
        self.failed: str | None = None
        self.flight_dump_path: str | None = None
        # stage name -> in-place restarts performed this run
        self.restarts: dict[str, int] = {}

    # -- supervision --------------------------------------------------------

    def supervise(
        self,
        *,
        until=None,
        timeout_s: float = 30.0,
        heartbeat_timeout_s: float = 5.0,
        poll_s: float = 0.02,
        on_poll=None,
        restart=None,
    ) -> bool:
        """Watchdog loop (run.c:252-330): returns True when `until()` says
        done; kills the whole topology and returns False if any stage dies,
        signals FAIL, or stops heartbeating — UNLESS a restart policy
        covers the victim, in which case the stage is respawned IN PLACE
        against its existing shm rings (runtime/restart.RestartPolicy;
        the child reattaches via Stage.resume_from_rings: consumers at
        their published fseqs, producers at their recovered frontiers,
        replay deduped).  A stage that exhausts its bounded attempts
        degrades to today's fail-fast + flight dump.

        restart: RestartPolicy (every stage) | {stage: RestartPolicy}
        (listed stages only) | None (fail-fast always, the old behavior).

        on_poll(handle): called once per watchdog iteration BEFORE the
        liveness checks — the fault-injection hook (chaos/faults.py
        schedules stage kills/freezes through it), also usable for live
        sampling.  It runs in the supervisor, so anything it does to the
        brood is judged by the same checks as a real failure."""
        from firedancer_tpu.runtime.restart import policy_for

        deadline = time.monotonic() + timeout_s
        pending: dict[str, float] = {}  # stage -> respawn-at (monotonic)
        while time.monotonic() < deadline:
            if on_poll is not None:
                on_poll(self)
            if until is not None and until(self):
                return True
            now_s = time.monotonic()
            for name in [n for n, t in pending.items() if now_s >= t]:
                del pending[name]
                self._respawn_stage(name)
            now = time.monotonic_ns()
            for name, p in self.procs.items():
                if name in pending:
                    continue  # reaped; its respawn is scheduled
                cnc = self.cncs[name]
                hb = cnc.last_heartbeat
                if not p.is_alive() or cnc.signal == CNC_SIG_FAIL:
                    why = (f"died (alive={p.is_alive()}, "
                           f"signal={cnc.signal})")
                elif hb and now - hb > heartbeat_timeout_s * 1e9:
                    why = f"heartbeat stale ({(now - hb) / 1e9:.1f}s)"
                else:
                    continue
                pol = policy_for(restart, name)
                if pol is not None and not self._spec_of(name).restartable:
                    # the policy names this stage but its spec never
                    # opted in: without transactional progress (and with
                    # whatever in-memory state the stage holds) a
                    # respawn would silently lose work — refuse and
                    # fail fast rather than degrade delivery semantics
                    _log.warning(
                        f"stage '{name}' is covered by a restart policy "
                        f"but not declared restartable "
                        f"(Topology.stage(restartable=True)); failing "
                        f"fast instead of respawning"
                    )
                    pol = None
                attempt = self.restarts.get(name, 0) + 1
                if pol is not None and attempt <= pol.max_restarts:
                    delay = pol.delay_s(name, attempt)
                    self.restarts[name] = attempt
                    _log.warning(
                        f"stage '{name}' {why}; in-place restart "
                        f"{attempt}/{pol.max_restarts} after "
                        f"{delay * 1e3:.0f}ms backoff"
                    )
                    if self._reap_stage(name):
                        pending[name] = time.monotonic() + delay
                        continue
                    _log.warning(
                        f"stage '{name}' could not be reaped (process "
                        f"survived SIGKILL); aborting the restart"
                    )
                self.failed = name
                extra = (f" after {self.restarts[name]} in-place restarts"
                         if self.restarts.get(name) else "")
                _log.warning(
                    f"stage '{name}' {why}{extra}; killing topology")
                self.dump_flight(f"stage '{name}' {why}{extra}")
                self.kill()
                return False
            time.sleep(poll_s)
        return until is None  # plain timeout counts as failure iff waiting

    def _spec_of(self, name: str) -> StageSpec:
        return next(s for s in self.topo.stages if s.name == name)

    def _reap_stage(self, name: str) -> bool:
        """Take one dead/wedged stage's corpse down and scrub its cnc
        verdict so the watchdog judges the RESPAWN, not the crash.
        Returns False if the old process could not be killed — a
        respawn then MUST NOT happen (two producers on one ring would
        corrupt it); the caller falls through to fail-fast."""
        p = self.procs[name]
        if p.is_alive():
            try:
                os.kill(p.pid, _signal.SIGCONT)  # a SIGSTOPped victim
            except (OSError, TypeError):
                pass
            p.terminate()
        p.join(timeout=5)
        if p.is_alive():  # SIGTERM blocked/stuck: escalate
            try:
                os.kill(p.pid, _signal.SIGKILL)
            except (OSError, TypeError):
                pass
            p.join(timeout=5)
            if p.is_alive():
                return False
        cnc = self.cncs[name]
        cnc.signal = rings.CNC_SIG_BOOT
        cnc.heartbeat(time.monotonic_ns())
        return True

    def _respawn_stage(self, name: str) -> None:
        """Spawn a fresh process for `name` against the topology's
        EXISTING segments (same uid, same rings, same cnc + metrics shm):
        _stage_main(resume=True) makes the stage reattach its cursors
        instead of starting at seq 0."""
        spec = self.run_specs[name]
        # the respawned child gets a fresh boot-grace heartbeat window
        self.cncs[name].heartbeat(time.monotonic_ns())
        ctx = mp.get_context("spawn")
        p = ctx.Process(
            target=_stage_main, args=(spec, self._link_names, self.uid),
            kwargs={"resume": True, "parent_pid": os.getpid()},
            name=spec.name,
        )
        p.daemon = True
        p.start()
        self.procs[name] = p
        _log.notice(f"respawned stage '{name}' in place, pid={p.pid}")

    # -- stages held in the caller's process --------------------------------

    def build_held(self, name: str, **override):
        """Build held stage `name` in this process with the builder and
        kwargs a child would have been given, and hold it."""
        spec = self.run_specs[name]
        stage = spec.builder(self.links, self.cncs[name],
                             **{**spec.kwargs, **override})
        self.hold(stage)
        return stage

    def hold(self, stage) -> None:
        """The caller built held stage `stage.name` over `links` and
        `cncs[name]`: bind it to its metrics segment and flight ring
        like a child binds its own, so that monitor, scrape and
        `counters` see every stage alike."""
        if stage.name in self.procs or stage.name not in self.met_views:
            raise ValueError(f"stage '{stage.name}' is not held by this "
                             f"launch (held=...)")
        stage.attach_observability(*self.met_views[stage.name])
        self.held[stage.name] = stage

    def dead(self) -> list[str]:
        """Spawned stages whose process is gone or that signalled FAIL."""
        return [n for n, p in self.procs.items()
                if not p.is_alive() or self.cncs[n].signal == CNC_SIG_FAIL]

    def wait_running(self, timeout_s: float = 120.0,
                     poll_s: float = 0.01) -> None:
        """Until every spawned stage is in its run loop (its builder
        returned: RUN and a heartbeat); a stage that dies booting, or
        the timeout, raises with the stage's name."""
        deadline = time.monotonic() + timeout_s
        waiting = list(self.procs)
        while waiting:
            dead = self.dead()
            if dead:
                self.failed = dead[0]
                self.dump_flight(f"stage '{dead[0]}' died booting")
                raise RuntimeError(f"stage '{dead[0]}' died booting")
            waiting = [n for n in waiting
                       if self.cncs[n].signal != CNC_SIG_RUN
                       or not self.cncs[n].last_heartbeat]
            if time.monotonic() > deadline:
                raise RuntimeError(f"stages {waiting} not running after "
                                   f"{timeout_s:.0f}s")
            if waiting:
                time.sleep(poll_s)

    def counters(self, timeout_s: float = 2.0) -> dict[str, dict]:
        """stage -> counter -> value for every stage, held or spawned.
        A spawned stage is asked to put its counters out first (its
        C-side ones into its metrics, those into shm: CNC_SIG_SYNC,
        answered from its run loop) and is read from its shm segment;
        a held one is asked in place.  A stage that does not answer in
        `timeout_s` (dead, wedged, still booting) is read as it last
        flushed.  Each tile answers at its own instant (they are apart
        by what the tiles' current calls take): counters of two tiles
        agree only once the work between them has settled."""
        asked = []
        for name, p in self.procs.items():
            cnc = self.cncs[name]
            if p.is_alive() and cnc.signal == CNC_SIG_RUN:
                cnc.signal = rings.CNC_SIG_SYNC
                asked.append(name)
        for st in self.held.values():
            st.sync_counters()
        deadline = time.monotonic() + timeout_s
        while asked and time.monotonic() < deadline:
            asked = [n for n in asked
                     if self.cncs[n].signal == rings.CNC_SIG_SYNC
                     and self.procs[n].is_alive()]
        for name in asked:     # unanswered: do not leave the request up
            if self.cncs[name].signal == rings.CNC_SIG_SYNC:
                self.cncs[name].signal = CNC_SIG_RUN
        out = {}
        for spec in self.topo.stages:
            reg = self.met_views.get(spec.name, (None, None))[0]
            if reg is None:
                continue
            c = fm.registry_counters(reg)
            st = self.held.get(spec.name)
            if st is not None:      # and what the schema does not name
                c.update(st.metrics.counters)
            out[spec.name] = c
        return out

    def halt(self, timeout_s: float = 10.0) -> None:
        """Clean shutdown: HALT every cnc, join, terminate stragglers."""
        for cnc in self.cncs.values():
            if cnc.signal != CNC_SIG_FAIL:
                cnc.signal = CNC_SIG_HALT
        deadline = time.monotonic() + timeout_s
        for p in self.procs.values():
            p.join(max(deadline - time.monotonic(), 0.1))
        self.kill()

    def kill(self) -> None:
        """No spawned stage outlives this call: SIGTERM, and SIGKILL
        for what has not gone 5 s later (a child stuck in a C call, or
        one that blocks the signal)."""
        for p in self.procs.values():
            if p.is_alive():
                # a SIGSTOPped child ignores SIGTERM until continued —
                # thaw first so terminate() cannot hang the join below
                try:
                    os.kill(p.pid, _signal.SIGCONT)
                except (OSError, TypeError):
                    pass
                p.terminate()
        deadline = time.monotonic() + 5
        for p in self.procs.values():
            p.join(timeout=max(deadline - time.monotonic(), 0.05))
        for p in self.procs.values():
            if p.is_alive():
                p.kill()
                p.join(timeout=5)

    # -- fault injection (the chaos harness's supervisor surface) ------------

    def kill_stage(self, name: str, sig: int | None = None) -> None:
        """Deliver `sig` (default SIGKILL) to ONE stage process and leave
        the verdict to the supervisor loop — the stage-kill fault: the
        watchdog must notice, dump the flight rings, and fail fast."""
        p = self.procs[name]
        if p.pid is not None and p.is_alive():
            os.kill(p.pid, sig if sig is not None else _signal.SIGKILL)

    def freeze_stage(self, name: str) -> None:
        """SIGSTOP one stage: the process stays alive but its heartbeat
        goes stale — the wedged-stage fault (cnc heartbeat contract)."""
        self.kill_stage(name, _signal.SIGSTOP)

    def thaw_stage(self, name: str) -> None:
        self.kill_stage(name, _signal.SIGCONT)

    def shm_names(self) -> list[str]:
        """Every shared-memory segment name this topology owns (links +
        cnc + metrics + what its stages make: Topology.owned) — the
        chaos leak check scans /dev/shm for them after close()."""
        out = [f"fdtpu_{spec.name}_{self.uid}" for spec in self.topo.links]
        for spec in self.topo.stages:
            out.append(_cnc_shm_name(self.uid, spec.name))
            out.append(_met_shm_name(self.uid, spec.name))
        return out + [o for o in self.owned if "/" not in o]

    def left_behind(self) -> list[str]:
        """After close(): spawned stages still alive and segments or
        directories of the run still there, by name.  Empty is the
        contract."""
        left = [f"process:{n}" for n, p in self.procs.items()
                if p.is_alive()]
        left += [f"shm:{n}" for n in self.shm_names()
                 if os.path.exists(os.path.join("/dev/shm", n))]
        left += [f"dir:{o}" for o in self.owned
                 if "/" in o and os.path.exists(o)]
        return left

    def dump_flight(self, reason: str = "") -> str | None:
        """Write the crash dump — every stage's flight ring + a final
        metrics snapshot — to RUN_DIR (the supervisor's abnormal-exit
        path; also callable any time for a live snapshot).  The file
        OUTLIVES close(): it is the evidence trail."""
        import json as _json

        from firedancer_tpu.runtime import monitor as mon

        if not self.met_views:
            return None
        obj = fm.flight_dump_obj(self.uid, self.met_views,
                                 failed=self.failed, reason=reason)
        path = mon.flight_dump_path(self.uid)
        try:
            with open(path, "w") as f:
                _json.dump(obj, f)
            self.flight_dump_path = path
            _log.notice(f"flight-recorder dump written: {path}")
            return path
        except OSError as e:  # diagnostics must never mask the real failure
            _log.warning(f"flight dump failed: {e}")
            return None

    def close(self) -> None:
        """Everything of the run goes: the children, the descriptor,
        every segment and directory — also after a child died, and
        twice is once.  Held stages are the caller's: they must have
        dropped their link views (Stage.ins / outs, drop_native_views)
        before this."""
        import shutil

        from firedancer_tpu.runtime import monitor as mon

        if self._closed:
            return
        self._closed = True
        self.held = {}
        mon.remove_descriptor(self.uid)
        self.kill()
        for link in self.links.values():
            try:
                link.close()
            except BufferError:
                pass    # a view still pins the mapping: unlink all the same
            try:
                link.unlink()
            except FileNotFoundError:
                pass
        for o in self.owned:
            if "/" in o:
                shutil.rmtree(o, ignore_errors=True)
            else:   # made by a stage that may have died before its unlink
                try:
                    os.unlink(os.path.join("/dev/shm", o))
                except FileNotFoundError:
                    pass
        # numpy views into the metric and cnc segments must drop before
        # close — a pinned view turns close() into a BufferError and the
        # interpreter-exit SharedMemory.__del__ into stderr noise
        self.met_views = {}
        for cnc in self.cncs.values():
            cnc.cells = np.zeros(2 + Cnc.NDIAG, dtype=rings.U64)
        import gc

        gc.collect()
        # close and unlink SEPARATELY: a close() refused by a straggling
        # exported view (a caller that kept a met_views registry) must
        # never skip the unlink, or the /dev/shm entry leaks past the
        # topology's lifetime — the chaos harness's reclaim invariant
        # scans for exactly that.  _quiet_shm_close also detaches the
        # refused wrapper so interpreter-exit __del__ stays silent.
        for s in list(self._cnc_shms.values()) + list(self._met_shms.values()):
            _quiet_shm_close(s)
            try:
                s.unlink()
            except FileNotFoundError:
                pass

    # -- monitor ------------------------------------------------------------

    def snapshot(self) -> list[dict]:
        """Per-stage liveness + diag counters (the monitor sample)."""
        from firedancer_tpu.runtime.stage import Stage

        now = time.monotonic_ns()
        out = []
        for spec in self.topo.stages:
            name = spec.name
            p = self.procs.get(name)    # None: held in this process
            cnc = self.cncs[name]
            hb = cnc.last_heartbeat
            row = {
                "stage": name,
                "alive": p.is_alive() if p is not None else True,
                "signal": cnc.signal,
                "heartbeat_age_ms": (now - hb) / 1e6 if hb else None,
                "frags_in": cnc.diag(Stage.DIAG_FRAGS_IN),
                "frags_out": cnc.diag(Stage.DIAG_FRAGS_OUT),
                "overrun": cnc.diag(Stage.DIAG_OVERRUN),
                "backpressure": cnc.diag(Stage.DIAG_BACKPRESSURE),
                "iters": cnc.diag(Stage.DIAG_ITER),
            }
            reg = self.met_views.get(name, (None, None))[0]
            row.update(fm.latency_row(reg))
            row["loop"] = fm.loop_row([reg])
            row["funk"] = fm.funk_row(reg)
            out.append(row)
        return out

    def format_monitor(self) -> str:
        """The table, with what of its loop time since boot each tile
        worked (busy%) and was held by the tile behind it (backp%),
        from the shm segments.  The limiting tile is the busiest one;
        the tiles in front of it show backp%, those behind it poll."""
        rows = self.snapshot()
        hdr = (
            f"{'stage':<12}{'alive':<7}{'hb_ms':>8}{'in':>10}{'out':>10}"
            f"{'busy%':>7}{'backp%':>7}"
            f"{'ovrn':>7}{'bkp':>7}{'p50 lat':>10}{'p99 lat':>10}"
        )
        lines = [hdr]
        for r in rows:
            hb = f"{r['heartbeat_age_ms']:.1f}" if r["heartbeat_age_ms"] else "-"
            sh = r["loop"] and fm.loop_shares(r["loop"])
            busy, backp = ((f"{sh['busy_pct']:.0f}", f"{sh['backp_pct']:.0f}")
                           if sh else ("-", "-"))
            lines.append(
                f"{r['stage']:<12}{str(r['alive']):<7}{hb:>8}"
                f"{r['frags_in']:>10}{r['frags_out']:>10}"
                f"{busy:>7}{backp:>7}"
                f"{r['overrun']:>7}{r['backpressure']:>7}"
                f"{fm.format_latency_ms(r.get('lat_p50_ms')):>10}"
                f"{fm.format_latency_ms(r.get('lat_p99_ms')):>10}"
            )
        funk = fm.format_funk({r["stage"]: r["funk"] for r in rows})
        if funk:    # the bank tiles' one account store
            lines.append(funk)
        return "\n".join(lines)


def _with_uid(v, uid: str):
    return v.format(uid=uid) if isinstance(v, str) and "{uid}" in v else v


def launch(topo: Topology, *, namespace: str | None = None,
           held: tuple[str, ...] = ()) -> TopologyHandle:
    """`namespace` prefixes every segment name this topology creates
    (links, cnc, metrics): N simultaneous topologies in one box — e.g.
    one per validator of a cluster — stay disjoint in /dev/shm, and a
    supervisor FAIL/close reclaims only its own validator's segments.

    `held`: stages that are NOT spawned.  The caller runs them in its
    own process — the process that holds the chip and traces it, or
    the one whose generator a check counts — over the same shm links:
    it builds each with the builder a child would use, from
    `handle.links` and `handle.cncs[name]`, and hands it to
    `handle.hold`.  Their segments exist like any stage's, so monitor,
    scrape and `counters()` see one topology."""
    # fail fast IN THE PARENT: a mis-wired graph raises a readable
    # TopologyError here, before any shm segment or child process exists
    # (the fd_topob contract — validation precedes boot)
    topo.validate()
    unknown = set(held) - {s.name for s in topo.stages}
    if unknown:
        raise ValueError(f"held stages {sorted(unknown)} are not in the "
                         f"topology")
    ctx = mp.get_context("spawn")  # fresh interpreters: see module docstring
    uid = shm.fresh_uid(namespace)
    links: dict[str, shm.ShmLink] = {}
    link_names: dict[str, str] = {}
    for spec in topo.links:
        sn = f"fdtpu_{spec.name}_{uid}"
        links[spec.name] = shm.ShmLink.create(
            sn, depth=spec.depth, mtu=spec.mtu, n_fseq=spec.n_consumers,
            dcache_sz=spec.dcache_sz,
        )
        link_names[spec.name] = sn
    cncs: dict[str, Cnc] = {}
    cnc_shms: dict[str, shared_memory.SharedMemory] = {}
    met_shms: dict[str, shared_memory.SharedMemory] = {}
    met_views: dict[str, tuple] = {}
    for spec in topo.stages:
        s = shared_memory.SharedMemory(
            name=_cnc_shm_name(uid, spec.name), create=True, size=Cnc.footprint()
        )
        cnc_shms[spec.name] = s
        cncs[spec.name] = Cnc(
            np.frombuffer(s.buf, dtype=rings.U64, count=2 + Cnc.NDIAG)
        )
        # one metrics segment per stage, sized by the declared schema
        # (+ the flight-recorder ring), created before any child exists
        # so a stage that crashes during boot still has a ring to dump
        schema = _spec_schema(spec)
        ms = shared_memory.SharedMemory(
            name=_met_shm_name(uid, spec.name), create=True,
            size=fm.metrics_segment_footprint(schema),
        )
        met_shms[spec.name] = ms
        met_views[spec.name] = fm.metrics_segment_init(ms.buf, schema)
    owned = [_with_uid(o, uid) for o in topo.owned]
    handle = TopologyHandle(topo, uid, links, cncs, cnc_shms, {},
                            met_shms, met_views, link_names, owned)
    try:
        for o in owned:
            if "/" in o:
                os.makedirs(o, exist_ok=True)
        for spec in topo.stages:
            run_spec = handle.run_specs[spec.name] = replace(spec, kwargs={
                k: _with_uid(v, uid) for k, v in spec.kwargs.items()})
            if spec.name in held:
                continue
            p = ctx.Process(
                target=_stage_main, args=(run_spec, link_names, uid),
                kwargs={"parent_pid": os.getpid()}, name=spec.name,
            )
            p.daemon = True
            p.start()
            handle.procs[spec.name] = p
            _log.info(f"spawned stage '{spec.name}' pid={p.pid}")
    except BaseException:
        handle.close()      # no half-launched run is left behind
        raise
    # advertise the run so `fdtpu monitor` / `fdtpu ready` / `fdtpu
    # metrics` can attach from another process (runtime/monitor.py);
    # the metrics entries carry the schema so an uninvolved scraper can
    # reconstruct the registry layout without importing stage classes
    from firedancer_tpu.runtime import monitor as mon

    mon.write_descriptor(
        uid,
        {s.name: _cnc_shm_name(uid, s.name) for s in topo.stages},
        metrics={
            s.name: {
                "shm": _met_shm_name(uid, s.name),
                "schema": fm.schema_to_obj(_spec_schema(s)),
            }
            for s in topo.stages
        },
        # sharded-serving labels: physical stage -> {shard, logical}, so
        # scrapers label series per shard and the monitor can aggregate
        shards={
            s.name: {"shard": s.shard, "logical": s.logical or s.name}
            for s in topo.stages
            if s.shard is not None
        },
    )
    return handle
