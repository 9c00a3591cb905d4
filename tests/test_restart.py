"""Self-healing supervisor: deterministic RestartPolicy backoff, ring
cursor recovery (mcache frontier + fseq resume + the replay-dedup
publish guard), in-place restart of a real process stage under induced
SIGKILL with an exactly-once stream diff, and the crash-loop degradation
to the existing fail-fast + flight-dump path (ISSUE 14)."""

import os
import time

import pytest

from firedancer_tpu.runtime import topo as ft
from firedancer_tpu.runtime.restart import RestartPolicy, policy_for
from firedancer_tpu.runtime.stage import Stage
from firedancer_tpu.tango import shm
from firedancer_tpu.utils import metrics as fm


# -- policy determinism -------------------------------------------------------


def test_restart_policy_schedule_deterministic_per_seed():
    a = RestartPolicy(max_restarts=5, backoff_base_s=0.05, seed=7)
    b = RestartPolicy(max_restarts=5, backoff_base_s=0.05, seed=7)
    # byte-identical schedules for identical (seed, stage)
    assert repr(a.schedule("verify")) == repr(b.schedule("verify"))
    assert a.schedule("verify") == b.schedule("verify")
    # different stages / seeds draw different jitter
    assert a.schedule("verify") != a.schedule("pack")
    assert a.schedule("verify") != RestartPolicy(
        max_restarts=5, backoff_base_s=0.05, seed=8).schedule("verify")
    # exponential shape with bounded jitter: attempt k in
    # [base*mult^(k-1), base*mult^(k-1)*(1+jitter_frac))
    for k, d in enumerate(a.schedule("verify"), start=1):
        lo = a.backoff_base_s * a.backoff_mult ** (k - 1)
        assert lo <= d < lo * (1 + a.jitter_frac)
    with pytest.raises(ValueError):
        a.delay_s("verify", 0)


def test_restart_policy_resolution():
    pol = RestartPolicy(max_restarts=1)
    assert policy_for(None, "x") is None
    assert policy_for(pol, "x") is pol
    assert policy_for({"relay": pol}, "relay") is pol
    assert policy_for({"relay": pol}, "sink") is None


# -- ring cursor recovery -----------------------------------------------------


def test_mcache_recover_frontier_chunk_and_sigs():
    uid = shm.fresh_uid("trc")
    link = shm.ShmLink.create(f"fdtpu_rc_{uid}", depth=8, mtu=256)
    try:
        # untouched ring: a resumed producer starts at 0
        assert link.mcache.recover() == (0, 0, set())
        prod = shm.Producer(link)
        cons = shm.Consumer(link, lazy=1)
        for i in range(5):
            assert prod.try_publish(b"x" * 100, sig=1000 + i)
        front, chunk, sigs = link.mcache.recover()
        assert front == 5
        assert sigs == {1000 + i for i in range(5)}
        # the recovered chunk continues AFTER the last frag's payload
        assert chunk == link.dcache._chunk
        # a fresh producer resumed from the ring continues seamlessly
        for _ in range(5):
            cons.poll()
        cons.publish_progress()
        p2 = shm.Producer(link)
        guard = p2.resume()
        assert p2.seq == 5 and guard == sigs
        assert p2.try_publish(b"y" * 100, sig=2000)
        r = cons.poll()
        assert isinstance(r, tuple) and int(r[0][1]) == 2000
    finally:
        link.close()
        link.unlink()


def test_consumer_resume_from_published_fseq():
    uid = shm.fresh_uid("trf")
    link = shm.ShmLink.create(f"fdtpu_rf_{uid}", depth=16, mtu=64)
    try:
        prod = shm.Producer(link)
        cons = shm.Consumer(link, lazy=4)
        for i in range(10):
            prod.try_publish(b"f%02d" % i, sig=i)
        for _ in range(10):
            cons.poll()
        # lazy=4: the fseq trails the cursor; a crashed consumer resumes
        # at the PUBLISHED progress and replays the gap (at-least-once;
        # the stage-level guard makes the wire exactly-once)
        published = cons.fseq.query()
        assert published < cons.seq
        c2 = shm.Consumer(link, lazy=4)
        assert c2.resume() == published
        replayed = []
        while True:
            r = c2.poll()
            if not isinstance(r, tuple):
                break
            replayed.append(int(r[0][1]))
        assert replayed == list(range(published, 10))
    finally:
        link.close()
        link.unlink()


def test_publish_guard_dedups_replay_then_disarms():
    uid = shm.fresh_uid("tpg")
    l_in = shm.ShmLink.create(f"fdtpu_gi_{uid}", depth=32, mtu=64)
    l_out = shm.ShmLink.create(f"fdtpu_go_{uid}", depth=32, mtu=64)

    class Relay(Stage):
        def after_frag(self, in_idx, meta, payload):
            self.publish(0, payload, sig=int(meta[1]))

    try:
        prod = shm.Producer(l_in)
        sink = shm.Consumer(l_out, lazy=1)
        relay = Relay("relay", ins=[shm.Consumer(l_in, lazy=4)],
                      outs=[shm.Producer(l_out)])
        relay.require_credit = True
        for i in range(6):
            prod.try_publish(b"p%02d" % i, sig=i)
        while relay.run_once():
            pass
        relay.ins[0].publish_progress()
        # "crash": a fresh relay resumes against the same rings with its
        # input cursor rolled back 3 frags (the unpublished-fseq window)
        relay.ins[0].fseq.publish(3)
        relay2 = Relay("relay", ins=[shm.Consumer(l_in, lazy=4)],
                       outs=[shm.Producer(l_out)])
        relay2.require_credit = True
        relay2.resume_from_rings()
        assert relay2.ins[0].seq == 3
        assert relay2.outs[0].seq == 6
        for i in range(6, 9):  # new work past the crash point
            prod.try_publish(b"p%02d" % i, sig=i)
        while relay2.run_once():
            pass
        # the wire carries every sig exactly once, in order
        got = []
        while True:
            r = sink.poll()
            if not isinstance(r, tuple):
                break
            got.append(int(r[0][1]))
        assert got == list(range(9))
        assert relay2.metrics.get("restart_dedup") == 3
        # the guard disarmed at the first new sig
        assert not relay2._resume_guards
    finally:
        l_in.close()
        l_in.unlink()
        l_out.close()
        l_out.unlink()


# -- in-place restart of real processes ---------------------------------------


class GenStage(Stage):
    def __init__(self, *args, limit=100, **kwargs):
        super().__init__(*args, **kwargs)
        self.limit = limit
        self._i = 0

    def after_credit(self):
        for _ in range(8):
            if self._i >= self.limit:
                return
            if not self.publish(0, b"frag%06d" % self._i, sig=self._i):
                return
            self._i += 1


class RelayStage(Stage):
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.require_credit = True

    def after_frag(self, in_idx, meta, payload):
        self.publish(0, payload, sig=int(meta[1]))


class SinkStage(Stage):
    pass


class DyingRelayStage(RelayStage):
    """Dies hard on every frag >= crash_at: restartable but hopeless."""

    def __init__(self, *args, crash_at=10, **kwargs):
        super().__init__(*args, **kwargs)
        self.crash_at = crash_at

    def after_frag(self, in_idx, meta, payload):
        if int(meta[1]) >= self.crash_at:
            os._exit(43)
        super().after_frag(in_idx, meta, payload)


def build_gen(links, cnc, limit=100):
    return GenStage("gen", outs=[shm.make_producer(links["gr"])], cnc=cnc,
                    limit=limit)


def build_relay(links, cnc):
    return RelayStage(
        "relay", ins=[shm.make_consumer(links["gr"], lazy=8)],
        outs=[shm.make_producer(links["rs"], reliable_fseq_idx=[0, 1])],
        cnc=cnc)


def build_dying_relay(links, cnc, crash_at=10):
    return DyingRelayStage(
        "relay", ins=[shm.make_consumer(links["gr"], lazy=8)],
        outs=[shm.make_producer(links["rs"], reliable_fseq_idx=[0, 1])],
        cnc=cnc, crash_at=crash_at)


def _restart_topology(n, relay_builder=build_relay, **relay_kw):
    topo = ft.Topology()
    topo.link("gr", depth=256, mtu=64)
    topo.link("rs", depth=256, mtu=64, n_consumers=2)
    topo.stage("gen", build_gen, limit=n, outs=["gr"])
    topo.stage("relay", relay_builder, ins=["gr"], outs=["rs"],
               restartable=True, **relay_kw)
    topo.stage("sink", SinkStageBuilder, ins=["rs"])
    return topo


def SinkStageBuilder(links, cnc):
    return SinkStage("sink", ins=[shm.make_consumer(links["rs"], lazy=8)],
                     cnc=cnc)


def test_in_place_restart_exactly_once_stream_diff():
    """SIGKILL the relay twice mid-stream: the supervisor respawns it in
    place against the SAME rings (no new shm, no topology relaunch) and
    the parent-side observer sees every sig exactly once, in order."""
    N = 3000
    h = ft.launch(_restart_topology(N))
    obs = shm.Consumer(h.links["rs"], fseq_idx=1, lazy=4)
    segs_before = set(h.shm_names())
    got = []
    killed = [0]

    def on_poll(hh):
        while True:
            r = obs.poll()
            if not isinstance(r, tuple):
                break
            got.append(int(r[0][1]))
        if len(got) > 400 and killed[0] == 0:
            killed[0] = 1
            hh.kill_stage("relay")
        elif len(got) > 1500 and killed[0] == 1:
            killed[0] = 2
            hh.kill_stage("relay")

    try:
        ok = h.supervise(
            until=lambda hh: len(got) >= N, timeout_s=90,
            on_poll=on_poll,
            restart=RestartPolicy(max_restarts=3, backoff_base_s=0.03,
                                  seed=11))
        deadline = time.monotonic() + 3
        while time.monotonic() < deadline and len(got) < N:
            r = obs.poll()
            if isinstance(r, tuple):
                got.append(int(r[0][1]))
            else:
                time.sleep(0.005)
        assert ok, f"supervise failed (failed={h.failed!r})"
        assert killed[0] == 2, "both kills must have fired"
        assert h.restarts == {"relay": 2}
        assert h.failed is None and h.flight_dump_path is None
        # THE stream diff: exactly once, in order
        assert got == list(range(N))
        # same rings throughout: no segment was recreated
        assert set(h.shm_names()) == segs_before
        # the respawned child left restart evidence on the flight ring
        rec = h.met_views["relay"][1]
        assert any(r[1] == fm.EV_RESTART for r in rec.records())
        h.halt()
    finally:
        del obs
        h.close()


def test_crash_loop_degrades_to_fail_fast_with_dump():
    """A relay that dies deterministically on the same frag can never be
    saved: the policy's bounded attempts run out and the supervisor
    takes the whole topology down exactly as before — victim named,
    flight dump on disk, segments reclaimed by close()."""
    pol = RestartPolicy(max_restarts=2, backoff_base_s=0.02, seed=3)
    h = ft.launch(_restart_topology(200, build_dying_relay, crash_at=10))
    names = h.shm_names()
    try:
        t0 = time.monotonic()
        ok = h.supervise(until=lambda hh: False, timeout_s=60,
                         restart=pol)
        assert ok is False
        assert h.failed == "relay"
        assert h.restarts == {"relay": 2}  # bounded attempts, then stop
        assert time.monotonic() - t0 < 45
        assert h.flight_dump_path and os.path.exists(h.flight_dump_path)
        assert all(not p.is_alive() for p in h.procs.values())
    finally:
        h.close()
    import glob

    for n in names:
        assert not os.path.exists(f"/dev/shm/{n}"), n


def test_restart_covers_stale_heartbeat_too():
    """A frozen (SIGSTOP) stage trips the heartbeat watchdog; with a
    policy armed the wedged process is reaped and respawned in place
    instead of killing the topology.

    The heartbeat timeout is also a respawn's boot grace
    (_respawn_stage stamps the cnc; the child heartbeats only once it
    runs), so it has to outlast a spawned child's imports — about a
    second on an idle box, more beside five other test workers — or
    each respawn is judged stale in turn and the budget runs out."""
    N = 4000
    h = ft.launch(_restart_topology(N))
    obs = shm.Consumer(h.links["rs"], fseq_idx=1, lazy=4)
    got = []
    froze = [False]

    def on_poll(hh):
        while True:
            r = obs.poll()
            if not isinstance(r, tuple):
                break
            got.append(int(r[0][1]))
        if len(got) > 300 and not froze[0]:
            froze[0] = True
            hh.freeze_stage("relay")

    try:
        ok = h.supervise(
            until=lambda hh: len(got) >= N, timeout_s=90,
            heartbeat_timeout_s=4.0, on_poll=on_poll,
            restart=RestartPolicy(max_restarts=2, backoff_base_s=0.02,
                                  seed=5))
        deadline = time.monotonic() + 3
        while time.monotonic() < deadline and len(got) < N:
            r = obs.poll()
            if isinstance(r, tuple):
                got.append(int(r[0][1]))
            else:
                time.sleep(0.005)
        assert ok, f"supervise failed (failed={h.failed!r})"
        assert froze[0]
        assert set(h.restarts) == {"relay"}  # the frozen stage, no other
        assert got == list(range(N))
        h.halt()
    finally:
        del obs
        h.close()
