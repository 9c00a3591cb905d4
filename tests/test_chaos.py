"""The chaos harness (ISSUE 7): scenario determinism, invariant
checking, fault injection through the real supervisor, the tango lossy
shim, and the teardown hygiene the harness's reclaim invariant rides on.

Tier-1 runs the cheap scenarios at reduced scale; the full catalog at
production scale (1k-client storm, two-slot leader handoff) rides the
slow marker and the CI chaos-smoke job runs the two cheapest end to end
via the CLI."""

import json
import os

import pytest

from firedancer_tpu.chaos import faults as cf
from firedancer_tpu.chaos import invariants as inv
from firedancer_tpu.chaos import scenario as cs
from firedancer_tpu.tango import shm
from firedancer_tpu.utils.rng import Rng


# -- the lossy shim -----------------------------------------------------------


def _mk_link(tag, depth=256, mtu=128):
    return shm.ShmLink.create(
        f"fdtpu_tchaos_{tag}_{os.getpid()}", depth=depth, mtu=mtu)


def test_lossy_consumer_drop_dup_reorder_deterministic():
    from firedancer_tpu.tango.lossy import LossyConsumer

    def run(seed):
        link = _mk_link(f"lossy{seed}")
        try:
            prod = shm.Producer(link)
            cons = LossyConsumer(shm.Consumer(link, lazy=8), Rng(seed, 1),
                                 drop_p=0.2, dup_p=0.15, reorder_p=0.25)
            sent = [b"frag-%03d" % i for i in range(120)]
            got = []
            i = 0
            while True:
                if i < len(sent):
                    prod.try_publish(sent[i], sig=i)
                    i += 1
                r = cons.poll()
                if isinstance(r, tuple):
                    got.append(bytes(r[1]))
                elif i >= len(sent):
                    r2 = cons.poll()  # one more: flush shim-held frags
                    if isinstance(r2, tuple):
                        got.append(bytes(r2[1]))
                    else:
                        break
            return got, cons.dropped, cons.duplicated, cons.reordered
        finally:
            link.close()
            link.unlink()

    got1, d1, u1, r1 = run(5)
    got2, d2, u2, r2 = run(5)
    assert (got1, d1, u1, r1) == (got2, d2, u2, r2)  # seed-replayable
    assert d1 > 0 and u1 > 0 and r1 > 0  # every fault kind fired
    # conservation: delivered + dropped == sent + duplicated
    assert len(got1) + d1 == 120 + u1
    # no corruption, no invention
    assert set(got1) <= {b"frag-%03d" % i for i in range(120)}


# -- invariant machinery ------------------------------------------------------


def test_invariant_suite_and_violation_artifact(tmp_path, monkeypatch):
    suite = inv.InvariantSuite()
    assert suite.check("good", True)
    assert not suite.check("bad", False, "broke")
    assert not suite.ok
    assert [c.name for c in suite.violations()] == ["bad"]
    assert suite.summary() == {"bad": False, "good": True}
    with pytest.raises(inv.InvariantViolation):
        suite.require("worse", False, "very")
    # a violated cooperative scenario captures flight + trace artifacts
    monkeypatch.setenv("FDTPU_RUN_DIR", str(tmp_path))
    import importlib

    from firedancer_tpu.runtime import monitor as mon

    importlib.reload(mon)
    try:
        from firedancer_tpu.runtime.stage import Stage

        st = Stage("lonely")
        result = cs.ScenarioResult("unit", 3, suite)
        cs._capture_coop_failure(result, [st])
        assert len(result.artifacts) == 2
        flight, trace = result.artifacts
        dump = json.load(open(flight))
        assert "lonely" in dump["stages"]
        assert "worse" in dump["reason"] and "bad" in dump["reason"]
        tr = json.load(open(trace))
        assert tr["traceEvents"]
    finally:
        monkeypatch.delenv("FDTPU_RUN_DIR")
        importlib.reload(mon)


def test_payload_digest_order_independent():
    a = [b"x", b"yy", b"zzz"]
    assert inv.payload_digest(a) == inv.payload_digest(list(reversed(a)))
    assert inv.payload_digest(a) != inv.payload_digest(a[:2])


def test_conservation_check_catches_a_leak():
    suite = inv.InvariantSuite()
    report = {
        "benchg": {"txn_gen": 10},
        "verify0": {"txn_verified": 9},  # one txn vanished unexplained
        "dedup": {"dedup_dup": 0},
        "pack": {"txn_in": 9, "txn_scheduled": 9, "microblocks": 2,
                 "microblock_done": 2},
        "bank0": {"txn_exec": 9},
    }
    inv.check_pipeline_conservation(suite, report, 9)
    assert not suite.ok
    assert "verify-accounts-for-generated" in [
        c.name for c in suite.violations()]


# -- scenarios (tier-1 scale) -------------------------------------------------


def test_dedup_flood_scenario_deterministic():
    r1 = cs.run_dedup_flood(seed=11, duration=20)
    assert r1.ok, r1.suite.describe()
    r2 = cs.run_dedup_flood(seed=11, duration=20)
    assert r1.summary() == r2.summary()
    # the fault injection really fired
    assert r1.info["shim_duplicated"] > 0
    assert r1.info["shim_reordered"] > 0


def test_fork_storm_scenario_deterministic_and_seed_sensitive():
    r1 = cs.run_fork_storm(seed=11)
    assert r1.ok, r1.suite.describe()
    assert cs.run_fork_storm(seed=11).summary() == r1.summary()
    r3 = cs.run_fork_storm(seed=12)
    assert r3.ok
    assert r3.summary()["info"] != r1.summary()["info"]


def test_connection_storm_small_scale():
    """Tier-1 slice of the acceptance storm: the full >=1k population
    rides the slow matrix; the machinery (retry gate statelessness,
    budget audit, honest delivery through the gate) is identical."""
    from firedancer_tpu.runtime import net_native

    r = cs.run_connection_storm(seed=11, duration=60, n_clients=48,
                                n_honest=3)
    assert r.ok, r.suite.describe()
    assert r.info["retry_tx"] == r.info["storm"] + r.info["honest"]
    assert r.info["amplification_capped"] is True
    # the native net lane (ISSUE 18): armed whenever the .so builds, and
    # every established honest conn moved onto the fast path
    assert r.info["net_native"] == net_native.available()
    if r.info["net_native"]:
        assert r.info["net_conn_exported"] == r.info["honest"]


@pytest.mark.slow
@pytest.mark.timeout(1200)
def test_connection_storm_10k_native():
    """The ISSUE 18 acceptance storm: 10k concurrent clients against the
    ingress with the native sweep client armed — RetryGate stays
    stateless, the 3x anti-amplification ledger holds from the outside,
    honest txns land exactly once over the native lane, and the
    per-seed summary diffs clean across two full runs."""
    r1 = cs.run_connection_storm(seed=7, duration=600, n_clients=10000,
                                 n_honest=32)
    assert r1.ok, r1.suite.describe()
    checks = r1.summary()["checks"]
    for name in ("retry-per-untokened-initial",
                 "storm-allocates-no-connections",
                 "amplification-budget-held",
                 "honest-txns-delivered-exactly-once"):
        assert checks[name], name
    from firedancer_tpu.runtime import net_native

    assert r1.info["net_native"] == net_native.available()
    if r1.info["net_native"]:
        assert r1.info["net_conn_exported"] == r1.info["honest"]
    r2 = cs.run_connection_storm(seed=7, duration=600, n_clients=10000,
                                 n_honest=32)
    assert r1.summary() == r2.summary()


def test_stage_kill_scenario_and_restart():
    """ISSUE 7 satellite: kill one stage mid-run -> the topology fails
    fast naming the victim, the flight dump exists as the failure
    artifact, every /dev/shm segment is reclaimed after close(), and a
    restart runs clean."""
    r = cs.run_stage_kill(seed=11, duration=30)
    assert r.ok, r.suite.describe()
    checks = r.summary()["checks"]
    for name in ("supervisor-fails-fast", "victim-identified",
                 "flight-dump-written", "shm-reclaimed",
                 "restart-runs-clean", "restart-shm-reclaimed",
                 "shm-registry-conservation"):
        assert checks[name], name
    # the dump + trace landed as artifacts
    assert any(a.endswith("_trace.json") for a in r.artifacts)
    for a in r.artifacts:
        if "flight" in os.path.basename(a):
            os.remove(a)  # dumps outlive runs by design; tidy the host


def test_freeze_fault_detected_by_stale_heartbeat():
    """The wedge fault: SIGSTOP keeps the process alive but silences its
    cnc heartbeat — the supervisor must kill the topology on staleness,
    and close() must still reclaim every segment (the SIGCONT-before-
    terminate path)."""
    from firedancer_tpu.runtime import topo as ft

    h = ft.launch(cs._kill_topology(limit=1_000_000))
    names = h.shm_names()
    try:
        assert cs._wait_registry(h, "sink", "frags_in", 32, timeout_s=30)
        inj = cf.FaultInjector([cf.FreezeStage("relay", at_s=0.05)]).arm()
        ok = h.supervise(until=lambda hh: False, timeout_s=30,
                         heartbeat_timeout_s=1.0, on_poll=inj)
        assert ok is False
        assert h.failed == "relay"
        assert inj.all_fired()
        assert h.flight_dump_path and os.path.exists(h.flight_dump_path)
        os.remove(h.flight_dump_path)
    finally:
        h.close()
    suite = inv.InvariantSuite()
    inv.check_shm_reclaimed(suite, names)
    assert suite.ok, suite.describe()


# -- the CLI ------------------------------------------------------------------


def test_chaos_cli_run_is_deterministic(capsys):
    from firedancer_tpu.__main__ import main

    rc1 = main(["chaos", "run", "dedup-flood", "--seed", "7",
                "--duration", "20"])
    out1 = capsys.readouterr().out
    rc2 = main(["chaos", "run", "dedup-flood", "--seed", "7",
                "--duration", "20"])
    out2 = capsys.readouterr().out
    assert rc1 == rc2 == 0
    assert out1 == out2  # the replay contract, at the CLI surface
    doc = json.loads(out1)
    assert doc["scenario"] == "dedup-flood" and doc["ok"] is True
    # the summary artifact landed at the deterministic path
    assert os.path.exists(os.path.join(
        cs._run_dir(), "fdtpu_chaos_dedup-flood_s7.json"))


def test_chaos_cli_list_and_unknown(capsys):
    from firedancer_tpu.__main__ import main

    assert main(["chaos", "list"]) == 0
    out = capsys.readouterr().out
    for name in cs.SCENARIOS:
        assert name in out
    assert main(["chaos", "run", "no-such-scenario"]) == 2


# -- teardown hygiene (ISSUE 7 satellite: the BENCH-tail fix) -----------------


def test_pipeline_close_drops_every_shm_view():
    """LeaderPipeline.close() must leave every link's SharedMemory fully
    closed (fd gone, buffer released): a pinned view here is exactly the
    'BufferError: cannot close exported pointers exist' spray at
    interpreter exit."""
    from firedancer_tpu.models.leader import build_leader_pipeline

    pipe = build_leader_pipeline(n_verify=1, n_bank=1, pool_size=4,
                                 gen_limit=4, batch=8, max_msg_len=256)
    pipe.close()
    for link in pipe.links:
        assert link._shm._buf is None
        assert getattr(link._shm, "_fd", -1) == -1


def test_shmlink_close_survives_external_view(tmp_path):
    """An external attacher still holding a view must not be able to
    turn close() into exit noise: the wrapper detaches so its __del__
    is a no-op, and unlink still reclaims the name."""
    link = _mk_link("extview")
    external = shm.Consumer(link, lazy=8)  # pins fseq views
    name = link._shm.name
    link.close()
    link.unlink()
    assert not os.path.exists(os.path.join("/dev/shm", name))
    # the wrapper can no longer raise from __del__
    assert link._shm._mmap is None or link._shm._buf is None
    del external


# -- the full catalog (production scale) --------------------------------------


@pytest.mark.slow
@pytest.mark.timeout(1200)
@pytest.mark.parametrize("name", sorted(cs.SCENARIOS))
def test_scenario_matrix_full_scale(name):
    """Every named scenario at its production defaults — including the
    >=1k-client connection storm (the acceptance bar) and the two-slot
    leader handoff with its XLA compiles."""
    r = cs.run_scenario(name, seed=7)
    assert r.ok, f"{name}:\n{r.suite.describe()}"


# -- slot-clock plane scenarios (ISSUE 14) ------------------------------------


def test_crash_mid_slot_scenario():
    """In-place restart under the slot clock: two SIGKILLs mid-slot are
    absorbed by the restart policy (exactly-once stream diff across
    both), the slot clock never misses a beat, and the crash-loop flank
    degrades to the fail-fast + flight-dump path within the bounded
    attempts — the ISSUE 14 acceptance pair in one scenario run."""
    r = cs.run_crash_mid_slot(seed=11, n_frags=2000, n_slots=4,
                              slot_ms=250.0, boot_grace_s=4.0)
    assert r.ok, r.suite.describe()
    checks = r.summary()["checks"]
    for name in ("both-kills-fired", "kills-landed-mid-stream",
                 "relay-restarted-in-place", "exactly-once-no-loss",
                 "exactly-once-no-dup", "stream-order-preserved",
                 "crash-cost-no-slots", "crash-loop-fails-fast",
                 "crash-loop-attempts-bounded",
                 "crash-loop-flight-dump-written", "shm-reclaimed",
                 "crash-loop-shm-reclaimed"):
        assert checks[name], name
    assert r.info["restarts"] == 2
    # the backoff schedule in the summary is the POLICY's deterministic
    # one: reproducible from (seed, stage) alone
    from firedancer_tpu.runtime.restart import RestartPolicy

    pol = RestartPolicy(max_restarts=3, backoff_base_s=0.03, seed=11)
    assert r.info["restart_schedule_ms"] == [
        round(d * 1e3, 3) for d in pol.schedule("relay")]


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_slot_overrun_scenario_deterministic():
    """The full leader topology on the wall clock, twice with one seed:
    identical summaries (the chaos determinism contract), the frozen
    boundaries always exactly two missed slots."""
    a = cs.run_slot_overrun(seed=7)
    assert a.ok, a.suite.describe()
    b = cs.run_slot_overrun(seed=7)
    assert a.summary() == b.summary()
    assert a.info["missed"] == 2
