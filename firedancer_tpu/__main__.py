"""fdctl-style CLI: `python -m firedancer_tpu <action>`.

Mirrors the reference's action table (/root/reference/src/app/fdctl/
main1.c: run / monitor / keys / configure / version).  Measuring is not an
action here: the benchmark is `python3 benchmarks/run.py` (BENCHMARK.json).

    run        build the leader pipeline from a TOML config and drive it
               (--processes: one supervised OS process per stage;
               --sandbox: seccomp jail each stage); monitor table on exit.
               [layout] replay_stage_count = 1 is the follower's verify
               phase instead (config/replay-verify-v5e.toml)
    monitor    live per-stage TUI attached to a running topology
    ready      block until every stage of a running topology is RUN
    metrics    Prometheus scrape surface over a running topology's shm
               metric segments (--once prints; --serve binds the
               metric-tile HTTP endpoint), from an uninvolved process
    trace      flight-recorder rings -> Chrome trace-event JSON (open
               the output in Perfetto / chrome://tracing)
    chaos      the scenario harness: adversarial load + fault injection
               + invariant checking over the full validator loop
               (`chaos list`; `chaos run <scenario> --seed S`)
    configure  host setup stages: check | init (shm, fds, cpus, THP...)
    keys       new <path> | pubkey <path> — identity keypair management
    warmup     compile the program a verify stage of this geometry
               dispatches (--batch, --max-msg-len, --devices) through
               the persistent compile cache, before a leader slot
    genesis    create | show a genesis blob (+ faucet key)
    snapshot   inspect a snapshot archive
    ledger     show | ingest | replay a stored ledger (bank-hash checks)
    backtest   replay a consensus scenario through ghost/tower
    config     print the effective layered configuration
    version    print the framework version

Every action takes --config <file.toml> where relevant (layered over the
embedded defaults, utils/config.py).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

__version__ = "0.7.0"

# `run` streams --txns transfers over this many funded payers: pack puts at
# most one transaction per payer into a microblock, and at the configured
# device batch a stream over the generator's default 8 drains slower than
# verify feeds it, so pack's pool overflows and sheds (fd_benchg rotates a
# bounded funded account set the same way)
RUN_PAYERS = 64


def _load_cfg(args):
    from firedancer_tpu.utils.config import load_config

    return load_config(args.config)


def cmd_run(args) -> int:
    cfg = _load_cfg(args)
    if cfg.layout.replay_stage_count:
        return _run_replay(args, cfg)
    if args.processes:
        # the launching parent never initialises a backend: the verify
        # child owns the chip (models/leader_topo.build_verify)
        return _run_processes(args)
    from firedancer_tpu.utils.platform import select_device

    platform, kind, count = select_device(args.cpu)
    from firedancer_tpu.models.leader import build_leader_pipeline_from_config

    cfg = _load_cfg(args)
    pipe = build_leader_pipeline_from_config(
        cfg, pool_size=args.txns, gen_limit=args.txns, n_payers=RUN_PAYERS
    )
    rpc_srv = None
    try:  # the pipeline must close even if the RPC bind fails (EADDRINUSE)
        if args.rpc_port is not None:
            from firedancer_tpu.runtime.rpc import PipelineView, RpcServer

            rpc_srv = RpcServer(
                PipelineView(pipeline=pipe), port=args.rpc_port
            )
            print(f"# rpc listening on {rpc_srv.addr}", file=sys.stderr)
        print(f"# leader pipeline on {platform}:{kind} x{count}: "
              f"{len(pipe.verifies)} verify (batch {cfg.verify.batch}), "
              f"{len(pipe.banks)} bank stages; {args.txns} txns",
              file=sys.stderr)
        # compile (or load) before the clock starts: set-up, not rate
        warm_s = sum(v.warmup() for v in pipe.verifies)
        print(f"# verify program ready in {warm_s:.1f}s", file=sys.stderr)
        t0 = time.time()
        pipe.run(until_txns=args.txns, max_iters=2_000_000)
        dt = time.time() - t0
        executed = sum(b.metrics.get("txn_exec") for b in pipe.banks)
        print(f"{'stage':<10}{'in':>10}{'out':>10}{'extra':>30}")
        for s in pipe.stages:
            m = s.metrics
            extra = ""
            if s is pipe.pack:
                extra = f"microblocks={m.get('microblocks')}"
            if s is pipe.shred:
                extra = f"fec_sets={m.get('fec_sets')}"
            print(f"{s.name:<10}{m.get('frags_in'):>10}{m.get('frags_out'):>10}"
                  f"{extra:>30}")
        print(f"# {executed} txns committed in {dt:.2f}s "
              f"({executed / dt:.0f} txn/s)")
        return 0 if executed == args.txns else 1
    finally:
        if rpc_srv is not None:
            rpc_srv.close()
        pipe.close()


def _run_processes(args) -> int:
    """The fdctl-run model: every stage its own supervised OS process
    over shm links, optional per-stage jail, monitor table at exit.
    Done means what it means on the cooperative path: the bank executed
    every generated transaction."""
    from firedancer_tpu.models.leader_topo import (
        build_leader_topology_from_config,
    )
    from firedancer_tpu.runtime import topo as ft

    cfg = _load_cfg(args)
    if cfg.layout.benchs_stage_count > 0:
        return _run_front(args, cfg)
    n_bank = cfg.layout.bank_stage_count
    if n_bank > 1 and not cfg.development.bench.disable_status_cache:
        # a status cache a bank process would let a repeat land once a
        # tile (leader_topo.build_leader_topology refuses it)
        print(f"# the process topology runs 1 bank stage (config asks "
              f"{n_bank}): more take [development.bench] "
              f"disable_status_cache", file=sys.stderr)
        n_bank = 1
    sandbox = {"rlimits": {"nofile": 512}} if args.sandbox else None
    # the config's batch, widths, deadline, ring depths, pack rule and
    # slot cadence; the generator's payers are the bank's genesis
    topo = build_leader_topology_from_config(
        cfg, n_bank=n_bank, n_txns=args.txns, pool_size=args.txns,
        verify_cpu=args.cpu, n_payers=RUN_PAYERS, sandbox=sandbox,
        boot_grace_s=5.0 if cfg.poh.slot_ms > 0 else 0.0,
    )
    h = ft.launch(topo)
    try:
        print(f"# {len(h.procs)} stage processes; descriptor "
              f"fdtpu_run_{h.uid}.json"
              + (" (sandboxed)" if sandbox else ""), file=sys.stderr)
        def executed() -> int:
            # the bank tiles commit into one account store
            return sum(h.met_views[f"bank{b}"][0].get("txn_exec")
                       for b in range(n_bank))

        t0 = time.time()
        ok = h.supervise(
            until=lambda h: executed() >= args.txns,
            # the verify child compiles (or loads) its program in its
            # builder, before its first heartbeat: boot is bounded by
            # timeout_s, a wedged running stage by the heartbeat
            timeout_s=1200,
            heartbeat_timeout_s=300,
        )
        dt = time.time() - t0
        print(h.format_monitor())
        n_exec = executed()
        print(f"# {n_exec} txns committed in {dt:.2f}s (boot and "
              f"compile included)")
        h.halt()
        return 0 if ok and n_exec == args.txns else 1
    finally:
        h.close()


def _run_replay(args, cfg) -> int:
    """The follower's verify phase (layout.replay_stage_count = 1):
    replaysrc -> verify0 -> replayout over entry batches, from
    build_replay_topology_from_config — a process a tile with
    --processes, else the same topology's stages held on this process's
    one thread (the cooperative form).  --txns is rounded up to whole
    slots of replay.slot_txns.  Done: every slot has its verdict; exit
    0 when the slots offered with a flipped signature bit, and no
    others, are dead."""
    from firedancer_tpu.models.leader_topo import (
        build_replay_topology_from_config,
    )
    from firedancer_tpu.runtime import topo as ft

    r = cfg.replay
    n_slots = max(1, -(-args.txns // r.slot_txns))
    n_dead = n_slots // r.dead_one_in_slots if r.dead_one_in_slots else 0
    sandbox = {"rlimits": {"nofile": 512}} if args.sandbox else None
    topo = build_replay_topology_from_config(
        cfg, n_slots=n_slots, pool_size=min(args.txns, 4096),
        n_payers=RUN_PAYERS, verify_cpu=args.cpu, sandbox=sandbox)
    names = [s.name for s in topo.stages]
    h = ft.launch(topo, held=() if args.processes else tuple(names))
    stages: list = []
    try:
        print(f"# replay verify: {n_slots} slots of {r.slot_txns} txns, "
              f"device batch {cfg.verify.batch}; "
              + (f"{len(h.procs)} stage processes" if args.processes
                 else "one thread") + f"; descriptor fdtpu_run_{h.uid}.json",
              file=sys.stderr)
        v = h.met_views["verify0"][0]

        def verdicts() -> int:
            return sum(v.get(k) for k in ("slots_live", "slots_dead_sig",
                                          "slots_dead_poh",
                                          "slots_dead_parse"))

        t0 = time.time()
        if args.processes:
            ok = h.supervise(until=lambda h: verdicts() >= n_slots,
                             timeout_s=1200, heartbeat_timeout_s=300)
        else:
            stages += [h.build_held(n) for n in names]
            t_end = time.monotonic() + 1200
            while verdicts() < n_slots and time.monotonic() < t_end:
                for _ in range(64):
                    for s in stages:
                        s.run_once()
                for s in stages:
                    s.sync_counters()
            ok = verdicts() >= n_slots
        dt = time.time() - t0
        print(h.format_monitor())
        live, dead = v.get("slots_live"), v.get("slots_dead_sig")
        print(f"# {live} slots live, {dead} dead by a signature, "
              f"{v.get('entry_txn_out')} txns verified and handed on in "
              f"{dt:.2f}s (boot and compile included)")
        h.halt()
        return 0 if ok and live == n_slots - n_dead and dead == n_dead else 1
    finally:
        for s in stages:        # held stages drop their views first
            s.ins, s.outs = [], []
            s.drop_native_views()
        h.close()


def _run_front(args, cfg) -> int:
    """The front door (layout.benchs_stage_count >= 1): benchg ->
    benchs x S -> loopback UDP/QUIC -> quic -> verify -> out, a process
    a tile.  Done: every generated transaction left the verify tile."""
    from firedancer_tpu.models.leader_topo import (
        build_quic_topology_from_config,
    )
    from firedancer_tpu.runtime import topo as ft

    sandbox = {"rlimits": {"nofile": 512}} if args.sandbox else None
    topo = build_quic_topology_from_config(
        cfg, n_txns=args.txns, pool_size=args.txns, n_payers=RUN_PAYERS,
        verify_cpu=args.cpu, sandbox=sandbox)
    h = ft.launch(topo)
    try:
        print(f"# {len(h.procs)} stage processes; descriptor "
              f"fdtpu_run_{h.uid}.json"
              + (" (sandboxed)" if sandbox else ""), file=sys.stderr)

        def out() -> int:
            return (h.met_views["out"][0].get("frags_in")
                    + h.met_views["verify0"][0].get("verify_fail"))

        t0 = time.time()
        ok = h.supervise(until=lambda h: out() >= args.txns,
                         timeout_s=1200, heartbeat_timeout_s=300)
        dt = time.time() - t0
        print(h.format_monitor())
        n_out = out()
        print(f"# {n_out} txns through quic and verify in {dt:.2f}s (boot "
              f"and compile included)")
        h.halt()
        return 0 if ok and n_out == args.txns else 1
    finally:
        h.close()


def cmd_keys(args) -> int:
    from firedancer_tpu.ops.ref import ed25519_ref as ref
    from firedancer_tpu.protocol.base58 import b58_encode

    if args.action == "new":
        secret = os.urandom(32)
        with open(args.path, "wb") as f:
            os.fchmod(f.fileno(), 0o600)
            f.write(secret)
        print(f"wrote identity key to {args.path}")
        print(f"pubkey: {b58_encode(ref.public_key(secret))}")
        return 0
    secret = open(args.path, "rb").read()
    if len(secret) != 32:
        print("malformed key file", file=sys.stderr)
        return 1
    print(b58_encode(ref.public_key(secret)))
    return 0


def cmd_warmup(args) -> int:
    """Compile what a deployment dispatches — the verify stage's one
    program at (--batch, --max-msg-len), placed over --devices chips as
    `[verify] devices` places it — through the persistent compile cache
    (utils/platform.enable_compile_cache): the leader's boot-time
    obligation, run BEFORE a slot, so traffic never waits on XLA.  It
    builds no stage and no rings: it calls what VerifyStage.warmup()
    calls (runtime/verify.warm_program).  Second runs load from the
    cache in seconds — pass --assert-warm S to fail (exit 2) when the
    compile/load took longer, which is how CI proves the cache-hit path
    works."""
    from firedancer_tpu.utils import platform as fp

    platform, _, _ = fp.select_device(args.cpu,
                                      device_count=max(args.devices, 8))
    from firedancer_tpu.ops import sigverify as sv
    from firedancer_tpu.runtime import verify as fv

    try:
        sharding = fv.mesh_row_sharding(args.batch, args.devices)
    except ValueError as e:
        print(f"warmup: {e}", file=sys.stderr)
        return 1
    compile_s = fv.warm_program(args.batch, args.max_msg_len, sharding)
    print(json.dumps({
        "program": sv.ed25519_verify_batch_fused.__name__,
        "devices": args.devices,
        "platform": platform,
        "batch": args.batch,
        "max_msg_len": args.max_msg_len,
        "compile_s": round(compile_s, 2),
        "cache_dir": fp.compile_cache_dir(),
    }))
    if args.assert_warm is not None and compile_s > args.assert_warm:
        print(f"warmup: compile/load took {compile_s:.1f}s "
              f"> --assert-warm {args.assert_warm}s (cache miss?)",
              file=sys.stderr)
        return 2
    return 0


def cmd_genesis(args) -> int:
    """fddev dev's bootstrap half: create genesis (+ faucet key) or
    inspect an existing blob."""
    from firedancer_tpu.flamenco import genesis as fg
    from firedancer_tpu.ops.ref import ed25519_ref as ref

    if args.action == "create":
        import os
        import secrets

        faucet_secret = secrets.token_bytes(32)
        blob = fg.genesis_create(
            faucet_pubkey=ref.public_key(faucet_secret),
            faucet_lamports=args.lamports,
        )
        # secret written only after the blob builds, owner-read-only
        # (the cmd_keys discipline: a faucet key is a signing key)
        with open(args.path + ".faucet", "wb") as f:
            os.fchmod(f.fileno(), 0o600)
            f.write(faucet_secret)
        with open(args.path, "wb") as f:
            f.write(blob)
        print(f"genesis {args.path} hash={fg.genesis_hash(blob).hex()} "
              f"faucet-key={args.path}.faucet")
        return 0
    blob = open(args.path, "rb").read()
    g = fg.genesis_parse(blob)
    print(f"hash:            {fg.genesis_hash(blob).hex()}")
    print(f"hashes_per_tick: {g.hashes_per_tick}")
    print(f"ticks_per_slot:  {g.ticks_per_slot}")
    print(f"slots_per_epoch: {g.slots_per_epoch}")
    print(f"accounts:        {len(g.accounts)}")
    return 0


def cmd_snapshot(args) -> int:
    """Snapshot inspection (the operator-facing face of
    flamenco/snapshot.py; creation happens via the runtime).  Falls back
    to the REAL Agave manifest dialect when the archive is a genuine
    cluster snapshot."""
    from firedancer_tpu.flamenco import snapshot as snap
    from firedancer_tpu.flamenco.types import CodecError

    try:
        man, accounts = snap.snapshot_read(args.path)
    except (snap.SnapshotError, CodecError) as internal_err:
        # not the internal dialect -> try the real Agave manifest; if
        # that fails too, surface BOTH causes, not a misleading second
        # error alone
        try:
            funk, m, summary = snap.agave_snapshot_load(args.path)
        except Exception as agave_err:
            raise SystemExit(
                f"not an internal-dialect archive ({internal_err}) and "
                f"not an Agave archive ({agave_err})"
            )
        print(f"dialect:   agave")
        print(f"slot:      {summary['slot']} (epoch {summary['epoch']})")
        print(f"bank hash: {summary['bank_hash'].hex()}")
        print(f"accounts:  {summary['accounts']}")
        print(f"cap:       {summary['capitalization']}")
        print(f"votes:     {summary['vote_accounts']} vote accounts, "
              f"{summary['stake_delegations']} delegations")
        return 0
    kind = f"incremental (base slot {man.base_slot})" if man.base_slot else "full"
    print(f"slot:      {man.slot} ({kind})")
    print(f"bank hash: {man.bank_hash.hex()}")
    print(f"accounts:  {man.account_cnt}")
    if man.deleted:
        print(f"deletions: {len(man.deleted)}")
    from firedancer_tpu.flamenco.executor import acct_decode

    total = sum(acct_decode(v)[0] for v in accounts.values())
    print(f"lamports:  {total}")
    return 0


def cmd_config(args) -> int:
    import dataclasses

    cfg = _load_cfg(args)

    def dump(obj, indent=""):
        for f in dataclasses.fields(obj):
            v = getattr(obj, f.name)
            if dataclasses.is_dataclass(v):
                print(f"{indent}[{f.name}]")
                dump(v, indent)
            else:
                print(f"{indent}{f.name} = {v!r}")

    dump(cfg)
    return 0


def cmd_monitor(args) -> int:
    """fdctl monitor parity: attach to a live run's cnc regions and
    redraw per-stage rates in place (runtime/monitor.py)."""
    from firedancer_tpu.runtime.monitor import MonitorSession

    try:
        ses = MonitorSession.attach(args.descriptor)
    except (RuntimeError, OSError) as e:
        print(f"monitor: {e}", file=sys.stderr)
        return 1
    try:
        ses.run(interval_s=args.interval, iterations=args.iterations)
    finally:
        ses.close()
    return 0


def cmd_metrics(args) -> int:
    """The metric-tile position (fd_metric.c): attach to a live run's
    shm metric segments READ-ONLY and serve/print the Prometheus text
    exposition — a process the topology never knows about."""
    from firedancer_tpu.runtime.monitor import MonitorSession

    try:
        ses = MonitorSession.attach(args.descriptor)
    except (RuntimeError, OSError) as e:
        print(f"metrics: {e}", file=sys.stderr)
        return 1
    try:
        if not ses.registries():
            print("metrics: run exposes no metrics segments "
                  "(pre-metrics descriptor?)", file=sys.stderr)
            return 1
        if args.once:
            sys.stdout.write(ses.scrape())
            return 0
        from firedancer_tpu.utils.metrics import MetricsServer

        def resolve():
            # re-resolve the registry set on every scrape: if the run
            # behind the descriptor was replaced (or a metrics segment
            # joined late) the server must not keep exposing a stale
            # boot-time snapshot of counters
            ses.refresh()
            return ses.registries(), ses.shard_labels()

        srv = MetricsServer(ses.registries(), port=args.serve,
                            labels=ses.shard_labels(), resolver=resolve)
        try:
            host, port = srv.addr
            print(f"# serving /metrics on http://{host}:{port}/ (^C exits)",
                  file=sys.stderr)
            try:
                while True:
                    time.sleep(1.0)
            except KeyboardInterrupt:
                pass
        finally:
            srv.close()
        return 0
    finally:
        ses.close()


def cmd_trace(args) -> int:
    """Export flight-recorder rings as Chrome trace-event JSON: from a
    crash dump (--dump, written by the supervisor on any stage FAIL) or
    live from the newest running topology."""
    from firedancer_tpu.runtime import monitor as mon
    from firedancer_tpu.utils.metrics import flight_to_chrome_trace

    try:
        if args.dump is not None:
            with open(args.dump) as f:
                dump = json.load(f)
        elif args.descriptor is not None or mon.list_runs():
            from firedancer_tpu.runtime.monitor import MonitorSession

            ses = MonitorSession.attach(args.descriptor)
            try:
                dump = ses.flight_dump()
            finally:
                ses.close()
        else:
            dumps = mon.list_flight_dumps()
            if not dumps:
                print("trace: no live run and no flight dumps found",
                      file=sys.stderr)
                return 1
            print(f"# using newest flight dump {dumps[0]}", file=sys.stderr)
            with open(dumps[0]) as f:
                dump = json.load(f)
    except (RuntimeError, OSError, json.JSONDecodeError) as e:
        print(f"trace: {e}", file=sys.stderr)
        return 1
    trace = flight_to_chrome_trace(dump)
    with open(args.out, "w") as f:
        json.dump(trace, f)
    n = len(trace["traceEvents"])
    print(f"# wrote {n} trace events to {args.out}", file=sys.stderr)
    return 0


def cmd_slotreport(args) -> int:
    """Per-slot structured report over the native observability plane
    (runtime/slot_report.py): live session, post-mortem flight dump(s),
    or an in-process cluster run."""
    from firedancer_tpu.runtime import monitor as mon
    from firedancer_tpu.runtime import slot_report as sr

    try:
        if args.cluster:
            rep = sr.run_cluster_report(args.cluster, slots=args.slots,
                                        seed=args.seed)
        elif args.dump:
            reports = []
            for path in args.dump:
                with open(path) as f:
                    reports.append(sr.build_report(json.load(f)))
            rep = reports[0] if len(reports) == 1 \
                else sr.aggregate_reports(reports)
        elif args.descriptor is not None or mon.list_runs():
            from firedancer_tpu.runtime.monitor import MonitorSession

            ses = MonitorSession.attach(args.descriptor)
            try:
                rep = sr.report_from_session(ses)
            finally:
                ses.close()
        else:
            dumps = mon.list_flight_dumps()
            if not dumps:
                print("slotreport: no live run and no flight dumps found",
                      file=sys.stderr)
                return 1
            print(f"# using newest flight dump {dumps[0]}", file=sys.stderr)
            with open(dumps[0]) as f:
                rep = sr.build_report(json.load(f))
    except (RuntimeError, OSError, json.JSONDecodeError) as e:
        print(f"slotreport: {e}", file=sys.stderr)
        return 1
    if args.normalize:
        rep = sr.normalize(rep)
    text = sr.dumps(rep)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        print(f"# wrote slot report to {args.out}", file=sys.stderr)
    else:
        sys.stdout.write(text)
    return 0


def cmd_ready(args) -> int:
    """fdctl ready parity: exit 0 once every stage is RUN, 1 on timeout
    or failure."""
    from firedancer_tpu.runtime.monitor import MonitorSession

    try:
        ses = MonitorSession.attach(args.descriptor)
    except (RuntimeError, OSError) as e:
        print(f"ready: {e}", file=sys.stderr)
        return 1
    try:
        ok = ses.wait_ready(timeout_s=args.timeout)
    finally:
        ses.close()
    print("ready" if ok else "not ready")
    return 0 if ok else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="firedancer_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    runp = sub.add_parser("run", help="drive the leader pipeline")
    runp.add_argument("--config", default=None)
    runp.add_argument("--txns", type=int, default=256)
    runp.add_argument(
        "--cpu", action="store_true",
        help="run on the CPU backend (default: the TPU, and fail without one)",
    )
    runp.add_argument(
        "--rpc-port", type=int, default=None,
        help="serve JSON-RPC (getTransactionCount/getSlot/...) during the run",
    )
    runp.add_argument(
        "--processes", action="store_true",
        help="run every stage as its own supervised OS process "
             "(the fdctl run model); the verify child owns the chip, "
             "every other child is pinned to the CPU",
    )
    runp.add_argument(
        "--sandbox", action="store_true",
        help="with --processes: jail each stage (seccomp deny of "
             "spawn/exec/priv syscalls + rlimits)",
    )

    keysp = sub.add_parser("keys", help="identity keypair management")
    keysp.add_argument("action", choices=["new", "pubkey"])
    keysp.add_argument("path")

    wup = sub.add_parser(
        "warmup",
        help="compile the verify stage's program (persistent cache)",
    )
    wup.add_argument("--devices", type=int, default=1,
                     help="chips behind the stage ([verify] devices)")
    wup.add_argument("--batch", type=int, default=256,
                     help="lanes a batch ([verify] batch)")
    wup.add_argument("--max-msg-len", type=int, default=256)
    wup.add_argument("--cpu", action="store_true",
                     help="compile for (virtual) CPU devices (default: "
                          "require the TPU)")
    wup.add_argument("--assert-warm", type=float, default=None, metavar="S",
                     help="exit 2 unless compile/load finished within S "
                          "seconds (the CI cache-hit proof)")

    cfgp = sub.add_parser("config", help="print effective configuration")
    cfgp.add_argument("--config", default=None)

    genp = sub.add_parser("genesis", help="create/inspect a genesis blob")
    genp.add_argument("action", choices=["create", "show"])
    genp.add_argument("path")
    genp.add_argument("--lamports", type=int, default=500_000_000_000_000)

    snapp = sub.add_parser("snapshot", help="inspect a snapshot archive")
    snapp.add_argument("path")

    cfgst = sub.add_parser(
        "configure", help="host setup stages: check or apply"
    )
    cfgst.add_argument("action", choices=["check", "init"])
    cfgst.add_argument("--config", default=None)

    btp = sub.add_parser(
        "backtest", help="replay a consensus scenario through ghost/tower"
    )
    btp.add_argument("--scenario", default=None,
                     help="scenario JSON (default: synthetic partition)")
    btp.add_argument("--seed", default=None)
    btp.add_argument("--total-stake", type=int, default=None)

    monp = sub.add_parser(
        "monitor", help="live per-stage TUI of a running topology"
    )
    monp.add_argument("--descriptor", default=None,
                      help="run descriptor path (default: newest live run)")
    monp.add_argument("--interval", type=float, default=1.0)
    monp.add_argument("--iterations", type=int, default=None,
                      help="sample count (default: until ^C)")

    readyp = sub.add_parser(
        "ready", help="block until every stage heartbeats in RUN"
    )
    readyp.add_argument("--descriptor", default=None)
    readyp.add_argument("--timeout", type=float, default=60.0)

    metp = sub.add_parser(
        "metrics", help="Prometheus scrape surface over a running topology"
    )
    metp.add_argument("--descriptor", default=None,
                      help="run descriptor path (default: newest live run)")
    g = metp.add_mutually_exclusive_group()
    g.add_argument("--once", action="store_true",
                   help="print one text-exposition snapshot and exit")
    g.add_argument("--serve", type=int, default=0, metavar="PORT",
                   help="serve /metrics over HTTP (0 = ephemeral port)")

    trcp = sub.add_parser(
        "trace", help="flight recorder -> Chrome trace JSON (Perfetto)"
    )
    trcp.add_argument("--out", default="trace.json")
    trcp.add_argument("--dump", default=None,
                      help="a flight dump written by the supervisor on FAIL"
                           " (default: live run, else newest dump)")
    trcp.add_argument("--descriptor", default=None,
                      help="run descriptor to snapshot live (optional)")

    srp = sub.add_parser(
        "slotreport",
        help="per-slot JSON report: seal/miss, sweep-phase p50/p99,"
             " native-vs-punt, funk writes, restarts",
    )
    srp.add_argument("--descriptor", default=None,
                     help="run descriptor to snapshot live (optional)")
    srp.add_argument("--dump", nargs="+", default=None, metavar="DUMP",
                     help="flight dump file(s); several -> aggregated"
                          " multi-node report")
    srp.add_argument("--cluster", type=int, default=0, metavar="N",
                     help="boot an N-validator in-process cluster and"
                          " report it (chaos/cluster.py)")
    srp.add_argument("--slots", type=int, default=6,
                     help="cluster mode: slots to run")
    srp.add_argument("--seed", type=int, default=7,
                     help="cluster mode: harness seed (same seed ->"
                          " byte-identical report)")
    srp.add_argument("--out", default=None,
                     help="write JSON here (default: stdout)")
    srp.add_argument("--normalize", action="store_true",
                     help="strip timing-dependent fields (CI determinism"
                          " diffs)")

    chp = sub.add_parser(
        "chaos",
        help="scenario harness: adversarial load + faults + invariants",
    )
    chp.add_argument("action", choices=["run", "list"])
    chp.add_argument("scenario", nargs="?", default=None,
                     help="scenario name (see `chaos list`)")
    chp.add_argument("--seed", type=int, default=0,
                     help="run seed; identical seeds -> identical "
                          "invariant summaries (the replay contract)")
    chp.add_argument("--duration", type=float, default=None,
                     help="wall-clock budget in seconds (scenario default"
                          " if omitted)")
    chp.add_argument("--clients", type=int, default=None,
                     help="connection-storm population size")

    ledp = sub.add_parser("ledger", help="ingest/inspect/replay a ledger")
    ledp.add_argument("action", choices=["show", "ingest", "replay"])
    ledp.add_argument("store", help="blockstore directory")
    ledp.add_argument("capture", nargs="?", default=None,
                      help="shredcap/pcap for ingest")
    ledp.add_argument("--funk-dir", default=None)
    ledp.add_argument("--poh-seed", default=None, help="hex 32B")
    ledp.add_argument("--record", default=None,
                      help="write per-slot bank hashes to this JSON")
    ledp.add_argument("--check", default=None,
                      help="diff bank hashes against this JSON")

    sub.add_parser("version", help="print version")

    args = p.parse_args(argv)
    from firedancer_tpu.utils.platform import NoChipError

    try:
        return _dispatch(args)
    except NoChipError as e:
        print(f"firedancer_tpu {args.cmd}: {e}", file=sys.stderr)
        return 1


def _dispatch(args) -> int:
    if args.cmd == "run":
        return cmd_run(args)
    if args.cmd == "keys":
        return cmd_keys(args)
    if args.cmd == "warmup":
        return cmd_warmup(args)
    if args.cmd == "config":
        return cmd_config(args)
    if args.cmd == "genesis":
        return cmd_genesis(args)
    if args.cmd == "snapshot":
        return cmd_snapshot(args)
    if args.cmd == "ledger":
        from firedancer_tpu import ledger as _ledger

        return _ledger.main(args)
    if args.cmd == "configure":
        from firedancer_tpu.utils import hostcfg
        from firedancer_tpu.utils.config import load_config

        return hostcfg.main(args, load_config(args.config))
    if args.cmd == "backtest":
        from firedancer_tpu.choreo import backtest as _bt

        return _bt.main(args)
    if args.cmd == "monitor":
        return cmd_monitor(args)
    if args.cmd == "ready":
        return cmd_ready(args)
    if args.cmd == "metrics":
        return cmd_metrics(args)
    if args.cmd == "trace":
        return cmd_trace(args)
    if args.cmd == "slotreport":
        from firedancer_tpu.utils.platform import force_cpu_backend

        force_cpu_backend()  # cluster mode never takes the chip
        return cmd_slotreport(args)
    if args.cmd == "chaos":
        from firedancer_tpu.utils.platform import (
            enable_compile_cache,
            force_cpu_backend,
        )

        force_cpu_backend()  # scenarios never take the chip
        enable_compile_cache()
        from firedancer_tpu.chaos import scenario as _chaos

        return _chaos.main(args)
    if args.cmd == "version":
        print(f"firedancer_tpu {__version__}")
        return 0
    return 2


if __name__ == "__main__":
    sys.exit(main())
