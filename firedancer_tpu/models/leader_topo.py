"""The leader pipeline as a PROCESS topology (fdctl-run shape).

models/leader.py wires the flagship pipeline for the cooperative
in-process scheduler (tests, bench); this module wires the SAME stages
into runtime/topo's process runner — one OS process per stage over the
same shm links, cnc supervision, monitor — the reference's operational
model (fd_topo_run.c boots tiles as processes; run.c supervises).

Builders are MODULE-LEVEL functions (the topo runner spawns fresh
interpreters — see runtime/topo.py on why fork is unusable with XLA —
so every builder and its kwargs must pickle).

One process per chip, and the verify stage is that process: the verify
child calls require_chip() and is the only process of the topology that
initialises the TPU; every other child that can reach JAX (shred, the
fused poh+shred, store) pins itself to the CPU before its first device
use, and the launching parent never initialises a backend.  All of them
share the one persistent compile cache (utils/platform.py).
"""

from __future__ import annotations

import hashlib

from firedancer_tpu.pack.cost import MAX_BANK_TILES
from firedancer_tpu.runtime import topo as ft
from firedancer_tpu.tango import shm
from firedancer_tpu.utils import log as fl


_log = fl.get_logger("leader_topo")


# ring depths, in frags, by link (the cooperative pipeline sizes every
# ring but "ss" from verify.receive_buffer_depth)
LINK_DEPTHS = {"gv": 1024, "vd": 1024, "pb": 256, "bp": 256, "bd": 256,
               "ps": 1024, "ss": 4096}

# what a topology's tiles make beside the links, named per run
# (Topology.own: the supervisor's to take away, whichever tile made it
# and whether or not that tile lived): the bank tiles' one funk segment,
# the store's slots
_FUNK_SHM = "fdtpu_funk_{uid}"


def _store_dir_template() -> str:
    from firedancer_tpu.runtime import monitor as mon

    return mon.RUN_DIR + "/fdtpu_store_{uid}"


def _cpu():
    from firedancer_tpu.utils.platform import enable_compile_cache, force_cpu_backend

    force_cpu_backend()
    enable_compile_cache()


def _warm_verify(stage, dev) -> None:
    """Compile (or load from the cache) the stage's program in the
    builder, before the run loop's first heartbeat: a compile inside the
    loop would read as a wedged stage to the supervisor."""
    warm_s = stage.warmup()
    _log.notice(f"stage {stage.name} device={dev[0]} kind={dev[1]!r} "
                f"count={dev[2]} warmup_s={warm_s:.2f}")


def build_benchg(links, cnc, *, pool_size, n_txns, n_payers=8, out="gv"):
    from firedancer_tpu.runtime.benchg import BenchGStage, gen_transfer_pool

    return BenchGStage(
        gen_transfer_pool(pool_size, n_payers=n_payers),
        "benchg",
        outs=[shm.make_producer(links[out])],
        cnc=cnc,
        limit=n_txns,
    )


def build_verify(links, cnc, *, batch, max_msg_len=256, precomputed=False,
                 cpu=False, batch_deadline_s=0.002, replay=False):
    """The verify tile; `replay`: the follower's, which takes entry
    batches from "rv" and hands them on to "vo"
    (runtime/replay_verify.py) where the leader's takes transactions
    from "gv" and hands them to "vd"."""
    from firedancer_tpu.utils.platform import select_device

    dev = None if precomputed else select_device(cpu)
    if replay:
        from firedancer_tpu.runtime.replay_verify import (
            ReplayVerifyStage as cls,
        )
    else:
        from firedancer_tpu.runtime.verify import VerifyStage as cls
    lin, lout, lazy = ("rv", "vo", 8) if replay else ("gv", "vd", 32)
    stage = cls(
        "verify0",
        ins=[shm.make_consumer(links[lin], lazy=lazy)],
        outs=[shm.make_producer(links[lout])],
        cnc=cnc,
        batch=batch,
        max_msg_len=max_msg_len,
        batch_deadline_s=batch_deadline_s,
        precomputed_ok=precomputed,
    )
    if dev is not None:
        _warm_verify(stage, dev)
    return stage


def build_benchs(links, cnc, *, idx, n, run_dir, peer_pub, max_datagram,
                 capture=False):
    """Sender tile `idx` of `n` on the generator's ring: waits for the
    quic tile's address in the run's directory, then handshakes — both
    before its first heartbeat, so set-up covers them."""
    from firedancer_tpu.runtime.benchs import BenchSStage, wait_addr

    addr = wait_addr(quic_addr_file(run_dir), 120.0)
    return BenchSStage(
        f"benchs{idx}",
        ins=[shm.make_consumer(links["gb"], fseq_idx=idx, lazy=16)],
        cnc=cnc,
        addr=addr,
        expected_peer=peer_pub,
        max_datagram=max_datagram,
        shard_idx=idx,
        shard_cnt=n,
        capture=f"{run_dir}/benchs{idx}" if capture else None,
    )


def build_quic(links, cnc, *, secret, run_dir, host, port, rx_burst,
               reasm_depth, max_conns, retry, stream_window):
    from firedancer_tpu.runtime.net import QuicIngressStage

    return QuicIngressStage(
        "quic",
        outs=[shm.make_producer(links["gv"])],
        cnc=cnc,
        host=host,
        port=port,
        rx_burst=rx_burst,
        identity_secret=secret,
        reasm_depth=reasm_depth,
        max_conns=max_conns,
        retry=retry,
        stream_window=stream_window,
        addr_file=quic_addr_file(run_dir),
    )


def build_out(links, cnc):
    from firedancer_tpu.runtime.benchs import OutStage

    return OutStage("out", ins=[shm.make_consumer(links["vd"], lazy=64)],
                    cnc=cnc)


def build_dedup(links, cnc):
    from firedancer_tpu.runtime.dedup import DedupStage

    return DedupStage(
        "dedup",
        ins=[shm.make_consumer(links["vd"], lazy=32)],
        outs=[shm.make_producer(links["dp"])],
        cnc=cnc,
    )


def build_pack(links, cnc, *, n_bank, slot_clock=None, shed_keep=None,
               limits=None):
    from firedancer_tpu.runtime.pack_stage import PackStage

    return PackStage(
        "pack",
        ins=[shm.make_consumer(links["dp"], lazy=32)]
        + [shm.make_consumer(links[f"bd{b}"], lazy=8) for b in range(n_bank)],
        outs=[shm.make_producer(links[f"pb{b}"]) for b in range(n_bank)],
        cnc=cnc,
        bank_cnt=n_bank,
        # a process pipeline has real inter-stage latency: schedule as
        # soon as anything is pending
        min_pending=1,
        mb_deadline_s=0.0,
        clock=slot_clock,
        shed_keep=shed_keep,
        limits=limits,
    )


def build_pack_native(links, cnc, *, n_bank, txn_links, slot_clock=None,
                      shed_keep=None, hold_when_full=False, limits=None):
    """The fused native dedup+pack stage: consumes the verify output
    links directly (no dedup process) and runs native/fd_pack.cpp via
    one FFI crossing per burst.  The parent only wires this when
    pack/scheduler_native.available() said so pre-boot (the .so is
    already built; the child just loads it)."""
    from firedancer_tpu.runtime.pack_stage import NativePackStage

    return NativePackStage(
        "pack",
        ins=[shm.make_consumer(links[l], lazy=32) for l in txn_links]
        + [shm.make_consumer(links[f"bd{b}"], lazy=8) for b in range(n_bank)],
        outs=[shm.make_producer(links[f"pb{b}"]) for b in range(n_bank)],
        cnc=cnc,
        bank_cnt=n_bank,
        n_txn_ins=len(txn_links),
        min_pending=1,
        mb_deadline_s=0.0,
        clock=slot_clock,
        shed_keep=shed_keep,
        hold_when_full=hold_when_full,
        limits=limits,
    )


# the bank tiles' funk fork, by a name its supervisor knows too (the
# account store is read back through NativeFunk.attach_readonly)
BANK_FORK_XID = b"leader_topo:bank"


def build_bank(links, cnc, *, bank_idx, slot=1, slot_clock=None, n_payers=8,
               genesis=None, funk_shm=None, status_cache=True):
    # every bank process has its own BankCtx (SlotExecution, native
    # session) over ONE account store: with `funk_shm` (the run's name
    # for the native funk's segment) bank tile 0 makes the store, funds
    # the genesis and prepares the fork BANK_FORK_XID, and every other
    # bank tile attaches to both as one more writer
    # (genesis_bank_ctx(funk_attach=): it waits until tile 0 says the
    # store is whole) — upstream shares fd_funk in a wksp across its
    # bank tiles the same way.  Pack's account locks order two tiles
    # that touch one account; the sweep lane reads through the segment
    # what the other left (native/fd_bank.cpp).
    # genesis: default_bank_ctx's arguments where the caller has them
    # (a traffic shape's payers), else the generator's n_payers.
    # status_cache: False = [development.bench] disable_status_cache.
    from firedancer_tpu.runtime.bank import BankStage, default_bank_ctx

    if genesis is None:
        genesis = {"slot": slot, "n_payers": n_payers}
    if not status_cache:
        genesis = dict(genesis, with_status_cache=False)
    stage = BankStage(
        f"bank{bank_idx}",
        ins=[shm.make_consumer(links[f"pb{bank_idx}"], lazy=8)],
        outs=[
            shm.make_producer(links[f"bp{bank_idx}"]),
            shm.make_producer(links[f"bd{bank_idx}"]),
        ],
        cnc=cnc,
        bank_idx=bank_idx,
        ctx=default_bank_ctx(**genesis, funk_shm=funk_shm,
                             fork_xid=BANK_FORK_XID,
                             funk_attach=bank_idx > 0),
        clock=slot_clock,
    )
    stage.require_credit = True
    return stage


def build_poh(links, cnc, *, n_bank, slot_clock=None):
    from firedancer_tpu.runtime.poh_stage import PohStage

    stage = PohStage(
        "poh",
        ins=[shm.make_consumer(links[f"bp{b}"], lazy=8) for b in range(n_bank)],
        outs=[shm.make_producer(links["ps"])],
        cnc=cnc,
        clock=slot_clock,
    )
    stage.require_credit = True
    return stage


def build_shred(links, cnc, *, secret, slot, batch_target_sz=4096):
    _cpu()  # reedsol can dispatch on device: the chip is the verify child's
    from firedancer_tpu.ops.ref import ed25519_ref as ref
    from firedancer_tpu.runtime.shred_stage import ShredStage

    return ShredStage(
        "shred",
        ins=[shm.make_consumer(links["ps"], lazy=8)],
        outs=[shm.make_producer(links["ss"])],
        cnc=cnc,
        signer=lambda root: ref.sign(secret, root),
        secret=secret,  # arms the native shredder lane when available
        slot=slot,
        batch_target_sz=batch_target_sz,
    )


def build_poh_shred_fused(links, cnc, *, n_bank, secret, slot,
                          slot_clock=None):
    """The fused poh+shred crash domain (runtime/shred_stage.
    FusedPohShredStage) as ONE process: the poh->shred ring hop ("ps")
    disappears, entries feed the shredder in-process, and the
    supervisor restarts clock and shredder together — entries can never
    be stranded on a ring between them."""
    _cpu()  # the shred half's reedsol can dispatch on device
    from firedancer_tpu.ops.ref import ed25519_ref as ref
    from firedancer_tpu.runtime.shred_stage import FusedPohShredStage

    stage = FusedPohShredStage(
        "poh_shred",
        ins=[shm.make_consumer(links[f"bp{b}"], lazy=8)
             for b in range(n_bank)],
        outs=[shm.make_producer(links["ss"])],
        cnc=cnc,
        clock=slot_clock,
        signer=lambda root: ref.sign(secret, root),
        secret=secret,  # arms the native shredder lane when available
        shred_slot=slot,
        batch_target_sz=4096,
    )
    stage.require_credit = True
    return stage


def build_store(links, cnc, *, leader_pub, persist_dir=None):
    """persist_dir: the leader's own store tile as the cooperative
    pipeline builds it (models/leader.py: it trusts its own signing
    path), writing each stored slot where the supervisor reads the
    block back (runtime/store.StoredSlots).  None: a store that
    signature-checks every set and keeps them in its own memory."""
    _cpu()  # the resolver's RS recover can dispatch on device
    from firedancer_tpu.ops.ref import ed25519_ref as ref
    from firedancer_tpu.runtime.store import StoreStage

    own = persist_dir is not None
    return StoreStage(
        "store",
        ins=[shm.make_consumer(links["ss"], lazy=64)],
        cnc=cnc,
        verify_sig=None if own else lambda r, s: ref.verify(r, s, leader_pub),
        trust_membership=own,
        persist_dir=persist_dir,
    )


def build_leader_topology(
    *,
    n_txns: int = 64,
    pool_size: int = 64,
    batch: int = 32,
    n_bank: int = 1,
    leader_seed: bytes = b"leader",
    slot: int = 1,
    sandbox: dict | None = None,
    native_pack: bool | None = None,
    slot_clock=None,
    boot_grace_s: float = 0.0,
    shed_keep: int | None = None,
    verify_precomputed: bool = False,
    fuse_poh_shred: bool = False,
    max_msg_len: int = 256,
    verify_cpu: bool = False,
    n_payers: int = 8,
    batch_deadline_s: float = 0.002,
    depths: dict | None = None,
    genesis: dict | None = None,
    hold_when_full: bool = False,
    persist: bool = False,
    shred_batch_target_sz: int = 4096,
    block_limits=None,
    status_cache: bool = True,
) -> ft.Topology:
    """n_payers: the generator's funded payer set, known to benchg and
    to the bank's genesis alike (models/leader.build_leader_pipeline).

    depths: ring depths by link ("gv", "vd", "pb", "bp", "bd", "ps",
    "ss": LINK_DEPTHS; a key left out keeps its depth there).
    genesis: `default_bank_ctx`'s arguments for the bank tile (a
    traffic shape's payers) in place of slot / n_payers.
    hold_when_full: pack leaves its txn input unpolled while its pool
    has no room for a burst (PackStage): backpressure through every
    ring up to the source, no eviction.
    persist: the tiles keep what a supervisor reads after the drain
    where it can reach it — the store tile writes each stored slot
    under the run's directory (`store_dir(handle)`,
    runtime/store.StoredSlots), and the bank tiles' funk segment has
    the run's name (`bank_funk_shm(handle)`, NativeFunk
    .attach_readonly, fork BANK_FORK_XID); close() removes both.
    n_bank > 1: B bank processes over that ONE segment (`build_bank`),
    which then has the run's name whether or not `persist` — so it
    takes the native funk, and `status_cache=False`: a status cache a
    process would let a repeat that outlives pack's tags land once a
    tile, and none lives in the segment yet.
    block_limits: pack's (pack/scheduler.BlockLimits; None: stock), in
    whichever pack lane runs.
    status_cache: False = the bank tiles keep none
    ([development.bench] disable_status_cache): exactly-once rests on
    the verify tile's, dedup's and pack's signature tags.

    verify_cpu: the verify child runs its kernel on the CPU backend
    instead of owning the chip (tests, chip-less boxes) — the CPU is what
    a caller asks for, never a fallback.

    sandbox: utils/sandbox.enter kwargs applied to EVERY stage child
    (the per-tile jail; fd_topo_run's seccomp step).  The default policy
    shape: {"rlimits": {"nofile": 512}} + the spawn/exec/priv deny list,
    with thread-creating clones allowed for XLA.

    native_pack: None = auto — when pack/scheduler_native.available()
    (checked HERE in the parent, which also builds the .so so children
    just load it), the dedup process disappears and the pack process
    runs the fused native dedup+pack lane over the verify link.

    slot_clock (runtime/slot_clock.SlotClockCfg): run the topology
    against the real wall-clock cadence.  The cfg is anchored HERE, in
    the parent, `boot_grace_s` into the future (children need real time
    to spawn — XLA imports take seconds on cold boxes), so every child
    derives the SAME slot boundaries from one shared monotonic epoch.
    With n_slots set on the cfg, the leader window ends ON THE SCHEDULE
    — poh stops sealing at the last slot's deadline regardless of how
    much load is still draining (the handoff contract); supervise with
    `until=leader_window_done(...)` to observe it.

    fuse_poh_shred: collapse poh and shred into ONE crash domain
    (FusedPohShredStage): the "ps" link and the separate shred process
    disappear, and the fused stage consumes the bank entry links and
    produces wire shreds directly.  Supervise with
    `leader_window_done(n, stage="poh_shred")` in this mode."""
    from firedancer_tpu.models.leader import resolve_native_pack
    from firedancer_tpu.ops.ref import ed25519_ref as ref

    # per-kind metric schemas: launch() sizes each stage's shm metrics
    # segment from these (and records them in the run descriptor, so a
    # scraper reconstructs the layout without importing these classes)
    from firedancer_tpu.runtime.bank import BankStage
    from firedancer_tpu.runtime.dedup import DedupStage
    from firedancer_tpu.runtime.pack_stage import PackStage
    from firedancer_tpu.runtime.poh_stage import PohStage
    from firedancer_tpu.runtime.shred_stage import ShredStage
    from firedancer_tpu.runtime.store import StoreStage
    from firedancer_tpu.runtime.verify import VerifyStage

    if slot_clock is not None:
        slot_clock = slot_clock.anchored(boot_grace_s)

    if not 1 <= n_bank <= MAX_BANK_TILES:
        raise ValueError(f"bank_stage_count {n_bank}: pack schedules to 1.."
                         f"{MAX_BANK_TILES} banks (pack/cost.MAX_BANK_TILES)")
    if n_bank > 1:
        from firedancer_tpu.funk import funk_native

        if not funk_native.available():
            raise ValueError(
                f"bank_stage_count {n_bank} in processes needs the native "
                f"funk (one shm segment every bank tile writes): "
                f"native/fd_funk.so did not build or FDTPU_NATIVE_FUNK=0, "
                f"and the Python funk lives in one process")
        if status_cache:
            raise ValueError(
                f"bank_stage_count {n_bank} in processes needs "
                f"[development.bench] disable_status_cache: a status cache "
                f"a bank process would let a repeat that outlives pack's "
                f"tags land once a tile")

    use_native_pack = resolve_native_pack(native_pack)
    d = dict(LINK_DEPTHS, **(depths or {}))
    topo = ft.Topology()
    topo.link("gv", depth=d["gv"], mtu=1232)
    topo.link("vd", depth=d["vd"], mtu=4096)
    if not use_native_pack:
        topo.link("dp", depth=d["vd"], mtu=4096)
    for b in range(n_bank):
        topo.link(f"pb{b}", depth=d["pb"], mtu=65536)
        topo.link(f"bp{b}", depth=d["bp"], mtu=65536)
        topo.link(f"bd{b}", depth=d["bd"], mtu=64)
    if not fuse_poh_shred:
        topo.link("ps", depth=d["ps"], mtu=65536)
    topo.link("ss", depth=d["ss"], mtu=1232)
    funk_shm = topo.own(_FUNK_SHM) if persist or n_bank > 1 else None
    persist_dir = topo.own(_store_dir_template()) if persist else None

    secret = hashlib.sha256(leader_seed).digest()
    leader_pub = ref.public_key(secret)

    # ins/outs mirror what each builder above actually wires — the
    # pre-boot topology checker (analysis FD1xx) validates the graph
    # against these declarations before launch() creates any shm.
    # pack is deliberately NOT credit_gated: it keeps draining the banks'
    # done-feedback (bd) links while backpressured on pb, which is what
    # breaks the pack<->bank cycle (FD107's rationale).
    sb = sandbox
    topo.stage("benchg", build_benchg, pool_size=pool_size, n_txns=n_txns,
               n_payers=n_payers, sandbox=sb, outs=["gv"])
    topo.stage("verify0", build_verify, batch=batch,
               max_msg_len=max_msg_len, sandbox=sb,
               precomputed=verify_precomputed, cpu=verify_cpu,
               batch_deadline_s=batch_deadline_s,
               ins=["gv"], outs=["vd"], schema=VerifyStage.metrics_schema())
    if hold_when_full and not use_native_pack:
        raise ValueError("hold_when_full is the native pack lane's here "
                         "(native/fd_pack.so did not build or is off)")
    if use_native_pack:
        topo.stage("pack", build_pack_native, n_bank=n_bank,
                   txn_links=["vd"], sandbox=sb,
                   slot_clock=slot_clock, shed_keep=shed_keep,
                   hold_when_full=hold_when_full, limits=block_limits,
                   ins=["vd"] + [f"bd{b}" for b in range(n_bank)],
                   outs=[f"pb{b}" for b in range(n_bank)],
                   schema=PackStage.metrics_schema_n(n_bank))
    else:
        topo.stage("dedup", build_dedup, sandbox=sb, ins=["vd"], outs=["dp"],
                   schema=DedupStage.metrics_schema())
        topo.stage("pack", build_pack, n_bank=n_bank, sandbox=sb,
                   slot_clock=slot_clock, shed_keep=shed_keep,
                   limits=block_limits,
                   ins=["dp"] + [f"bd{b}" for b in range(n_bank)],
                   outs=[f"pb{b}" for b in range(n_bank)],
                   schema=PackStage.metrics_schema_n(n_bank))
    for b in range(n_bank):
        topo.stage(f"bank{b}", build_bank, bank_idx=b, slot=slot, sandbox=sb,
                   slot_clock=slot_clock, n_payers=n_payers,
                   genesis=genesis, funk_shm=funk_shm,
                   status_cache=status_cache,
                   ins=[f"pb{b}"], outs=[f"bp{b}", f"bd{b}"],
                   credit_gated=True, schema=BankStage.metrics_schema())
    if fuse_poh_shred:
        from firedancer_tpu.runtime.shred_stage import FusedPohShredStage

        topo.stage("poh_shred", build_poh_shred_fused, n_bank=n_bank,
                   secret=secret, slot=slot, sandbox=sb,
                   slot_clock=slot_clock,
                   ins=[f"bp{b}" for b in range(n_bank)], outs=["ss"],
                   credit_gated=True,
                   schema=FusedPohShredStage.metrics_schema())
    else:
        topo.stage("poh", build_poh, n_bank=n_bank, sandbox=sb,
                   slot_clock=slot_clock,
                   ins=[f"bp{b}" for b in range(n_bank)], outs=["ps"],
                   credit_gated=True, schema=PohStage.metrics_schema())
        topo.stage("shred", build_shred, secret=secret, slot=slot,
                   batch_target_sz=shred_batch_target_sz,
                   sandbox=sb, ins=["ps"], outs=["ss"],
                   schema=ShredStage.metrics_schema())
    topo.stage("store", build_store, leader_pub=leader_pub, sandbox=sb,
               persist_dir=persist_dir, ins=["ss"],
               schema=StoreStage.metrics_schema())
    return topo


def build_leader_topology_from_config(cfg, *, genesis: dict | None = None,
                                      slot_clock=None,
                                      **overrides) -> ft.Topology:
    """The process topology derived from a typed Config
    (utils/config.py), as models/leader
    .build_leader_pipeline_from_config derives the cooperative one: one
    configuration file describes both forms.  From the config: the
    verify batch, message width and deadline, the ring depths
    ([links]; the generator's ring is verify.receive_buffer_depth),
    pack's full-pool rule, the shredder's batch target, and the slot
    cadence (poh.slot_ms; `slot_clock`, a SlotClockCfg, overrides it).
    `genesis`: the bank tiles' (`default_bank_ctx`'s arguments).  The
    tiles persist what they hold (`build_leader_topology`'s `persist`).
    [development.bench]: larger_max_cost_per_block is pack's block cost
    limit (models/leader.block_limits_of), disable_status_cache the
    bank tiles' status cache.

    layout.bank_stage_count = B bank tiles, each a process, over one
    funk segment (`build_bank`); B > 1 takes disable_status_cache and
    the native funk (`build_leader_topology` refuses by name).  A tile
    that dies takes the topology down: restart under load is not part
    of this deployment yet."""
    from firedancer_tpu.models.leader import block_limits_of

    if slot_clock is None and cfg.poh.slot_ms > 0:
        from firedancer_tpu.runtime.slot_clock import SlotClockCfg

        slot_clock = SlotClockCfg(slot_ms=cfg.poh.slot_ms,
                                  ticks_per_slot=cfg.poh.ticks_per_slot)
    ln = cfg.links
    kw = dict(
        n_bank=cfg.layout.bank_stage_count,
        batch=cfg.verify.batch,
        max_msg_len=cfg.verify.max_msg_len,
        batch_deadline_s=cfg.verify.batch_deadline_ms / 1e3,
        depths={"gv": cfg.verify.receive_buffer_depth,
                "vd": ln.verify_pack, "pb": ln.pack_bank,
                "bp": ln.bank_poh, "bd": ln.bank_done,
                "ps": ln.poh_shred, "ss": ln.shred_store},
        hold_when_full=cfg.pack.hold_when_full,
        shred_batch_target_sz=cfg.shred.batch_target_sz,
        genesis=genesis,
        slot_clock=slot_clock,
        persist=True,
        block_limits=block_limits_of(cfg),
        status_cache=not cfg.development.bench.disable_status_cache,
    )
    kw.update(overrides)
    return build_leader_topology(**kw)


def _quic_dir_template() -> str:
    from firedancer_tpu.runtime import monitor as mon

    return mon.RUN_DIR + "/fdtpu_quic_{uid}"


def quic_addr_file(run_dir: str) -> str:
    return run_dir + "/quic.addr"


def quic_dir(handle) -> str:
    """The front-door topology's directory of the run: the quic tile's
    address, and each sender tile's key log and captured datagrams
    (`benchs<i>.keys`, `benchs<i>.dgrams`: runtime/benchs.QuicSender)."""
    return _quic_dir_template().format(uid=handle.uid)


def build_quic_topology_from_config(
    cfg, *, n_txns: int = 64, pool_size: int = 64, n_payers: int = 8,
    leader_seed: bytes = b"leader", sandbox: dict | None = None,
    verify_precomputed: bool = False, verify_cpu: bool = False,
    capture: bool = False,
) -> ft.Topology:
    """The front door as a process topology, from the typed Config:

        benchg -> gb -> benchs x S -> (loopback UDP, QUIC) -> quic
               -> gv -> verify0 -> vd -> out

    S = layout.benchs_stage_count sender tiles share the generator's
    ring (sender k takes seq % S == k), each with one QUIC connection
    pinned to the quic tile's identity; the quic tile listens where
    [net] says and writes the address it got into the run's directory
    (`quic_dir(handle)`), where `capture` also puts each sender's key
    log and first datagrams; verify is the tile the config's [verify]
    describes, at ITS row bound (max_msg_len: 1,232 unless the file
    narrows it).  The ring in front of verify is
    verify.receive_buffer_depth deep, the generator's too; verify's
    output ring is links.verify_pack.

    What it cannot build it refuses by name: no sender tile
    (benchs_stage_count 0 is the topology without a front); more than
    one verify tile (N verify tiles each taking seq % N of the quic
    tile's ring exist only cooperatively, and a chip belongs to one
    process)."""
    from firedancer_tpu.ops.ref import ed25519_ref as ref
    from firedancer_tpu.runtime.benchs import BenchSStage, OutStage
    from firedancer_tpu.runtime.net import QuicIngressStage
    from firedancer_tpu.runtime.verify import VerifyStage

    n_benchs = cfg.layout.benchs_stage_count
    if n_benchs < 1:
        raise ValueError(
            "the front-door topology needs layout.benchs_stage_count >= 1 "
            "(0 is the topology without a quic tile: "
            "build_leader_topology_from_config)")
    if cfg.layout.verify_stage_count != 1:
        raise ValueError(
            f"quic with layout.verify_stage_count = "
            f"{cfg.layout.verify_stage_count}: the process topology has one "
            f"verify tile behind the quic tile until N verify tiles each "
            f"take seq % N of its ring in processes of their own")
    if cfg.verify.devices != 1:
        raise ValueError("quic with verify.devices > 1: the front-door "
                         "topology runs its verify tile on one chip")
    topo = ft.Topology()
    depth = cfg.verify.receive_buffer_depth
    topo.link("gb", depth=depth, mtu=1232, n_consumers=n_benchs)
    topo.link("gv", depth=depth, mtu=1232)
    topo.link("vd", depth=cfg.links.verify_pack, mtu=4096)
    run_dir = topo.own(_quic_dir_template())
    secret = hashlib.sha256(leader_seed).digest()
    sb = sandbox
    topo.stage("benchg", build_benchg, pool_size=pool_size, n_txns=n_txns,
               n_payers=n_payers, out="gb", sandbox=sb, outs=["gb"])
    for i in range(n_benchs):
        topo.stage(f"benchs{i}", build_benchs, idx=i, n=n_benchs,
                   run_dir=run_dir, peer_pub=ref.public_key(secret),
                   max_datagram=cfg.quic.max_datagram, capture=capture,
                   sandbox=sb, ins=["gb"],
                   schema=BenchSStage.metrics_schema())
    topo.stage("quic", build_quic, secret=secret, run_dir=run_dir,
               host=cfg.net.listen_host, port=cfg.net.listen_port,
               rx_burst=cfg.net.rx_burst, reasm_depth=cfg.quic.reasm_depth,
               max_conns=cfg.quic.max_conns, retry=cfg.quic.retry,
               stream_window=cfg.quic.stream_window, sandbox=sb,
               outs=["gv"], schema=QuicIngressStage.metrics_schema())
    topo.stage("verify0", build_verify, batch=cfg.verify.batch,
               max_msg_len=cfg.verify.max_msg_len, sandbox=sb,
               precomputed=verify_precomputed, cpu=verify_cpu,
               batch_deadline_s=cfg.verify.batch_deadline_ms / 1e3,
               ins=["gv"], outs=["vd"], schema=VerifyStage.metrics_schema())
    topo.stage("out", build_out, sandbox=sb, ins=["vd"],
               schema=OutStage.metrics_schema())
    return topo


def build_replay_source(links, cnc, *, pool_size, n_payers, n_slots,
                        slot_txns, corrupt_slots, shape):
    from firedancer_tpu.runtime.benchg import gen_transfer_pool
    from firedancer_tpu.runtime.replay_verify import ReplaySourceStage

    return ReplaySourceStage(
        gen_transfer_pool(pool_size, n_payers=n_payers), "replaysrc",
        outs=[shm.make_producer(links["rv"])], cnc=cnc, n_slots=n_slots,
        slot_txns=slot_txns, corrupt_slots=corrupt_slots, shape=shape)


def build_replay_out(links, cnc):
    from firedancer_tpu.runtime.replay_verify import ReplayOutStage

    return ReplayOutStage("replayout",
                          ins=[shm.make_consumer(links["vo"], lazy=16)],
                          cnc=cnc)


def build_replay_topology_from_config(
    cfg, *, n_slots: int = 2, pool_size: int = 64, n_payers: int = 8,
    sandbox: dict | None = None, verify_precomputed: bool = False,
    verify_cpu: bool = False,
) -> ft.Topology:
    """The follower's verify phase as a process topology, from the
    typed Config (layout.replay_stage_count = 1):

        replaysrc -> rv -> verify0 -> vo -> replayout

    The source tile offers `n_slots` slots as [replay] and [poh]
    describe them — replay.slot_txns transfers a slot (a pool of
    `pool_size`, replayed) in entries of replay.txns_per_entry and
    poh.ticks_per_slot ticks of poh.hashes_per_tick hashes, cut into
    entry batches of replay.entries_per_batch entries, a frag each —
    with one flipped signature bit in every
    replay.dead_one_in_slots-th slot.  verify0 is the replay verify
    stage (runtime/replay_verify.py) at [verify]'s device batch, row
    bound and deadline: VerifyStage's batch life and the one program
    behind an intake of entry batches.  replayout stands where a
    replay tile executes: it counts entry batches and verdicts.  The
    ring in front of verify0 is verify.receive_buffer_depth deep, the
    one behind replay.out_depth, both replay.frag_mtu wide."""
    from firedancer_tpu.runtime.replay_verify import (
        ReplayOutStage, ReplaySourceStage, ReplayVerifyStage,
    )

    if cfg.layout.replay_stage_count != 1:
        raise ValueError(
            "the replay topology needs layout.replay_stage_count = 1 (0 is "
            "the leader's side: build_leader_topology_from_config)")
    if cfg.verify.devices != 1:
        raise ValueError("replay with verify.devices > 1: the replay "
                         "topology runs its verify tile on one chip")
    r = cfg.replay
    topo = ft.Topology()
    topo.link("rv", depth=cfg.verify.receive_buffer_depth, mtu=r.frag_mtu)
    topo.link("vo", depth=r.out_depth, mtu=r.frag_mtu)
    every = r.dead_one_in_slots
    topo.stage(
        "replaysrc", build_replay_source, pool_size=pool_size,
        n_payers=n_payers, n_slots=n_slots, slot_txns=r.slot_txns,
        corrupt_slots=tuple(range(every - 1, n_slots, every)) if every
        else (),
        shape=dict(txns_per_entry=r.txns_per_entry,
                   entries_per_batch=r.entries_per_batch,
                   ticks_per_slot=cfg.poh.ticks_per_slot,
                   hashes_per_tick=cfg.poh.hashes_per_tick),
        sandbox=sandbox, outs=["rv"],
        schema=ReplaySourceStage.metrics_schema())
    topo.stage("verify0", build_verify, replay=True, batch=cfg.verify.batch,
               max_msg_len=cfg.verify.max_msg_len, sandbox=sandbox,
               precomputed=verify_precomputed, cpu=verify_cpu,
               batch_deadline_s=cfg.verify.batch_deadline_ms / 1e3,
               ins=["rv"], outs=["vo"],
               schema=ReplayVerifyStage.metrics_schema())
    topo.stage("replayout", build_replay_out, sandbox=sandbox, ins=["vo"],
               schema=ReplayOutStage.metrics_schema())
    return topo


def store_dir(handle) -> str:
    """Where a `persist` topology's store tile writes its slots."""
    return _store_dir_template().format(uid=handle.uid)


def bank_funk_shm(handle) -> str:
    """The shm name of the bank tiles' funk (a `persist` topology's, or
    one with more than one bank tile)."""
    return _FUNK_SHM.format(uid=handle.uid)


def build_leader_topology_fused(**kw) -> ft.Topology:
    """build_leader_topology with the fusion knob on: the fused
    poh+shred crash domain as a checkable flagship variant — the
    default `--topo` spec fdlint's FD1xx (link/credit invariants) and
    FD4xx (crash-domain map) passes validate alongside the unfused
    topology."""
    kw.setdefault("fuse_poh_shred", True)
    return build_leader_topology(**kw)


def leader_window_done(n_slots: int, stage: str = "poh"):
    """An `until` predicate for TopologyHandle.supervise: the leader
    window is over once poh has resolved every scheduled slot — sealed
    or MISSED, both count; the handoff fires on the schedule, not on
    drain.  Reads the poh stage's shm metrics registry (values are at
    most one housekeeping interval stale, which is exactly the jitter
    budget the grace window already absorbs)."""

    def _done(handle) -> bool:
        reg = handle.met_views.get(stage, (None, None))[0]
        if reg is None:
            return False
        return (reg.get("slots_sealed") + reg.get("slot_missed")
                >= n_slots)

    return _done
