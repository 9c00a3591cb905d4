"""The flagship model: the full leader TPU pipeline, assembled.

    benchg -> verify (TPU sigverify, xN round-robin) -> dedup
           -> pack -> bank xB -> poh -> shred -> store

This is the e2e slice of the reference's Frankendancer leader topology
(/root/reference/src/app/fdctl/run/topos/fd_frankendancer.c:96-111) with
ingress replaced by the synthetic generator (net/quic are later
milestones) and the store stage doubling as the FEC-resolver receive path
that proves the emitted shreds reassemble.  Stages talk over tango shm
links and are driven either by the in-process cooperative scheduler here
(tests, bench) or by the process topology runner.

Link map (names follow the reference's link table, fd_frankendancer.c:55-83):
    gen_verify      benchg -> verify xN (round-robin by seq)
    verify_dedup[i] verify i -> dedup (single-producer rings)
    dedup_pack      dedup -> pack
    pack_bank[b]    pack -> bank b (microblock frames)
    bank_poh[b]     bank b -> poh (executed microblocks)
    bank_done[b]    bank b -> pack (lock release; the reference uses
                    bank_busy fseqs, same role)
    poh_shred       poh -> shred (entries)
    shred_store     shred -> store (wire shreds)
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass

from firedancer_tpu.ops.ref import ed25519_ref as ref
from firedancer_tpu.runtime.bank import BankCtx, BankStage, default_bank_ctx
from firedancer_tpu.runtime.benchg import BenchGStage, gen_transfer_pool
from firedancer_tpu.runtime.dedup import DedupStage
from firedancer_tpu.runtime.pack_stage import NativePackStage, PackStage
from firedancer_tpu.runtime.poh_stage import PohStage
from firedancer_tpu.runtime.shred_stage import ShredStage
from firedancer_tpu.runtime.stage import DEFAULT_BURST
from firedancer_tpu.runtime.store import StoreStage
from firedancer_tpu.runtime.verify import VerifyStage
from firedancer_tpu.tango import shm


def resolve_native_pack(native_pack: bool | None) -> bool:
    """None = auto: use the fused native pack+dedup lane when the .so is
    available and FDTPU_NATIVE_PACK != 0 (the same auto-detect posture as
    the bank stage's native executor lane)."""
    if native_pack is not None:
        return bool(native_pack)
    from firedancer_tpu.pack import scheduler_native as sn

    return sn.available()


@dataclass
class LeaderPipeline:
    stages: list
    links: list
    benchg: BenchGStage
    verifies: list[VerifyStage]
    dedup: DedupStage | None  # None on the fused native-pack lane
    pack: PackStage
    banks: list[BankStage]
    poh: PohStage
    shred: ShredStage
    store: StoreStage
    leader_pub: bytes
    bank_ctx: BankCtx = None

    def run(self, *, max_iters: int = 200_000, until_txns: int | None = None,
            finish: bool = True):
        """Cooperative round-robin until pack has accepted `until_txns`
        txns (or max_iters sweeps), then drain the whole pipe to the
        store.  finish=False leaves the pipe hot (benchmark warmup)."""
        for _ in range(max_iters):
            for s in self.stages:
                s.run_once()
            if (
                until_txns is not None
                and self.pack.metrics.get("txn_in") >= until_txns
            ):
                break
        if finish:
            self.finish()

    def finish(self, *, max_sweeps: int = 50_000) -> None:
        """Drain: verify flush -> pack force-flush -> stop the poh clock ->
        shred flush -> sweep until quiescent."""
        if hasattr(self.benchg, "limit"):
            self.benchg.limit = 0  # stop generating (socket ingress
            #                        has no generator to stop)
        for v in self.verifies:
            v.flush()
        self._sweep(max_sweeps)
        self.pack.flush()
        self._sweep(max_sweeps)
        # stop the clock so tick entries stop flowing, then final shred
        self.poh.hashes_per_iter = 0
        self._sweep(max_sweeps)
        self.shred.flush(block_complete=True)
        self._sweep(max_sweeps)

    def _sweep(self, max_sweeps: int) -> None:
        """Run non-generator stages until none makes frag progress."""
        stages = [s for s in self.stages if s is not self.benchg]
        for _ in range(max_sweeps):
            progressed = False
            for s in stages:
                progressed |= bool(s.run_once())
            # pack may be waiting on schedulability rather than frags
            self.pack.after_credit()
            if not progressed and not self.pack.pack.pending_cnt():
                break

    def seal(self):
        """End of slot: bank hash over the state every bank committed,
        chaining the final PoH entry hash (what replay_block reproduces
        from the wire entries alone)."""
        return self.bank_ctx.seal(self.poh.last_entry_hash)

    def close(self):
        # Drop every stage's Producer/Consumer link views FIRST: a
        # lingering Fseq/mcache numpy view pins the mmap, close() then
        # fails with BufferError, and at interpreter exit every
        # SharedMemory.__del__ retries and spews 'cannot close exported
        # pointers exist' into whatever captured stderr.  Ordering is
        # the fix: views die, THEN the mappings close, THEN the names
        # unlink.
        if hasattr(self.benchg, "sock"):
            self.benchg.close()  # socket ingress: fd + native client
        for s in self.stages:
            half = getattr(s, "shred_half", None)
            if half is not None:  # fused poh+shred: the inner stage's
                half.ins = []     # link views must die too
                half.outs = []
                half.drop_native_views()
            s.ins = []
            s.outs = []
            # the in-crossing metrics plane + drainer plan hold views
            # over the metric segments a caller may own (the latency-
            # budget fixture attaches its own) — same ordering rule
            s.drop_native_views()
        import gc

        gc.collect()
        for link in self.links:
            link.close()
            link.unlink()

    def report(self) -> dict:
        return {s.name: dict(s.metrics.counters) for s in self.stages}


def _take_turns(verifies: list) -> None:
    """The one-thread form's flow control.  Its stages take turns, and
    its pack sheds what its pool cannot hold — it never leaves its ring
    unpolled (build_leader_pipeline's docstring; `hold_when_full` is the
    process form's) — so the one thing that keeps a closed-loop flood
    under what pack and the banks land in a turn (about 30 transfers)
    is how much the stage in front of pack takes in a turn.  Verify's
    own sweep, a quarter of the ring in front (256), is for a stage
    whose consumer pushes back or whose thread is its own; at 64 a turn
    this pipeline's pack drops half of what verify verified.  Here
    verify keeps Stage's sweep."""
    for v in verifies:
        v.burst = DEFAULT_BURST


def block_limits_of(cfg):
    """pack's block limits as the config states them: the stock ones
    (None: pack/scheduler.BlockLimits' defaults), or with
    [development.bench] larger_max_cost_per_block the bench profile's
    block cost."""
    if not cfg.development.bench.larger_max_cost_per_block:
        return None
    from firedancer_tpu.pack import cost
    from firedancer_tpu.pack.scheduler import BlockLimits

    return BlockLimits(max_cost_per_block=cost.LARGER_MAX_COST_PER_BLOCK)


def build_leader_pipeline_from_config(cfg, **overrides) -> "LeaderPipeline":
    """Topology derived from a typed Config (utils/config.py) — the
    config_parse -> topos/fd_frankendancer.c split."""
    kw = dict(
        block_limits=block_limits_of(cfg),
        status_cache=not cfg.development.bench.disable_status_cache,
        n_verify=cfg.layout.verify_stage_count,
        n_bank=cfg.layout.bank_stage_count,
        batch=cfg.verify.batch,
        max_msg_len=cfg.verify.max_msg_len,
        depth=cfg.verify.receive_buffer_depth,
        batch_deadline_s=cfg.verify.batch_deadline_ms / 1e3,
        verify_devices=cfg.verify.devices,
    )
    kw.update(overrides)
    g = cfg.genesis
    if (g.n_voters or g.slot_hashes) and kw.get("bank_ctx") is None:
        # the deployment's genesis: the validator set's vote accounts
        # and SlotHashes, beside the generator's funded payers
        from firedancer_tpu.runtime.bank import (
            genesis_bank_ctx, seeded_validators,
        )

        kw["bank_ctx"] = genesis_bank_ctx(
            n_payers=kw.get("n_payers", 8),
            with_status_cache=kw["status_cache"],
            **seeded_validators(n_voters=g.n_voters,
                                n_slot_hashes=g.slot_hashes))
    return build_leader_pipeline(**kw)


def build_leader_pipeline(
    *,
    n_verify: int = 1,
    n_bank: int = 2,
    pool_size: int = 512,
    gen_limit: int | None = None,
    batch: int = 128,
    max_msg_len: int = 256,
    depth: int = 1024,
    batch_deadline_s: float = 0.002,
    slot: int = 1,
    leader_seed: bytes = b"leader",
    verify_precomputed: bool = False,
    verify_comb_slots: int = 0,
    verify_devices: int = 1,
    bank_ctx: BankCtx | None = None,
    keep_entries: bool = False,
    keep_sets: bool = True,
    native_pack: bool | None = None,
    slot_clock=None,
    shed_keep: int | None = None,
    fuse_poh_shred: bool = False,
    udp_ingress: bool = False,
    n_payers: int = 8,
    block_limits=None,
    status_cache: bool = True,
) -> LeaderPipeline:
    """block_limits: pack's (pack/scheduler.BlockLimits; None: stock).
    status_cache: whether the default bank ctx keeps one (a `bank_ctx`
    the caller brings is the caller's).

    verify_devices: chips behind each verify stage (1 = the default
    device; n > 1 = a mesh of the first n local devices, `batch` lanes
    over all of them).

    n_payers: the generator's funded payer set (and the default bank
    ctx's genesis).  Pack schedules at most one transaction per payer
    into a microblock, so a long stream over few payers drains slower
    than verify feeds it and pack sheds what its pool cannot hold.

    keep_sets=False releases the shred stage from materializing
    FecSets in Python, which lets it adopt the zero-Python sweep lane
    (bench uses this; tests that read pipe.shred.sets keep the
    default).

    slot_clock (runtime/slot_clock.SlotClockCfg or a built SlotClock)
    runs the pipeline against the real wall-clock slot cadence: poh
    paces ticks to the deadline and seals/misses slots on schedule,
    pack closes the block at each boundary (the unscheduled tail
    carries over; shed_keep arms the load-shedding degraded mode), and
    the banks observe the boundaries.

    udp_ingress=True puts a real localhost socket at the front instead
    of the in-process generator: UdpIngressStage (native recvmmsg sweep
    when the net lane is up) publishes datagrams into gen_verify, so an
    e2e window covers ingress -> verify -> ... -> store over actual
    network bytes.  The caller feeds txns at pipe.benchg.addr."""
    use_native_pack = resolve_native_pack(native_pack)
    if slot_clock is not None:
        from firedancer_tpu.runtime.slot_clock import SlotClockCfg

        if isinstance(slot_clock, SlotClockCfg):
            # ONE anchor for every stage: each resolve_clock below then
            # derives identical boundaries from the same epoch
            slot_clock = slot_clock.anchored()
    uid = shm.fresh_uid()
    links = []

    def mklink(name, mtu, n_consumers=1, d=None):
        link = shm.ShmLink.create(
            f"fdtpu_{name}_{uid}", depth=d or depth, mtu=mtu, n_fseq=n_consumers
        )
        links.append(link)
        return link

    gen_verify = mklink("gv", mtu=1232, n_consumers=n_verify)
    verify_dedup = [mklink(f"vd{i}", mtu=4096) for i in range(n_verify)]
    # the fused native lane has no dedup stage: pack consumes the verify
    # links directly and probes the tcache inside its insert crossing
    dedup_pack = None if use_native_pack else mklink("dp", mtu=4096)
    pack_bank = [mklink(f"pb{b}", mtu=65536) for b in range(n_bank)]
    bank_poh = [mklink(f"bp{b}", mtu=65536) for b in range(n_bank)]
    bank_done = [mklink(f"bd{b}", mtu=64) for b in range(n_bank)]
    # the fused poh+shred crash domain has no poh->shred ring hop
    poh_shred = None if fuse_poh_shred else mklink("ps", mtu=65536)
    shred_store = mklink("ss", mtu=1232, d=4096)

    secret = hashlib.sha256(leader_seed).digest()
    leader_pub = ref.public_key(secret)

    if udp_ingress:
        from firedancer_tpu.runtime.net import UdpIngressStage

        benchg = UdpIngressStage(
            "net", outs=[shm.make_producer(gen_verify)], rx_burst=64
        )
    else:
        pool = gen_transfer_pool(pool_size, n_payers=n_payers)
        benchg = BenchGStage(
            pool, "benchg", outs=[shm.make_producer(gen_verify)],
            limit=gen_limit
        )
    verifies = [
        VerifyStage(
            f"verify{i}",
            ins=[shm.make_consumer(gen_verify, fseq_idx=i, lazy=32)],
            outs=[shm.make_producer(verify_dedup[i])],
            shard_idx=i,
            shard_cnt=n_verify,
            batch=batch,
            max_msg_len=max_msg_len,
            batch_deadline_s=batch_deadline_s,
            precomputed_ok=verify_precomputed,
            comb_slots=verify_comb_slots,
            devices=verify_devices,
        )
        for i in range(n_verify)
    ]
    _take_turns(verifies)
    if use_native_pack:
        dedup = None
        pack = NativePackStage(
            "pack",
            ins=[shm.make_consumer(l, lazy=32) for l in verify_dedup]
            + [shm.make_consumer(l, lazy=8) for l in bank_done],
            outs=[shm.make_producer(l) for l in pack_bank],
            bank_cnt=n_bank,
            n_txn_ins=n_verify,
            clock=slot_clock,
            shed_keep=shed_keep,
            limits=block_limits,
        )
    else:
        dedup = DedupStage(
            "dedup",
            ins=[shm.make_consumer(l, lazy=32) for l in verify_dedup],
            outs=[shm.make_producer(dedup_pack)],
        )
        pack = PackStage(
            "pack",
            ins=[shm.make_consumer(dedup_pack, lazy=32)]
            + [shm.make_consumer(l, lazy=8) for l in bank_done],
            outs=[shm.make_producer(l) for l in pack_bank],
            bank_cnt=n_bank,
            clock=slot_clock,
            shed_keep=shed_keep,
            limits=block_limits,
        )
    # ONE live bank shared by every bank stage (the Frankendancer shape:
    # all bank tiles commit into the same Agave bank over the FFI)
    if bank_ctx is None:
        bank_ctx = default_bank_ctx(slot=slot, n_payers=n_payers,
                                    with_status_cache=status_cache)
    banks = [
        BankStage(
            f"bank{b}",
            ins=[shm.make_consumer(pack_bank[b], lazy=8)],
            outs=[shm.make_producer(bank_poh[b]), shm.make_producer(bank_done[b])],
            bank_idx=b,
            ctx=bank_ctx,
            clock=slot_clock,
        )
        for b in range(n_bank)
    ]
    for bstage in banks:
        bstage.require_credit = True
    if fuse_poh_shred:
        from firedancer_tpu.runtime.shred_stage import FusedPohShredStage

        poh = FusedPohShredStage(
            "poh_shred",
            ins=[shm.make_consumer(l, lazy=8) for l in bank_poh],
            outs=[shm.make_producer(shred_store)],
            clock=slot_clock,
            signer=lambda root: ref.sign(secret, root),
            secret=secret,
            shred_slot=slot,
            keep_sets=keep_sets,
        )
        shred = poh.shred_half
    else:
        poh = PohStage(
            "poh",
            ins=[shm.make_consumer(l, lazy=8) for l in bank_poh],
            outs=[shm.make_producer(poh_shred)],
            clock=slot_clock,
        )
        shred = ShredStage(
            "shred",
            ins=[shm.make_consumer(poh_shred, lazy=8)],
            outs=[shm.make_producer(shred_store)],
            signer=lambda root: ref.sign(secret, root),
            secret=secret,  # arms the native shredder lane when available
            slot=slot,
            keep_sets=keep_sets,
        )
    poh.require_credit = True
    if keep_entries:
        poh.entries = []
    # the leader's own store trusts its own signing path (the reference's
    # shred tile only signature-verifies shreds arriving from OTHER
    # leaders on the retransmit path, fd_fec_resolver_new's NULL-signer
    # contract); receive-path resolvers (repair, turbine ingest, tests)
    # keep full verification
    store = StoreStage(
        "store",
        ins=[shm.make_consumer(shred_store, lazy=64)],
        verify_sig=None,
        trust_membership=True,
    )
    stages = [benchg, *verifies] + ([dedup] if dedup else []) \
        + [pack, *banks, poh] \
        + ([] if fuse_poh_shred else [shred]) + [store]
    return LeaderPipeline(
        stages=stages,
        links=links,
        benchg=benchg,
        verifies=verifies,
        dedup=dedup,
        pack=pack,
        banks=banks,
        poh=poh,
        shred=shred,
        store=store,
        leader_pub=leader_pub,
        bank_ctx=bank_ctx,
    )
