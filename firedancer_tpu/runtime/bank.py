"""Bank stage: executes pack's microblocks for real, feeds PoH, releases locks.

Pipeline position mirrors the reference's bank tile
(/root/reference/src/app/fdctl/run/tiles/fd_bank.c): consume a microblock
from pack, execute + commit it against the LIVE bank, hand the executed
microblock to poh for mixin, and signal pack that this bank is idle again
(the bank_busy release that lets pack schedule conflicting txns).

Execution is the real flamenco runtime: every bank stage commits into ONE
shared `SlotExecution` (flamenco/runtime.py) over funk — fees, status
cache, durable nonces, writability enforcement, native programs, the sBPF
VM with CPI.  That is the reference's shape too: all of Frankendancer's
bank tiles commit into the same live Agave bank through the FFI
(fd_bank.c:186-241); here the shared bank is the in-process `BankCtx`.
Pack guarantees concurrently-scheduled microblocks touch disjoint
accounts, so interleaved commits equal some serial order of the block.

A txn that fails to land (unfunded fee payer, stale blockhash, duplicate
signature) is DROPPED from the emitted entry — the recorded block carries
exactly the txns with an on-chain footprint, so a replayer
(flamenco/runtime.replay_block) reproduces the bank hash from the wire
entries alone.  Executed-but-failed txns landed (fee charged) and stay.

Process-runner note: the topo runner spawns each stage in its own
interpreter, so there the shared bank is the native funk's shm segment:
bank tile 0 makes the store and its fork, every other bank tile
attaches to both as one more writer (`genesis_bank_ctx(funk_attach=)`,
NativeFunk.attach), and each tile's native session takes every account
a microblock names from the segment, where another tile may have
written it since (native/fd_bank.cpp's read-through;
`session_refreshed`).  The
cooperative scheduler runs any bank count against the shared ctx.

Inputs:  ins[0] = pack->bank microblocks.
Outputs: outs[0] = bank->poh executed microblocks; outs[1] = done->pack.

Entry frame out: 32B mixin | u16 txn_cnt | (u16 len || raw txn payload)*.
Done frame out: empty payload, sig = bank index.

Native sweep lane (ISSUE 16): when the exec session and both out
producers are native, the whole after_frag hot path — microblock parse,
session exec, entry build, both publishes — runs inside ONE `fdr_sweep`
crossing per credit window (native/fd_bank.cpp via runtime/bank_native).
Python's before_credit drains the C result log each iteration: applies
the committed records to funk (still the authoritative store), resumes
punted/stalled microblocks on the Python lane IN ORDER, and re-syncs
the session (status-cache gate delta + dirty account values) before the
next sweep.  `FDTPU_NATIVE_BANK=0` forces the Python path.
"""

from __future__ import annotations

import hashlib

from firedancer_tpu.protocol import txn as ft
from firedancer_tpu.tango.rings import MCache
from firedancer_tpu.utils import metrics as fm
from .stage import Stage

# lazy singletons for _drain_native's per-iteration hot path (set on
# first drain; bank_native imports ctypes machinery, so module import
# time stays free of it for python-lane-only users)
_bd = None
_TXN_SUCCESS = None
_now_ns = None


def is_simple_vote(payload: bytes, desc_bytes: bytes) -> bool:
    """pack/cost.py's is_simple_vote over the packed descriptor: one
    instruction, and the vote program's."""
    db = desc_bytes
    if db[16] != 1:
        return False
    o = (db[9] | (db[10] << 8)) + 32 * db[17]
    return payload[o : o + 32] == ft.VOTE_PROGRAM


def parse_microblock(frame: bytes) -> tuple[int, list[bytes]]:
    """-> (mb_seq, [verified-frag bytes])."""
    mb_seq = int.from_bytes(frame[:4], "little")
    cnt = int.from_bytes(frame[4:6], "little")
    frags = []
    o = 6
    for _ in range(cnt):
        ln = int.from_bytes(frame[o : o + 2], "little")
        o += 2
        frags.append(frame[o : o + ln])
        o += ln
    return mb_seq, frags


class BankCtx:
    """The pipeline's live bank: one funk fork + SlotExecution shared by
    every bank stage (and by the pipeline's seal/publish at end of slot)."""

    def __init__(
        self,
        funk=None,
        *,
        slot: int = 1,
        parent_bank_hash: bytes = b"\x00" * 32,
        parent_xid: bytes | None = None,
        status_cache=None,
        blockhashes: tuple[bytes, ...] = (),
        executor=None,
        slot_hashes: list[tuple[int, bytes]] | None = None,
        fork_xid: bytes | None = None,
        join_fork: bool = False,
    ):
        from firedancer_tpu.funk import make_funk

        self.funk = funk if funk is not None else make_funk()
        self.slot = slot
        self.status_cache = status_cache
        if status_cache is not None:
            for bh in blockhashes:
                # recent enough to pass the 150-slot currency gate
                status_cache.register_blockhash(bh, max(0, slot - 1))
        self._parent_bank_hash = parent_bank_hash
        self._parent_xid = parent_xid
        self._executor = executor
        # the SlotHashes sysvar the slot runs under, newest first (None:
        # default_sysvars' empty one, under which every vote rejects)
        self._slot_hashes = slot_hashes
        # the slot's funk fork under a name the caller chose (None: the
        # execution's own), for a reader in another process; join_fork:
        # the fork is there, another process's (SlotExecution `join`)
        self._fork_xid = fork_xid
        self._join_fork = join_fork
        self._sx = None
        # force the native executor .so build/load NOW (one g++ shell-out
        # on cold hosts), not inside the first microblock's after_frag —
        # the same not-mid-stream discipline as verify.py's parser probe
        from firedancer_tpu.flamenco import exec_native

        exec_native.available()

    def fund(self, pubkey: bytes, lamports: int) -> None:
        """Genesis-style funding on the funk root (before the slot runs)."""
        from firedancer_tpu.flamenco.runtime import acct_build

        self.funk.rec_insert(None, pubkey, acct_build(lamports))

    def preload(self, pubkeys) -> None:
        """Push existing funk records into the native session overlay
        (one refresh crossing on the next sync).  A validator enters a
        slot with its accounts DB resident; the session overlay starts
        empty, so without this every first touch of an account punts a
        microblock to the resume lane.  Harnesses that know their
        account set call this after the pipeline arms to start the
        native sweeps steady-state.  No-op on the Python lane."""
        sx = self.sx
        if sx._native_for_batch() is not None:
            sx._native_dirty.update(bytes(k) for k in pubkeys)

    @property
    def sx(self):
        from firedancer_tpu.flamenco.runtime import SlotExecution

        if self._sx is None:
            self._sx = SlotExecution(
                self.funk,
                slot=self.slot,
                parent_bank_hash=self._parent_bank_hash,
                parent_xid=self._parent_xid,
                executor=self._executor,
                status_cache=self.status_cache,
                slot_hashes=self._slot_hashes,
                xid=self._fork_xid,
                join=self._join_fork,
            )
        return self._sx

    def execute(self, payload: bytes, desc: ft.Txn):
        return self.sx.execute(payload, desc)

    def execute_batch(self, items):
        """One burst (microblock) through SlotExecution.execute_batch:
        native-eligible txns ride the C++ lane in one FFI call."""
        return self.sx.execute_batch(items)

    def seal(self, poh_hash: bytes):
        """End of slot: bank hash over the committed state."""
        return self.sx.seal(poh_hash)

    def publish(self) -> None:
        self.sx.publish()


# size_of::<VoteStateVersions>() and its rent-exempt minimum under the
# default Rent ((3762 + 128) bytes x 3480 lamports a byte-year x 2 years)
VOTE_ACCOUNT_LAMPORTS = 27_074_400


def genesis_bank_ctx(
    *,
    slot: int = 1,
    seed: bytes = b"benchg",
    n_payers: int = 8,
    payer_lamports: int = 10**12,
    with_status_cache: bool = True,
    payers=None,
    voters=(),
    slot_hashes=None,
    preload=(),
    funk_shm: str | None = None,
    fork_xid: bytes | None = None,
    funk_attach: bool = False,
) -> BankCtx:
    """The bank a leader enters its slot with, made from a seed.

    funk_shm: the name of the account store's shm segment (the native
    funk's; a bank tile in a process of its own is given its run's, so
    that the supervisor can `NativeFunk.attach_readonly` it and can
    unlink it if the tile dies).  None: a name of the funk's own.
    fork_xid: the slot's funk fork under this name (BankCtx), which is
    what such a reader asks the store for.
    funk_attach: the store `funk_shm` and its fork `fork_xid` are
    another bank tile's, which made them from the same arguments: this
    ctx attaches to both as one more writer and funds nothing.  The
    tile that makes the store says it is whole (`set_ready`) once the
    genesis is in and the fork prepared; the attach waits for that.
    Without a status cache only: one a process would let a repeat land
    once a tile.

    Payers: the synthetic load's `n_payers` keypairs off `seed`
    (runtime/benchg.pool_payers), or the explicit `payers` pubkeys in
    their place, each funded; the pool's blockhash passes the
    status-cache currency gate.

    voters: (identity pubkey, vote account address) pairs.  Each
    identity is funded as a fee payer; each vote account holds a
    current-version VoteState (3,762 bytes, owner the vote program)
    with the identity as node, authorized voter and withdrawer.

    slot_hashes: the SlotHashes sysvar as (slot, hash) pairs, newest
    first; `slot`, the bank's, lies after them.  With it the sysvar is
    readable by the vote program and, as Clock, nameable as an account
    (Agave's vote instruction names both): the two sysvar accounts are
    created with the blobs the slot runs under.

    preload: addresses pushed into the native session before the first
    microblock (BankCtx.preload), so that no first touch punts."""
    from firedancer_tpu.flamenco.blockstore import StatusCache
    from firedancer_tpu.flamenco.runtime import acct_build
    from .benchg import pool_blockhash, pool_payers

    if slot_hashes is not None:
        slot_hashes = list(slot_hashes)
    funk = None
    if funk_attach:
        from firedancer_tpu.funk.funk_native import NativeFunk

        if with_status_cache:
            raise ValueError("a bank that attaches to another's store has "
                             "no status cache (with_status_cache=False)")
        ctx = BankCtx(NativeFunk.attach(funk_shm), slot=slot,
                      slot_hashes=slot_hashes, fork_xid=fork_xid,
                      join_fork=True)
        if preload:
            ctx.preload(preload)
        return ctx
    if funk_shm is not None:
        from firedancer_tpu.funk import funk_native

        if funk_native.available():
            funk = funk_native.NativeFunk(shm_name=funk_shm)
    ctx = BankCtx(
        funk,
        slot=slot,
        status_cache=StatusCache() if with_status_cache else None,
        blockhashes=(pool_blockhash(seed),),
        slot_hashes=slot_hashes,
        fork_xid=fork_xid,
    )
    if payers is None:
        payers = [pub for _, pub in pool_payers(seed, n_payers)]
    for pub in payers:
        ctx.fund(pub, payer_lamports)
    if voters:
        from firedancer_tpu.flamenco import agave_state as ast
        from firedancer_tpu.flamenco.vote_program import VOTE_STATE_SIZE
        from firedancer_tpu.protocol.txn import VOTE_PROGRAM

        for identity, vote_account in voters:
            ctx.fund(identity, payer_lamports)
            state = ast.vote_state_encode(ast.VoteState(
                node_pubkey=identity, authorized_withdrawer=identity,
                authorized_voters={0: identity}))
            ctx.funk.rec_insert(None, vote_account, acct_build(
                VOTE_ACCOUNT_LAMPORTS, state.ljust(VOTE_STATE_SIZE, b"\x00"),
                owner=VOTE_PROGRAM))
    if slot_hashes is not None:
        from firedancer_tpu.flamenco import types as T
        from firedancer_tpu.flamenco.runtime import default_sysvars
        from firedancer_tpu.flamenco.solcompat import SYSVAR_NAMES, SYSVAR_OWNER

        # the blobs SlotExecution will run the slot under (the fork is
        # prepared at the first `ctx.sx`: genesis writes come before it)
        blobs = {"clock": default_sysvars(slot)["clock"],
                 "slot_hashes": T.SLOT_HASHES.encode(
                     [T.SlotHash(s, h) for s, h in slot_hashes])}
        for addr, name in SYSVAR_NAMES.items():
            if name in blobs:
                ctx.funk.rec_insert(None, addr, acct_build(
                    1, blobs[name], owner=SYSVAR_OWNER))
    if preload:
        ctx.preload(preload)
    if funk is not None:
        # a store under a run's name: another bank tile may be waiting
        # to attach (funk_attach), and finds the genesis and the fork
        ctx.sx
        funk.set_ready()
    return ctx


def seeded_validators(seed: bytes = b"benchg", *, n_voters: int,
                      n_slot_hashes: int, first_slot: int = 1) -> dict:
    """`genesis_bank_ctx`'s `voters`, `slot_hashes` and `slot` for a
    validator set and a SlotHashes sysvar made from a seed: validator k
    has the identity key of secret sha256(seed | "voter<k>") and the
    vote account sha256(seed | "voteacct<k>"); slot s of the
    `n_slot_hashes` from `first_slot` on has the hash
    sha256(seed | "slothash<s>"); the bank's slot follows them."""
    from firedancer_tpu.ops.ref import ed25519_ref as ref

    voters = [(ref.public_key(hashlib.sha256(seed + b"voter%d" % k).digest()),
               hashlib.sha256(seed + b"voteacct%d" % k).digest())
              for k in range(n_voters)]
    last = first_slot + n_slot_hashes - 1
    return {"voters": voters,
            "slot_hashes": [
                (s, hashlib.sha256(seed + b"slothash%d" % s).digest())
                for s in range(last, first_slot - 1, -1)],
            "slot": last + 1}


def default_bank_ctx(**kw) -> BankCtx:
    """`genesis_bank_ctx`'s payer-only case: a ctx pre-funded for the
    synthetic benchg load (slot, seed, n_payers, payer_lamports,
    with_status_cache)."""
    return genesis_bank_ctx(**kw)


class BankStage(Stage):
    @classmethod
    def extra_schema(cls) -> fm.MetricsSchema:
        return (
            fm.MetricsSchema()
            .counter("txn_exec", "txns landed (fee charged)")
            .counter("txn_exec_failed", "landed txns whose program failed")
            .counter("txn_exec_votes", "simple votes among txn_exec")
            .counter("txn_exec_failed_votes",
                     "simple votes among txn_exec_failed (a vote that"
                     " lands after a later one of its validator fails"
                     " VoteTooOld and still pays its fee)")
            .counter("txn_rejected", "txns with no on-chain footprint")
            .counter("microblocks", "microblocks committed")
            .counter("native_exec",
                     "txns committed by the C++ fast lane")
            .counter("native_punt",
                     "C++ fast-lane punts resumed on the Python lane")
            .counter("slot_boundaries",
                     "slot-clock boundaries observed (slot-clock mode:"
                     " the in-flight microblock always finishes — commits"
                     " are atomic per after_frag — and the boundary is"
                     " only ever crossed BETWEEN microblocks)")
            # bank sweep lane (native/fd_bank.cpp), absolute values
            # copied from the C counter tail in during_housekeeping
            .counter("bank_mb_seen", "microblocks entering the C sweep")
            .counter("bank_mb_native",
                     "microblocks fully committed+published in C")
            .counter("bank_mb_stashed",
                     "microblocks stashed for the Python-lane drain"
                     " (punt, credit stall, or publish fallback)")
            .counter("bank_txn_native",
                     "txns the C sweep committed session-side")
            .counter("bank_credit_waits",
                     "sweep stalls: an out ring had no credit pre-exec")
            .counter("bank_mb_dropped",
                     "log-arena OOM before commit (never-path diag)")
            .counter("bank_funk_writes",
                     "records the C sweep inserted into the native funk"
                     " map in-crossing")
            .counter("bank_funk_falls",
                     "groups that fell back to full-value logging")
            .counter("session_refreshed",
                     "account values this tile's session took from the"
                     " store's segment: every account a microblock names"
                     " where other bank tiles write the store too (0 where"
                     " this tile is its one writer)")
            # this tile's use of the account store's lock (fd_funk.cpp;
            # NativeFunk.lock_stats), copied in during_housekeeping
            .counter("funk_lock_acquires",
                     "holds of the store's lock (the sweep: two a"
                     " microblock, its reads and its writes)")
            .counter("funk_lock_contended",
                     "of those, the ones that found it held")
            .counter("funk_lock_wait_ns", "ns spent waiting for it")
            # native-owned (ISSUE 20): fdb_frag_cb observes each
            # committed txn's commit latency in-crossing — the Python
            # facade never touches this histogram
            .histogram(
                "nbank_txn_lat_ns", fm.exp_buckets(1e3, 1e10, 24),
                "per-txn commit latency (tsorig -> session commit),"
                " stamped by the C sweep lane",
                native=True,
            )
        )

    def __init__(self, *args, bank_idx: int = 0, ctx: BankCtx | None = None,
                 clock=None, **kwargs):
        super().__init__(*args, **kwargs)
        self.bank_idx = bank_idx
        self.ctx = ctx if ctx is not None else default_bank_ctx()
        # the stage-extra plane histogram (ISSUE 20): the sweep harness
        # binds this name as the plane's xlat slot for fdb_frag_cb
        self.native_xlat_metric = "nbank_txn_lat_ns"
        # per-microblock commit latency vs the oldest txn's origin stamp
        # (the bencho measurement point: txn acknowledged by the runtime)
        self.commit_latencies_ns: list[int] = []
        # slot-clock awareness (runtime/slot_clock): the bank's half of
        # the deadline-aware block close is structural — a microblock
        # commit is atomic inside after_frag, so the boundary can only
        # fall between microblocks and "in-flight work finishes" needs
        # no special path.  The stage still OBSERVES boundaries (one
        # clock read per loop sweep in before_credit, FD202) so the
        # flight trace shows where each slot's commits ended.
        from .slot_clock import resolve_clock

        self._clock = resolve_clock(clock)
        self._clock_slot = (self._clock.cfg.slot0
                            if self._clock is not None else 0)
        # bank sweep lane: armed when the exec session is live and both
        # out producers are native — the sweep harness (stage.py) then
        # routes whole credit windows through fdb_frag_cb
        self._armed_ctx = None
        self._lock_long_seen = 0
        self._arm_native()

    def native_lanes(self) -> dict[str, bool]:
        return dict(super().native_lanes(),
                    bank=self._sweep_client is not None,
                    funk=hasattr(self.ctx.funk, "txn_diff"))

    def _arm_native(self) -> None:
        self._sweep_client = None
        from . import bank_native as bd

        if not bd.available():
            return
        if len(self.outs) < 2 or any(
            type(p).__name__ != "NativeProducer" for p in self.outs[:2]
        ):
            return
        sx = self.ctx.sx
        nat = sx._native_for_batch()
        if nat is None or sx._native_session is None:
            return
        try:
            hdr = bd.make_hdr(nat, gated=sx.status_cache is not None)
            self._sweep_client = bd.StageClient(
                sx._native_session, hdr, self.outs[0], self.outs[1],
                bank_idx=self.bank_idx,
            )
            self._armed_ctx = nat
            # native funk plane: when the authoritative store is the shm
            # map, the C side writes committed records into it inside
            # the sweep crossing and the drain shrinks to result-log
            # accounting (the xid is the slot's fork — BankCtx.sx is
            # one slot, so its lifetime is the client's)
            fk = sx.funk
            if hasattr(fk, "txn_diff") and getattr(fk, "_h", None):
                self._sweep_client.set_funk(fk, sx.xid)
        except bd.NativeUnavailable:
            self._sweep_client = None

    def _disarm_native(self) -> None:
        """The exec session died (poisoned mid-resume): the C client's
        session pointer is stale, so the sweep must never run again —
        close it BEFORE returning to the harness (which rebuilds its
        cached drainer on client change and falls back per-frag)."""
        c = self._sweep_client
        self._sweep_client = None
        self._armed_ctx = None
        if c is not None:
            c.close()

    def before_credit(self) -> None:
        self._drain_native()
        if self._clock is None:
            return
        now = self._clock.now()
        slot = self._clock.slot_at(now)
        last = self._clock.last_slot()
        if last is not None:
            slot = min(slot, last + 1)  # window-bounded, like pack's
        if slot > self._clock_slot:
            self._loop_worked = True    # a slot rolled
            self.metrics.inc("slot_boundaries", slot - self._clock_slot)
            self.trace(fm.EV_SLOT_ROLL, slot)
            self._clock_slot = slot

    def during_housekeeping(self) -> None:
        c = self._sweep_client
        counters = c.counters() if c is not None else {}
        sx = self.ctx._sx
        if sx is not None and sx.session_refreshed:
            # the Python lane's share of the read-through (a microblock
            # the sweep punted), beside the sweep's own
            counters["session_refreshed"] = sx.session_refreshed \
                + counters.get("session_refreshed", 0)
        self.metrics.counters.update(counters)
        if c is not None:
            self._copy_sweep_counters()
        fk = self.ctx.funk
        if hasattr(fk, "lock_stats"):
            st = fk.lock_stats()
            self.metrics.counters.update(
                funk_lock_acquires=st["acquires"],
                funk_lock_contended=st["contended"],
                funk_lock_wait_ns=st["wait_ns"])
            if st["long_waits"] != self._lock_long_seen:
                # the last wait over 100 us since the last look
                self._lock_long_seen = st["long_waits"]
                self.trace(fm.EV_FUNK_LOCK_WAIT, fm.funk_lock_wait_arg(
                    st["long_holder"], st["long_ns"]))

    def flush(self) -> None:
        """Settle any pending stash (end-of-run: the harness stops
        sweeping, so the result log must not hold unresumed work)."""
        self._drain_native()

    def _drain_native(self) -> None:
        """Drain the C sweep's result log: apply committed records to
        funk, resume stashed microblocks on the Python lane in arrival
        order, publish their frames, then re-sync the session so the
        next sweep sees every Python-side landing and write."""
        c = self._sweep_client
        if c is None:
            return
        # hot path: these run once per bank per iteration, so the import
        # machinery (1 dict probe per `from x import y` even when cached)
        # is hoisted into module-level lazy singletons
        global _bd, _TXN_SUCCESS, _now_ns
        if _bd is None:
            from . import bank_native as _bd_mod
            from firedancer_tpu.flamenco.runtime import TXN_SUCCESS as _ts
            from firedancer_tpu.tango.shm import now_ns as _nn
            _bd, _TXN_SUCCESS, _now_ns = _bd_mod, _ts, _nn
        bd, TXN_SUCCESS, now_ns = _bd, _TXN_SUCCESS, _now_ns

        sx = self.ctx.sx
        log = c.take_log()
        if log:
            self._loop_worked = True    # the last sweep's results land here
            groups = bd.parse_log(log)
            # All-or-nothing credit gate: the C lane stashed these
            # microblocks BECAUSE an out ring had no credit, and
            # Stage.publish drops on failure.  State application is not
            # replayable (funk writes would double-apply), so the whole
            # drain defers until the consumers freed enough credits for
            # every pending publish.  Meanwhile stash_pending keeps the
            # C lane appending raw frags, bounded by the input ring.
            need_ent = sum(1 for g in groups if g[4] == 0)
            need_done = sum(1 for g in groups if g[4] != 1)
            if need_ent or need_done:
                for p in self.outs[:2]:
                    p.refresh_credits()
                if (self.outs[0].cr_avail < need_ent
                        or self.outs[1].cr_avail < need_done):
                    return
            from_bytes = int.from_bytes
            for (mb_seq, tsorig, lat_ns, n_done, published, recs,
                 mb) in groups:
                _seq, frags = parse_microblock(mb)
                if published:
                    # entry (and for ==1 the done frame) already on the
                    # rings: result accounting only, straight off the
                    # frag bytes — no payload/descriptor slices, no
                    # per-frag tuple list
                    (n_ok, n_fail, n_rej, n_vote,
                     n_vote_fail) = sx.native_apply_group(frags, recs)
                    if n_ok:
                        self.metrics.inc("txn_exec", n_ok)
                    if n_fail:
                        self.metrics.inc("txn_exec_failed", n_fail)
                    if n_vote:
                        self.metrics.inc("txn_exec_votes", n_vote)
                    if n_vote_fail:
                        self.metrics.inc("txn_exec_failed_votes", n_vote_fail)
                    if n_rej:
                        self.metrics.inc("txn_rejected", n_rej)
                    self.metrics.inc("native_exec", n_done)
                    self.metrics.inc("microblocks")
                    self.trace(fm.EV_MICROBLOCK, n_ok)
                    if tsorig and len(self.commit_latencies_ns) < 100_000:
                        self.commit_latencies_ns.append(int(lat_ns))
                    if published == 2:
                        # entry is out; only the done frame was deferred
                        self.publish(1, b"", sig=self.bank_idx)
                    continue
                sigs: list[bytes] = []
                txns: list[bytes] = []
                batch = []
                n_ok = n_fail = n_rej = 0
                for frag, (status, fee, writes) in zip(frags, recs):
                    psz = from_bytes(frag[-2:], "little")
                    p, db = frag[:psz], frag[psz:-2]
                    batch.append((p, db, status, fee, writes))
                    if fee > 0:
                        sig_off = db[2] | (db[3] << 8)
                        sigs.append(p[sig_off : sig_off + 64])
                        txns.append(p)
                        n_ok += 1
                        if status != TXN_SUCCESS:
                            n_fail += 1
                        self._count_vote(p, db, status != TXN_SUCCESS)
                    else:
                        n_rej += 1
                if batch:
                    sx.native_apply_batch(batch)
                if n_ok:
                    self.metrics.inc("txn_exec", n_ok)
                if n_fail:
                    self.metrics.inc("txn_exec_failed", n_fail)
                if n_rej:
                    self.metrics.inc("txn_rejected", n_rej)
                self.metrics.inc("native_exec", n_done)
                # published == 0: resume the tail in order, then publish
                # both frames from Python (byte-identical entry format)
                items = []
                for frag in frags[n_done:]:
                    psz = int.from_bytes(frag[-2:], "little")
                    items.append((frag[:psz], None, frag[psz:-2]))
                nd0, np0 = sx.native_done_cnt, sx.native_punt_cnt
                results = self.ctx.execute_batch(items) if items else []
                d_native = sx.native_done_cnt - nd0
                d_punt = sx.native_punt_cnt - np0
                if d_native:
                    self.metrics.inc("native_exec", d_native)
                if d_punt:
                    self.metrics.inc("native_punt", d_punt)
                    self.trace(fm.EV_NATIVE_PUNT, d_punt)
                for (p, _desc, db), r in zip(items, results):
                    if r.fee > 0:
                        sig_off = db[2] | (db[3] << 8)
                        sigs.append(p[sig_off : sig_off + 64])
                        txns.append(p)
                        self.metrics.inc("txn_exec")
                        if r.status != TXN_SUCCESS:
                            self.metrics.inc("txn_exec_failed")
                        self._count_vote(p, db, r.status != TXN_SUCCESS)
                    else:
                        self.metrics.inc("txn_rejected")
                self.metrics.inc("microblocks")
                self.trace(fm.EV_MICROBLOCK, len(txns))
                if tsorig and len(self.commit_latencies_ns) < 100_000:
                    self.commit_latencies_ns.append(now_ns() - tsorig)
                if txns:
                    mixin = hashlib.sha256(b"".join(sigs)).digest()
                    out = bytearray()
                    out += mixin
                    out += len(txns).to_bytes(2, "little")
                    for p in txns:
                        out += len(p).to_bytes(2, "little")
                        out += p
                    self.publish(0, bytes(out), sig=mb_seq, tsorig=tsorig)
                self.publish(1, b"", sig=self.bank_idx)
            c.clear_log()
        # session coherence before the next sweep; a poisoned session
        # (mid-resume failure) permanently disarms the lane
        if not sx.native_sync():
            self._disarm_native()
            return
        # the env header follows BatchContext rebuilds (sysvar swap)
        nat = sx._native_ctx or None
        if nat is not self._armed_ctx and nat is not None:
            try:
                self._sweep_client.set_hdr(
                    bd.make_hdr(nat, gated=sx.status_cache is not None))
                self._armed_ctx = nat
            except bd.NativeUnavailable:
                self._disarm_native()

    def _count_vote(self, payload: bytes, desc_bytes: bytes,
                    failed: bool) -> None:
        """A landed txn on the Python-lane paths: count it if it is a
        simple vote (the sweep drain counts its own in
        SlotExecution.native_apply_group)."""
        if is_simple_vote(payload, desc_bytes):
            self.metrics.inc("txn_exec_votes")
            if failed:
                self.metrics.inc("txn_exec_failed_votes")

    def after_frag(self, in_idx: int, meta, payload: bytes) -> None:
        from firedancer_tpu.flamenco.runtime import TXN_SUCCESS

        if self._sweep_client is not None:
            # mixed-lane splice: a frag arrived on the per-frag path
            # while the sweep lane is armed — settle the C log first so
            # microblock order stays ring order, then commit in Python
            # (the next drain's sync re-ships whatever this dirties)
            self._drain_native()
        mb_seq, frags = parse_microblock(payload)
        # zero-copy commit path: the verified frag already carries
        # payload || packed descriptor || u16 payload_sz, which is exactly
        # what the native lane consumes — no Txn unpack for native
        # traffic (execute_batch unpacks + validates only on fallback)
        items = []
        for frag in frags:
            psz = int.from_bytes(frag[-2:], "little")
            items.append((frag[:psz], None, frag[psz:-2]))
        # native-lane attribution: bracket the batch with the shared
        # SlotExecution's counters (safe: bank stages sharing a ctx run
        # cooperatively in one thread; a process topology's bank tiles
        # have a ctx each)
        sx = self.ctx.sx
        nd0, np0 = sx.native_done_cnt, sx.native_punt_cnt
        results = self.ctx.execute_batch(items)
        d_native = sx.native_done_cnt - nd0
        d_punt = sx.native_punt_cnt - np0
        if d_native:
            self.metrics.inc("native_exec", d_native)
        if d_punt:
            self.metrics.inc("native_punt", d_punt)
            self.trace(fm.EV_NATIVE_PUNT, d_punt)
        sigs = []
        txns = []
        for (p, _desc, db), r in zip(items, results):
            # landed == fee charged: the SAME predicate SlotExecution
            # uses for signature_cnt and status-cache staging — the two
            # must never disagree or replay diverges from the sealed hash
            if r.fee > 0:
                # landed (fee-charged, possibly failed): part of the block
                sig_off = db[2] | (db[3] << 8)
                sigs.append(p[sig_off : sig_off + 64])
                txns.append(p)
                self.metrics.inc("txn_exec")
                if r.status != TXN_SUCCESS:
                    self.metrics.inc("txn_exec_failed")
                self._count_vote(p, db, r.status != TXN_SUCCESS)
            else:
                # no on-chain footprint: never recorded in an entry
                self.metrics.inc("txn_rejected")
        self.metrics.inc("microblocks")
        self.trace(fm.EV_MICROBLOCK, len(txns))
        tsorig = int(meta[MCache.COL_TSORIG])
        if tsorig and len(self.commit_latencies_ns) < 100_000:
            from firedancer_tpu.tango.shm import now_ns

            self.commit_latencies_ns.append(now_ns() - tsorig)
        if txns:
            mixin = hashlib.sha256(b"".join(sigs)).digest()
            out = bytearray()
            out += mixin
            out += len(txns).to_bytes(2, "little")
            for p in txns:
                out += len(p).to_bytes(2, "little")
                out += p
            self.publish(0, bytes(out), sig=mb_seq, tsorig=tsorig)  # -> poh
        self.publish(1, b"", sig=self.bank_idx)  # -> pack (lock release)
