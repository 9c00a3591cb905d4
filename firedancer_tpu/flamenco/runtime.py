"""Runtime: slot execution over funk with conflict-wave parallelism.

The execution-side slice of the reference's flamenco runtime
(/root/reference/src/flamenco/runtime/fd_runtime.c): a block's
transactions execute against a funk fork in *waves* — maximal groups of
transactions with disjoint account rw-sets (wave generation
fd_runtime.c:1717-1736, fd_runtime_execute_txns_in_waves_tpool :1815) —
and the slot finalizes into a bank hash chaining the parent hash, the
accounts-delta lattice hash, the signature count and the PoH hash
(fd_hashes.c's formula shape).

TPU-native twist: a wave's txns are executable in any order — the same
property the reference exploits with a tpool is what batches device
work here: per-wave sigverify batches ride ops/sigverify, and the
accounts-delta hash sums every modified account's lattice hash in ONE
device reduction (ops/lthash.combine_device) instead of a sequential
accumulation.

Account model: funk value bytes = `u64 lamports | 32B owner |
u8 executable | data` (executor.acct_encode/decode).  Program dispatch
goes through flamenco/executor.py — native programs (system, vote,
stake) plus sBPF programs with CPI; a failed txn still pays its fee,
errors never abort the block.
"""

from __future__ import annotations

import hashlib
import itertools
from dataclasses import dataclass, field

import numpy as np

_xid_seq = itertools.count()

from firedancer_tpu.flamenco import executor as fexec
from firedancer_tpu.flamenco.executor import (
    Account,
    Executor,
    InstrAccount,
    InstrError,
    TxnCtx,
    acct_decode,
    acct_encode,
)
from firedancer_tpu.funk import Funk
from firedancer_tpu.ops import lthash as lt
from firedancer_tpu.protocol import txn as ft

LAMPORTS_PER_SIGNATURE = 5000

TXN_SUCCESS = 0
TXN_ERR_FEE = -1                 # payer cannot cover the fee: txn dropped
TXN_ERR_INSUFFICIENT_FUNDS = -2  # program failed: fee charged, no effects
TXN_ERR_ACCT = -3                # unresolvable account index (ALT accounts
                                 # need the address-resolution stage)
TXN_ERR_PROGRAM = -4             # program/VM error: fee charged, no effects
TXN_ERR_BLOCKHASH = -5           # recent_blockhash unknown/expired: no fee
TXN_ERR_ALREADY_PROCESSED = -6   # signature already landed on this fork


def acct_lamports(val: bytes | None) -> int:
    return acct_decode(val)[0]


def acct_build(lamports: int, data: bytes = b"",
               owner: bytes = ft.SYSTEM_PROGRAM,
               executable: bool = False) -> bytes:
    return acct_encode(lamports, owner, executable, data)


@dataclass
class TxnResult:
    status: int
    fee: int


@dataclass
class BlockResult:
    slot: int
    bank_hash: bytes
    accounts_delta: np.ndarray  # (1024,) uint16 lattice value
    signature_cnt: int
    fees: int
    results: list[TxnResult]
    waves: list[list[int]]  # txn indices per wave
    xid: bytes


def _rw_sets(
    payload: bytes, desc: ft.Txn,
    extra: tuple[list[bytes], list[bytes]] | None = None,
) -> tuple[set[bytes], set[bytes]]:
    addrs = desc.acct_addrs(payload)
    w, r = set(), set()
    for i, a in enumerate(addrs):
        (w if desc.is_writable(i) else r).add(a)
    if extra is not None:
        # resolved ALT addresses: exact rw sets, plus a READ lock on each
        # table so an in-block extend/close serializes against its users
        ew, er = extra
        w.update(ew)
        r.update(er)
        for lut in desc.addr_luts:
            r.add(payload[lut.addr_off : lut.addr_off + 32])
    else:
        # unresolved (failed lookup or legacy caller without resolution):
        # conservatively WRITE-lock the table address itself so two txns
        # loading from one table never share a wave (the same rule the
        # pack scheduler applies, pack/scheduler.py acct_sets)
        for lut in desc.addr_luts:
            w.add(payload[lut.addr_off : lut.addr_off + 32])
    return w, r


def generate_waves(
    txns: list[tuple[bytes, ft.Txn]],
    extras: list[tuple[list[bytes], list[bytes]] | None] | None = None,
) -> list[list[int]]:
    """Partition txn indices into conflict-free waves, equivalent to
    serial block order: a writer lands strictly after every earlier
    reader AND writer of each of its accounts; a reader lands strictly
    after every earlier writer (readers may share a wave).  No
    gap-filling below a conflict — that would let a later txn's effects
    become visible to an earlier txn (the property the reference's wave
    generation preserves, fd_runtime.c:1717-1736)."""
    waves: list[list[int]] = []
    last_w: dict[bytes, int] = {}  # acct -> last wave with a writer
    last_r: dict[bytes, int] = {}  # acct -> last wave with a reader
    for i, (payload, desc) in enumerate(txns):
        w, r = _rw_sets(payload, desc,
                        extras[i] if extras is not None else None)
        wi = 0
        for a in w:
            wi = max(wi, last_w.get(a, -1) + 1, last_r.get(a, -1) + 1)
        for a in r:
            wi = max(wi, last_w.get(a, -1) + 1)
        while wi >= len(waves):
            waves.append([])
        waves[wi].append(i)
        for a in w:
            last_w[a] = max(last_w.get(a, -1), wi)
        for a in r:
            last_r[a] = max(last_r.get(a, -1), wi)
    return waves


_DEFAULT_EXECUTOR: Executor | None = None


def default_executor() -> Executor:
    global _DEFAULT_EXECUTOR
    if _DEFAULT_EXECUTOR is None:
        _DEFAULT_EXECUTOR = Executor()
    return _DEFAULT_EXECUTOR


def default_sysvars(slot: int) -> dict:
    """The sysvar blobs programs read via sol_get_*_sysvar: clock at the
    executing slot, default rent and epoch schedule (grows alongside the
    bank state)."""
    from firedancer_tpu.flamenco import types as T

    import hashlib as _hl

    sched = T.EpochSchedule()
    epoch = slot // sched.slots_per_epoch
    return {
        "clock": T.CLOCK.encode(T.Clock(slot=slot, epoch=epoch)),
        "rent": T.RENT.encode(T.Rent()),
        "epoch_schedule": T.EPOCH_SCHEDULE.encode(sched),
        # recent bank hashes the vote program validates against: empty
        # here (every vote rejects) until the caller supplies the real
        # entries — replay/consensus via execute_block(slot_hashes=...),
        # a leader's bank via BankCtx(slot_hashes=...) /
        # runtime/bank.genesis_bank_ctx
        "slot_hashes": T.SLOT_HASHES.encode([]),
        # Fees { fee_calculator: { lamports_per_signature } }
        "fees": LAMPORTS_PER_SIGNATURE.to_bytes(8, "little"),
        # EpochRewards: distribution_starting_block_height u64 |
        # num_partitions u64 | parent_blockhash 32 | total_points u128 |
        # total_rewards u64 | distributed_rewards u64 | active bool —
        # inactive outside the distribution window
        "epoch_rewards": bytes(8 + 8 + 32 + 16 + 8 + 8 + 1),
        "last_restart_slot": (0).to_bytes(8, "little"),
        # the slot's blockhash view for the nonce family; execute_block
        # overrides with the real parent bank hash
        "recent_blockhash": _hl.sha256(
            b"fdtpu:rbh:" + slot.to_bytes(8, "little")
        ).digest(),
    }


def _advance_nonce_account(funk, xid, payload, desc, addrs, sysvars) -> None:
    """A FAILED durable-nonce txn still advances its nonce account: the
    fee debit and the rotated nonce are the txn's on-chain footprint
    (fd_runtime.c saves the advanced nonce for failed txns too) — else,
    once StatusCache.purge_below prunes the signature, the identical
    signed txn passes durable_nonce_ok again and re-lands."""
    from firedancer_tpu.flamenco import nonce as _n

    ins = desc.instrs[0]
    key = addrs[payload[ins.acct_off]]
    lam, owner, ex, data = acct_decode(funk.rec_query(xid, key))
    state, auth, _cur = _n.decode_state(data)
    if state != _n.STATE_INIT:
        return
    bh = (sysvars or {}).get("recent_blockhash")
    if not bh:
        return
    data = bytearray(data)
    data[: _n.DATA_LEN] = _n.encode_state(
        _n.STATE_INIT, auth, _n.next_nonce(bh, key)
    )
    funk.rec_insert(xid, key, acct_encode(lam, owner, ex, bytes(data)))


def _execute_txn(
    funk: Funk, xid: bytes, payload: bytes, desc: ft.Txn,
    executor: Executor | None = None,
    sysvars: dict | None = None,
    extra: tuple[list[bytes], list[bytes]] | None = None,
    durable_nonce: bool = False,
) -> TxnResult:
    from firedancer_tpu.flamenco.programs import AcctError, FundsError

    executor = executor or default_executor()
    addrs = desc.acct_addrs(payload)
    if desc.addr_luts:
        if extra is None:
            # lookup resolution failed (missing/foreign/short table or
            # index out of range): typed per-txn failure, block continues
            return TxnResult(TXN_ERR_ACCT, 0)
        # combined index space: static, then loaded-writable, then
        # loaded-readonly — matching Txn.is_writable
        addrs = addrs + extra[0] + extra[1]
    if len(set(addrs)) != len(addrs):
        # AccountLoadedTwice analog: duplicate addresses would load as
        # independent copies — stale reads + lamport mint/burn at commit
        return TxnResult(TXN_ERR_ACCT, 0)
    payer = addrs[0]
    # the txn's requested compute budget + heap (SetComputeUnitLimit /
    # RequestHeapFrame) drive execution — pack only *costs* them; here
    # they are ENFORCED (the r3 gap: VM budget was fixed at 200k) — and
    # its priority fee (limit x SetComputeUnitPrice, the fee pack
    # ordered it by) is charged with the signature fee, whatever the
    # outcome (Agave's fee calculation)
    from firedancer_tpu.pack.cost import txn_budget_fee

    budget = txn_budget_fee(payload, desc)
    fee = LAMPORTS_PER_SIGNATURE * desc.signature_cnt + (
        budget[2] if budget is not None else 0)
    payer_val = funk.rec_query(xid, payer)
    if acct_lamports(payer_val) < fee:
        return TxnResult(TXN_ERR_FEE, 0)
    # charge the fee unconditionally (failed txns still pay, fd_executor);
    # written straight to funk so program failure cannot roll it back
    plam, powner, pex, pdata = acct_decode(payer_val)
    funk.rec_insert(xid, payer, acct_encode(plam - fee, powner, pex, pdata))

    def _fail(status: int) -> TxnResult:
        # fee-charged failure: a durable-nonce txn's nonce must rotate
        # even though every other program effect is discarded
        if durable_nonce:
            _advance_nonce_account(funk, xid, payload, desc, addrs, sysvars)
        return TxnResult(status, fee)

    # load the unique account set into host objects; program effects land
    # in funk only at commit, so failure = skip the writeback (fee stays)
    accounts = [
        Account.from_value(a, funk.rec_query(xid, a)) for a in addrs
    ]
    signer = [i < desc.signature_cnt for i in range(len(addrs))]
    writable = [desc.is_writable(i) for i in range(len(addrs))]
    baseline = [a.to_value() for a in accounts]
    if budget is None:
        # malformed compute-budget instruction: typed failure, fee stays
        # charged (pack's cost model would have dropped it pre-block)
        return _fail(TXN_ERR_PROGRAM)
    cu_limit, heap_size, _prio = budget
    # resolve upgradeable programs' programdata up front (the reference's
    # account loader does the same indirection, fd_executor.c load path);
    # a broken indirection surfaces as a typed failure at invoke time
    from firedancer_tpu.flamenco import bpf_loader as bl

    program_elfs: dict = {}
    for a in accounts:
        if a.executable and a.owner == bl.UPGRADEABLE_LOADER_PROGRAM:
            try:
                pd_addr = bl.program_programdata(bytes(a.data))
                pd_val = funk.rec_query(xid, pd_addr)
                _lam, _owner, _ex, pd_data = acct_decode(pd_val)
                deploy_slot, _auth = bl.programdata_meta(pd_data)
                program_elfs[a.key] = (bl.programdata_elf(pd_data),
                                       deploy_slot)
            except InstrError:
                pass  # left unresolved: invocation fails typed
    ctx = TxnCtx(accounts=accounts, signer=signer, writable=writable,
                 sysvars=sysvars or {}, budget=cu_limit,
                 heap_size=heap_size, program_elfs=program_elfs,
                 instr_datas=[
                     payload[i.data_off : i.data_off + i.data_sz]
                     for i in desc.instrs
                 ])

    for ins in desc.instrs:
        if ins.program_id >= len(addrs):
            return _fail(TXN_ERR_ACCT)
        prog = addrs[ins.program_id]
        data = payload[ins.data_off : ins.data_off + ins.data_sz]
        idx = payload[ins.acct_off : ins.acct_off + ins.acct_cnt]
        if any(i >= len(addrs) for i in idx):
            # ALT-loaded index: unresolvable until the address-resolution
            # stage exists — a typed failure, never an abort of the block
            return _fail(TXN_ERR_ACCT)
        iaccts = [InstrAccount(i, signer[i], writable[i]) for i in idx]
        try:
            executor.execute_instr(ctx, prog, iaccts, data)
        except FundsError:
            return _fail(TXN_ERR_INSUFFICIENT_FUNDS)
        except AcctError:
            return _fail(TXN_ERR_ACCT)
        except InstrError:
            return _fail(TXN_ERR_PROGRAM)
        except (ValueError, IndexError, KeyError, OverflowError):
            # instruction data/accounts are ATTACKER input; a native
            # program tripping an untyped exception is a failed txn,
            # never a block abort (defense in depth on top of the typed
            # errors — one crafted txn must not kill replay)
            return _fail(TXN_ERR_PROGRAM)

    # commit: writes may only land on accounts the wave generator saw as
    # writable, or concurrent wave execution diverges from serial order.
    # Validate EVERYTHING before the first insert — a partial commit
    # would break the "fee charged, no effects" failure contract.
    changed = []
    for i, a in enumerate(accounts):
        val = a.to_value()
        if val == baseline[i]:
            continue
        if not writable[i]:
            return _fail(TXN_ERR_ACCT)
        changed.append((a.key, val))
    for key, val in changed:
        funk.rec_insert(xid, key, val)
    return TxnResult(TXN_SUCCESS, fee)


class SlotExecution:
    """Incremental slot execution: the per-txn gate + execute + seal
    machinery shared by `execute_block` (the batch/replay path) and the
    pipeline's bank stages (the streaming leader path — the reference's
    bank tile commits into one live bank the same way,
    /root/reference/src/app/fdctl/run/tiles/fd_bank.c:186-241).

    Lifecycle: construct (prepares a funk fork), `execute()` txns as they
    arrive, `seal(poh_hash)` to finalize the bank hash, then `publish()`
    or `abandon()` once consensus picks the fork."""

    def __init__(
        self,
        funk: Funk,
        *,
        slot: int,
        parent_bank_hash: bytes = b"\x00" * 32,
        parent_xid: bytes | None = None,
        executor: Executor | None = None,
        status_cache=None,
        ancestors: set[int] | None = None,
        slot_hashes: list[tuple[int, bytes]] | None = None,
        xid: bytes | None = None,
        join: bool = False,
    ):
        self.funk = funk
        self.slot = slot
        self.parent_bank_hash = parent_bank_hash
        self.parent_xid = parent_xid
        self.executor = executor
        self.status_cache = status_cache
        self.ancestors = ancestors
        # xid carries a nonce: competing blocks for the SAME slot off the
        # same parent are distinct forks (consensus decides which
        # publishes).  The parent rides along as a digest, not verbatim —
        # embedding the full parent xid grows the key by ~15 bytes per
        # unpublished ancestor, and a partitioned fork chain blows past
        # the native funk's FFK_XID_MAX (128) within a handful of slots.
        # A caller that has to find the fork again from another process
        # (a supervisor reading a bank tile's funk) names it: `xid`.
        # `join`: the fork `xid` is there already, prepared by the
        # process that made the store — this execution is one more bank
        # tile committing into it (NativeFunk.attach), and leaves the
        # seal and the publish to the tile that prepared it.
        self.xid = xid if xid is not None else b"slot:%d:%d:%s" % (
            slot, next(_xid_seq),
            hashlib.sha256(parent_xid).hexdigest()[:24].encode()
            if parent_xid else b"root")
        if not join:
            funk.txn_prepare(parent_xid, self.xid)
        elif funk.txn_is_frozen(self.xid):     # raises: no such fork
            raise ValueError("the fork to join has children")
        self.sysvars = default_sysvars(slot)
        # durable nonces advance against the PARENT's bank hash: fresh,
        # deterministic, and fixed before any txn in this block runs
        self.sysvars["recent_blockhash"] = parent_bank_hash
        if slot_hashes is not None:
            from firedancer_tpu.flamenco import types as T

            self.sysvars["slot_hashes"] = T.SLOT_HASHES.encode(
                [T.SlotHash(s, h) for s, h in slot_hashes]
            )
        if status_cache is not None:
            status_cache.begin_block(self.xid, slot)
        # intra-block duplicates are tracked locally, NOT via the cache
        # with a widened ancestor set: cache insertions from a speculative
        # competing block at this same slot must never gate this block
        self._block_seen: set[tuple[bytes, bytes]] = set()
        # unrooted ancestor blocks gate too: their entries are still
        # STAGED in the status cache (publish hasn't folded them), but a
        # txn one of them carries must answer ALREADY_PROCESSED here —
        # the exactly-once contract across leader handoffs on one fork
        self._ancestor_xids: tuple[bytes, ...] = (
            tuple(funk.txn_ancestry(parent_xid))
            if parent_xid is not None else ()
        )
        # native executor fast lane (flamenco/exec_native.py), built
        # lazily on the first execute_batch; False = unavailable/disabled
        self._native_ctx = None
        self._native_sh_blob = None
        # slot-scoped native session (ISSUE 9 bank-lane residual): the
        # C++ side keeps the status-cache gate + an account-value overlay
        # across microblocks, so Python ships each account's value ONCE
        # (first touch, or after a Python-lane write dirties it) and
        # skips the per-txn gate checks entirely
        self._native_session = None
        self._native_poisoned = False  # a failed call leaves the session
        #                                stale: python lane for the rest
        self._gate_seen_delta: list[bytes] = []  # 96B bh||sig, py-landed
        self._gate_seeded = False
        self._gate_shipped_version = None  # StatusCache.version last sent
        self._native_known: set[bytes] = set()  # addrs the session holds
        self._native_dirty: set[bytes] = set()  # py-written since sync
        # values this lane shipped the session from a store that other
        # processes write too (execute_batch `shared`)
        self.session_refreshed = 0
        self._table_cache: dict = {}  # ALT decode, once per block
        self._before: dict[bytes, bytes | None] = {}  # start-of-slot view
        # native shm funk: seal() reads before/after pairs from the fork
        # overlay in one txn_diff crossing, so the per-write _before
        # snapshot maintenance on the drain path is dead weight
        self._funk_diff = hasattr(funk, "txn_diff")
        self.results: list[TxnResult] = []
        # interned TxnResults for the sweep drain: a burst of landed
        # transfers repeats a handful of (status, fee) pairs
        self._txnres_cache: dict[tuple, TxnResult] = {}
        # native-lane accounting, read by the bank stage's metrics: txns
        # committed by the C++ lane vs. punted back to the Python lane
        self.native_done_cnt = 0
        self.native_punt_cnt = 0
        self.signature_cnt = 0
        self.sealed: BlockResult | None = None

    def resolve(self, payload: bytes, desc: ft.Txn):
        """Resolve v0 address-table lookups against the START-of-slot
        state (in-block table extensions become visible next slot —
        Agave's visibility rule).  None = typed lookup failure."""
        if not desc.addr_luts:
            return ([], [])
        from firedancer_tpu.flamenco import alt as falt

        try:
            return falt.resolve_lookups(
                payload, desc,
                lambda k: self.funk.rec_query(self.parent_xid, k),
                slot=self.slot, table_cache=self._table_cache,
            )
        except falt.LookupError_:
            return None

    def execute(
        self, payload: bytes, desc: ft.Txn,
        extra: tuple[list[bytes], list[bytes]] | None | bool = False,
    ) -> TxnResult:
        """Gate + execute one txn on this slot's fork.  `extra` is the
        pre-resolved ALT addresses (pass the default to resolve here)."""
        if extra is False:
            extra = self.resolve(payload, desc)
        # snapshot the start-of-slot value of every account this txn can
        # touch, for the accounts-delta hash (query the PARENT view: an
        # earlier in-block writer must not shift this txn's "before")
        touched = desc.acct_addrs(payload) + (
            extra[0] + extra[1] if extra else []
        )
        for a in touched:
            if a not in self._before:
                self._before[a] = self.funk.rec_query(self.parent_xid, a)
        durable = False
        bh = sig = None
        if self.status_cache is not None:
            bh = desc.recent_blockhash(payload)
            sig = desc.signatures(payload)[0]
            if not self.status_cache.is_blockhash_valid(bh, self.slot):
                from firedancer_tpu.flamenco import nonce as _nonce

                if not _nonce.durable_nonce_ok(self.funk, self.xid,
                                               payload, desc):
                    r = TxnResult(TXN_ERR_BLOCKHASH, 0)
                    self.results.append(r)
                    return r
                durable = True
            if (bh, sig) in self._block_seen or self.status_cache.contains(
                bh, sig, self.ancestors
            ) or self.status_cache.contains_staged(
                bh, sig, self._ancestor_xids
            ):
                r = TxnResult(TXN_ERR_ALREADY_PROCESSED, 0)
                self.results.append(r)
                return r
        if self._native_session is not None:
            # this Python-lane execution may write any touched account:
            # the native session's cached values go stale until resynced
            # on next touch (the dirty set ships a fresh have=1 value).
            # Marked HERE — after the gate — so gated-out txns (which
            # can never write) don't churn the session's value cache.
            self._native_dirty.update(touched)
        r = _execute_txn(self.funk, self.xid, payload, desc,
                         executor=self.executor, sysvars=self.sysvars,
                         extra=extra, durable_nonce=durable)
        return self._finish(r, desc.signature_cnt, bh, sig)

    def _finish(self, r: TxnResult, sig_cnt: int, bh, sig,
                native: bool = False) -> TxnResult:
        """Post-execution bookkeeping shared by the Python and native
        lanes — the two must never disagree on the landed predicate."""
        if r.fee > 0:
            # the bank hash's signature count covers txns that LANDED
            # (fee-charged; dropped/gated txns leave no on-chain
            # footprint) — so a streaming leader and a replayer counting
            # only the recorded txns agree on the hash
            self.signature_cnt += sig_cnt
            if self.status_cache is not None:
                # any fee-charged txn occupies its signature (failed txns
                # landed on chain too — fd_txncache records both); staged
                # until the fork is chosen
                self._block_seen.add((bh, sig))
                self.status_cache.stage_insert(self.xid, bh, sig)
                if not native and self._native_session is not None \
                        and bh is not None and sig is not None:
                    # python-lane landing: the native gate learns it on
                    # the next crossing (native landings were inserted by
                    # the C++ side already)
                    self._gate_seen_delta.append(bh + sig)
        self.results.append(r)
        return r

    # -- native fast lane (flamenco/exec_native.py) ---------------------------

    def _native_for_batch(self):
        """The slot's native BatchContext, or None (disabled/unavailable).
        Rebuilt if the slot-hashes sysvar blob was swapped out."""
        if self._native_poisoned:
            return None
        sh = self.sysvars.get("slot_hashes")
        if self._native_ctx is None or self._native_sh_blob is not sh:
            from firedancer_tpu.flamenco import exec_native

            self._native_sh_blob = sh
            self._native_ctx = False
            if exec_native.available():
                clock_slot = clock_epoch = None
                blob = self.sysvars.get("clock")
                if blob:
                    from firedancer_tpu.flamenco import types as T

                    try:
                        c = T.CLOCK.decode(blob, 0)[0]
                        clock_slot, clock_epoch = c.slot, c.epoch
                    except T.CodecError:
                        pass  # no clock: vote txns fail typed, both lanes
                # rent env for the nonce partial-withdraw floor: flag 2
                # = blob present but undecodable (the C++ side punts at
                # the point of use; the Python lane owns that path)
                from firedancer_tpu.flamenco import types as T

                _rd = T.Rent()  # absent blob -> defaults (nonce.py)
                rent_flag = 1
                rent_lpby = _rd.lamports_per_byte_year
                rent_et = _rd.exemption_threshold
                rent_blob = self.sysvars.get("rent")
                if rent_blob:
                    try:
                        r = T.RENT.decode(rent_blob, 0)[0]
                        rent_lpby = r.lamports_per_byte_year
                        rent_et = r.exemption_threshold
                    except T.CodecError:
                        rent_flag = 2
                try:
                    if self._native_session is None:
                        # one session per SlotExecution: the overlay and
                        # gate survive a BatchContext rebuild (only the
                        # sysvar header changes)
                        self._native_session = exec_native.Session()
                    self._native_ctx = exec_native.BatchContext(
                        lamports_per_sig=LAMPORTS_PER_SIGNATURE,
                        clock_slot=clock_slot,
                        clock_epoch=clock_epoch,
                        slot_hashes=sh,
                        session=self._native_session,
                        recent_blockhash=self.sysvars.get(
                            "recent_blockhash"),
                        rent=(rent_flag, rent_lpby, rent_et),
                    )
                except exec_native.NativeUnavailable:
                    pass
        return self._native_ctx or None

    def _gate_args(self):
        """(valid_blockhashes | None, seen_delta) for the next native
        crossing — valid_blockhashes is None when the registry hasn't
        changed since last shipped (the session keeps its set; flag 2 on
        the wire), so steady state ships only the seen delta.  Returns
        None when there is no status cache (the Python lane does not
        gate either, so neither should the native side)."""
        sc = self.status_cache
        if sc is None:
            return None
        if sc.version == self._gate_shipped_version:
            valid = None
        else:
            valid = [bh for bh in sc.blockhash_slot
                     if sc.is_blockhash_valid(bh, self.slot)]
        if not self._gate_seeded:
            if valid is None:  # first call always ships the set
                valid = [bh for bh in sc.blockhash_slot
                         if sc.is_blockhash_valid(bh, self.slot)]
            # one-time seed: everything already visible to contains()
            # on this fork (committed ancestor entries + anything this
            # block landed before the session armed)
            self._gate_seeded = True
            vs = set(valid)
            for (bh, sig), slots in sc.seen.items():
                if bh in vs and (
                    self.ancestors is None
                    or any(s in self.ancestors for s in slots)
                ):
                    self._gate_seen_delta.append(bh + sig)
            # unrooted ancestor blocks' staged landings gate natively too
            # (the Python gate's contains_staged, shipped once)
            staged = getattr(sc, "_staged_seen", {})
            for x in self._ancestor_xids:
                for bh, sig in staged.get(x, ()):
                    if bh in vs:
                        self._gate_seen_delta.append(bh + sig)
            for bh, sig in self._block_seen:
                self._gate_seen_delta.append(bh + sig)
        if valid is not None:
            self._gate_shipped_version = sc.version
        return (valid, self._gate_seen_delta)

    def _poison_native(self) -> None:
        """A failed native call leaves the session overlay unsynced:
        disable the lane for the rest of this slot (python lane owns it)."""
        self._native_poisoned = True
        self._native_ctx = False
        if self._native_session is not None:
            self._native_session.close()
            self._native_session = None

    # -- bank sweep client (native/fd_bank.cpp via runtime/bank_native) -------

    def native_sync(self) -> bool:
        """Re-arm the C session before a bank sweep with ONE zero-txn
        crossing: the status-cache gate delta (Python-lane landings +
        valid-set changes) and refresh records for every dirty account
        (Python-lane writes since the last sync).  The sweep client
        builds its own requests with no per-account values (the session
        overlay is its only source), so this is the lane's whole
        coherence protocol.  No-op when already coherent; returns False
        when the native lane is unavailable/poisoned (the caller must
        not let the sweep run)."""
        nat = self._native_for_batch()
        if nat is None or self._native_session is None:
            return False
        sc = self.status_cache
        dirty = self._native_dirty
        need_gate = sc is not None and (
            not self._gate_seeded
            or sc.version != self._gate_shipped_version
            or bool(self._gate_seen_delta)
        )
        if not need_gate and not dirty:
            return True
        from firedancer_tpu.flamenco import exec_native

        gate = self._gate_args()
        n_delta = len(gate[1]) if gate is not None else 0
        refresh = []
        if dirty:
            q = self.funk.rec_query
            for a in dirty:
                refresh.append((a, q(self.xid, a) or b""))
        try:
            nat.run([], gate=gate, refresh=refresh)
        except exec_native.NativeUnavailable:
            self._poison_native()
            return False
        if n_delta:
            del self._gate_seen_delta[:n_delta]
        if refresh:
            self._native_known.update(a for a, _v in refresh)
            dirty.clear()
        return True

    def native_apply_rec(self, payload: bytes, desc_bytes: bytes,
                         status: int, fee: int, writes) -> TxnResult:
        """Apply one sweep-committed txn record (the C side already ran
        it against the session): funk writes, start-of-slot snapshots,
        and the shared landed bookkeeping.  writes: [(acct_idx, value)]
        with indices into the packed descriptor's account table."""
        db = desc_bytes
        bh = sig = None
        if fee > 0 and self.status_cache is not None:
            sig_off = db[2] | (db[3] << 8)
            bh_off = db[11] | (db[12] << 8)
            bh = payload[bh_off : bh_off + 32]
            sig = payload[sig_off : sig_off + 64]
        if writes:
            acct_off = db[9] | (db[10] << 8)
            before = self._before
            q = self.funk.rec_query
            known = self._native_known
            dirty = self._native_dirty
            for idx, val in writes:
                a = payload[acct_off + 32 * idx : acct_off + 32 * (idx + 1)]
                if not self._funk_diff and a not in before:
                    before[a] = q(self.parent_xid, a)
                self.funk.rec_insert(self.xid, a, val)
                known.add(a)
                dirty.discard(a)
        self.native_done_cnt += 1
        return self._finish(TxnResult(status, fee), db[1], bh, sig,
                            native=True)

    def native_apply_batch(self, txns) -> list[TxnResult]:
        """One sweep group's committed records in a single pass —
        semantically native_apply_rec over each (payload, desc_bytes,
        status, fee, writes) tuple, but the funk txn resolves/validates
        once for the whole batch and every per-txn attribute chase is
        hoisted to a local.  This is the drain's per-txn floor: the C
        side already ran the txns, so everything left here is
        authoritative-state application."""
        before = self._before
        q = self.funk.rec_query
        recs_d = self.funk.txn_recs_for_write(self.xid)
        known = self._native_known
        dirty = self._native_dirty
        pxid = self.parent_xid
        xid = self.xid
        sc = self.status_cache
        block_seen = self._block_seen
        stage_insert = sc.stage_insert if sc is not None else None
        results = self.results
        track_before = not self._funk_diff
        out = []
        sig_cnt = 0
        for payload, db, status, fee, writes in txns:
            if writes:
                acct_off = db[9] | (db[10] << 8)
                for idx, val in writes:
                    a = payload[acct_off + 32 * idx:acct_off + 32 * (idx + 1)]
                    if track_before and a not in before:
                        before[a] = q(pxid, a)
                    recs_d[a] = val if type(val) is bytes else bytes(val)
                    known.add(a)
                    dirty.discard(a)
            self.native_done_cnt += 1
            r = TxnResult(status, fee)
            if fee > 0:
                sig_cnt += db[1]
                if stage_insert is not None:
                    sig_off = db[2] | (db[3] << 8)
                    bh_off = db[11] | (db[12] << 8)
                    bh = payload[bh_off : bh_off + 32]
                    sig = payload[sig_off : sig_off + 64]
                    block_seen.add((bh, sig))
                    stage_insert(xid, bh, sig)
            results.append(r)
            out.append(r)
        self.signature_cnt += sig_cnt
        return out

    def native_apply_group(self, frags, recs) -> tuple:
        """One FULLY-published sweep group straight off the frag bytes —
        semantically native_apply_batch over (frag[:psz], frag[psz:-2],
        status, fee, writes) tuples, but the drain's published!=0 path
        needs only the accounting, so the payload/descriptor slices are
        never materialized.  With the native funk plane armed the record
        stream arrives stripped (the values already live in the shm map)
        and the only per-txn slices left are the bh/sig pair the status
        cache keys on.  Returns (n_ok, n_fail, n_rej, n_vote,
        n_vote_fail): landed, landed with a failed program, no
        footprint, and the simple votes (pack/cost.py is_simple_vote:
        one instruction, the vote program's) among the first two."""
        before = self._before
        q = self.funk.rec_query
        recs_d = self.funk.txn_recs_for_write(self.xid)
        known = self._native_known
        dirty = self._native_dirty
        pxid = self.parent_xid
        xid = self.xid
        sc = self.status_cache
        if sc is not None:
            # stage_insert unrolled: the two per-xid structure probes
            # hoist out of the loop (one staged batch per group)
            staged_append = sc._staged[xid][1].append
            staged_add = sc._staged_seen[xid].add
        else:
            staged_append = None
        seen_add = self._block_seen.add
        res_append = self.results.append
        # landed transfers repeat the same (status, fee) almost every
        # txn: intern the TxnResults (readers never mutate them — the
        # dataclass exists to carry the pair out of the slot)
        res_cache = self._txnres_cache
        track_before = not self._funk_diff
        n_ok = n_fail = n_rej = n_vote = n_vote_fail = 0
        sig_cnt = 0
        vote_program = ft.VOTE_PROGRAM
        for frag, (status, fee, writes) in zip(frags, recs):
            psz = frag[-2] | (frag[-1] << 8)
            acct_off = frag[psz + 9] | (frag[psz + 10] << 8)
            if writes:
                for idx, val in writes:
                    a = frag[acct_off + 32 * idx : acct_off + 32 * (idx + 1)]
                    if track_before and a not in before:
                        before[a] = q(pxid, a)
                    recs_d[a] = val if type(val) is bytes else bytes(val)
                    known.add(a)
                    dirty.discard(a)
            if fee > 0:
                n_ok += 1
                if status != TXN_SUCCESS:
                    n_fail += 1
                if frag[psz + 16] == 1:
                    # one instruction: the vote program's?  (its key's
                    # first byte is 7, the system program's 0)
                    po = acct_off + 32 * frag[psz + 17]
                    if frag[po] == 7 and frag[po : po + 32] == vote_program:
                        n_vote += 1
                        if status != TXN_SUCCESS:
                            n_vote_fail += 1
                sig_cnt += frag[psz + 1]
                if staged_append is not None:
                    sig_off = frag[psz + 2] | (frag[psz + 3] << 8)
                    bh_off = frag[psz + 11] | (frag[psz + 12] << 8)
                    t = (frag[bh_off : bh_off + 32],
                         frag[sig_off : sig_off + 64])
                    seen_add(t)
                    staged_append(t)
                    staged_add(t)
            else:
                n_rej += 1
            r = res_cache.get((status, fee))
            if r is None:
                r = TxnResult(status, fee)
                if len(res_cache) < 64:
                    res_cache[(status, fee)] = r
            res_append(r)
        self.native_done_cnt += n_ok + n_rej
        self.signature_cnt += sig_cnt
        return n_ok, n_fail, n_rej, n_vote, n_vote_fail

    @staticmethod
    def _unpack_trailer(payload: bytes, desc_bytes: bytes) -> ft.Txn:
        """Packed trailer -> validated Txn (decode_verified's contract)."""
        try:
            desc, end = ft.txn_unpack(desc_bytes)
        except Exception as e:
            raise ValueError(f"packed descriptor unparseable: {e}") from e
        if end != len(desc_bytes):
            raise ValueError("packed descriptor trailer size mismatch")
        if not ft.txn_desc_valid(desc, len(payload)):
            raise ValueError("packed descriptor fails validation")
        return desc

    def execute_batch(self, items) -> list[TxnResult]:
        """Execute a burst of txns in block order, routing runs of
        native-eligible txns through one FFI call each (the bank stage's
        per-microblock commit path).  items: (payload, desc, desc_bytes)
        tuples — desc (a Txn) or desc_bytes (the packed trailer) may be
        None, not both.  Anything the native lane cannot take — Python
        lane programs, lookup tables, stale blockhashes (durable-nonce
        candidates), duplicate signatures — flushes the pending run and
        goes through `execute` unchanged."""
        base = len(self.results)
        nat = self._native_for_batch()
        if nat is not None:
            from firedancer_tpu.flamenco.exec_native import eligible_packed
        # session mode: the C++ side owns the status-cache gate + the
        # account-value overlay, so the per-txn python gate checks and
        # the per-call funk value marshalling disappear (ISSUE 9)
        session = self._native_session if nat is not None else None
        # a store other processes write too (NativeFunk.attach): what the
        # session holds of an account may be another bank tile's past,
        # so every value ships from the store (the sweep lane's
        # read-through, native/fd_bank.cpp, on this lane) and is counted
        # as there (`session_refreshed`)
        shared = self._funk_diff and self.funk.writers() > 1
        pend: list[list] = []   # [payload, desc_bytes, addrs, vals, bh, sig, sig_cnt]
        pend_keys: set = set()

        def fallback(payload, desc, desc_bytes):
            if desc is None:
                desc = self._unpack_trailer(payload, desc_bytes)
            self.execute(payload, desc)

        def flush():
            if pend:
                self._flush_native(nat, pend, session)
                pend.clear()
                pend_keys.clear()

        for payload, desc, desc_bytes in items:
            if nat is None or self._native_poisoned:
                # poisoned mid-batch: the cached locals point at a dead
                # session — stop marshalling into it and finish on the
                # Python lane immediately
                fallback(payload, desc, desc_bytes)
                continue
            if desc_bytes is None:
                desc_bytes = ft.txn_pack(desc)
            psz = len(payload)
            db = desc_bytes
            if len(db) < 17:
                flush()
                fallback(payload, desc, desc_bytes)
                continue
            sig_cnt = db[1]
            sig_off = db[2] | (db[3] << 8)
            acct_cnt = db[8]
            acct_off = db[9] | (db[10] << 8)
            bh_off = db[11] | (db[12] << 8)
            if (
                db[13]  # lut_cnt: the ALT-resolution path is Python's
                or sig_cnt == 0
                or acct_cnt == 0
                or sig_off + 64 > psz
                or bh_off + 32 > psz
                or acct_off + 32 * acct_cnt > psz
                or not eligible_packed(payload, db)
            ):
                flush()
                fallback(payload, desc, desc_bytes)
                continue
            bh = payload[bh_off : bh_off + 32]
            sig = payload[sig_off : sig_off + 64]
            if session is None and self.status_cache is not None and (
                not self.status_cache.is_blockhash_valid(bh, self.slot)
                or (bh, sig) in pend_keys
                or (bh, sig) in self._block_seen
                or self.status_cache.contains(bh, sig, self.ancestors)
                or self.status_cache.contains_staged(bh, sig,
                                                     self._ancestor_xids)
            ):
                # legacy (session-less) path: stale blockhash
                # (durable-nonce candidate) or duplicate — the Python
                # gate owns these; a pending-run twin must land first so
                # the duplicate gate sees it.  With a session the C++
                # gate decides in-line instead.
                flush()
                fallback(payload, desc, desc_bytes)
                continue
            addrs = []
            vals = []
            q = self.funk.rec_query
            before = self._before
            if session is not None:
                known = self._native_known
                dirty = self._native_dirty
                for i in range(acct_cnt):
                    a = payload[acct_off + 32 * i : acct_off + 32 * (i + 1)]
                    addrs.append(a)
                    if a not in before:
                        before[a] = q(self.parent_xid, a)
                    if a in known and a not in dirty and not shared:
                        vals.append(None)  # the session holds it current
                    else:
                        vals.append(q(self.xid, a) or b"")
                        known.add(a)
                        dirty.discard(a)
                        self.session_refreshed += shared
            else:
                for i in range(acct_cnt):
                    a = payload[acct_off + 32 * i : acct_off + 32 * (i + 1)]
                    addrs.append(a)
                    if a not in before:
                        before[a] = q(self.parent_xid, a)
                    vals.append(q(self.xid, a))
                pend_keys.add((bh, sig))
            pend.append([payload, desc_bytes, addrs, vals, bh, sig, sig_cnt])
        flush()
        return self.results[base:]

    def _run_gated(self, entry) -> None:
        """Python-lane execution for an already-gated native entry (a
        C++ punt on the legacy session-less path): fresh blockhash, not
        a duplicate, no lookup tables."""
        payload, desc_bytes, _addrs, _vals, bh, sig, sig_cnt = entry
        desc = self._unpack_trailer(payload, desc_bytes)
        r = _execute_txn(self.funk, self.xid, payload, desc,
                         executor=self.executor, sysvars=self.sysvars,
                         extra=([], []), durable_nonce=False)
        self._finish(r, sig_cnt, bh, sig)

    def _run_ungated(self, entry) -> None:
        """Python-lane execution for an UNGATED native entry (a session
        punt: the C++ gate stopped before deciding — possibly a stale
        blockhash / durable-nonce candidate): the full execute() path
        owns gating, _before snapshots, and dirty-marking."""
        payload, desc_bytes = entry[0], entry[1]
        desc = self._unpack_trailer(payload, desc_bytes)
        self.execute(payload, desc, ([], []))

    def _flush_native(self, nat, pend: list, session=None) -> None:
        """Run the pending native-eligible txns in order: one FFI call
        per run, punts re-routed through the Python lane, and the
        remainder resubmitted.  Session mode: account values live in
        the C++ overlay across calls, so no per-call refresh loop; the
        gate delta rides the same crossing."""
        from firedancer_tpu.flamenco import exec_native

        i = 0
        while i < len(pend):
            chunk = pend[i:]
            gate = self._gate_args() if session is not None else None
            n_delta = len(gate[1]) if gate else 0
            try:
                if session is not None:
                    n_done, punted, recs = nat.run(chunk, gate=gate)
                else:
                    n_done, punted, recs = nat.run(chunk)
            except exec_native.NativeUnavailable:
                if session is not None:
                    # the session overlay may be out of sync with funk
                    # now: retire it for the rest of the slot
                    self._poison_native()
                    for entry in chunk:
                        self._run_ungated(entry)
                else:
                    # oversized response / native wedge: finish in Python
                    for entry in chunk:
                        self._run_gated(entry)
                return
            if n_delta:
                # the session absorbed these python-lane landings
                del self._gate_seen_delta[:n_delta]
            for entry, (status, fee, writes) in zip(chunk, recs):
                addrs = entry[2]
                for idx, val in writes:
                    self.funk.rec_insert(self.xid, addrs[idx], val)
                self._finish(TxnResult(status, fee), entry[6], entry[4],
                             entry[5], native=True)
            i += n_done
            self.native_done_cnt += n_done
            if punted and i < len(pend):
                self.native_punt_cnt += 1
                if session is not None:
                    self._run_ungated(pend[i])
                    i += 1
                    # the punt ran on the Python lane and dirtied its
                    # accounts: remainder entries marked session-known
                    # (vals None) for those accounts must re-ship fresh
                    # values — the first shipper re-syncs the session
                    dirty = self._native_dirty
                    if dirty:
                        for entry in pend[i:]:
                            vals = entry[3]
                            for j, a in enumerate(entry[2]):
                                if a in dirty:
                                    vals[j] = self.funk.rec_query(
                                        self.xid, a) or b""
                                    dirty.discard(a)
                else:
                    self._run_gated(pend[i])
                    i += 1
            elif n_done == 0 and not punted:
                # defensive: a native lane that makes no progress must
                # not spin — finish the remainder in Python
                for entry in pend[i:]:
                    if session is not None:
                        self._run_ungated(entry)
                    else:
                        self._run_gated(entry)
                return
            if i < len(pend) and session is None:
                # legacy path only: refresh the remainder's funk values
                # (the stateless overlay restarts empty each call); the
                # session keeps its own writes and the punt txn's
                # accounts were dirty-marked by execute()
                for entry in pend[i:]:
                    entry[3] = [self.funk.rec_query(self.xid, a)
                                for a in entry[2]]

    def seal(self, poh_hash: bytes = b"\x00" * 32,
             waves: list[list[int]] | None = None) -> BlockResult:
        """Finalize: accounts-delta lattice hash (one device reduction
        over +new / -old) chained into the bank hash."""
        vals = []
        signs = []
        diff_fn = getattr(self.funk, "txn_diff", None)
        if diff_fn is not None:
            # native shm store: the slot's whole before/after read-out is
            # ONE FFI crossing over the fork's own overlay.  Equivalent
            # to the _before walk — an account touched but never written
            # has before == after and cancels out of the lattice sum, and
            # the overlay's parent view IS the start-of-slot value
            # (parent overlays freeze while this fork is live).
            pairs = ((a, bef, aft) for a, bef, aft in diff_fn(self.xid))
        else:
            q = self.funk.rec_query
            pairs = ((a, self._before[a], q(self.xid, a))
                     for a in self._before)
        for a, before, after in sorted(pairs):
            if after == before:
                continue
            if before is not None:
                vals.append(lt.lthash_of(a + before))
                signs.append(-1)
            if after is not None:
                vals.append(lt.lthash_of(a + after))
                signs.append(1)
        if vals:
            # pad the row count to a power of two (zero rows, sign 0 —
            # the lattice sum is unchanged): a cluster of banks sealing
            # blocks of varying account counts would otherwise compile
            # one XLA reduction per distinct N
            cap = 1 << (len(vals) - 1).bit_length()
            if cap != len(vals):
                vals.extend([lt.lthash_zero()] * (cap - len(vals)))
                signs.extend([0] * (cap - len(signs)))
            delta = np.asarray(
                lt.combine_device(np.stack(vals), np.asarray(signs))
            )
        else:
            delta = lt.lthash_zero()
        bank_hash = hashlib.sha256(
            self.parent_bank_hash
            + hashlib.sha256(delta.tobytes()).digest()
            + self.signature_cnt.to_bytes(8, "little")
            + poh_hash
        ).digest()
        if self.status_cache is not None:
            self.status_cache.stage_blockhash(self.xid, poh_hash)
        self.sealed = BlockResult(
            slot=self.slot,
            bank_hash=bank_hash,
            accounts_delta=delta,
            signature_cnt=self.signature_cnt,
            fees=sum(r.fee for r in self.results),
            results=list(self.results),
            waves=waves if waves is not None else [],
            xid=self.xid,
        )
        return self.sealed

    def publish(self) -> None:
        """Consensus chose this fork: fold it into funk's root."""
        if self.status_cache is not None:
            self.status_cache.commit_block(self.xid)
        self.funk.txn_publish(self.xid)

    def abandon(self) -> None:
        if self.status_cache is not None:
            self.status_cache.drop_block(self.xid)
        self.funk.txn_cancel(self.xid)


def execute_block(
    funk: Funk,
    *,
    slot: int,
    txns: list[bytes],
    parent_bank_hash: bytes = b"\x00" * 32,
    poh_hash: bytes = b"\x00" * 32,
    parent_xid: bytes | None = None,
    publish: bool = False,
    status_cache=None,
    ancestors: set[int] | None = None,
    slot_hashes: list[tuple[int, bytes]] | None = None,
    parsed: list | None = None,
) -> BlockResult:
    """Execute a block's txns on a fresh funk fork; compute the bank hash.
    `parsed`, the (payload, Txn) pairs of `txns`, hands over a parse the
    caller has made already (replay_block's).

    The fork stays in-prep (consensus decides) unless publish=True.
    status_cache (flamenco/blockstore.StatusCache) arms the two
    consensus-critical txn gates: recent-blockhash currency (150-slot
    age) and cross-slot duplicate-signature rejection (filtered by
    `ancestors` when given — fork awareness).  Executed signatures are
    recorded, and this slot's poh_hash registers as a usable blockhash."""
    if parsed is None:
        parsed = []
        for p in txns:
            t = ft.txn_parse(p)
            if t is None:
                raise ValueError("malformed txn in block")
            parsed.append((p, t))
    sx = SlotExecution(
        funk, slot=slot, parent_bank_hash=parent_bank_hash,
        parent_xid=parent_xid, status_cache=status_cache,
        ancestors=ancestors, slot_hashes=slot_hashes,
    )
    extras = [sx.resolve(p, t) for p, t in parsed]
    waves = generate_waves(parsed, extras)
    order = [i for wave in waves for i in wave]
    # wave txns are conflict-free: host executes in index order, a
    # tpool/device executes them concurrently — same result either way
    for i in order:
        p, t = parsed[i]
        sx.execute(p, t, extra=extras[i])
    # sx.results is in execution order; BlockResult keeps block order
    by_block_order = [None] * len(parsed)
    for pos, i in enumerate(order):
        by_block_order[i] = sx.results[pos]
    sx.results = by_block_order
    result = sx.seal(poh_hash, waves=waves)
    if publish:
        sx.publish()
    # else: the caller owns the fork decision — commit_block(xid) when
    # the fork is chosen, drop_block(xid) when it is abandoned
    return result


def replay_block(
    funk: Funk,
    *,
    slot: int,
    entries: list[tuple[int, bytes, list[bytes]]],
    poh_seed: bytes,
    parent_bank_hash: bytes = b"\x00" * 32,
    parent_xid: bytes | None = None,
    publish: bool = False,
    status_cache=None,
    ancestors: set[int] | None = None,
    slot_hashes: list[tuple[int, bytes]] | None = None,
) -> BlockResult | None:
    """The non-leader path: verify the PoH chain over wire entries, then
    execute the block (fd_replay's after_frag shape).  None = PoH fraud."""
    from firedancer_tpu.runtime import poh as fpoh

    # one parse a transaction: its first signature for the chain's
    # mixins, the rest for execution
    parsed = [[(p, ft.txn_parse(p)) for p in txs] for _, _, txs in entries]
    if any(t is None for ent in parsed for _, t in ent):
        return None
    ok, _segments = fpoh.replay_entries(
        poh_seed, entries,
        first_sigs=[[t.signatures(p)[0] for p, t in ent] for ent in parsed])
    if not ok:
        return None
    txns = [p for _, _, txs in entries for p in txs]
    poh_hash = entries[-1][1] if entries else b"\x00" * 32
    return execute_block(
        funk,
        slot=slot,
        txns=txns,
        parent_bank_hash=parent_bank_hash,
        poh_hash=poh_hash,
        parent_xid=parent_xid,
        publish=publish,
        status_cache=status_cache,
        ancestors=ancestors,
        # the replayer's view of recent bank hashes — votes in this
        # block validate against it (empty would reject every vote)
        slot_hashes=slot_hashes,
        parsed=[pt for ent in parsed for pt in ent],
    )
