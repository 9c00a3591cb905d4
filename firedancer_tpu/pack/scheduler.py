"""Conflict-aware microblock scheduler (the pack library proper).

Behavioral port of /root/reference/src/ballet/pack/fd_pack.c:

  - pending transactions ordered by reward/cost ratio, compared exactly as
    r1*c2 > r2*c1 (no floating point; fd_pack.c:41-47);
  - separate pending pool for simple votes (scheduled against the vote
    cost limit); every microblock takes votes FIRST, up to VOTE_FRACTION
    of its cost and of its transaction slots, then fills from the
    regular pool (fd_pack_schedule_next_microblock's vote_fraction);
    a full pool never evicts a vote for a non-vote;
  - an account in use by an in-flight microblock blocks conflicting txns:
    write-locks are exclusive, read-locks are shared (fd_pack_bitset.h's
    semantics via per-account reader/writer bank masks);
  - consensus-critical block limits: total cost, vote cost, per-account
    write cost, data bytes incl. 48-byte microblock overhead
    (fd_pack.h:18-49);
  - microblock_done(bank) releases that bank's account locks;
  - end_block() resets block accounting, keeping unscheduled txns.

The ordered pool is a sorted list with bisect insertion — the treap's role
(ordered iteration + O(log n) insert/delete) at host-model scale.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field

from firedancer_tpu.protocol import txn as ft
from . import cost as fc


@dataclass
class OrdTxn:
    payload: bytes
    desc: ft.Txn
    cost: fc.TxnCost
    rewards: int
    _sets: tuple | None = field(default=None, repr=False, compare=False)
    _key: object = field(default=None, repr=False, compare=False)

    def sort_key(self):
        # descending by rewards/cost; bisect needs ascending, so negate via
        # ratio inversion: store (-rewards/cost) as exact fraction tuple.
        # Compare r1/c1 > r2/c2 as r1*c2 > r2*c1 -> key = Fraction-free.
        # CACHED: bisect probes call this O(log n) times per insert and
        # the scheduler once per scanned entry — building a fresh key
        # object each time dominated the host-path profile.
        if self._key is None:
            self._key = _RatioKey(self.rewards, self.cost.total)
        return self._key

    def first_sig(self) -> bytes:
        return self.desc.signatures(self.payload)[0]

    def acct_sets(self) -> tuple[set[bytes], set[bytes], set[bytes]]:
        """(static_writable, readonly, lock_writable), computed once.

        lock_writable = static_writable plus, for v0 txns, the address of
        every referenced lookup table: ALT-loaded accounts cannot be
        resolved without an address-resolution stage, so any txn with
        lookups conservatively write-locks the table address itself — two
        txns loading from the same table serialize, and can never write the
        same ALT-loaded account concurrently (the reference locks resolved
        ALT accounts, fd_pack_bitset.h semantics)."""
        if self._sets is None:
            addrs = self.desc.acct_addrs(self.payload)
            w, r = set(), set()
            for i, a in enumerate(addrs):
                (w if self.desc.is_writable(i) else r).add(a)
            lw = set(w)
            for lut in self.desc.addr_luts:
                lw.add(self.payload[lut.addr_off : lut.addr_off + 32])
            self._sets = (w, r, lw)
        return self._sets

    def accounts(self) -> tuple[set[bytes], set[bytes]]:
        """(writable, readonly) static account addresses."""
        w, r, _ = self.acct_sets()
        return w, r


class _RatioKey:
    """Orders by rewards/cost DESC without floats: r1*c2 > r2*c1."""

    __slots__ = ("r", "c")

    def __init__(self, r: int, c: int):
        self.r = r
        self.c = max(c, 1)

    def __lt__(self, other):  # "less" = schedules earlier = higher ratio
        return self.r * other.c > other.r * self.c

    def __eq__(self, other):
        return self.r * other.c == other.r * self.c


# fd_pack_schedule_next_microblock(pack, total_cus, vote_fraction, ...):
# the pack tile's VOTE_FRACTION 0.75, kept exact as a ratio of integers
# so that both lanes cut at the same transaction
VOTE_FRACTION_NUM = 3
VOTE_FRACTION_DEN = 4


@dataclass
class BlockLimits:
    max_cost_per_block: int = fc.MAX_COST_PER_BLOCK
    max_vote_cost_per_block: int = fc.MAX_VOTE_COST_PER_BLOCK
    max_write_cost_per_acct: int = fc.MAX_WRITE_COST_PER_ACCT
    max_data_bytes_per_block: int = fc.MAX_DATA_PER_BLOCK


@dataclass
class _Microblock:
    """What one schedule call has chosen so far, over both pools."""

    chosen: list = field(default_factory=list)
    taken_w: set = field(default_factory=set)
    taken_r: set = field(default_factory=set)
    cost: int = 0
    vote_cost: int = 0
    data: int = 0
    write_cost: dict = field(default_factory=dict)


class Pack:
    def __init__(
        self,
        *,
        bank_cnt: int = 4,
        depth: int = 4096,
        limits: BlockLimits | None = None,
        max_txn_per_microblock: int = 31,
        max_schedule_search: int = 256,
    ):
        if bank_cnt > fc.MAX_BANK_TILES:
            raise ValueError(f"bank_cnt > {fc.MAX_BANK_TILES}")
        self.bank_cnt = bank_cnt
        self.depth = depth
        self.limits = limits or BlockLimits()
        self.max_txn_per_microblock = max_txn_per_microblock
        # bounded scheduling lookahead: scan at most this many pool
        # entries per microblock (the reference bounds its treap walk the
        # same way) — an all-conflicting deep pool must not make every
        # schedule call O(pool)
        self.max_schedule_search = max_schedule_search
        self._pending: list[OrdTxn] = []  # sorted by _RatioKey
        self._pending_votes: list[OrdTxn] = []
        self._sigs: set[bytes] = set()
        # sig -> (pool, OrdTxn) index: delete_by_sig without a pool scan
        # (the treap+map pairing of fd_pack.c, at host-model scale)
        self._by_sig: dict[bytes, OrdTxn] = {}
        # account locks: addr -> [writer_mask, reader_mask] of bank bits
        self._in_use: dict[bytes, list[int]] = {}
        self._bank_accts: list[list[tuple[bytes, bool]]] = [
            [] for _ in range(bank_cnt)
        ]
        # block accounting
        self.cost_used = 0
        self.vote_cost_used = 0
        self.data_bytes_used = 0
        self._write_cost: dict[bytes, int] = {}
        # cumulative counts the pack stage copies into its metrics
        # (the native lane reports the same five out of its crossings)
        self.stat_evicted = 0          # pooled txns a better newcomer evicted
        self.stat_dropped_votes = 0    # votes refused or evicted
        self.stat_votes_dropped_regular_pending = 0  # ...with a non-vote pooled
        self.stat_scheduled_votes = 0
        self.stat_conflict_skips = 0   # scan steps over an account in use

    # -- intake --------------------------------------------------------------

    def insert(self, payload: bytes, desc: ft.Txn | None = None) -> bool:
        """Add a verified txn to the pool; False = rejected/dropped."""
        t = desc or ft.txn_parse(payload)
        if t is None:
            return False
        c = fc.compute_cost(payload, t)
        if c is None:
            return False
        sig = t.signatures(payload)[0]
        if sig in self._sigs:
            return False
        vote = c.is_simple_vote
        pool = self._pending_votes if vote else self._pending
        ord_txn = OrdTxn(payload, t, c, c.rewards(t.signature_cnt))
        if len(self._pending) + len(self._pending_votes) >= self.depth:
            # full.  Votes are consensus traffic: while a non-vote is
            # pooled a vote is never the one to go — an arriving vote
            # takes the place of the lowest-priority non-vote whatever
            # the ratios say (a vote's 5,000 lamports stand over the
            # dearer cost, so by ratio it would always lose).  A non-vote
            # evicts the worst non-vote iff it strictly beats it, and
            # never a vote; among votes alone the ratio decides.
            if self._pending:
                worst = self._pending[-1]
                if not vote and not (ord_txn.sort_key() < worst.sort_key()):
                    return False
            elif vote and self._pending_votes and (
                    ord_txn.sort_key() < self._pending_votes[-1].sort_key()):
                worst = self._pending_votes[-1]
                self._count_vote_drop()
            else:   # a pool of votes refuses a non-vote; depth <= 0
                if vote:
                    self._count_vote_drop()
                return False
            self._remove(worst)
            self.stat_evicted += 1
        bisect.insort(pool, ord_txn, key=OrdTxn.sort_key)
        self._sigs.add(sig)
        self._by_sig[sig] = ord_txn
        return True

    def _count_vote_drop(self) -> None:
        """A vote leaves (or is refused by) the pool unscheduled.  The
        second count is the guarantee's: it stays 0 while the rule above
        holds, and says so if a later rule breaks it."""
        self.stat_dropped_votes += 1
        if self._pending:
            self.stat_votes_dropped_regular_pending += 1

    def _remove(self, o: OrdTxn) -> None:
        # bisect to the sort-key position, then identity-match within the
        # (tiny) equal-key run: O(log n), no value-equality pool scan —
        # the treap-delete role of fd_pack.c at host-model scale
        key = o.sort_key()
        for pool in (self._pending, self._pending_votes):
            i = bisect.bisect_left(pool, key, key=OrdTxn.sort_key)
            found = False
            while i < len(pool) and pool[i].sort_key() == key:
                if pool[i] is o:
                    del pool[i]
                    found = True
                    break
                i += 1
            if found:
                break
        self._sigs.discard(o.first_sig())
        self._by_sig.pop(o.first_sig(), None)

    def delete_by_sig(self, sig: bytes) -> bool:
        o = self._by_sig.get(sig)
        if o is None:
            return False
        self._remove(o)
        return True

    def shed_lowest(self, n: int) -> int:
        """Deadline load-shedding (the slot-clock degraded mode): drop
        up to `n` of the LOWEST-priority pending regular txns — the pool
        tail, the same end the delete-worst eviction rule trims — and
        return how many were shed.  Votes are consensus traffic and are
        never shed."""
        shed = 0
        while shed < n and self._pending:
            self._remove(self._pending[-1])
            shed += 1
        return shed

    def pending_cnt(self) -> int:
        return len(self._pending) + len(self._pending_votes)

    # -- scheduling ----------------------------------------------------------

    def _conflicts(self, bank: int, writable: set, readonly: set) -> bool:
        other = ~(1 << bank)
        for a in writable:
            u = self._in_use.get(a)
            if u and ((u[0] | u[1]) & other):
                return True
        for a in readonly:
            u = self._in_use.get(a)
            if u and (u[0] & other):
                return True
        return False

    def _fits_block(
        self,
        o: OrdTxn,
        vote: bool,
        writable: set,
        mb_cost: int,
        mb_vote_cost: int,
        mb_data: int,
        mb_write_cost: dict[bytes, int],
    ) -> bool:
        """Limit checks including cost already chosen *within* the current
        microblock (mb_*) — the reference decrements its running cu/byte
        limits inside the scheduling loop (fd_pack.c:1134), so limits bind
        per selection, not merely per committed microblock."""
        lim = self.limits
        if self.cost_used + mb_cost + o.cost.total > lim.max_cost_per_block:
            return False
        if vote and (
            self.vote_cost_used + mb_vote_cost + o.cost.total
            > lim.max_vote_cost_per_block
        ):
            return False
        sz = len(o.payload)
        if (
            self.data_bytes_used + mb_data + sz + fc.MICROBLOCK_DATA_OVERHEAD
            > lim.max_data_bytes_per_block
        ):
            return False
        for a in writable:
            if (
                self._write_cost.get(a, 0)
                + mb_write_cost.get(a, 0)
                + o.cost.total
                > lim.max_write_cost_per_acct
            ):
                return False
        return True

    def schedule_next_microblock(self, bank: int) -> list[OrdTxn]:
        """Select a conflict-free microblock for `bank` (fd_pack.c
        fd_pack_schedule_next_microblock): votes first, up to
        VOTE_FRACTION of the cost the block has left and of the
        microblock's transaction slots (at least one), then the regular
        pool fills what remains — all under the block's limits and the
        account locks.  With no vote pooled this is the regular scan
        alone.  Chosen txns' accounts become in-use by this bank until
        microblock_done(bank)."""
        if not 0 <= bank < self.bank_cnt:
            raise ValueError("bad bank index")
        mb = _Microblock()
        max_txn = self.max_txn_per_microblock
        vote_txns = max(1, max_txn * VOTE_FRACTION_NUM // VOTE_FRACTION_DEN)
        vote_cost = (max(self.limits.max_cost_per_block - self.cost_used, 0)
                     * VOTE_FRACTION_NUM // VOTE_FRACTION_DEN)
        self._scan(bank, self._pending_votes, True, mb,
                   min(vote_txns, max_txn), vote_cost)
        self.stat_scheduled_votes += len(mb.chosen)
        self._scan(bank, self._pending, False, mb, max_txn, None)
        chosen = mb.chosen
        if not chosen:
            return []
        # commit locks + block accounting
        for o in chosen:
            sw, lr, lw = o.acct_sets()
            for a in lw:
                self._in_use.setdefault(a, [0, 0])[0] |= 1 << bank
                self._bank_accts[bank].append((a, True))
            for a in lr:
                self._in_use.setdefault(a, [0, 0])[1] |= 1 << bank
                self._bank_accts[bank].append((a, False))
            for a in sw:
                self._write_cost[a] = self._write_cost.get(a, 0) + o.cost.total
            self.cost_used += o.cost.total
            self.data_bytes_used += len(o.payload)
        self.vote_cost_used += mb.vote_cost
        self.data_bytes_used += fc.MICROBLOCK_DATA_OVERHEAD
        return chosen

    def _scan(self, bank: int, pool: list[OrdTxn], votes: bool,
              mb: "_Microblock", max_txn: int, cost_cap: int | None) -> None:
        """One pool's pass of a microblock: take in priority order what
        neither conflicts nor breaks a limit, until the microblock holds
        `max_txn` (or, for votes, `cost_cap` cost units)."""
        chosen = mb.chosen
        # scan IN PLACE: skipped entries never move (so they keep their
        # priority order for free), chosen indices are deleted after the
        # scan — the pop(0)+re-insort shape was O(pool^2) whenever the
        # pool ran deep with conflicting txns
        chosen_idx: list[int] = []
        i = 0
        limit = min(len(pool), self.max_schedule_search)
        while i < len(pool) and len(chosen) < max_txn:
            if i >= limit and chosen:
                # bounded lookahead only once something was chosen: an
                # all-unschedulable WINDOW must not starve schedulable
                # txns sitting past it (the empty case falls through to
                # a full scan — the pre-bound behavior)
                break
            o = pool[i]
            sw, lr, lw = o.acct_sets()
            # conflicts within this microblock too: serial execution inside
            # a microblock is NOT a thing — the bank executes it as one
            # conflict-free parallel burst.
            if (
                self._conflicts(bank, lw, lr)
                or (lw & (mb.taken_w | mb.taken_r))
                or (lr & mb.taken_w)
            ):
                self.stat_conflict_skips += 1
                i += 1
                continue
            if (cost_cap is not None
                    and mb.cost + o.cost.total > cost_cap) \
                    or not self._fits_block(o, votes, sw, mb.cost,
                                            mb.vote_cost, mb.data,
                                            mb.write_cost):
                i += 1
                continue
            self._sigs.discard(o.first_sig())
            self._by_sig.pop(o.first_sig(), None)
            chosen.append(o)
            chosen_idx.append(i)
            i += 1
            mb.taken_w |= lw
            mb.taken_r |= lr
            mb.cost += o.cost.total
            if votes:
                mb.vote_cost += o.cost.total
            mb.data += len(o.payload)
            for a in sw:
                mb.write_cost[a] = mb.write_cost.get(a, 0) + o.cost.total
        for j in reversed(chosen_idx):
            pool.pop(j)

    def microblock_done(self, bank: int) -> None:
        """Release `bank`'s account locks (execution finished)."""
        for a, was_write in self._bank_accts[bank]:
            u = self._in_use.get(a)
            if u is None:
                continue
            u[0 if was_write else 1] &= ~(1 << bank)
            if not (u[0] | u[1]):
                del self._in_use[a]
        self._bank_accts[bank] = []

    def end_block(self) -> None:
        self.cost_used = 0
        self.vote_cost_used = 0
        self.data_bytes_used = 0
        self._write_cost.clear()
        for b in range(self.bank_cnt):
            self.microblock_done(b)
