"""One FULL validator loop — the node the cluster harness boots N of.

Every subsystem here is the repo's real one, composed the way a
standalone validator composes them (the reference's fd_firedancer
topology, SURVEY §flamenco/§choreo/§disco), driven cooperatively so a
whole cluster fits one box deterministically:

  - cluster discovery: a real `runtime/gossip.GossipNode` over loopback
    UDP (CRDS push/pull, signed contact info) advertising this node's
    TVU and repair ports;
  - block intake: a TVU UDP socket feeding `runtime/fec_resolver`
    (per-shred merkle membership + one leader-signature check per FEC
    set against the wsample epoch schedule) into the flamenco
    `Blockstore`;
  - turbine: received shreds retransmit to this node's children per
    `protocol/shred_dest` (the stake-ordered tree every node derives
    identically from the epoch stakes); the leader sends each shred to
    its tree root.  Every arrival lands in a receipt ledger
    (slot/idx/sender/lane) so the harness can audit that shreds only
    ever travel tree-legal paths (or repair);
  - repair: a `runtime/repair.RepairServer` serving this node's
    blockstore, and a client that walks orphan chains (Orphan /
    HighestWindowIndex / WindowIndex with retry+backoff+peer rotation)
    verifying every repaired shred's merkle proof + leader signature
    before it enters block history;
  - replay + consensus: complete slots replay through
    `flamenco/runtime.replay_block` onto a funk fork tree tracked by
    choreo `Forks`, fork choice by choreo `Ghost`, voting through
    choreo `Tower`/`Voter` as REAL signed vote transactions on the
    wire; roots advance by a supermajority-depth rule that publishes
    funk + status cache and prunes ghost/forks;
  - leader: when the epoch schedule names this node, it executes its
    TPU inbox against the live bank (`SlotExecution` — the staged
    status-cache gate keeps resubmitted txns exactly-once across
    handoffs), builds real PoH entries, shreds them (reedsol parity +
    merkle + signature) and fans them out over the tree;
  - cold boot: `cold_boot_from_snapshot` rebuilds bank state from a
    peer's snapshot archive (flamenco/snapshot) and rejoins by
    repairing forward — the laggard-catchup path.
"""

from __future__ import annotations

import hashlib
import socket
from collections import deque
from dataclasses import dataclass

from firedancer_tpu.choreo.forks import Forks
from firedancer_tpu.choreo.ghost import Ghost
from firedancer_tpu.choreo.voter import Voter
from firedancer_tpu.flamenco.blockstore import Blockstore, StatusCache
from firedancer_tpu.flamenco.runtime import SlotExecution, replay_block
from firedancer_tpu.funk import Funk, make_funk
from firedancer_tpu.ops import bmtree
from firedancer_tpu.ops.ref import ed25519_ref as ref
from firedancer_tpu.protocol import shred as fs
from firedancer_tpu.protocol import txn as ft
from firedancer_tpu.protocol.shred_dest import NO_DEST, Dest, ShredDest
from firedancer_tpu.protocol.wsample import EpochLeaders, epoch_leaders
from firedancer_tpu.runtime import repair as fr
from firedancer_tpu.runtime.fec_resolver import FecResolver
from firedancer_tpu.runtime.gossip import GossipNode
from firedancer_tpu.runtime.poh import PohChain
from firedancer_tpu.runtime.poh_stage import build_entry, parse_entry
from firedancer_tpu.runtime.repair import RepairClient, RepairServer
from firedancer_tpu.runtime.shred_stage import deshred_entry_batch
from firedancer_tpu.runtime.shredder import EntryBatchMeta, Shredder
from firedancer_tpu.utils.rng import Rng

VOTE_MAGIC = b"FDVT"  # vote-txn datagram tag on the TVU wire

MAX_UDP = 65536


@dataclass(frozen=True)
class GenesisConfig:
    """What every validator of one cluster agrees on before slot 1:
    identities + stakes (the epoch-stake set the wsample leader schedule
    and the Turbine tree both derive from), funded accounts, and the
    recent blockhashes the txn gate honors."""

    stakes: tuple  # ((pubkey, stake), ...) sorted stake desc, then pubkey
    accounts: tuple = ()  # ((pubkey, lamports), ...)
    blockhashes: tuple = ()
    epoch: int = 0
    slot0: int = 1
    slot_cnt: int = 128

    @property
    def root_slot(self) -> int:
        return self.slot0 - 1

    @property
    def total_stake(self) -> int:
        return sum(s for _, s in self.stakes)

    def leaders(self) -> EpochLeaders:
        return epoch_leaders(self.epoch, self.slot0, self.slot_cnt,
                             list(self.stakes))


@dataclass
class ShredReceipt:
    """One shred arrival: the per-node receipt ledger row the turbine
    fanout audit replays the tree against."""

    slot: int
    idx: int
    is_data: bool
    fec_set_idx: int
    src: tuple  # (host, port) the datagram came from
    lane: str  # "turbine" | "repair"


class _RepairFace:
    """repair.RepairServer-compatible face over the flamenco Blockstore
    (get / highest) so one block history serves both replay and repair."""

    def __init__(self, bs: Blockstore):
        self._bs = bs

    def get(self, slot: int, idx: int):
        return self._bs.shreds.get((slot, idx))

    def highest(self, slot: int, min_idx: int = 0):
        m = self._bs.meta.get(slot)
        if m is None or not m.received:
            return None
        hi = max(m.received)
        if hi < min_idx:
            return None
        return self._bs.shreds.get((slot, hi))


class Validator:
    def __init__(
        self,
        secret: bytes,
        *,
        genesis: GenesisConfig,
        clock,  # () -> ms, the cluster's deterministic wallclock
        seed: int = 0,
        index: int = 0,
        fanout: int = 2,
        txns_per_microblock: int = 8,
        tick_hashes: int = 8,
        max_repair_attempts: int = 3,
        repair_spins: int = 400,
    ):
        self.secret = secret
        self.pubkey = ref.public_key(secret)
        self.genesis = genesis
        self.clock = clock
        self.index = index
        self.fanout = fanout
        self.txns_per_microblock = txns_per_microblock
        self.tick_hashes = tick_hashes
        self.max_repair_attempts = max_repair_attempts
        self.repair_spins = repair_spins
        self._stake_of = dict(genesis.stakes)
        self.stake = self._stake_of.get(self.pubkey, 0)
        self.lsched = genesis.leaders()

        # -- wire endpoints (all real loopback UDP) --------------------------
        self.tvu_sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:  # shred fan-in bursts: do not let the kernel drop silently
            self.tvu_sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF,
                                     1 << 20)
        except OSError:
            pass
        self.tvu_sock.bind(("127.0.0.1", 0))
        self.tvu_sock.setblocking(False)
        self.tpu_sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.tpu_sock.bind(("127.0.0.1", 0))
        self.tpu_sock.setblocking(False)
        self.blockstore = Blockstore()
        self.repair_server = RepairServer(_RepairFace(self.blockstore),
                                          secret)
        self.repair_client = RepairClient(secret,
                                          rng=Rng(seed, 0x4EA1 + index))
        self.gossip = GossipNode(
            secret,
            tvu_port=self.tvu_sock.getsockname()[1],
            repair_port=self.repair_server.addr[1],
            clock=clock,
        )
        self.gossip.set_stakes(dict(genesis.stakes))

        # -- bank state ------------------------------------------------------
        self.funk = make_funk()
        self.status_cache = StatusCache()
        self._apply_genesis()
        self.forks = Forks(genesis.root_slot)
        self.ghost = Ghost(genesis.root_slot)
        self.voter = Voter(vote_account=self.pubkey,
                           voter_pubkey=self.pubkey,
                           sign=lambda msg: ref.sign(secret, msg))
        self.resolver = FecResolver(max_inflight=64)
        self.shredder = Shredder(
            signer=lambda root: ref.sign(secret, root), shred_version=1)

        # -- ledgers / loop state -------------------------------------------
        self.blocks: dict[int, object] = {}  # slot -> BlockResult
        self.landed: dict[int, list[bytes]] = {}  # slot -> landed first-sigs
        self.receipts: list[ShredReceipt] = []
        self.rejected_sets = 0  # completed FEC sets failing the leader sig
        self.missed_slots: list[int] = []
        self.dead_slots: set[int] = set()  # gave up repairing
        self._repair_attempts: dict[int, int] = {}
        self._retransmitted: set[tuple[int, int]] = set()
        self._seen_slots: set[int] = set()
        self._pending_votes: dict[int, list] = {}  # slot -> [(pk, stake, bh)]
        self._applied_votes: dict[bytes, int] = {}  # voter pk -> latest slot
        self.tpu_pending: deque = deque()
        self.tpu_seen: set[bytes] = set()
        self._outbox: deque = deque()  # (addr, datagram)
        self.outbox_rate = 8  # datagrams sent per step
        self._dest_addrs: dict[bytes, tuple] = {}  # pubkey -> tvu addr
        self._sdest: ShredDest | None = None
        self.alive = True
        self.frozen = False
        self.vote_conflicts = 0
        self.cold_boots = 0
        self.repaired_shreds = 0
        self.repair_kinds: dict[str, int] = {}
        self.rooted_slots: list[int] = []  # published path, oldest first

    # -- genesis / identity --------------------------------------------------

    def _apply_genesis(self) -> None:
        from firedancer_tpu.flamenco.runtime import acct_build

        for pk, lamports in self.genesis.accounts:
            self.funk.rec_insert(None, pk, acct_build(lamports))
        for bh in self.genesis.blockhashes:
            self.status_cache.register_blockhash(bh, self.genesis.root_slot)

    @property
    def tvu_addr(self):
        return self.tvu_sock.getsockname()

    @property
    def tpu_addr(self):
        return self.tpu_sock.getsockname()

    def leader_for(self, slot: int) -> bytes | None:
        return self.lsched.leader_for_slot(slot)

    def is_leader(self, slot: int) -> bool:
        return self.leader_for(slot) == self.pubkey

    # -- turbine tree --------------------------------------------------------

    def build_dests(self, tvu_addrs: dict[bytes, tuple]) -> None:
        """Fix the turbine destination set: stake order comes from the
        EPOCH STAKES (identical on every node — tree agreement must not
        depend on gossip convergence); addresses come from gossip
        discovery.  Called once the harness sees full discovery."""
        self._dest_addrs = dict(tvu_addrs)
        dests = [Dest(pubkey=pk, stake=st) for pk, st in self.genesis.stakes]
        self._sdest = ShredDest(dests, self.lsched, self.pubkey)

    def dest_table_from_gossip(self) -> dict[bytes, tuple]:
        out = {self.pubkey: self.tvu_addr}
        for pk, info in self.gossip.table.items():
            out[pk] = (socket.inet_ntoa(info.ip4.to_bytes(4, "big")),
                       info.tvu_port)
        return out

    def _dest_pk(self, i: int) -> bytes:
        return self._sdest.dests[i].pubkey

    # -- the cooperative loop ------------------------------------------------

    def step(self) -> None:
        """One sweep: wire in, wire out, replay, root housekeeping."""
        if not self.alive:
            return
        if self.frozen:
            # a frozen node's NIC drops: drain and discard so the queues
            # never deliver stale traffic at thaw (the laggard fault)
            self._drain_discard()
            return
        self.gossip.poll()
        self.repair_server.poll()
        self.poll_wire()
        self.drain_outbox()
        self.try_replay()

    def poll_wire(self, burst: int = 64) -> None:
        """TVU (shreds + votes) and TPU (txn submissions) intake."""
        for _ in range(burst):
            try:
                data, src = self.tvu_sock.recvfrom(MAX_UDP)
            except (BlockingIOError, InterruptedError):
                break
            if data[:4] == VOTE_MAGIC:
                self._on_vote(bytes(data[4:]))
            else:
                self._on_shred(bytes(data), src, lane="turbine")
        for _ in range(burst):
            try:
                data, _src = self.tpu_sock.recvfrom(MAX_UDP)
            except (BlockingIOError, InterruptedError):
                break
            self._on_tpu(bytes(data))

    def _drain_discard(self) -> None:
        for sock in (self.tvu_sock, self.tpu_sock,
                     self.gossip.sock, self.repair_server.sock):
            for _ in range(256):
                try:
                    sock.recvfrom(MAX_UDP)
                except (BlockingIOError, InterruptedError):
                    break

    def drain_outbox(self) -> None:
        for _ in range(self.outbox_rate):
            if not self._outbox:
                return
            addr, dg = self._outbox.popleft()
            self.tvu_sock.sendto(dg, addr)

    def close(self) -> None:
        self.alive = False
        for sock in (self.tvu_sock, self.tpu_sock):
            sock.close()
        self.gossip.close()
        self.repair_server.close()
        self.repair_client.close()

    # -- shred ingest + turbine retransmit -----------------------------------

    def _on_shred(self, buf: bytes, src, lane: str) -> None:
        s = fs.parse(buf)
        if s is None:
            return
        self.receipts.append(ShredReceipt(
            slot=s.slot, idx=s.idx, is_data=s.is_data,
            fec_set_idx=s.fec_set_idx, src=src, lane=lane))
        # repair watches SEEN slots, not just blockstore-partial ones: a
        # set stuck in the resolver (no coding shred yet — the leader
        # died before parity went out) is invisible to the blockstore
        # but must still drive repair toward recovery-or-missed
        self._seen_slots.add(s.slot)
        if lane == "turbine":
            key = (s.slot, s.idx if s.is_data else (1 << 32) + s.idx)
            if key not in self._retransmitted and self._sdest is not None:
                self._retransmitted.add(key)
                for ci in self._sdest.children_for(
                    s.slot, s.idx, s.is_data, fanout=self.fanout
                ):
                    addr = self._dest_addrs.get(self._dest_pk(ci))
                    if addr is not None:
                        self._outbox.append((addr, buf))
        out = self.resolver.add_shred(buf)
        if out is not None:
            self._on_fec_set(out)

    def _on_fec_set(self, st) -> None:
        """A completed FEC set: ONE leader-signature check against the
        epoch schedule gates the whole set into block history (the
        fd_fec_resolver amortization; membership proofs were checked
        per shred by the resolver)."""
        leader = self.leader_for(st.slot)
        sig = fs.parse(st.data_shreds[0]).signature(st.data_shreds[0])
        if leader is None or not ref.verify(st.merkle_root, sig, leader):
            self.rejected_sets += 1
            return
        for buf in st.data_shreds:
            self.blockstore.insert_shred(buf)

    def _verify_repaired(self, buf: bytes) -> bool:
        """A repaired shred arrives alone (no set context): full merkle
        membership + leader signature before it may enter block history
        — repair peers are untrusted."""
        s = fs.parse(buf)
        if s is None or not s.is_data:
            return False
        leader = self.leader_for(s.slot)
        if leader is None:
            return False
        leaf = bmtree.hash_leaf_full(s.merkle_leaf_data(buf))
        root = bmtree.verify_proof(leaf, s.idx - s.fec_set_idx,
                                   s.merkle_proof(buf))
        return ref.verify(root, s.signature(buf), leader)

    # -- votes ---------------------------------------------------------------

    def broadcast_vote(self, payload: bytes) -> None:
        dg = VOTE_MAGIC + payload
        for pk, addr in self._dest_addrs.items():
            if pk != self.pubkey:
                self._outbox.append((addr, dg))

    def _on_vote(self, payload: bytes) -> None:
        from firedancer_tpu.flamenco.vote_program import VOTE_IX
        from firedancer_tpu.flamenco.types import U32

        t = ft.txn_parse(payload)
        if t is None:
            return
        addrs = t.acct_addrs(payload)
        voter_pk = addrs[0]
        stake = self._stake_of.get(voter_pk, 0)
        if stake <= 0:
            return
        if not ref.verify(t.message(payload), t.signatures(payload)[0],
                          voter_pk):
            return
        instr = t.instrs[0]
        data = payload[instr.data_off : instr.data_off + instr.data_sz]
        tag, off = U32.decode(data, 0)
        if tag != 2:
            return
        vote, _ = VOTE_IX.decode(data, off)
        slot = vote.slots[-1]
        self.apply_vote(voter_pk, slot, stake, vote.hash)

    def apply_vote(self, voter_pk: bytes, slot: int, stake: int,
                   bank_hash: bytes) -> None:
        if self._applied_votes.get(voter_pk, -1) >= slot:
            return  # LMD: only newer votes move stake
        if slot <= self.ghost.root:
            return  # rooted history: nothing left to choose
        if slot not in self.ghost.nodes:
            # buffered until replay inserts the slot (partition heal:
            # the other side's votes arrive before its blocks replay)
            self._pending_votes.setdefault(slot, []).append(
                (voter_pk, stake, bank_hash))
            return
        blk = self.blocks.get(slot)
        if blk is not None and bank_hash != blk.bank_hash:
            self.vote_conflicts += 1
            return
        self._applied_votes[voter_pk] = slot
        self.ghost.vote(voter_pk, slot, stake)

    def _flush_pending_votes(self, slot: int) -> None:
        for voter_pk, stake, bank_hash in self._pending_votes.pop(slot, []):
            self.apply_vote(voter_pk, slot, stake, bank_hash)

    def is_ancestor(self, a: int, b: int) -> bool:
        """Ancestry oracle for the tower: the rooted chain is by
        definition an ancestor of everything live, and pruned slots are
        on no live fork — ghost's raw walk would KeyError on a tower
        vote older than the root (deep lockouts outlive root advance)."""
        if a <= self.ghost.root:
            return True
        if a not in self.ghost.nodes or b not in self.ghost.nodes:
            return False
        return self.ghost.is_ancestor(a, b)

    def ghost_weight(self, slot: int) -> int:
        """Weight oracle for the tower's threshold check: a pruned
        (rooted) slot holds the whole cluster by definition."""
        if slot in self.ghost.nodes:
            return self.ghost.weight(slot)
        return self.genesis.total_stake if slot <= self.ghost.root else 0

    def maybe_vote(self) -> None:
        """Vote for the ghost head through the tower's safety checks;
        an approved vote is a REAL signed vote txn on the wire."""
        head = self.ghost.head()
        if head == self.ghost.root or head not in self.blocks:
            return
        payload = self.voter.maybe_vote(
            head,
            self.genesis.blockhashes[0],
            is_ancestor=self.is_ancestor,
            ghost_weight=self.ghost_weight,
            total_stake=self.genesis.total_stake,
            bank_hash=self.blocks[head].bank_hash,
        )
        if payload is None:
            return
        self.apply_vote(self.pubkey, head, self.stake,
                        self.blocks[head].bank_hash)
        self.broadcast_vote(payload)

    # -- replay --------------------------------------------------------------

    def _parent_slot_of(self, slot: int) -> int | None:
        buf = self.blockstore.shreds.get((slot, 0))
        if buf is None:
            return None
        s = fs.parse(buf)
        return slot - s.parent_off

    def _ancestor_slots(self, parent_slot: int) -> set[int]:
        """The executing bank's full-chain ancestor set for the
        status-cache gate: the live fork path PLUS the rooted history —
        everything below the root is canonical by definition, so a txn
        rooted long ago must still answer ALREADY_PROCESSED when
        resubmitted (a root-relative set would forget it once the root
        advances past its landing slot)."""
        out = {parent_slot} | set(self.forks.ancestors(parent_slot))
        out.update(self.rooted_slots)
        out.add(self.genesis.root_slot)
        return out

    def try_replay(self) -> None:
        for slot in sorted(self.blockstore.meta):
            if slot <= self.forks.root_slot or slot in self.blocks:
                continue
            if slot in self.dead_slots:
                continue
            if not self.blockstore.is_complete(slot):
                continue
            parent = self._parent_slot_of(slot)
            if parent is None:
                continue
            if parent not in self.forks or not self.forks.get(parent).frozen:
                continue  # repair_tick walks the orphan chain
            self.replay_slot(slot, parent)
        self.maybe_vote()
        self.maybe_publish()

    def replay_slot(self, slot: int, parent_slot: int) -> bool:
        """Replay a stored slot on its parent's fork: the PoH chain over
        its entries, then execution (flamenco/runtime.replay_block, one
        parse a transaction for both).  The block's SIGNATURES are
        still checked here by `ops/ref` in Python, one at a time as the
        executor meets them — the cluster harness's toy sizes.  The
        stage that does it at rate, before execution, is
        runtime/replay_verify.ReplayVerifyStage (entry batches in,
        every signature on the device in 16,384-lane batches, a slot
        dead from its first failing entry batch; deployment
        `replay-verify-v5e`): this method does not go through it yet
        (ROADMAP Queue 2: execution behind the stage)."""
        parent = self.forks.get(parent_slot)
        entries = [parse_entry(e) for e in deshred_entry_batch(
            self.blockstore.entry_batch_bytes(slot))]
        ancestors = self._ancestor_slots(parent_slot)
        res = replay_block(
            self.funk, slot=slot, entries=entries,
            poh_seed=parent.poh_hash,
            parent_bank_hash=parent.bank_hash, parent_xid=parent.xid,
            status_cache=self.status_cache, ancestors=ancestors,
        )
        if res is None:
            # PoH fraud: the block can never become part of this node's
            # chain; remember so replay doesn't spin on it
            self.dead_slots.add(slot)
            return False
        poh_hash = entries[-1][1] if entries else parent.poh_hash
        self.forks.insert(slot, parent_slot)
        self.forks.freeze(slot, xid=res.xid, bank_hash=res.bank_hash,
                          poh_hash=poh_hash)
        self.ghost.insert(slot, parent_slot)
        self.blocks[slot] = res
        self.landed[slot] = [
            ft.txn_parse(p).signatures(p)[0]
            for _n, _h, txns in entries for p in txns
        ]
        self._flush_pending_votes(slot)
        return True

    # -- root advance --------------------------------------------------------

    root_lag = 4  # head-to-root depth before a publish is considered

    def maybe_publish(self) -> None:
        """Advance the root to the head's `root_lag`-deep ancestor once a
        supermajority of stake is voting inside that subtree: funk +
        status cache publish the chain, ghost/forks prune everything
        else (fd_replay's funk_publish coordination)."""
        head = self.ghost.head()
        candidate = head
        for _ in range(self.root_lag):
            parent = self.ghost.nodes[candidate].parent
            if parent is None:
                break
            candidate = parent
        if candidate == self.ghost.root or candidate == self.genesis.root_slot:
            return
        if 3 * self.ghost.weight(candidate) < 2 * self.genesis.total_stake:
            return
        old_root = self.forks.root_slot
        path = [s for s in sorted(
            set(self.forks.ancestors(candidate)) | {candidate})
            if s > old_root]
        for s in path:
            if s in self.blocks:
                self.status_cache.commit_block(self.blocks[s].xid)
        self.funk.txn_publish(self.blocks[candidate].xid)
        pruned = self.forks.publish(candidate)
        # the published chain's funk txns are GONE (folded into root, the
        # children reparented to root): a later block parented exactly at
        # the new root must fork off funk's root (parent_xid=None), not
        # off a deleted xid
        self.forks.get(candidate).xid = None
        for s in pruned:
            if s in self.blocks and s not in path:
                self.status_cache.drop_block(self.blocks[s].xid)
        self.ghost.publish(candidate)
        self.rooted_slots.extend(path)

    @property
    def root_slot(self) -> int:
        return self.forks.root_slot

    def root_bank_hash(self) -> bytes:
        f = self.forks.get(self.forks.root_slot)
        return f.bank_hash

    def best_chain(self) -> list[int]:
        """Published history + the ghost-head fork, oldest first — the
        chain this node currently believes in."""
        out = []
        cur = self.ghost.head()
        while cur is not None and cur != self.ghost.root:
            out.append(cur)
            cur = self.ghost.nodes[cur].parent
        return self.rooted_slots + out[::-1]

    def chain_landed(self) -> set[bytes]:
        """First signatures of every txn landed on the best chain."""
        out: set[bytes] = set()
        for slot in self.best_chain():
            out.update(self.landed.get(slot, ()))
        return out

    # -- leader path ---------------------------------------------------------

    def _on_tpu(self, payload: bytes) -> None:
        t = ft.txn_parse(payload)
        if t is None:
            return
        sig = t.signatures(payload)[0]
        if sig in self.tpu_seen:
            return
        self.tpu_seen.add(sig)
        self.tpu_pending.append(payload)

    def produce_block(self, slot: int) -> bool:
        """Leader side: execute the TPU inbox on the fork-choice head,
        build PoH entries, shred, queue the turbine fan-out.  The block
        freezes locally immediately (the leader replays nothing)."""
        if self._sdest is None or slot in self.blocks:
            return False
        parent_slot = self.ghost.head()
        parent = self.forks.get(parent_slot)
        if not parent.frozen or slot <= parent_slot:
            return False
        txns = list(self.tpu_pending)
        self.tpu_pending.clear()
        # inbox dedup covers the PENDING window only: a txn whose first
        # landing died with a fork must re-enter when the client
        # resubmits it (the status-cache gate owns real dup rejection)
        self.tpu_seen.clear()
        ancestors = self._ancestor_slots(parent_slot)
        sx = SlotExecution(
            self.funk, slot=slot, parent_bank_hash=parent.bank_hash,
            parent_xid=parent.xid, status_cache=self.status_cache,
            ancestors=ancestors,
        )
        chain = PohChain(hash=parent.poh_hash)
        entries = []
        landed_sigs = []
        for off in range(0, len(txns), self.txns_per_microblock):
            group = txns[off : off + self.txns_per_microblock]
            payloads, sigs = [], []
            for p in group:
                t = ft.txn_parse(p)
                if t is None:
                    continue
                r = sx.execute(p, t)
                if r.fee > 0:  # landed (the entry-inclusion predicate)
                    payloads.append(p)
                    sigs.append(t.signatures(p)[0])
            if not payloads:
                continue
            chain.mixin(hashlib.sha256(b"".join(sigs)).digest())
            entries.append((1, chain.hash, payloads))
            landed_sigs.extend(sigs)
        # closing tick: the slot's clock keeps running past the last txn
        chain.append(self.tick_hashes)
        entries.append((self.tick_hashes, chain.hash, []))
        poh_hash = chain.hash
        res = sx.seal(poh_hash)
        self.forks.insert(slot, parent_slot)
        self.forks.freeze(slot, xid=sx.xid, bank_hash=res.bank_hash,
                          poh_hash=poh_hash)
        self.ghost.insert(slot, parent_slot)
        self.blocks[slot] = res
        self.landed[slot] = landed_sigs

        batch = bytearray()
        for e in entries:
            eb = build_entry(*e)
            batch += len(eb).to_bytes(4, "little")
            batch += eb
        parent_off = min(slot - parent_slot, 0xFFFF)
        sets = self.shredder.entry_batch_to_fec_sets(
            bytes(batch), slot=slot,
            meta=EntryBatchMeta(parent_offset=parent_off,
                                block_complete=True),
        )
        for st in sets:
            for buf in st.data_shreds:
                self.blockstore.insert_shred(buf)
            for buf in st.data_shreds + st.parity_shreds:
                s = fs.parse(buf)
                di = self._sdest.first_for(s.slot, s.idx, s.is_data)
                if di == NO_DEST:
                    continue
                addr = self._dest_addrs.get(self._dest_pk(di))
                if addr is not None:
                    self._outbox.append((addr, buf))
        self.maybe_vote()
        return True

    # -- repair (catch-up) ---------------------------------------------------

    def repair_peers(self) -> list[tuple]:
        """((addr, recipient_pubkey), ...) of live-looking peers, stake
        order — the gossip table is the live view (expired/dead peers
        fell out of it via GossipNode.housekeeping), and the recipient
        pubkey rides along because peers' signing repair servers refuse
        misdirected requests."""
        out = []
        for pk, _stake in self.genesis.stakes:
            info = self.gossip.table.get(pk)
            if info is None or pk == self.pubkey:
                continue
            addr = (socket.inet_ntoa(info.ip4.to_bytes(4, "big")),
                    info.repair_port)
            out.append((addr, pk))
        return out

    def _repair_one(self, peers, slot: int, idx: int, *, kind: str,
                    spin) -> bytes | None:
        self.repair_kinds[kind] = self.repair_kinds.get(kind, 0) + 1
        got = self.repair_client.request(
            peers, slot, idx, kind=kind, spin=spin,
            max_spins=self.repair_spins, retries=max(len(peers) - 1, 0),
        )
        if got is not None and self._verify_repaired(got):
            s = fs.parse(got)
            if s.slot != slot:
                # the client's nonce+slot validation already rejects
                # mismatched replies; this is the last-line boundary so a
                # future client change can never let a validly-signed
                # OTHER-slot shred count as progress for this request
                return None
            self.receipts.append(ShredReceipt(
                slot=s.slot, idx=s.idx, is_data=s.is_data,
                fec_set_idx=s.fec_set_idx,
                src=self.repair_client.last_peer or ("", 0),
                lane="repair"))
            self._seen_slots.add(s.slot)
            self.blockstore.insert_shred(got)
            self.repaired_shreds += 1
            return got
        return None

    def repair_tick(self, spin=None, *, current_slot: int | None = None,
                    budget: int = 8) -> int:
        """Bounded repair sweep: walk orphan chains back from known
        slots, then fill holes in incomplete past slots.  `spin` pumps
        the serving side (the harness: the REST of the cluster keeps
        running — catch-up happens under load).  Returns shreds
        recovered this sweep."""
        if self._sdest is None:
            return 0
        peers = self.repair_peers()
        if not peers:
            return 0
        got = 0
        # orphan walk: a slot we can see whose parent we lack
        known = set(self.blockstore.meta) | set(self.forks.slots())
        for slot in sorted(self.blockstore.meta):
            if got >= budget:
                break
            if slot <= self.forks.root_slot:
                continue
            parent = self._parent_slot_of(slot)
            if parent is None or parent <= self.forks.root_slot:
                continue
            if parent in known or parent in self.dead_slots:
                continue
            shred = self._repair_one(peers, parent, 0, kind="orphan",
                                     spin=spin)
            if shred is not None:
                got += 1
            else:
                self._bump_attempts(parent)
        # hole fill: incomplete (or resolver-stuck) slots behind the tip
        tip = current_slot if current_slot is not None else (
            max(set(self.blockstore.meta) | self._seen_slots, default=0))
        for slot in sorted(set(self.blockstore.meta) | self._seen_slots):
            if got >= budget:
                break
            if slot <= self.forks.root_slot:
                continue
            if slot >= tip or slot in self.dead_slots or slot in self.blocks:
                continue
            m = self.blockstore.meta.get(slot)
            if m is not None and m.complete:
                continue
            if m is None or m.last_index is None:
                # probe strictly PAST what we hold: a peer echoing back a
                # shred we already have is not progress, and a slot the
                # whole cluster only has a fragment of (leader died
                # mid-broadcast) must time out toward missed, not loop
                probe = (max(m.received, default=-1) + 1) if m else 0
                if self._repair_one(peers, slot, probe,
                                    kind="highest_window_index",
                                    spin=spin) is None:
                    self._bump_attempts(slot)
                    continue
                got += 1
                m = self.blockstore.meta[slot]
            for idx in m.missing():
                if got >= budget:
                    break
                if self._repair_one(peers, slot, idx, kind="window_index",
                                    spin=spin) is not None:
                    got += 1
                else:
                    self._bump_attempts(slot)
                    break
        return got

    def _bump_attempts(self, slot: int) -> None:
        n = self._repair_attempts.get(slot, 0) + 1
        self._repair_attempts[slot] = n
        if n >= self.max_repair_attempts:
            # nobody can serve it (leader died mid-broadcast): a MISSED
            # slot is an observation, not a fatal error
            self.dead_slots.add(slot)
            if slot not in self.missed_slots:
                self.missed_slots.append(slot)

    # -- snapshot cold boot --------------------------------------------------

    def write_snapshot(self, path: str) -> int:
        """Serve this node's published root as a snapshot archive (what
        a laggard cold-boots from)."""
        from firedancer_tpu.flamenco.snapshot import snapshot_write

        return snapshot_write(
            self.funk, path, slot=self.forks.root_slot,
            bank_hash=self.root_bank_hash(),
        )

    def cold_boot_from_snapshot(self, path: str) -> int:
        """Laggard catch-up, the heavy half: throw away local bank state
        and rebuild from a peer's snapshot — funk root at the snapshot
        slot, fresh fork/ghost trees rooted there — then rejoin by
        repairing forward.  Returns the snapshot slot."""
        from firedancer_tpu.flamenco.snapshot import snapshot_load

        funk, man = snapshot_load(path)
        self.funk = funk
        self.status_cache = StatusCache()
        for bh in self.genesis.blockhashes:
            self.status_cache.register_blockhash(bh, man.slot)
        self.forks = Forks(man.slot, root_bank_hash=man.bank_hash)
        # the snapshot's bank hash chains replay exactly like a locally
        # frozen parent; poh seed for the next slot comes from the next
        # block's shreds' parent chain (its producer used the real poh
        # hash, which rides IN the entries we replay — the chain check
        # seeds from the parent's poh_hash, so restore it from a peer's
        # fork record via repair of the root slot's last entry is not
        # needed: the harness guarantees root blocks carry poh in forks)
        self.ghost = Ghost(man.slot)
        from firedancer_tpu.choreo.tower import Tower

        self.voter.tower = Tower()
        self.voter.last_sent = man.slot
        self.blocks = {}
        self.landed = {}
        self.dead_slots = set()
        self._seen_slots = set()
        self.rooted_slots = []
        self._repair_attempts.clear()
        self._pending_votes.clear()
        self._applied_votes.clear()
        self.resolver = FecResolver(max_inflight=64)
        self.cold_boots += 1
        return man.slot

    def adopt_root_poh(self, poh_hash: bytes) -> None:
        """Cold boot rider: the snapshot manifest carries the bank hash
        but not the PoH tip; the harness hands it over from the serving
        peer's fork record (a real manifest's bank fields include it)."""
        self.forks.get(self.forks.root_slot).poh_hash = poh_hash


def make_cluster_genesis(
    n: int,
    *,
    seed: int = 0,
    base_stake: int = 1000,
    accounts: tuple = (),
    blockhashes: tuple = (),
    slot_cnt: int = 128,
    epoch: int = 0,
) -> tuple[GenesisConfig, list[bytes]]:
    """N identities with distinct, near-even stakes (uneven enough that
    weighted sampling is exercised, even enough that the wsample leader
    schedule rotates through several identities), in Agave stake order."""
    secrets = [hashlib.sha256(b"cluster-v-%d-%d" % (seed, i)).digest()
               for i in range(n)]
    pairs = []
    for i, sec in enumerate(secrets):
        pairs.append((ref.public_key(sec), base_stake + 7 * i))
    pairs.sort(key=lambda kv: (-kv[1], kv[0]))
    genesis = GenesisConfig(
        stakes=tuple(pairs), accounts=tuple(accounts),
        blockhashes=tuple(blockhashes), slot_cnt=slot_cnt, epoch=epoch,
    )
    return genesis, secrets
