"""The plain reference for a block's effect on the account store:
genesis + the stored block's transactions, in block order -> the final
state.  Plain Python over the wire bytes; imports nothing of
`flamenco/`, `pack/` or `native/`.  What it knows of the ledger's rules:

  - every transaction in a block paid 5,000 lamports a signature from
    its first account, plus ceil(limit x price / 10^6) where it names a
    compute-unit price (limit: SetComputeUnitLimit, else 200,000 an
    instruction that is not the compute budget's), whatever came of it;
  - its instructions take effect together or not at all;
  - a system `Transfer` moves its lamports if the source holds them;
  - a `Vote` is accepted iff the vote account's authorized voter
    signed, the slot is above the account's last voted slot, the hash
    is that slot's SlotHashes entry and the timestamp does not run
    backwards; an accepted slot goes onto the account's tower (expired
    lockouts pop, the 32nd vote roots the oldest for a credit, deeper
    votes double their lockouts).

This file exists twice, byte for byte (held so by
benchmarks/tests/test_mainnet_mix.py): `firedancer_tpu/ops/ref/
ledger_replay.py` for the program's tests, and `benchmarks/harness/
ledger_reference.py`, because the reference that judges the program in
the benchmark is the benchmark's own file."""

from __future__ import annotations

from dataclasses import dataclass, field

SYSTEM_PROGRAM = bytes(32)
VOTE_PROGRAM = bytes.fromhex(
    "0761481d357474bb7c4d7624ebd3bdb3d8355e73d11043fc0da3538000000000")
COMPUTE_BUDGET_PROGRAM = bytes.fromhex(
    "0306466fe5211732ffecadba72c39be7bc8ce5bbc5f7126b2c439b3a40000000")
FEE_PER_SIGNATURE = 5000
MAX_LOCKOUT_HISTORY = 31


@dataclass
class VoteAccount:
    authority: bytes
    tower: list = field(default_factory=list)   # [slot, confirmations]
    root: int | None = None
    credits: int = 0
    last_timestamp: tuple = (0, 0)              # (slot, timestamp)
    accepted: int = 0

    @property
    def last_voted_slot(self) -> int | None:
        return self.tower[-1][0] if self.tower else None


def _compact(p: bytes, o: int) -> tuple[int, int]:
    v = shift = 0
    while True:
        b = p[o]
        o += 1
        v |= (b & 0x7F) << shift
        if not b & 0x80:
            return v, o
        shift += 7


def parse(txn: bytes):
    """-> (signature count, account keys, [(program key, account
    indices, data)]) of a legacy transaction."""
    n_sig, o = _compact(txn, 0)
    o += 64 * n_sig + 3
    n_keys, o = _compact(txn, o)
    keys = [txn[o + 32 * k:o + 32 * (k + 1)] for k in range(n_keys)]
    o += 32 * n_keys + 32
    n_ins, o = _compact(txn, o)
    instrs = []
    for _ in range(n_ins):
        prog = keys[txn[o]]
        n, o = _compact(txn, o + 1)
        accts = list(txn[o:o + n])
        n, o = _compact(txn, o + n)
        instrs.append((prog, accts, txn[o:o + n]))
        o += n
    return n_sig, keys, instrs


def fee(n_sig: int, instrs) -> int:
    limit = price = None
    for prog, _a, data in instrs:
        if prog == COMPUTE_BUDGET_PROGRAM and data[0] == 2:
            limit = int.from_bytes(data[1:5], "little")
        elif prog == COMPUTE_BUDGET_PROGRAM and data[0] == 3:
            price = int.from_bytes(data[1:9], "little")
    if limit is None:
        limit = 200_000 * sum(p != COMPUTE_BUDGET_PROGRAM
                              for p, _a, _d in instrs)
    return FEE_PER_SIGNATURE * n_sig \
        + -(-min(limit, 1_400_000) * (price or 0) // 1_000_000)


def _vote(va: VoteAccount, signers: set, data: bytes, slot_hashes: dict,
          clock_slot: int) -> bool:
    """One `Vote` instruction (tag 2, one slot, with timestamp) against
    a copy of the account.  -> accepted."""
    n = int.from_bytes(data[4:12], "little")
    slot = int.from_bytes(data[12:20], "little")
    ts = int.from_bytes(data[53:61], "little", signed=True)
    last = va.last_voted_slot
    if (n != 1 or data[52] != 1 or va.authority not in signers
            or (last is not None and slot <= last)
            or slot_hashes.get(slot) != data[20:52]
            or slot < va.last_timestamp[0] or ts < va.last_timestamp[1]):
        return False
    while va.tower and va.tower[-1][0] + 2 ** va.tower[-1][1] < slot:
        va.tower.pop()
    if len(va.tower) == MAX_LOCKOUT_HISTORY:
        rooted = va.tower.pop(0)
        va.root = rooted[0]
        latency = min(max(0, clock_slot - rooted[0]), 255)
        va.credits += 1 if latency == 0 else 16 if latency <= 2 \
            else max(16 - (latency - 2), 1)
    va.tower.append([slot, 1])
    for depth, lk in enumerate(va.tower):
        if len(va.tower) > depth + lk[1]:
            lk[1] += 1
    va.last_timestamp = (slot, ts)
    va.accepted += 1
    return True


def replay(lamports: dict, vote_accounts: dict, slot_hashes: dict,
           clock_slot: int, txns) -> dict:
    """lamports: pubkey -> balance at genesis (an absent account holds
    0); vote_accounts: address -> VoteAccount; slot_hashes: slot -> hash;
    txns: the block's transactions in order.  Mutates and returns
    {"lamports", "vote_accounts", "votes", "votes_failed",
    "transfers_failed"}."""
    votes = votes_failed = transfers_failed = 0
    for txn in txns:
        n_sig, keys, instrs = parse(txn)
        lamports[keys[0]] = lamports.get(keys[0], 0) - fee(n_sig, instrs)
        signers = set(keys[:n_sig])
        moved: dict = {}
        voted: dict = {}
        ok = True
        for prog, accts, data in instrs:
            if prog == SYSTEM_PROGRAM and data[:4] == b"\x02\x00\x00\x00":
                src, dst = keys[accts[0]], keys[accts[1]]
                amount = int.from_bytes(data[4:12], "little")
                have = lamports.get(src, 0) + moved.get(src, 0)
                ok = src in signers and have >= amount
                moved[src] = moved.get(src, 0) - amount
                moved[dst] = moved.get(dst, 0) + amount
            elif prog == VOTE_PROGRAM:
                addr = keys[accts[0]]
                va = voted[addr] = VoteAccount(**{
                    **vars(vote_accounts[addr]),
                    "tower": [list(lk) for lk in vote_accounts[addr].tower]})
                ok = _vote(va, {keys[i] for i in accts if i < n_sig}, data,
                           slot_hashes, clock_slot)
            elif prog != COMPUTE_BUDGET_PROGRAM:
                raise ValueError("the reference knows no such program")
            if not ok:
                break
        is_vote = len(instrs) == 1 and instrs[0][0] == VOTE_PROGRAM
        votes += is_vote
        if ok:
            for k, d in moved.items():
                lamports[k] = lamports.get(k, 0) + d
            vote_accounts.update(voted)
        elif is_vote:
            votes_failed += 1
        else:
            transfers_failed += 1
    return {"lamports": lamports, "vote_accounts": vote_accounts,
            "votes": votes, "votes_failed": votes_failed,
            "transfers_failed": transfers_failed}
