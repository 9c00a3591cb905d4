"""The leader pipeline over a bank that opens its slot with a validator
set: generator -> verify (device) -> pack(+dedup) -> banks -> poh ->
shred -> store, as topologies/leader.py builds it, where the shape's
`genesis` names vote accounts, SlotHashes and a preload set beside the
payers (the program's `genesis_bank_ctx` makes them; `default_bank_ctx`,
which leader.py calls, is that function's payer-only case and passes
the rest through).

What is added here: the checks that hold the account store to a plain
replay of the stored block (harness/ledger_reference.py), votes to the
guarantee that none is dropped while a non-vote is pending, and rows to
the configured message width; and the vote counts on the check line.
A program without that genesis function cannot run this configuration:
loading this file refuses it by name, with exit code 2, before anything
is built, compiled or signed.
"""

from __future__ import annotations

import sys

from firedancer_tpu.runtime import bank

from harness import ledger_reference as ledger
from harness.manifest import Manifest
from harness.rowmap import RowMap

_leader = Manifest().topology("leader")

if not hasattr(bank, "genesis_bank_ctx"):
    print("benchmark: this program's bank has no genesis_bank_ctx (vote "
          "accounts, SlotHashes, preload): it cannot run a leader_votes "
          "configuration", file=sys.stderr)
    raise SystemExit(2)


class System(_leader.System):
    def __init__(self, config: dict, gen_kw: dict, control: str | None,
                 genesis: dict):
        super().__init__(config, gen_kw, control, genesis)
        self.genesis = genesis

    def _block(self) -> list[bytes]:
        """The stored block's transactions in block order (the
        program's own entry parsers over what the store holds)."""
        from firedancer_tpu.runtime.poh_stage import parse_entry
        from firedancer_tpu.runtime.shred_stage import deshred_entry_batch

        store = self.pipe.store
        return [txn for slot in sorted(store.sets_by_slot)
                for entry in deshred_entry_batch(store.entry_batch_bytes(slot))
                for txn in parse_entry(entry)[2]]

    def _replay(self) -> dict:
        """The plain reference over genesis and the stored block, against
        the program's account store after the drain."""
        from firedancer_tpu.flamenco.agave_state import vote_state_decode
        from firedancer_tpu.flamenco.runtime import acct_decode

        g = self.genesis
        funded = 10**12           # genesis_bank_ctx's payer_lamports
        lamports = {k: funded for k in g["payers"]}
        lamports.update({ident: funded for ident, _va in g["voters"]})
        lamports.update({va: bank.VOTE_ACCOUNT_LAMPORTS
                         for _ident, va in g["voters"]})
        block = self._block()
        # only rows of the pool are replayed: anything else in the block
        # is already a miss of the harness's own check
        known = RowMap(self.gen.pool).of_payloads(block) >= 0
        ref = ledger.replay(
            lamports,
            {va: ledger.VoteAccount(ident) for ident, va in g["voters"]},
            dict(g["slot_hashes"]), g["slot"],
            [t for t, ok in zip(block, known) if ok])
        sx = self.pipe.bank_ctx.sx
        off = 0
        for key, want in ref["lamports"].items():
            val = sx.funk.rec_query(sx.xid, key)
            off += (acct_decode(val)[0] if val else 0) != want
        for key, want in ref["vote_accounts"].items():
            vs = vote_state_decode(acct_decode(
                sx.funk.rec_query(sx.xid, key))[3])
            got = (vs.votes[-1].lockout.slot if vs.votes else None,
                   vs.root_slot, len(vs.votes), vs.credits())
            off += got != (want.last_voted_slot, want.root,
                           len(want.tower), want.credits)
        banks = self.pipe.banks
        accepted = sum(b.metrics.get("txn_exec_votes")
                       - b.metrics.get("txn_exec_failed_votes") for b in banks)
        return {"off": off,
                "accepted_off": abs(accepted - (ref["votes"]
                                                - ref["votes_failed"])),
                "votes": ref["votes"], "votes_failed": ref["votes_failed"],
                "transfers_failed": ref["transfers_failed"]}

    def extra_checks(self) -> dict:
        checks = super().extra_checks()
        self.replayed = r = self._replay()
        pack, verify = self.pipe.pack.metrics, self.verify.metrics
        checks.update({
            # accounts whose lamports, or vote accounts whose last voted
            # slot, root, tower depth or credits, differ from the replay
            "account_store_off_ledger_replay": (r["off"], 0),
            # the banks' own count of accepted votes against the replay's
            "votes_accepted_minus_replay": (r["accepted_off"], 0),
            "votes_dropped_while_regular_pending":
                (pack.get("votes_dropped_while_regular_pending"), 0),
            "msg_too_long": (verify.get("msg_too_long"), 0),
        })
        return checks

    def notes(self) -> dict:
        pack = self.pipe.pack.metrics
        banks = self.pipe.banks
        r = getattr(self, "replayed", {})
        return dict(
            super().notes(),
            votes_landed=sum(b.metrics.get("txn_exec_votes") for b in banks),
            votes_landed_failed=sum(b.metrics.get("txn_exec_failed_votes")
                                    for b in banks),
            replay={k: r.get(k) for k in ("votes", "votes_failed",
                                          "transfers_failed")},
            pack={k: pack.get(k) for k in (
                "txn_in", "txn_dropped", "txn_dropped_votes",
                "txn_scheduled", "txn_scheduled_votes", "microblocks",
                "conflict_skips")})


prewarm = _leader.prewarm
