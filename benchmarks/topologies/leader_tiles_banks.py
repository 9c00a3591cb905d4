"""topologies/leader_tiles.py's process-per-tile leader with B bank tiles
(`layout.bank_stage_count`), each a process, committing into ONE account
store: one native-funk shm segment that bank tile 0 makes and the others
attach to as writers (models/leader_topo.build_bank).  Pack's account
locks order two tiles that touch one account; each tile's native session
takes from the segment what another left (`session_refreshed`).

What leader_tiles.System reads of `bank0` alone is read here of every
bank: the tap follows all B bank -> poh rings, the drain's "nothing
moves" test and `tap_txn_minus_bank_txn_exec` take the banks' `txn_exec`
summed, and one more check holds that every bank tile executed.  The
account store's check is leader_tiles' own: the ONE store, read through
`attach_readonly`, against the plain replay of the stored block — an
update lost between two tiles is a balance off by one transfer.  The
check line gains a block a bank (what it executed, what its session took
from the segment, its use of the store's lock, a second) and pack's
microblocks a bank.  A program that cannot run B > 1 bank tiles in
processes cannot run this configuration: loading this file refuses it by
name, with exit code 2, before anything is built, compiled or signed.
"""

from __future__ import annotations

import sys
import time

from firedancer_tpu.funk.funk_native import NativeFunk
from firedancer_tpu.utils import config as fcfg

from harness.manifest import Manifest
from harness.stages import CommitTap

if not hasattr(NativeFunk, "attach") \
        or not hasattr(fcfg.Config(), "development"):
    print("benchmark: this program's bank tiles cannot share an account "
          "store across processes (funk/funk_native.NativeFunk.attach, "
          "[development.bench] in utils/config): it runs one bank tile a "
          "process topology and cannot run a leader_tiles_banks "
          "configuration", file=sys.stderr)
    raise SystemExit(2)

_tiles = Manifest().topology("leader_tiles")

# a bank tile's counters that the check line carries a second
_BANK_RATES = ("txn_exec", "session_refreshed", "funk_lock_acquires",
               "funk_lock_contended", "funk_lock_wait_ns")


class System(_tiles.System):
    def __init__(self, config: dict, gen_kw: dict, control: str | None,
                 genesis: dict):
        n = config["program_config"]["layout"]["bank_stage_count"]
        self.banks = [f"bank{b}" for b in range(n)]
        self._sum_banks = False
        self._snap_at: list[float] = []     # when the runner read
        super().__init__(config, gen_kw, control, genesis)
        # every bank's commit ring, not bank0's alone
        self.tap = CommitTap([self.handle.links[f"bp{b}"]
                              for b in range(n)])
        self.stages = [self.gen, self.verify, self.tap]

    def _txn_exec(self, c: dict) -> int:
        return sum(c[b]["txn_exec"] for b in self.banks)

    def _read(self) -> dict:
        c = super()._read()
        if self._sum_banks:
            # the drain's "nothing moves" test asks bank0 what the banks
            # executed: the answer is every tile's
            c = dict(c, bank0=dict(c["bank0"], txn_exec=self._txn_exec(c)))
        return c

    def counters(self) -> dict:
        self._snap_at.append(time.monotonic())
        return super().counters()

    def drain(self, limit_s: float) -> bool:
        self._sum_banks = True
        try:
            return super().drain(limit_s)
        finally:
            self._sum_banks = False

    def extra_checks(self) -> dict:
        out = super().extra_checks()
        c = self._final
        out["tap_txn_minus_bank_txn_exec"] = (
            abs(self.tap.n_txn - self._txn_exec(c)), 0)
        out["banks_that_executed_nothing"] = (
            sum(not c[b]["txn_exec"] for b in self.banks), 0)
        return out

    def notes(self) -> dict:
        out = super().notes()
        if len(self._snaps) >= 2:
            c0, c1 = self._snaps[:2]
            secs = self._snap_at[1] - self._snap_at[0]   # the window
            out["banks_per_s"] = {
                b: {k: round((c1[b].get(k, 0) - c0[b].get(k, 0)) / secs, 1)
                    for k in _BANK_RATES} for b in self.banks}
            out["pack_per_s"] = {
                k: round((v - c0["pack"].get(k, 0)) / secs, 1)
                for k, v in c1["pack"].items()
                if k.startswith("mb_scheduled_b") or k == "bank_idle_polls"}
        return out


prewarm = _tiles.prewarm
