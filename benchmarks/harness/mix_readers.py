"""Arithmetic of the readers over pack's and the banks' counters under
a mixed load (votes, priced transfers onto hot accounts, repeats): what
pack put into a microblock and passed over, what its pool gave up, how
much of the banks' work the native executor took, how many votes landed
failed, and what the two tag caches dropped.  All of them are counters
in `run["counters"]`, deltas over the measured window.  A program
without the counter (an older commit) gives None, and the metric is
left out."""

from __future__ import annotations

PACK, VERIFY = "pack", "verify0"


def _ratio(c: dict, num: str, den: str, scale: float = 1.0,
           known_by: str | None = None):
    """scale x c[num] / c[den]; None where the denominator did not move
    or the program has no such counter.  A stage's counters list only
    those that have counted, so a numerator that may stay 0 all run
    (`known_by` given) reads 0 where `known_by`, a counter that came
    with it and always counts under this traffic, is there."""
    if not c.get(den) or (known_by or num) not in c:
        return None
    return scale * c.get(num, 0) / c[den]


def _banks(run) -> dict:
    """The banks' counters, summed (stages named bank0, bank1, ...)."""
    out: dict = {}
    for name, c in run["counters"].items():
        if name.startswith("bank"):
            for k, v in c.items():
                out[k] = out.get(k, 0) + v
    return out


def vote_share_pct(run):
    """Votes among the transactions pack scheduled."""
    return _ratio(run["counters"].get(PACK, {}), "txn_scheduled_votes",
                  "txn_scheduled", 100.0)


def mb_fill_txn(run):
    """Transactions a microblock."""
    return _ratio(run["counters"].get(PACK, {}), "txn_scheduled",
                  "microblocks")


def conflict_skips_per_txn(run):
    """Pending transactions a schedule scan passed over for a locked
    account, per transaction scheduled."""
    return _ratio(run["counters"].get(PACK, {}), "conflict_skips",
                  "txn_scheduled", known_by="txn_scheduled_votes")


def dropped_pct(run):
    """What pack's pool refused or evicted, of what it took in."""
    return _ratio(run["counters"].get(PACK, {}), "txn_dropped", "txn_in",
                  100.0, known_by="txn_scheduled_votes")


def native_txn_pct(run):
    """Landed transactions the banks' C sweep committed itself.  (C
    counts at the commit, Python when it drains the sweep's log a sweep
    later, so a window's edges can put the share a microblock's worth
    over 100.)"""
    return _ratio(_banks(run), "bank_txn_native", "txn_exec", 100.0)


def punt_per_100_txn(run):
    """Punts of the native executor to the Python lane."""
    return _ratio(_banks(run), "native_punt", "txn_exec", 100.0,
                  known_by="bank_txn_native")


def vote_failed_pct(run):
    """Landed votes whose program failed (a validator's earlier vote
    scheduled after a later one: VoteTooOld)."""
    return _ratio(_banks(run), "txn_exec_failed_votes", "txn_exec_votes",
                  100.0, known_by="txn_exec_votes")


def dup_pct(run):
    """Offers the two tag caches dropped (verify's 16 deep, pack's
    65,536), of all offers of the window."""
    c = run["counters"]
    if not run.get("offered") or "dedup_dup" not in c.get(PACK, {}) \
            or "dedup_dup" not in c.get(VERIFY, {}):
        return None
    return 100.0 * (c[VERIFY]["dedup_dup"] + c[PACK]["dedup_dup"]) \
        / run["offered"]
