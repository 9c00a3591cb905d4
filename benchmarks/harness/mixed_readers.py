"""Arithmetic of the readers over a verify stage fed transactions of
one to eight signatures with a dedup stage behind it: the lanes no
verdict was used of (left empty because the next transaction did not
fit, spent on transactions that failed whole, spent on transactions
that dedup then dropped), the signatures a transaction carries, what
the two tag caches dropped, what a transaction costs in the dedup
stage, and the waits on either side of it.  All but the stage timer are
the program's counters in `run["counters"]`, deltas over the measured
window.  A program without the counter (an older commit) gives None,
and the metric is left out."""

from __future__ import annotations

from . import span_readers as sr

VERIFY, DEDUP = sr.VERIFY, "dedup"


def _pct_of_lanes(run, stage: str, counter: str):
    """100 x a stage's counter / the signature lanes verify dispatched."""
    lanes = run["counters"].get(VERIFY, {}).get("batch_elems")
    c = run["counters"].get(stage, {})
    if not lanes or counter not in c:
        return None
    return 100.0 * c[counter] / lanes


def fit_pad_pct(run):
    """Lanes that batches sealed for want of room left empty (the next
    transaction's signatures did not fit), of the lanes that carried a
    signature."""
    return _pct_of_lanes(run, VERIFY, "batch_fit_pad_lanes")


def fail_lanes_pct(run):
    """Lanes spent on transactions that failed whole."""
    return _pct_of_lanes(run, VERIFY, "verify_fail_elems")


def late_dup_lanes_pct(run):
    """Lanes spent on transactions that the dedup stage then dropped:
    repeats too far behind for verify's own tag cache."""
    return _pct_of_lanes(run, DEDUP, "dedup_dup_sigs")


def sigs_per_txn(run):
    """Signatures a transaction that reached verification carried."""
    v = run["counters"].get(VERIFY, {})
    if not v.get("txn_in") or "elems_in" not in v:
        return None
    return v["elems_in"] / v["txn_in"]


def dup_pct(run):
    """Offers the two tag caches dropped (verify's 16 deep, dedup's
    65,536), of all offers of the window."""
    c = run["counters"]
    if not run.get("offered") or "dedup_dup" not in c.get(DEDUP, {}) \
            or "dedup_dup" not in c.get(VERIFY, {}):
        return None
    return 100.0 * (c[VERIFY]["dedup_dup"] + c[DEDUP]["dedup_dup"]) \
        / run["offered"]


def dedup_us_per_txn(run):
    """Host time inside the dedup stage's run_once (harness timer,
    measured window) per frag it consumed."""
    n = run["counters"].get(DEDUP, {}).get("frags_in")
    if not run.get("timers_s") or DEDUP not in run["timers_s"] or not n:
        return None
    return 1e6 * run["timers_s"][DEDUP] / n


def in_verify_ms(run):
    """Mean wait at dedup's intake less the one at verify's: what a
    frag spent in the verify stage and on the ring behind it."""
    return sr._in_verify_ms(run, sr.wait_ms(run, DEDUP))


def in_dedup_ms(run):
    """Mean wait at the sink's intake less the one at dedup's: what a
    frag that left the pair spent in the dedup stage and on the ring
    behind it."""
    before, after = sr.wait_ms(run, DEDUP), sr.wait_ms(run, "sink")
    if before is None or after is None:
        return None
    return after - before
