"""The comparison that decides `correct`: what came out of the timed
path against what was offered, under the configuration's guarantees.

Every number compared is a count with the limit 0 (the comparisons are
exact), printed beside its limit in every run."""

from __future__ import annotations

import numpy as np

from . import reference

N_SAMPLE = 1024  # rows held to the plain reference, corrupted ones first


def offered_counts(n_offered: int, n_pool: int) -> np.ndarray:
    """How often each pool row was offered when rows [0, n_offered) were
    published in order, wrapping over the pool."""
    full, rest = divmod(n_offered, n_pool)
    out = np.full((n_pool,), full, dtype=np.int64)
    out[:rest] += 1
    return out


def compare(*, pool: np.ndarray, n_pool: int, bad: np.ndarray,
            n_offered: int, landed: np.ndarray, unknown: int,
            verify_fail: int, dropped: int, drained: bool,
            window: tuple[int, int], seed: int) -> dict:
    """-> {"numbers": {name: (value, limit)}, "failed": ..., ...}.
    `landed[i]` is how often pool row i came out; `window` the offered
    index range of the measured window."""
    offered = offered_counts(n_offered, n_pool)
    valid = np.ones((n_pool,), dtype=bool)
    valid[bad] = False
    expect = np.where(valid, offered, 0)
    extra = np.maximum(landed - expect, 0)
    missing = np.maximum(expect - landed, 0)
    n_missing = int(missing.sum())
    bad_offered = int(offered[bad].sum())
    # the sample held to the plain reference: every corrupted row that
    # was offered (up to half the sample), the rest seeded valid rows
    rng = np.random.default_rng([seed, 0x5A])
    off_rows = np.flatnonzero(offered > 0)
    bad_rows = bad[offered[bad] > 0][: N_SAMPLE // 2]
    good_rows = off_rows[valid[off_rows]]
    take = min(N_SAMPLE - len(bad_rows), len(good_rows))
    sample = np.concatenate([
        bad_rows, rng.choice(good_rows, size=take, replace=False)
    ]) if take else bad_rows
    ref = reference.verdicts(pool, sample)
    explained = n_missing == dropped or (not drained and n_missing >= dropped)
    disagree = 0
    for i, ok in ref.items():
        if ok != bool(valid[i]):
            disagree += 1          # the reference against the construction
        elif not ok and landed[i] > 0:
            disagree += 1          # an invalid signature landed
        elif ok and landed[i] == 0 and not explained:
            disagree += 1          # a valid one vanished uncounted
    lo, hi = window
    # failed: valid transactions offered in the window that never landed
    # (for a wrapping pool the shortfall is not attributable to a lap,
    # so it is charged to the window whole)
    if n_offered <= n_pool:
        failed = int(missing[lo:hi].sum())
    else:
        failed = n_missing
    return {
        "numbers": {
            "landed_but_not_due": (int(extra.sum()), 0),
            "landed_bytes_matching_nothing_offered": (int(unknown), 0),
            "missing_and_uncounted": (0 if explained
                                      else abs(n_missing - dropped), 0),
            "verify_fail_minus_corrupted_offered":
                (abs(verify_fail - bad_offered), 0),
            "reference_sample_disagreements": (disagree, 0),
        },
        "failed": failed,
        "missing": n_missing,
        "dropped_counted": dropped,
        "corrupted_offered": bad_offered,
        "corrupted_landed": int(landed[bad].sum()),
        "reference_sample": len(ref),
    }
