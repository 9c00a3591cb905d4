"""The comparison that decides `correct`: what came out of the timed
path against what was offered, under the configuration's guarantees.

What was offered is the shape's: offer k is row `order[k % len(order)]`.
What is due of it is the topology's, which knows what dedups behind the
generator (`System.due`).  Every number compared is a count with the
limit 0 (the comparisons are exact), printed beside its limit in every
run."""

from __future__ import annotations

import numpy as np

from . import reference

N_SAMPLE = 1024  # rows held to the plain reference, corrupted ones first


def offered_rows(order: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """The pool rows of offers [lo, hi)."""
    return order[np.arange(lo, hi, dtype=np.int64) % len(order)]


def tcache_keeps(tags: np.ndarray, depth: int) -> np.ndarray:
    """Which of a stream of tags a tag cache of `depth` lets through
    (the program's TCache, fd_tcache.h: a tag among the last `depth`
    let through is dropped, and a dropped one is not inserted again)."""
    n = len(tags)
    keep = np.ones((n,), dtype=bool)
    by = np.argsort(tags, kind="stable")
    run = tags[by]
    if not ((run[1:] == run[:-1]) & (np.diff(by) <= depth)).any():
        # the first drop needs a repeat within `depth` offers of it
        return keep
    ring: list = [None] * depth
    live: set = set()
    at = 0
    for k, t in enumerate(tags.tolist()):
        if t in live:
            keep[k] = False
            continue
        live.discard(ring[at])
        ring[at] = t
        live.add(t)
        at = (at + 1) % depth
    return keep


def through_verify(offered: np.ndarray, valid: np.ndarray, depth: int):
    """The verify stage's rule over the offered rows, in order: its tag
    cache (`depth` deep) drops a row offered again within its memory,
    a corrupted row then fails whole, the rest pass.
    -> (the rows that pass, in order; transactions failed; offers
    dropped as duplicates)."""
    kept = offered[tcache_keeps(offered, depth)]
    ok = valid[kept]
    return kept[ok], int((~ok).sum()), len(offered) - len(kept)


def compare(*, pool, offered: np.ndarray, due: dict,
            landed: np.ndarray, unknown: int, verify_fail: int, dedup: int,
            dropped: int, drained: bool, window: tuple[int, int],
            seed: int) -> dict:
    """-> {"numbers": {name: (value, limit)}, "failed": ..., ...}.
    `offered` is the pool row of every offer of the run, `window` the
    range of offers of the measured window; `due` what the topology
    says of them (`landings` per row, `verify_fail` in transactions,
    `duplicates` dropped by the deployment's dedup); `landed[i]` how
    often pool row i came out, `dedup` the program's own dedup counts."""
    n_pool = pool.n
    offers = np.bincount(offered, minlength=n_pool)
    bad, valid = pool.bad, pool.valid
    expect = due["landings"]
    extra = np.maximum(landed - expect, 0)
    missing = np.maximum(expect - landed, 0)
    n_missing = int(missing.sum())
    # the sample held to the plain reference: every corrupted row that
    # was offered (up to half the sample), the rest seeded valid rows
    rng = np.random.default_rng([seed, 0x5A])
    off_rows = np.flatnonzero(offers > 0)
    bad_rows = bad[offers[bad] > 0][: N_SAMPLE // 2]
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
    # failed: landings due of rows offered in the window that did not
    # come, at most as many a row as the window offered it
    in_window = np.bincount(offered[window[0]:window[1]], minlength=n_pool)
    failed = int(np.minimum(missing, in_window).sum())
    return {
        "numbers": {
            "landed_but_not_due": (int(extra.sum()), 0),
            "landed_bytes_matching_nothing_offered": (int(unknown), 0),
            "missing_and_uncounted": (0 if explained
                                      else abs(n_missing - dropped), 0),
            # in the counter's unit, transactions: a row fails whole
            "verify_fail_minus_corrupted_offered":
                (abs(verify_fail - due["verify_fail"]), 0),
            "duplicates_offered_minus_dedup_counted":
                (abs(due["duplicates"] - dedup), 0),
            "reference_sample_disagreements": (disagree, 0),
        },
        "failed": failed,
        "missing": n_missing,
        "dropped_counted": dropped,
        "duplicates_offered": due["duplicates"],
        "dedup_counted": dedup,
        "corrupted_offered": int(offers[bad].sum()),
        "corrupted_landed": int(landed[bad].sum()),
        "corrupted_rows_offered": int((offers[bad] > 0).sum()),
        "corrupted_rows_landed": int((landed[bad] > 0).sum()),
        "reference_sample": len(ref),
    }
