"""Tail / median arithmetic and the landed-set comparison."""

import numpy as np
import pytest

from harness import check, stats
from harness import traffic as T
from harness.manifest import Manifest


def test_quantile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.quantile(v, 0.95) == 95
    assert stats.quantile(v, 0.5) == 50
    assert stats.quantile(v, 1.0) == 100
    assert stats.quantile([7], 0.95) == 7
    assert stats.quantile([3, 1, 2], 0.5) == 2
    assert stats.median([4, 1, 3, 2]) == 2
    with pytest.raises(ValueError):
        stats.quantile([], 0.5)
    t = stats.tail_ms(np.arange(1, 21) * 1e6)
    assert t == {"n": 20, "p50_ms": 10.0, "p95_ms": 19.0, "max_ms": 20.0}
    assert stats.tail_ms([]) == {"n": 0}


def test_offers_follow_the_order_and_wrap_over_it():
    order = np.array([2, 0, 3, 3])
    rows = check.offered_rows(order, 0, 10)
    assert rows.tolist() == [2, 0, 3, 3, 2, 0, 3, 3, 2, 0]
    assert np.bincount(rows, minlength=4).tolist() == [3, 0, 3, 4]
    assert check.offered_rows(order, 3, 6).tolist() == [3, 2, 0]
    assert check.offered_rows(order, 0, 0).tolist() == []


def _compare(landed_of, n_offered=256, **kw):
    n = 256
    man = Manifest()
    shape = man.shape({})
    pool = T.PoolJob(man.shape_path({}), 9, n, {"n_payers": 64,
                     "n_dests": 1024}, {}, workers=1).result()
    pool.bad = bad = shape.corrupt(pool, 64, 9)
    offered = check.offered_rows(shape.order(pool, 9, {}), 0, n_offered)
    offers = np.bincount(offered, minlength=n)
    # what a tile with nothing deduping behind it says is due
    due = {"landings": np.where(pool.valid, offers, 0),
           "verify_fail": int(offers[bad].sum()), "duplicates": 0}
    args = dict(pool=pool, offered=offered, due=due,
                landed=landed_of(offers.copy(), bad), unknown=0,
                verify_fail=due["verify_fail"], dedup=0, dropped=0,
                drained=True, window=(0, n), seed=9)
    args.update(kw)
    return check.compare(**args)


def _sound(offered, bad):
    offered[bad] = 0
    return offered


def _misses(res):
    return {k for k, (v, lim) in res["numbers"].items() if v > lim}


def test_sound_run_compares_clean():
    res = _compare(_sound)
    assert not _misses(res) and res["failed"] == 0
    assert res["corrupted_offered"] == 4 and res["corrupted_landed"] == 0


@pytest.mark.parametrize("case", ["corrupted_landed", "duplicate",
                                  "lost_uncounted", "lost_counted",
                                  "verify_fail_off", "dedup_off",
                                  "wrapping_pool"])
def test_each_fault_is_caught(case):
    if case == "corrupted_landed":   # the all-pass mask
        res = _compare(lambda o, bad: o, verify_fail=0)
        assert {"landed_but_not_due", "reference_sample_disagreements",
                "verify_fail_minus_corrupted_offered"} <= _misses(res)
    elif case == "duplicate":
        def dup(o, bad):
            o = _sound(o, bad)
            o[1] += 1
            return o
        assert _misses(_compare(dup)) == {"landed_but_not_due"}
    elif case == "lost_uncounted":
        def lose(o, bad):
            o = _sound(o, bad)
            o[:] = 0
            return o
        res = _compare(lose)
        assert "missing_and_uncounted" in _misses(res)
        assert res["failed"] == 252
    elif case == "lost_counted":     # the program counted its drop
        def lose1(o, bad):
            o = _sound(o, bad)
            o[1] = 0
            return o
        res = _compare(lose1, dropped=1)
        assert not _misses(res) and res["failed"] == 1
    elif case == "verify_fail_off":
        assert _misses(_compare(_sound, verify_fail=3)) == {
            "verify_fail_minus_corrupted_offered"}
    elif case == "dedup_off":        # the program dropped one as a duplicate
        assert _misses(_compare(_sound, dedup=1)) == {
            "duplicates_offered_minus_dedup_counted"}
    else:
        res = _compare(_sound, n_offered=600)
        assert not _misses(res) and 8 <= res["corrupted_offered"] <= 12
