"""The timeline tool's join of annotations and module events, on a
hand-made trace: FIFO from the anchor, the two drop conditions, a backlog
of reaps at the start (as start_trace leaves one), gaps."""

import importlib.util
import os

from harness.manifest import BENCH_DIR

_spec = importlib.util.spec_from_file_location(
    "batch_timeline", os.path.join(BENCH_DIR, "tools", "batch_timeline.py"))
bt = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bt)

MS = 1_000_000


def _trace():
    """Batches 7..12, 8 ms programs back to back from t = 10 ms; batch k
    is dispatched 6 ms before it runs and reaped 1 ms after it ends.
    The trace begins at 0: batch 7 was dispatched before it."""
    modules, disp, reap = [], {}, {}
    for i, seq in enumerate(range(7, 13)):
        m0 = (10 + 8 * i) * MS
        modules.append([m0, m0 + 8 * MS - 10_000])
        if seq > 7:
            disp[seq] = [m0 - 6 * MS, m0 - 4 * MS]
        reap[seq] = [m0 + 9 * MS, m0 + 9 * MS + MS // 2]
    return {"verify.dispatch": disp, "verify.reap": reap,
            "verify.publish": {}}, modules


def test_join_is_fifo_from_the_lowest_anchor_that_breaks_no_order():
    spans, modules = _trace()
    got = bt.join(spans, modules)
    assert [r["seq"] for r in got["rows"]] == [8, 9, 10, 11, 12]
    assert got["dropped"] == 0 and got["unmatched"] == 1
    r = got["rows"][0]
    assert r["dispatch_ms"] == 2.0 and r["exec_ms"] == 7.99
    assert r["queue_ms"] == 4.0 and r["reap_lag_ms"] == 1.01


def test_a_batch_whose_order_fails_is_dropped_and_counted():
    spans, modules = _trace()
    spans["verify.reap"][10][0] = modules[3][1] - 1     # before its module ends
    d = spans["verify.dispatch"][11]
    spans["verify.dispatch"][11] = [modules[4][0] + 1, d[1] + 8 * MS]
    got = bt.join(spans, modules)
    assert [r["seq"] for r in got["rows"]] == [8, 9, 12]
    assert got["dropped"] == 2


def test_no_batch_with_both_spans_means_no_join():
    spans, modules = _trace()
    spans["verify.reap"] = {7: [0, 1]}
    assert bt.join(spans, modules) == {"rows": [], "dropped": 0,
                                       "unmatched": 6}


def test_a_backlog_of_reaps_at_the_start_does_not_move_the_anchor():
    """start_trace held the thread: batches 1..6 finished before the
    capture and are reaped late, in a burst, after module 0 ended."""
    spans, modules = _trace()
    for i, seq in enumerate(range(1, 7)):
        spans["verify.reap"][seq] = [modules[0][1] + i * MS // 2,
                                     modules[0][1] + i * MS // 2 + 1000]
    got = bt.join(spans, modules)
    assert [r["seq"] for r in got["rows"]] == [8, 9, 10, 11, 12]
    assert got["dropped"] == 0


def test_gaps_are_labelled_by_the_covering_annotation():
    spans, modules = _trace()
    modules[2][1] -= 3 * MS            # a 3.01 ms hole before module 3
    spans["verify.publish"][8] = [modules[2][1], modules[3][0]]
    top = bt.gaps(spans, modules, top=2)
    assert top[0] == ["verify.publish#8", 3010.0]
    assert top[1][1] == 10.0


def test_a_lost_module_event_does_not_shift_the_batches_after_it():
    spans, modules = _trace()
    del modules[2]                     # batch 9's module was not recorded
    got = bt.join(spans, modules)
    assert [r["seq"] for r in got["rows"]] == [8, 10, 11, 12]
    assert got["dropped"] == 0 and got["unmatched"] == 1
