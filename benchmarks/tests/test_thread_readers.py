"""The readers of the thread's ledger on hand-made `run` records (known
deltas -> known numbers; a program without the counter -> None), and
every per-layer entry that uses them resolving to its reader file by
name, beside its harness-timer twin."""

import pytest

from harness import thread_readers as tr
from harness.manifest import Manifest

HOSTS = ["pack", "bank0", "bank1", "poh", "shred", "store"]
MS = 1_000_000


def _loop(work_ms, poll_ms, hk_ms, **more):
    return dict(loop_work_ns=work_ms * MS, loop_work_n=1000,
                loop_poll_ns=poll_ms * MS, loop_poll_n=9000,
                loop_hk_ns=hk_ms * MS, **more)


def _run(**over):
    verify = _loop(
        2400, 500, 100, batches=400, frags_in=400_000,
        batch_h2d_ns=120 * MS, batch_launch_ns=80 * MS,
        batch_reap_ns=240 * MS, batch_publish_ns=560 * MS,
        batch_open_ns=9_000 * MS, batch_inflight_ns=3_600 * MS,
        chip_empty_ns=15_000 * MS, chip_empty_n=400,
        chip_empty_call_ns=1_500 * MS, chip_empty_away_ns=12_000 * MS)
    run = {
        "window_s": 20.0, "served": 400_000, "sweeps": 50_000,
        "host_stages": HOSTS,
        "counters": {
            "benchg": _loop(900, 50, 50),
            "verify0": verify,
            "pack": _loop(2400, 400, 100),
            "bank0": _loop(2400, 300, 100),
            "bank1": _loop(2000, 400, 100),
            "poh": _loop(1500, 400, 100),
            "shred": _loop(1100, 300, 100),
            "store": _loop(2400, 300, 100),
            "dedup": _loop(800, 300, 100, frags_in=160_000),
        },
    }
    run.update(over)
    return run


def _without(run, stage, *keys):
    for k in keys:
        del run["counters"][stage][k]
    return run


def test_verify_work_and_what_no_phase_stamps():
    run = _run()
    assert tr.verify_work_ms_per_batch(run) == pytest.approx(6.0)
    # 2,400 ms of working calls less the 1,000 ms of the four stamped
    # blocking calls, over 400 batches
    assert tr.verify_offcall_ms_per_batch(run) == pytest.approx(3.5)
    assert tr.verify_work_ms_per_batch(
        _without(_run(), "verify0", "loop_work_ns")) is None
    assert tr.verify_offcall_ms_per_batch(
        _without(_run(), "verify0", "loop_work_ns")) is None
    assert tr.verify_offcall_ms_per_batch(
        _without(_run(), "verify0", "batch_reap_ns")) is None
    run = _run()
    run["counters"]["verify0"]["batches"] = 0
    assert tr.verify_work_ms_per_batch(run) is None     # nothing dispatched
    assert tr.verify_offcall_ms_per_batch(run) is None
    assert tr.verify_work_ms_per_batch(_run(counters={})) is None


def test_host_and_dedup_work_per_transaction():
    run = _run()
    # 11,800 ms of working calls in the six host stages, 400,000 landed
    assert tr.host_work_us_per_txn(run) == pytest.approx(29.5)
    assert tr.dedup_work_us_per_txn(run) == pytest.approx(5.0)
    assert tr.host_work_us_per_txn(
        _without(_run(), "poh", "loop_work_ns")) is None    # an older stage
    assert tr.host_work_us_per_txn(_run(host_stages=[])) is None
    assert tr.host_work_us_per_txn(_run(served=0)) is None
    assert tr.dedup_work_us_per_txn(
        _without(_run(), "dedup", "loop_work_ns")) is None
    run = _run()
    del run["counters"]["dedup"]
    assert tr.dedup_work_us_per_txn(run) is None


def test_what_of_the_thread_the_ledger_covers():
    run = _run()
    per = tr.stage_loop_ns(run)
    assert per["verify0"] == 3000 * MS and per["dedup"] == 1200 * MS
    total = sum(per.values())
    assert tr.thread_accounted_pct(run) == pytest.approx(
        100.0 * total / 20e9) and total == 19_700 * MS
    # every stage's own ledger fits in the window
    assert all(v <= run["window_s"] * 1e9 for v in per.values())
    # a stage without the ledger is left out; none with it -> None
    run = _without(_run(), "store", "loop_poll_ns")
    assert tr.thread_accounted_pct(run) == pytest.approx(
        100.0 * (total - 2800 * MS) / 20e9)
    bare = {"window_s": 20.0,
            "counters": {"verify0": {"batches": 3}, "sink": {}}}
    assert tr.stage_loop_ns(bare) is None
    assert tr.thread_accounted_pct(bare) is None


def test_when_the_chip_was_empty_and_whose_time_it_was():
    run = _run()
    assert tr.chip_empty_pct(run) == pytest.approx(75.0)
    assert tr.chip_empty_away_pct(run) == pytest.approx(80.0)
    assert tr.chip_empty_call_pct(run) == pytest.approx(10.0)
    assert tr.chip_empty_away_pct(run) + tr.chip_empty_call_pct(run) <= 100
    old = _without(_run(), "verify0", "chip_empty_ns")
    assert tr.chip_empty_pct(old) is None
    assert tr.chip_empty_away_pct(old) is None
    assert tr.chip_empty_call_pct(old) is None
    assert tr.chip_empty_away_pct(
        _without(_run(), "verify0", "chip_empty_away_ns")) is None
    # a window in which the chip never ran dry: 0 of it, and none of none
    run = _run()
    run["counters"]["verify0"].update(
        chip_empty_ns=0, chip_empty_n=0, chip_empty_call_ns=0,
        chip_empty_away_ns=0)
    assert tr.chip_empty_pct(run) == 0.0
    assert tr.chip_empty_away_pct(run) == tr.chip_empty_call_pct(run) == 0.0
    assert tr.chip_empty_pct(_run(counters={})) is None


FAMILIES = {
    "verify.work_ms_per_batch": (tr.verify_work_ms_per_batch,
                                 "verify.stage_ms_per_batch"),
    "verify.offcall_ms_per_batch": (tr.verify_offcall_ms_per_batch, None),
    "host.work_us_per_txn": (tr.host_work_us_per_txn, "host.us_per_txn"),
    "dedup.work_us_per_txn": (tr.dedup_work_us_per_txn, "dedup.us_per_txn"),
    "thread.accounted_pct": (tr.thread_accounted_pct, None),
    "chip.empty_pct": (tr.chip_empty_pct, "device.idle_pct"),
    "chip.empty_away_pct": (tr.chip_empty_away_pct, None),
    "chip.empty_call_pct": (tr.chip_empty_call_pct, None),
}


def test_every_entry_resolves_to_its_reader_beside_its_twin():
    man = Manifest()
    by_name = {m["name"]: m for m in man.data["per_layer"]}
    seen = 0
    for name, m in by_name.items():
        family, _, variant = name.rpartition(".")
        if family not in FAMILIES:
            continue
        seen += 1
        fn, twin = FAMILIES[family]
        assert man.reader("per_layer", name) is fn
        assert m["source"] == "program_span"
        if twin is not None:
            # the harness-timer (or trace) twin stays: same cells, same
            # layer, moving the same end-to-end metric
            t = by_name[f"{twin}.{variant}"]
            assert (t["workloads"], t["layer"], t["moves"]) \
                == (m["workloads"], m["layer"], m["moves"])
            assert t["source"] != "program_span"
    assert seen == 33
    # every cell reports the ledger's coverage and the chip's three
    for w in man.data["workloads"]:
        names = {m["name"].rpartition(".")[0]
                 for m in man.metrics("per_layer", w["name"])}
        assert {"thread.accounted_pct", "chip.empty_pct",
                "chip.empty_away_pct", "chip.empty_call_pct",
                "verify.work_ms_per_batch",
                "verify.offcall_ms_per_batch"} <= names
