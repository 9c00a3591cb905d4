"""The readers of the program's own stamps on hand-made `run` records
(known deltas -> known numbers; nothing to read -> None), and every
per-layer entry that uses them resolving to its reader file by name."""

import pytest

from harness import span_readers as sr
from harness.manifest import Manifest

PHASES = ("open", "sealed_wait", "h2d", "launch", "inflight", "reap",
          "publish")
NS = {"open": 6_000_000_000, "sealed_wait": 8_000_000_000,
      "h2d": 1_000_000_000, "launch": 500_000_000,
      "inflight": 120_000_000_000, "reap": 1_500_000_000,
      "publish": 250_000_000}


def _run(**over):
    verify = {"batches": 2000, "batch_elems": 64000,
              "frag_wait_ns": 64000 * 3_000_000, "frag_wait_n": 64000}
    verify.update({f"batch_{p}_ns": v for p, v in NS.items()})
    run = {
        "window_s": 20.0, "served": 60000, "sweeps": 50000,
        "host_stages": ["pack", "bank0", "bank1", "poh", "shred", "store"],
        "counters": {
            "verify0": verify,
            "sink": {"frag_wait_ns": 63500 * 93_000_000,
                     "frag_wait_n": 63500},
            "pack": {"frag_wait_ns": 63500 * 93_000_000,
                     "frag_wait_n": 63500 + 4000, "microblock_done": 4000},
            "bank0": {"sweep_busy_ns": 300_000_000, "sweep_crossings": 3000},
            "bank1": {"sweep_busy_ns": 200_000_000, "sweep_crossings": 2000},
            "shred": {"sweep_busy_ns": 100_000_000, "sweep_crossings": 2500},
            "poh": {"frags_in": 5000}, "store": {"frags_in": 9000},
        },
    }
    run.update(over)
    return run


@pytest.mark.parametrize("phase", PHASES)
def test_phase_ms_per_batch(phase):
    read = getattr(sr, f"{phase}_ms_per_batch")
    assert read(_run()) == pytest.approx(NS[phase] / 2000 / 1e6)
    run = _run()
    del run["counters"]["verify0"][f"batch_{phase}_ns"]
    assert read(run) is None                    # an older program
    run = _run()
    run["counters"]["verify0"]["batches"] = 0
    assert read(run) is None                    # nothing dispatched
    assert read(_run(counters={})) is None


def test_the_phases_are_the_known_waterfall():
    run = _run()
    got = {p: getattr(sr, f"{p}_ms_per_batch")(run) for p in PHASES}
    assert got == pytest.approx({"open": 3.0, "sealed_wait": 4.0, "h2d": 0.5,
                                 "launch": 0.25, "inflight": 60.0,
                                 "reap": 0.75, "publish": 0.125})


def test_path_waits():
    run = _run()
    assert sr.to_verify_ms(run) == pytest.approx(3.0)
    assert sr.in_verify_ms_tile(run) == pytest.approx(90.0)
    # pack's done frames are counted out by number
    assert sr.in_verify_ms_leader(run) == pytest.approx(90.0)
    assert sr.wait_ms(run, "pack") == pytest.approx(
        63500 * 93.0 / 67500)


@pytest.mark.parametrize("read", [sr.to_verify_ms, sr.in_verify_ms_tile,
                                  sr.in_verify_ms_leader])
def test_path_waits_with_nothing_to_read(read):
    run = _run()
    for stage in ("verify0", "sink", "pack"):
        run["counters"][stage].pop("frag_wait_ns")
    assert read(run) is None
    run = _run()
    for stage in ("verify0", "sink", "pack"):
        run["counters"][stage]["frag_wait_n"] = 0
    run["counters"]["pack"]["microblock_done"] = 0
    assert read(run) is None
    assert read(_run(counters={})) is None


def test_in_verify_needs_both_ends():
    run = _run()
    del run["counters"]["sink"]
    assert sr.in_verify_ms_tile(run) is None
    assert sr.in_verify_ms_leader(run) is not None
    del run["counters"]["pack"]
    assert sr.in_verify_ms_leader(run) is None


def test_in_crossing_and_empty_sweeps():
    run = _run()
    assert sr.in_crossing_us_per_txn(run) == pytest.approx(
        600_000_000 / 60000 / 1e3)
    assert sr.empty_sweep_pct(run) == pytest.approx(
        100.0 * (1 - 7500 / (50000 * 3)))


@pytest.mark.parametrize("read", [sr.in_crossing_us_per_txn,
                                  sr.empty_sweep_pct])
def test_host_readers_with_nothing_to_read(read):
    assert read(_run(host_stages=[])) is None       # the tile
    run = _run()
    del run["host_stages"]
    assert read(run) is None
    run = _run()
    for n in ("bank0", "bank1", "shred"):
        del run["counters"][n]["sweep_busy_ns"]     # an older program
    assert read(run) is None
    assert read(_run(served=0, sweeps=0)) is None


NEW = ([f"verify.{p}_ms_per_batch.{v}" for p in PHASES
        for v in ("tile", "leader")]
       + [f"path.{q}_verify_ms.{v}" for q in ("to", "in")
          for v in ("tile", "leader")]
       + ["host.in_crossing_us_per_txn.leader", "host.empty_sweep_pct.leader"])


@pytest.mark.parametrize("name", NEW)
def test_every_new_entry_finds_its_reader(name):
    man = Manifest()
    entry = next(m for m in man.data["per_layer"] if m["name"] == name)
    read = man.reader("per_layer", name)
    assert read.__module__ == "harness.span_readers"
    v = read(_run())
    assert v is not None and v >= 0
    tile = name.endswith(".tile")
    assert entry["workloads"] == (
        ["verify-spam-flood", "verify-spam-paced"] if tile
        else ["leader-transfer-flood", "leader-transfer-paced"])
    assert entry["moves"] == ("verify_per_s" if tile else "landed_per_s")
    assert entry["better"] == "lower"
    # a program without the stamps: the metric is left out, nothing raises
    assert read({"counters": {"verify0": {"batches": 5}}, "served": 1,
                 "sweeps": 1, "host_stages": ["pack"]}) is None
