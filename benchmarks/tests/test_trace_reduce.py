"""The reduction from a trace to numbers: on a hand-made trace whose
answers can be worked out on paper, and on a small trace recorded on the
chip (tests/data/trace_small.json.gz: 40 ms of a traced window of
verify-spam-flood with 1 ms of its operations, written by
`run.py --keep-trace`)."""

import json
import os

import pytest

from harness import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_hand_made_trace():
    # window [100, 1100) cut to 1050 where the capture ended; one device;
    # programs at 200..600 and 1000..1300 (cut), another at 700..750;
    # operations kept for [100, 700): a while loop around two body ops,
    # and a stray op cut by the window's start
    trace = {"window": [100, 1100], "devices": {"/device:TPU:0": {
        "modules": [["jit_prog(1)", 200, 400], ["jit_prog(1)", 1000, 300],
                    ["jit_other(2)", 700, 50], ["jit_other(2)", 50, 100]],
        "ops": [["while", 200, 400], ["body_a", 200, 100],
                ["body_b", 350, 150], ["copy", 50, 100]],
        "ops_window": [100, 700], "last_ns": 1050,
    }}}
    spans = [("verify0", 0, 140), ("bank0", 600, 900), ("poh", 900, 1000)]
    r = tr.reduce(trace, "jit_prog", spans)
    assert r["window_s"] == pytest.approx(950e-9)
    # busy: [100,150) + [200,600) + [700,750) + [1000,1050) = 550
    assert r["busy_s"] == pytest.approx(550e-9)
    # only the program run that lies wholly inside the window counts
    assert r["program_runs"] == 1
    assert r["program_s"] == pytest.approx(400e-9)
    ops = dict(r["device_ops"])
    assert ops["while"] == pytest.approx(150e-9)   # self time only
    assert ops["body_a"] == pytest.approx(100e-9)
    assert ops["body_b"] == pytest.approx(150e-9)
    assert ops["copy"] == pytest.approx(50e-9)     # clipped
    # operations cover all of the programs' time where they were kept
    assert r["ops_cover"] == pytest.approx(1.0)
    # gaps: [750,1000) mostly under bank0, [600,700) under bank0,
    # [150,200) under nothing
    assert r["idle_gaps"][0] == ["bank0", pytest.approx(250e-9)]
    assert r["idle_gaps"][1] == ["bank0", pytest.approx(100e-9)]
    assert r["idle_gaps"][2] == ["between_sweeps", pytest.approx(50e-9)]
    assert r["n_gaps"] == 3
    assert r["idle_gap_s"] + r["busy_s"] == pytest.approx(r["window_s"])


def test_short_names_add_up_by_kind():
    assert tr._short("%multiply_fusion.951 = s32[20,1024]{1,0} fusion(s32"
                     ) == "multiply_fusion"
    assert tr._short("%while.262 = (s32[]") == "while"
    assert tr._short("copy") == "copy"


def test_trace_without_window_or_device_is_refused():
    with pytest.raises(ValueError):
        tr.reduce({"window": None, "devices": {"d": {}}}, "p")
    with pytest.raises(ValueError):
        tr.reduce({"window": [0, 1], "devices": {}}, "p")


def test_recorded_chip_trace():
    import gzip

    with gzip.open(os.path.join(DATA, "trace_small.json.gz"), "rt") as f:
        trace = json.load(f)
    with open(os.path.join(DATA, "trace_small.expect.json")) as f:
        want = json.load(f)
    r = tr.reduce(trace, want["program"])
    assert r["program_runs"] == want["program_runs"]
    for k in ("window_s", "busy_s", "program_s"):
        assert r[k] == pytest.approx(want[k], rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert [n for n, _ in r["device_ops"][:3]] == want["top_ops"]
    assert r["idle_gap_s"] + r["busy_s"] == pytest.approx(r["window_s"])
    assert 0.9 < r["ops_cover"] <= 1.0
