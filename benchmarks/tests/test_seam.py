"""The seam a later cell is added through: a traffic file names its
shape and its arrivals, and both are files.  The proof is a cell laid
as files alone — the toy shape and the on/off arrivals of
tests/data/seam, a traffic file and two `workloads` entries — into a
temporary copy of BENCHMARK.json + benchmarks/, run on both topologies
with no copied file altered.  Around it: the helpers the check and the
topologies share, each held to what it stands for."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from harness import check, reference
from harness.manifest import load_module
from harness.rowmap import RowMap

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SEAM = os.path.join(BENCH, "tests", "data", "seam")
ACCOUNTS = {"n_payers": 64, "n_dests": 1024}
CELLS = {"toy-tile": ("verify-tile-v5e", "verify.batch=16"),
         "toy-leader": ("leader-v5e", "program_config.verify.batch=16")}


@pytest.fixture(scope="module")
def toy():
    return load_module(os.path.join(SEAM, "shapes", "toy.py"), "shape_toy")


@pytest.fixture(scope="module")
def pool(toy):
    return toy.build(4, 600, ACCOUNTS, {})


def test_toy_rows_are_transactions_the_reference_and_the_program_parse(
        toy, pool):
    from firedancer_tpu.protocol import txn as ft

    assert sorted(set(pool.sigs.tolist())) == [1, 2, 3]
    assert sorted(set(pool.len.tolist())) == [215, 311, 407]
    assert pool.classes == ("transfer", "cosigned")
    assert (pool.cls == (pool.sigs > 1)).all()
    for i in (0, 1, 2, 599):
        sigs, pks, msg = reference.split(pool.row(i))
        desc = ft.txn_parse(pool.row(i))
        assert desc is not None and desc.signature_cnt == len(sigs) \
            == pool.sigs[i] == len(pks)
    assert all(reference.verdicts(pool, range(0, 600, 7)).values())
    # a range of rows is the same rows (the spawned workers' split)
    part = toy.build(4, 600, ACCOUNTS, {}, 100, 130)
    assert [part.row(k) for k in range(30)] \
        == [pool.row(100 + k) for k in range(30)]


def test_toy_corruption_fails_a_row_whole_from_any_signature(toy):
    pool = toy.build(4, 600, ACCOUNTS, {})
    clean = pool.buf.copy()
    bad = toy.corrupt(pool, 10, 4)
    assert len(bad) == 60
    at = np.flatnonzero(pool.buf != clean)
    assert len(at) == 60                      # one bit a row
    sig = (at - pool.off[bad] - 1) // 64
    assert ((0 <= sig) & (sig < pool.sigs[bad])).all() and (sig > 0).any()
    got = reference.verdicts(pool, range(600))
    assert {i for i, ok in got.items() if not ok} == set(bad.tolist())


def test_toy_order_repeats_skips_and_is_no_prefix(toy, pool):
    order = toy.order(pool, 4, {})
    n = np.bincount(order, minlength=pool.n)
    assert (n[np.arange(pool.n) % 10 == 3] == 2).all()
    assert (n[np.arange(pool.n) % 10 != 3] == 1).all()
    assert (order[:pool.n] != np.arange(pool.n)).any()
    assert (toy.order(pool, 4, {}) == order).all()
    gap = np.array([np.diff(np.flatnonzero(order == r))[0]
                    for r in np.flatnonzero(n == 2)])
    assert (gap <= 16).any() and (gap > 16).any()


def test_on_off_arrivals_keep_the_mean_rate_and_the_silences():
    onoff = load_module(os.path.join(SEAM, "arrivals", "onoff.py"), "onoff")
    tr = {"rate_per_s": 4000, "on_ms": 30, "off_ms": 20}
    due = onoff.due_ns(tr, 20_000, 2**31 + 5)
    assert (np.diff(due) >= 0).all() and abs(due[-1] / 1e9 - 5.0) < 0.2
    assert ((due % 50_000_000) < 30_000_000).all()   # none while off
    assert (onoff.due_ns(tr, 20_000, 2**31 + 5) == due).all()


@pytest.mark.parametrize("depth", [1, 4, 16])
def test_tcache_keeps_is_the_programs_tcache(depth):
    from firedancer_tpu.tango.rings import TCache

    rng = np.random.default_rng(depth)
    for tags in (rng.integers(1, 40, size=3000),       # repeats all over
                 np.arange(1, 3001) % 1000 + 1,        # a pool, replayed
                 np.repeat(np.arange(1, 200), 3)):     # offered thrice
        tc = TCache(depth)
        want = [not tc.insert(int(t)) for t in tags]
        assert check.tcache_keeps(np.asarray(tags), depth).tolist() == want


def test_rowmap_finds_a_landed_payload_by_what_it_is(pool):
    rm = RowMap(pool)
    rows = [5, 0, 599, 301, 5]
    payloads = [pool.row(i) for i in rows]
    assert rm.of_payloads(payloads).tolist() == rows
    assert rm.of_tags(pool.first_sig_tags()[rows]).tolist() == rows
    broken = bytearray(pool.row(7))
    broken[-1] ^= 1                                   # one byte off
    cut = pool.row(8)[:-1]                            # one byte short
    assert rm.of_payloads([bytes(broken), cut, b"", b"\x01" * 215]).tolist() \
        == [-1] * 4
    assert rm.of_tags(np.array([12345], dtype=np.uint64)).tolist() == [-1]
    assert rm.of_payloads([]).tolist() == []


def test_the_check_counts_duplicates_apart_from_failures(toy):
    """`check.compare` on hand-made landings: what the topology says is
    due lands, so nothing fails; one valid landing taken away is one
    `failed`; a row that landed once more than due is not due."""
    pool = toy.build(4, 600, ACCOUNTS, {})
    pool.bad = toy.corrupt(pool, 50, 4)
    offered = check.offered_rows(toy.order(pool, 4, {}), 0, 900)
    assert len(offered) == 900 and offered[700] == offered[700 - 660]
    valid = pool.valid
    offers = np.bincount(offered, minlength=pool.n)
    due = {"landings": np.where(valid, np.minimum(offers, 1), 0),
           "verify_fail": int(offers[~valid].sum()),
           "duplicates": int((offers - 1)[valid & (offers > 0)].sum())}
    kw = dict(pool=pool, offered=offered, due=due, unknown=0,
              verify_fail=due["verify_fail"], dedup=due["duplicates"],
              dropped=0, drained=True, window=(100, 900), seed=1)
    res = check.compare(landed=due["landings"].copy(), **kw)
    assert all(v == 0 for v, _ in res["numbers"].values())
    assert res["failed"] == 0 and res["duplicates_offered"] > 0
    gone = due["landings"].copy()
    row = offered[500]
    assert valid[row]
    gone[row] -= 1
    res = check.compare(landed=gone, **kw)
    assert res["failed"] == 1
    assert res["numbers"]["missing_and_uncounted"][0] == 1
    more = due["landings"].copy()
    more[row] += 1
    res = check.compare(landed=more, **kw)
    assert res["numbers"]["landed_but_not_due"][0] == 1
    res = check.compare(landed=due["landings"].copy(),
                        **dict(kw, dedup=due["duplicates"] - 1))
    assert res["numbers"]["duplicates_offered_minus_dedup_counted"][0] == 1


# -- the proof: a cell laid as files alone ----------------------------------

def _digests(bench: str) -> dict:
    out = {}
    for base, dirs, files in os.walk(bench):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, bench)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """BENCHMARK.json + benchmarks/ copied, and the toy cell laid into
    the copy: three files and two `workloads` entries."""
    tmp = tmp_path_factory.mktemp("seam")
    shutil.copytree(BENCH, tmp / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    laid = []
    for sub, name in (("shapes", "toy.py"), ("arrivals", "onoff.py"),
                      ("traffic", "toy-burst.json")):
        shutil.copy(os.path.join(SEAM, sub, name), tmp / "benchmarks" / sub)
        laid.append(os.path.join(sub, name))
    with open(os.path.join(SEAM, "traffic", "toy-burst.json")) as f:
        corrupt = dict(json.load(f), corrupt_one_in=16)
    with open(tmp / "benchmarks" / "traffic" / "toy-corrupt.json", "w") as f:
        json.dump(corrupt, f)
    laid.append(os.path.join("traffic", "toy-corrupt.json"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    before = json.dumps(man, sort_keys=True)
    for traffic in ("toy-burst", "toy-corrupt"):
        for cell, (config, _) in CELLS.items():
            man["workloads"].append({
                "name": cell + traffic[3:], "config": config,
                "traffic": traffic, "chips": 1, "why": "the seam's proof"})
    added = man["workloads"][-4:]
    del man["workloads"][-4:]
    assert json.dumps(man, sort_keys=True) == before   # entries only
    man["workloads"] += added
    with open(tmp / "BENCHMARK.json", "w") as f:
        json.dump(man, f)
    return tmp, laid


def _run(tmp, cell, override):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         str(2**31 + 30), "--seconds", "1", "--trace", "0", "--cpu",
         "--control", "allpass", "--set", override],
        cwd=tmp, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()]
    return lines[-1], next(ln for ln in lines if "check" in ln)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cell_laid_as_files_runs_and_its_duplicates_are_counted(
        checkout, cell):
    tmp, laid = checkout
    out, chk = _run(tmp, cell + "-burst", CELLS[cell][1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 1000
    assert chk["duplicates_offered"] == chk["dedup_counted"] > 0
    assert all(v["value"] == 0 for v in chk["check"].values())
    # nothing the copy brought was altered, and nothing else was needed
    got = _digests(str(tmp / "benchmarks"))
    assert {k: v for k, v in got.items() if k not in laid} == _digests(BENCH)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_its_corrupted_rows_land_under_the_all_pass_mask(checkout, cell):
    tmp, _ = checkout
    out, chk = _run(tmp, cell + "-corrupt", CELLS[cell][1])
    assert out["correct"] is False
    assert chk["corrupted_rows_landed"] == chk["corrupted_rows_offered"] > 0
    assert chk["check"]["landed_but_not_due"]["value"] \
        == chk["corrupted_landed"] > 0
    for k in ("landed_bytes_matching_nothing_offered", "missing_and_uncounted",
              "pool_exhausted", "compiles_in_window",
              "native_lanes_not_armed"):
        assert chk["check"][k]["value"] == 0, k
