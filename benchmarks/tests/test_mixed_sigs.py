"""The `mixed-sigs` shape, its files and the cell made of them: rows
that the program and the reference parse alike, the distribution of
signature counts the traffic file promises, pure functions of the seed,
the corrupted bit in every position, the eight new readers, and
rehearsals of the cell with and without corrupted rows."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from harness import mixed_readers as mx
from harness import reference
from harness.manifest import Manifest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
MAN = Manifest()
CELL = MAN.cell("verify-mixed-sigs")
CONFIG = MAN.config(CELL)
TRAFFIC = MAN.traffic(CELL)
ACCOUNTS = CONFIG["traffic_accounts"]
SHAPE = MAN.shape(TRAFFIC)
SEED = 2**31 + 33
N = 3000


@pytest.fixture(scope="module")
def pool():
    return SHAPE.build(SEED, N, ACCOUNTS, TRAFFIC)


def test_the_files_carry_the_issues_parameters():
    assert TRAFFIC["kind"] == "flood" and TRAFFIC["shape"] == "mixed-sigs"
    assert TRAFFIC["corrupt_one_in"] == 128 and TRAFFIC["warmup_s"] == 2.0
    assert TRAFFIC["pool_txn_per_s"] % 5000 == 0
    assert ACCOUNTS == {"n_payers": 4096, "n_dests": 1024}
    v, d = CONFIG["verify"], CONFIG["dedup"]
    assert (v["batch"], v["max_msg_len"], v["batch_deadline_ms"]) \
        == (1024, 384, 2.0)
    assert v["receive_buffer_depth"] == v["out_depth"] == d["out_depth"] \
        == 1024 and v["out_mtu"] == d["out_mtu"] == 4096
    assert (v["tcache_depth"], d["tcache_depth"]) == (16, 65536)
    assert CONFIG["topology"] == "verify_dedup" and CELL["chips"] == 1
    assert CELL["traffic"] == "mixed-sigs-flood"
    entry = next(c for c in MAN.data["configs"]
                 if c["name"] == "verify-dedup-v5e")
    assert entry["source"] == CONFIG["source"] \
        and "configs[2]" in entry["source"]
    assert len(CONFIG["guarantees"]) == 5 and len(CONFIG["assumed"]) >= 6 \
        and len(CONFIG["reduced"]) == 3
    # the program's depths are the stated ones, and the mainnet mix
    # repeats at the same share and distances
    from firedancer_tpu.runtime.dedup import DEDUP_TCACHE_DEPTH
    from firedancer_tpu.runtime.verify import VERIFY_TCACHE_DEPTH

    assert (VERIFY_TCACHE_DEPTH, DEDUP_TCACHE_DEPTH) == (16, 65536)
    mix = MAN.shape({"shape": "mainnet-mix"})
    assert (SHAPE.REPEAT_SHARE, SHAPE.NEAR, SHAPE.FAR) \
        == (mix.REPEAT_SHARE, mix.NEAR, mix.FAR) \
        == (0.10, (1, 8), (1024, 32768))


def test_signature_counts_follow_one_over_k():
    pl = SHAPE.plan(SEED, ACCOUNTS, 0, 50_000)
    k = pl["k"]
    share = np.bincount(k, minlength=9)[1:] / len(k)
    want = np.array([36.8, 18.4, 12.3, 9.2, 7.4, 6.1, 5.3, 4.6]) / 100
    assert np.abs(share - want).max() < 0.007
    assert abs(k.mean() - 2.94) < 0.03
    # each count carries an equal eighth of the signatures
    lanes = np.bincount(k, weights=k, minlength=9)[1:] / k.sum()
    assert np.abs(lanes - 0.125).max() < 0.006
    # the signers of a row are distinct, the payer among all keys
    who = pl["signers"]
    for kk in range(2, 9):
        rows = who[k == kk][:, :kk]
        assert (np.sort(rows, axis=1)[:, 1:]
                != np.sort(rows, axis=1)[:, :-1]).all()
    assert who.min() == 0 and who.max() == ACCOUNTS["n_payers"] - 1
    assert np.bincount(who[:, 0], minlength=4096).max() < 40
    assert pl["dest"].min() == 0 and pl["dest"].max() == 1023


def test_rows_parse_as_the_program_and_the_reference_parse_them(pool):
    from firedancer_tpu.protocol import txn as ft

    assert pool.classes == ("transfer",) and pool.n == N
    assert (pool.len == 119 + 96 * pool.sigs).all()
    assert set(pool.sigs.tolist()) == set(range(1, 9))
    assert int(pool.len.max()) == 887 and int(pool.len.min()) == 215
    assert len({pool.row(i) for i in range(N)}) == N          # all distinct
    for i in range(0, N, 11):
        row = pool.row(i)
        sigs, pks, msg = reference.split(row)
        k = int(pool.sigs[i])
        assert len(msg) == 118 + 32 * k <= CONFIG["verify"]["max_msg_len"]
        desc = ft.txn_parse(row)
        assert desc is not None and desc.signature_cnt == len(sigs) == k \
            == len(pks) == len(set(pks))
        assert desc.acct_addrs(row)[:k] == pks
        assert desc.readonly_signed_cnt == k - 1
        assert desc.readonly_unsigned_cnt == 1
    assert all(reference.verdicts(pool, range(0, N, 5)).values())
    # one signer: byte for byte the transfer shape's message layout
    one = pool.row(int(np.flatnonzero(pool.sigs == 1)[0]))
    assert one[65:69] == b"\x01\x00\x01\x03" \
        and one[-18:-12] == b"\x01\x02\x02\x00\x01\x0c"


def test_a_row_range_equals_the_same_rows_of_the_whole_pool(pool):
    part = SHAPE.build(SEED, 10 * N, ACCOUNTS, TRAFFIC, 2040, 2110)
    assert [part.row(k) for k in range(70)] \
        == [pool.row(2040 + k) for k in range(70)]
    assert (part.sigs == pool.sigs[2040:2110]).all()
    # across the generator's block boundary too
    plan = SHAPE.plan(SEED, ACCOUNTS, 4090, 4102)
    whole = SHAPE.plan(SEED, ACCOUNTS, 0, 8192)
    assert (plan["k"] == whole["k"][4090:4102]).all()
    assert (plan["signers"] == whole["signers"][4090:4102]).all()
    other = SHAPE.build(SEED + 1, N, ACCOUNTS, TRAFFIC, 0, 8)
    assert [other.row(k) for k in range(8)] != [pool.row(k) for k in range(8)]


DIGESTS = {
    3: ("ce6790404c6b7f0f", "3e312474785880d6", "6c669a0c38b989e3"),
    2**31 + 12345: ("c05e4eb0f90f85a1", "03c50fa85f138ec9",
                    "2d987261ebda3793"),
}


@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_the_shape_is_a_pure_function_of_the_seed(seed):
    pool = SHAPE.build(seed, 512, ACCOUNTS, TRAFFIC)
    bad = SHAPE.corrupt(pool, 128, seed)
    order = SHAPE.order(pool, seed, TRAFFIC)
    got = tuple(hashlib.sha256(np.ascontiguousarray(a).tobytes())
                .hexdigest()[:16] for a in (pool.buf, bad, order))
    assert got == DIGESTS[seed]


def test_the_corrupted_bit_is_found_in_every_position():
    """One row in 128, one bit, in one of its k signatures chosen
    uniformly: over a pool's worth every position 0..7 is hit, and a
    row fails whole whichever it is."""
    sigs = np.tile(np.arange(1, 9), 128 * 40)

    class P:
        n = len(sigs)
        off = np.cumsum(119 + 96 * sigs) - (119 + 96 * sigs)
        buf = np.zeros(int((119 + 96 * sigs).sum()), dtype=np.uint8)

    P.sigs = sigs
    bad = SHAPE.corrupt(P, 128, SEED)
    assert len(bad) == P.n // 128 and (np.diff(bad // 128) == 1).all()
    at = np.flatnonzero(P.buf)
    assert len(at) == len(bad)                                # one bit a row
    which = (at - P.off[bad] - 1) // 64
    assert ((0 <= which) & (which < sigs[bad])).all()
    assert set(which.tolist()) == set(range(8))
    # uniform over a row's own signatures: the last of eight as often
    # as the first
    eight = which[sigs[bad] == 8]
    assert len(eight) > 20 and (eight == 7).any() and (eight == 0).any()
    pool = SHAPE.build(SEED, 1280, ACCOUNTS, TRAFFIC)
    bad = SHAPE.corrupt(pool, 128, SEED)
    got = reference.verdicts(pool, range(1280))
    assert {i for i, ok in got.items() if not ok} == set(bad.tolist())


def test_order_repeats_a_tenth_at_the_two_distances():
    class P:
        n = 400_000
    order = SHAPE.order(P, SEED, TRAFFIC)
    assert (SHAPE.order(P, SEED, TRAFFIC) == order).all()
    n_rep = len(order) - P.n
    assert abs(n_rep / len(order) - 0.10) < 0.001
    assert (np.bincount(order, minlength=P.n) >= 1).all()     # none skipped
    first_at = np.full(P.n, -1)
    pos = np.arange(len(order))
    first_at[order[::-1]] = pos[::-1]
    assert (np.diff(first_at) > 0).all()      # first offers in pool order
    rep = np.flatnonzero(first_at[order] != pos)
    gap = rep - first_at[order[rep]]
    settled = rep > SHAPE.FAR[1] + 16
    near = gap <= 16
    assert abs(near[settled].mean() - 0.5) < 0.01
    # near: inside verify's 16-deep tag cache; far: past it, inside
    # dedup's 65,536
    assert gap[near & settled].min() >= 1 and gap[near & settled].max() <= 12
    far = gap[~near & settled]
    assert far.min() >= 1024 and far.max() <= 32768 + 8 < 65536


def _run(**kw):
    base = {"counters": {}, "offered": 1000, "served": 800,
            "timers_s": None}
    base.update(kw)
    return base


def test_mixed_readers_arithmetic():
    run = _run(
        counters={
            "verify0": {"batches": 10, "batch_elems": 10_000,
                        "batch_fit_pad_lanes": 17, "verify_fail_elems": 80,
                        "txn_in": 3400, "elems_in": 9996, "dedup_dup": 50,
                        "frag_wait_ns": 2_000_000 * 3400,
                        "frag_wait_n": 3400},
            "dedup": {"frags_in": 3300, "dedup_dup": 50,
                      "dedup_dup_sigs": 500,
                      "frag_wait_ns": 20_000_000 * 3300,
                      "frag_wait_n": 3300},
            "sink": {"frag_wait_ns": 21_500_000 * 3250,
                     "frag_wait_n": 3250}},
        timers_s={"gen": 1.0, "verify0": 4.0, "tap": 0.1, "dedup": 0.0132,
                  "sink": 1.0})
    assert mx.fit_pad_pct(run) == pytest.approx(0.17)
    assert mx.fail_lanes_pct(run) == pytest.approx(0.8)
    assert mx.late_dup_lanes_pct(run) == pytest.approx(5.0)
    assert mx.sigs_per_txn(run) == pytest.approx(2.94)
    assert mx.dup_pct(run) == pytest.approx(10.0)
    assert mx.dedup_us_per_txn(run) == pytest.approx(4.0)
    assert mx.in_verify_ms(run) == pytest.approx(18.0)
    assert mx.in_dedup_ms(run) == pytest.approx(1.5)
    # an untraced run has no stage timer
    assert mx.dedup_us_per_txn(dict(run, timers_s=None)) is None


NEW = {"verify.fit_pad_pct": (mx.fit_pad_pct, "program_counter"),
       "verify.sigs_per_txn": (mx.sigs_per_txn, "program_counter"),
       "verify.fail_lanes_pct": (mx.fail_lanes_pct, "program_counter"),
       "dedup.late_dup_lanes_pct": (mx.late_dup_lanes_pct,
                                    "program_counter"),
       "dedup.dup_pct": (mx.dup_pct, "program_counter"),
       "dedup.us_per_txn": (mx.dedup_us_per_txn, "host_clock"),
       "path.in_verify_ms": (mx.in_verify_ms, "program_counter"),
       "path.in_dedup_ms": (mx.in_dedup_ms, "program_counter")}


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_reader_entry_and_silence_where_counters_are_absent(name):
    entry = next(m for m in MAN.data["per_layer"]
                 if m["name"] == name + ".mixed")
    assert entry["workloads"] == ["verify-mixed-sigs"]
    assert entry["moves"] == "verify_per_s"
    assert entry["source"] == NEW[name][1]
    read = MAN.reader("per_layer", name + ".mixed")
    assert read.__module__ == "harness.mixed_readers" and read is NEW[name][0]
    # a program without the counters (the parent), a tile without a
    # dedup stage, an idle window: nothing, and no raise
    assert read(_run(counters={"verify0": {"batches": 3, "batch_elems": 9,
                                           "dedup_dup": 1},
                               "sink": {}})) is None
    assert read(_run(counters={"verify0": {}, "dedup": {}})) is None
    assert read({"counters": {}, "offered": 0, "served": 0}) is None


def test_the_cell_reports_21_mixed_metrics_over_files_that_exist():
    names = [m["name"] for m in MAN.metrics("per_layer", "verify-mixed-sigs")]
    assert len(names) == 21 and all(n.endswith(".mixed") for n in names)
    for n in names:
        assert callable(MAN.reader("per_layer", n))
    e2e = [m["name"] for m in MAN.metrics("end_to_end", "verify-mixed-sigs")]
    assert e2e == ["verify_per_s", "setup_s"]
    # the accepted cells report what they reported
    assert len(MAN.metrics("per_layer", "verify-spam-flood")) == 14
    assert len(MAN.metrics("per_layer", "leader-mainnet-mix")) == 25


def test_a_program_without_the_counters_is_refused_by_name(tmp_path):
    """The parent of this cell: its stages have no batch_fit_pad_lanes
    and no dedup_dup_sigs, so loading the topology fails at once with
    exit code 2 and nothing on stdout."""
    code = (
        "import sys\n"
        f"sys.path[:0] = [{BENCH!r}, {ROOT!r}]\n"
        "from firedancer_tpu.runtime.dedup import DedupStage\n"
        "from firedancer_tpu.utils import metrics as fm\n"
        "DedupStage.extra_schema = classmethod(lambda cls: "
        "fm.MetricsSchema().counter('dedup_dup'))\n"
        "from harness.manifest import Manifest\n"
        "Manifest().topology('verify_dedup')\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2 and p.stdout == ""
    assert "dedup_dup_sigs" in p.stderr and "verify_dedup" in p.stderr


# -- rehearsals of the cell ---------------------------------------------------

SETS = ["--set", "verify.batch=64", "--set", "dedup.tcache_depth=256"]


def _rehearse(cwd, cell, seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         str(seed), "--seconds", "1", "--trace", "0", "--cpu", "--control",
         "allpass"] + SETS, cwd=cwd, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()]
    return (lines[-1], next(ln for ln in lines if "check" in ln),
            next(ln for ln in lines if "window" in ln))


def test_allpass_mask_reads_incorrect_on_the_mixed_cell():
    """The run the check has to fail: every corrupted row lands."""
    out, chk, _win = _rehearse(ROOT, "verify-mixed-sigs", 2**31 + 133)
    assert out["correct"] is False and out["attempted"] > 1000
    assert out["metrics"] == {} and out["rehearsal"] is True
    assert chk["corrupted_rows_landed"] > 0
    assert chk["check"]["landed_but_not_due"]["value"] > 0
    for k in ("landed_bytes_matching_nothing_offered", "missing_and_uncounted",
              "lanes_minus_signatures_due", "sig_counts_never_landed",
              "fit_pad_lanes_over_7_a_full_batch", "tap_overrun",
              "msg_too_long", "pool_exhausted", "compiles_in_window",
              "native_lanes_not_armed"):
        assert chk["check"][k]["value"] == 0, k
    v = chk["verify"]
    assert v["batch_fit_pad_lanes"] > 0 and v["batch_close_full"] > 100
    assert v["elems_in"] == v["batch_elems"] > 2 * v["txn_in"]


def test_without_corrupted_rows_the_cell_is_correct_and_dedups_at_both(
        tmp_path):
    """The same cell with `corrupt_one_in` out of reach, laid as a
    traffic file and an entry into a copy: `correct` reads true, every
    check number is at its limit, and the repeats offered equal what
    verify's and dedup's tag caches counted."""
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    clean = dict(TRAFFIC, corrupt_one_in=10**9)
    with open(tmp_path / "benchmarks" / "traffic" / "mixed-sigs-clean.json",
              "w") as f:
        json.dump(clean, f)
    man = json.loads(json.dumps(MAN.data))
    man["workloads"].append(dict(CELL, name="verify-mixed-sigs-clean",
                                 traffic="mixed-sigs-clean"))
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(man, f)
    out, chk, win = _rehearse(tmp_path, "verify-mixed-sigs-clean",
                              2**31 + 233)
    assert out["correct"] is True and out["failed"] == 0
    assert all(v["value"] == 0 for v in chk["check"].values())
    assert chk["corrupted_offered"] == 0
    assert chk["duplicates_offered"] == chk["dedup_counted"] > 0
    assert win["window"]["dedup_dup"]["verify0"] > 0
    assert chk["dedup"]["dedup_dup"] > 0 and chk["dedup"]["dedup_dup_sigs"] \
        >= chk["dedup"]["dedup_dup"]
    assert win["window"]["served"] > win["window"]["offered"]   # signatures
