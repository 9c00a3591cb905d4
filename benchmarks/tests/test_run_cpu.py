"""The rest of a run with the timed path broken underneath: the leader
cell on the CPU at batch 16 with the verify stage's all-pass mask.  The
harness's look for a chip is skipped (--cpu); everything else is the
run the chip makes.  Corrupted transactions land, so `correct` is false;
and a rehearsal prints no metric."""

import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _run(*argv, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *argv], cwd=ROOT,
        env=e, capture_output=True, text=True, timeout=300)


def test_allpass_mask_reads_incorrect_on_the_leader_cell():
    p = _run("--workload", "leader-transfer-flood", "--seed",
             str(2**31 + 77), "--seconds", "1", "--trace", "0", "--cpu",
             "--control", "allpass", "--set", "program_config.verify.batch=16")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()]
    out = lines[-1]
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] is False and out["attempted"] > 1000
    assert out["metrics"] == {} and out["device"]["platform"] == "cpu"
    chk = next(ln for ln in lines if "check" in ln)
    assert chk["corrupted_landed"] == chk["corrupted_offered"] > 0
    assert chk["check"]["landed_but_not_due"]["value"] \
        == chk["corrupted_landed"]
    # everything else the run checks still held
    for k in ("landed_bytes_matching_nothing_offered", "missing_and_uncounted",
              "fec_sets_not_stored", "tap_txn_minus_bank_txn_exec",
              "pool_exhausted", "compiles_in_window"):
        assert chk["check"][k]["value"] == 0, k
    assert chk["slots_sealed"] >= 2


def test_no_chip_means_no_stdout_and_nonzero_exit():
    p = _run("--workload", "verify-spam-flood", "--seed", "1", "--seconds",
             "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_set_is_refused_outside_a_rehearsal():
    p = _run("--workload", "verify-spam-flood", "--seed", "1", "--seconds",
             "1", "--trace", "0", "--set", "verify.batch=16")
    assert p.returncode != 0 and p.stdout == ""


def test_bare_directory_prints_nothing_and_fails(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's own files
    has no program to measure: no result, exit code not 0."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "verify-spam-flood", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
