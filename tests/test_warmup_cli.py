"""`python -m firedancer_tpu warmup` compiles what a deployment
dispatches (ISSUE 44): the verify stage's one program at the stage's
shape, dtype and placement, through the call the stage's own warmup()
makes — so a stage of the same (batch, max_msg_len, devices) then
dispatches with no new compiled entry — and through the persistent
compile cache, so a second process loads what the first wrote.

conftest's toy arithmetic stands in for the program's (minutes of
compile on a CPU); everything around it is the real thing.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from firedancer_tpu.__main__ import main
from firedancer_tpu.runtime import verify_native as vn
from firedancer_tpu.runtime.benchg import gen_transfer_pool
from firedancer_tpu.runtime.verify import VerifyStage
from firedancer_tpu.tango import shm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH, MAX_MSG = 16, 256
KEYS = {"program", "devices", "platform", "batch", "max_msg_len",
        "compile_s", "cache_dir"}


def _warmup(capsys, *extra) -> tuple[int, dict]:
    rc = main(["warmup", "--cpu", "--batch", str(BATCH),
               "--max-msg-len", str(MAX_MSG), *extra])
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1
    return rc, json.loads(lines[0])


@pytest.mark.parametrize("devices", [1, 2, 4])
def test_warmup_compiles_the_program_the_stage_then_dispatches(
        devices, exchange, capsys):
    from firedancer_tpu.ops import sigverify as sv

    prog = sv.ed25519_verify_batch_fused
    rc, said = _warmup(capsys, "--devices", str(devices))
    assert rc == 0 and set(said) == KEYS
    assert (said["program"], said["devices"], said["batch"],
            said["max_msg_len"], said["platform"]) \
        == ("ed25519_verify_batch_fused", devices, BATCH, MAX_MSG, "cpu")
    # exactly one program, given the stage's one argument: the packed
    # rows of a whole batch, placed as `devices` places them
    assert exchange.programs == 1 and len(exchange.h2d) == 1
    rows = exchange.h2d[0]
    assert (rows.shape, str(rows.dtype)) \
        == ((BATCH, vn.row_width(MAX_MSG)), "uint8")
    assert len(rows.sharding.device_set) == devices
    assert prog._cache_size() == 1
    # a stage of that geometry: its first batch enters the same entry
    pool = gen_transfer_pool(5, n_payers=4, n_dests=8)
    uid = shm.fresh_uid()
    lin = shm.ShmLink.create(f"twc_i_{uid}", depth=64, mtu=1232, n_fseq=1)
    lout = shm.ShmLink.create(f"twc_o_{uid}", depth=64, mtu=4096, n_fseq=1)
    st = None
    try:
        st = VerifyStage("v0", ins=[shm.make_consumer(lin, lazy=8)],
                         outs=[shm.make_producer(lout)], batch=BATCH,
                         max_msg_len=MAX_MSG, batch_deadline_s=0.001,
                         devices=devices)
        prod = shm.make_producer(lin)
        for i, t in enumerate(pool):
            assert prod.try_publish(t, sig=i, tsorig=0)
        for _ in range(50):
            st.run_once()
        st.flush()
        assert st.metrics.get("batches") == 1
        assert st.metrics.get("batch_elems") == len(pool)
    finally:
        if st is not None:
            st.ins, st.outs = [], []
            st.drop_native_views()
        lin.close()
        lout.close()
    assert exchange.programs == 2
    assert prog._cache_size() == 1       # no new compiled entry
    assert exchange.h2d[1].sharding == rows.sharding


def test_a_bar_below_the_measured_time_exits_2(toy_verify_ok, capsys):
    rc, said = _warmup(capsys, "--devices", "2", "--assert-warm", "0")
    assert said["compile_s"] >= 0 and rc == 2
    rc, _said = _warmup(capsys, "--devices", "2", "--assert-warm", "600")
    assert rc == 0


def test_a_geometry_that_does_not_divide_is_refused(capsys):
    assert main(["warmup", "--cpu", "--devices", "3", "--batch",
                 str(BATCH)]) == 1
    out = capsys.readouterr()
    assert "does not divide" in out.err and not out.out.strip()


_CHILD = """
import sys
sys.path.insert(0, {tests!r})
import conftest
from firedancer_tpu.ops import sigverify as sv
sv._verify_ok = conftest.toy_verify_core
from firedancer_tpu.__main__ import main
sys.exit(main(sys.argv[1:]))
"""


def test_a_second_process_loads_what_the_first_compiled(tmp_path):
    """Two fresh processes over one cache directory: the first writes
    the program's entry, the second passes --assert-warm and writes
    none (the toy compiles in under JAX's one-second floor for the
    persistent cache, so the floor is taken away for the children)."""
    cache = tmp_path / "cache"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    argv = [sys.executable, "-c",
            _CHILD.format(tests=os.path.dirname(os.path.abspath(__file__))),
            "warmup", "--cpu", "--devices", "2", "--batch", str(BATCH),
            "--max-msg-len", str(MAX_MSG)]

    def entries() -> set:
        return {f for f in os.listdir(cache)
                if f.startswith("jit_ed25519_verify_batch_fused")}

    cold = subprocess.run(argv, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=300)
    assert cold.returncode == 0, cold.stderr[-2000:]
    wrote = entries()
    assert len(wrote) == 1
    assert json.loads(cold.stdout.splitlines()[-1])["cache_dir"] == str(cache)
    warm = subprocess.run(argv + ["--assert-warm", "120"], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)
    assert warm.returncode == 0, warm.stderr[-2000:]
    assert entries() == wrote
    said = json.loads(warm.stdout.splitlines()[-1])
    assert said["program"] == "ed25519_verify_batch_fused"
    assert said["compile_s"] >= 0
