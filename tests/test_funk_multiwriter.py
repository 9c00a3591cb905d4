"""The native funk segment with more than one writer (native/fd_funk.cpp
"Concurrency"; NativeFunk.attach): W writer PROCESSES over one segment,
held to the plain model the store ports (funk/funk.py's `Funk`, one
process, dicts) — disjoint keys at rate; keys handed from writer to
writer (each increments what the last left); values grown past their
block's capacity while another writer allocates; a reader attached read
only throughout, which never sees a torn record; a writer SIGKILLed
inside the lock, which the others name instead of hanging.

The writers are tests/funk_writers.py, a process each.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from firedancer_tpu.funk import funk_native
from firedancer_tpu.funk.funk import Funk
from firedancer_tpu.funk.funk_native import FunkLockError, NativeFunk

import funk_writers as fw

pytestmark = pytest.mark.skipif(
    not funk_native.available(), reason="native/fd_funk.so is not available")

HERE = os.path.dirname(os.path.abspath(__file__))
FORK = fw.FORK


def _name() -> str:
    return f"fdtpu_funk_mw_{os.getpid()}_{time.monotonic_ns() % 10**9}"


def _spawn(what: str, shm_name: str, idx: int, writers: int, n: int,
           **kw) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, os.path.join(HERE, "funk_writers.py"), what,
         shm_name, str(idx), str(writers), str(n)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, **kw)


def _last(p: subprocess.Popen, timeout: float = 120) -> dict:
    out, err = p.communicate(timeout=timeout)
    assert p.returncode == 0, err[-2000:]
    return json.loads(out.splitlines()[-1])


@pytest.fixture
def store():
    """A store with one fork, as a bank tile makes it (the creator is
    writer 1 and says when the store is whole)."""
    fk = NativeFunk(shm_name=_name(), max_sz=1 << 26)
    name = fk.shm_name
    fk.txn_prepare(None, FORK)
    fk.set_ready()
    yield fk
    fk.close()          # the creator's close unlinks the segment
    assert not os.path.exists(os.path.join("/dev/shm", name))


def _run_writers(store, what: str, writers: int, n: int, reader=None):
    """Start `writers` processes on case `what`, let `reader` look at
    the store while they run -> each writer's last line."""
    procs = [_spawn(what, store.shm_name, i, writers, n)
             for i in range(writers)]
    while reader is not None and any(p.poll() is None for p in procs):
        reader()
    outs = [_last(p) for p in procs]
    # the creator is writer 1; the others took 2.. in order of attach
    assert sorted(o["writer_id"] for o in outs) \
        == list(range(2, writers + 2))
    assert store.writers() == writers + 1
    return outs


def _same_as_model(store, model: Funk) -> None:
    keys = sorted(model.rec_keys(FORK))
    assert sorted(store.rec_keys(FORK)) == keys
    for k in keys:
        assert store.rec_query(FORK, k) == model.rec_query(FORK, k), k


@pytest.mark.parametrize("writers", [2, 4])
def test_disjoint_keys_at_rate_equal_the_plain_model(store, writers):
    n = 400
    outs = _run_writers(store, "disjoint", writers, n)
    model = Funk()
    model.txn_prepare(None, FORK)
    for idx in range(writers):
        for r in range(4):
            for i in range(n):
                model.rec_insert(FORK, b"w%d:%d" % (idx, i),
                                 fw.value(r * n + i))
    _same_as_model(store, model)
    # every write took the lock, and the writers did meet in it
    assert all(o["lock"]["acquires"] >= 4 * n for o in outs)


@pytest.mark.parametrize("writers", [2, 4])
def test_keys_handed_round_the_writers_count_every_hand_over(store, writers):
    """Writer w increments a key when its counter % writers == w: what
    each reads is what the last one, another process, left.  The final
    value is the number of hand-overs."""
    n = 40 * writers
    for k in range(8):
        store.rec_insert(FORK, b"ring:%d" % k, fw.value(0))
    outs = _run_writers(store, "ring", writers, n)
    assert all(o["left"] == 0 for o in outs)
    # every writer took its turn at every key, n / writers times
    assert [o["took"] for o in outs] == [8 * n // writers] * writers
    model = Funk()
    model.txn_prepare(None, FORK)
    for k in range(8):
        model.rec_insert(FORK, b"ring:%d" % k, fw.value(n))
    _same_as_model(store, model)


@pytest.mark.parametrize("writers", [2, 4])
def test_values_outgrow_their_blocks_while_others_allocate_and_a_reader_looks(
        store, writers):
    """The allocator and the freelists under several writers: a value
    that outgrows its block frees it and takes a new one, while another
    writer's fresh keys take blocks (the freed ones too).  A reader
    attached read only all the while sees whole values only: every
    byte of a value is the byte its first one is."""
    n = 48
    ro = []
    seen = [0, 0]

    def reader():
        if not ro:
            ro.append(NativeFunk.attach_readonly(store.shm_name))
        for idx in range(0, writers, 2):
            for i in range(n):
                v = ro[0].rec_query(FORK, b"g%d:%d" % (idx, i))
                seen[0] += 1
                if v is not None:
                    seen[1] += 1
                    assert v == v[:1] * len(v) and len(v) in [
                        24 << s for s in range(8)], (idx, i, len(v))

    try:
        _run_writers(store, "grow", writers, n, reader=reader)
        reader()
    finally:
        for r in ro:
            r.close()
    assert seen[1] > 0
    model = Funk()
    model.txn_prepare(None, FORK)
    for idx in range(writers):
        for step in range(8):
            for i in range(n):
                if idx % 2 == 0:
                    model.rec_insert(FORK, b"g%d:%d" % (idx, i),
                                     fw.grown(idx, i, step))
                else:
                    model.rec_insert(FORK, b"f%d:%d:%d" % (idx, step, i),
                                     fw.grown(idx, i, 0))
    _same_as_model(store, model)


def test_a_reader_never_sees_a_torn_record_while_four_writers_write(store):
    """Self-checking values (their length and every byte follow from
    their counter) rewritten by four processes; the read-only handle
    reads under the seqlock and every value it gets is whole."""
    n = 300
    ro = NativeFunk.attach_readonly(store.shm_name)
    reads = [0]

    def reader():
        for idx in range(4):
            for i in range(0, n, 7):
                v = ro.rec_query(FORK, b"w%d:%d" % (idx, i))
                if v is not None:
                    reads[0] += 1
                    assert fw.whole(v), (idx, i, v[:16])

    try:
        _run_writers(store, "disjoint", 4, n, reader=reader)
    finally:
        ro.close()
    assert reads[0] > 0
    with pytest.raises(RuntimeError):
        NativeFunk.attach_readonly(store.shm_name).rec_insert(
            FORK, b"k", b"v")


def test_a_writer_killed_inside_the_lock_is_named_and_nobody_hangs(store):
    """SIGKILL while holding the lock: the next writers' calls fail
    with FunkLockError naming the dead holder (its writer id and pid)
    at once — the kernel reports a robust mutex's dead owner —, here
    and in a process that attached before the death."""
    survivor = _spawn("survive", store.shm_name, 0, 2, 0,
                      stdin=subprocess.PIPE)
    assert json.loads(survivor.stdout.readline())["attached"]
    victim = _spawn("die", store.shm_name, 1, 2, 0)
    said = json.loads(victim.stdout.readline())
    assert said["locked"] and said["pid"] == victim.pid
    os.kill(victim.pid, signal.SIGKILL)
    victim.wait(10)
    t0 = time.monotonic()
    with pytest.raises(FunkLockError) as e:
        store.rec_insert(FORK, b"after", b"x")
    assert time.monotonic() - t0 < 2.0
    assert (e.value.writer, e.value.pid) == (said["writer"], victim.pid)
    assert f"pid {victim.pid}" in str(e.value)
    survivor.stdin.write("go\n")
    survivor.stdin.flush()
    out = _last(survivor, 30)
    assert out["raised"] and out["pid"] == victim.pid and out["s"] < 2.0
    assert out["writer"] == said["writer"]


def test_one_writer_takes_the_lock_and_never_waits(store):
    """The store a single bank tile owns: the lock is taken (a hold a
    call, or one around a group) and never contended; lock() nests."""
    before = store.lock_stats()
    store.lock()
    store.lock()
    for i in range(10):
        store.rec_insert(FORK, b"k%d" % i, b"v")
    store.unlock()
    store.unlock()
    store.rec_insert(FORK, b"k", b"v")
    st = store.lock_stats()
    assert st["acquires"] - before["acquires"] == 2
    assert st["contended"] == st["wait_ns"] == st["long_waits"] == 0
    assert store.writers() == 1 and store.writer_id == 1


def test_attach_waits_for_the_creator_to_say_the_store_is_whole():
    fk = NativeFunk(shm_name=_name(), max_sz=1 << 22)
    try:
        with pytest.raises(funk_native.NativeUnavailable,
                           match="no ready store"):
            NativeFunk.attach(fk.shm_name, timeout_s=0.2)
        fk.set_ready()
        w = NativeFunk.attach(fk.shm_name, timeout_s=5)
        assert (w.writer_id, fk.writers()) == (2, 2)
        w.rec_insert(None, b"a", b"1")
        assert fk.rec_query(None, b"a") == b"1"
        w.close()       # an attached handle never unlinks the segment
        assert os.path.exists(os.path.join("/dev/shm", fk.shm_name))
    finally:
        fk.close()


def test_attach_refuses_a_segment_of_another_layout():
    """The header carries the writers' lock since layout 2: a handle
    attaches only to the layout it was built for."""
    fk = NativeFunk(shm_name=_name(), max_sz=1 << 22)
    try:
        fk.set_ready()
        with open(os.path.join("/dev/shm", fk.shm_name), "r+b") as f:
            f.seek(8)               # ffk_hdr: u64 magic | u32 version
            assert f.read(4) == (2).to_bytes(4, "little")
            f.seek(8)
            f.write((1).to_bytes(4, "little"))
        with pytest.raises(funk_native.NativeUnavailable):
            NativeFunk.attach_readonly(fk.shm_name)
        with pytest.raises(funk_native.NativeUnavailable):
            NativeFunk.attach(fk.shm_name, timeout_s=0.1)
    finally:
        fk.close()
