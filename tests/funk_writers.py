"""A writer process of tests/test_funk_multiwriter.py (not a test):

    python funk_writers.py <what> <shm name> <writer index> <writers> <n>

attaches to the native funk segment the test made (NativeFunk.attach:
one more writer) and does its part of case <what>.  The last line of
its stdout is one JSON object."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from firedancer_tpu.funk.funk_native import FunkLockError, NativeFunk  # noqa: E402

FORK = b"fork:multiwriter"


def value(counter: int) -> bytes:
    """A value that says whether it is whole: its counter, then
    counter % 251 + 1 bytes that all repeat the counter's low byte."""
    return counter.to_bytes(8, "little") \
        + bytes([counter & 0xFF]) * (counter % 251 + 1)


def whole(val: bytes) -> bool:
    c = int.from_bytes(val[:8], "little")
    return val == value(c)


def grown(idx: int, i: int, step: int) -> bytes:
    """Writer idx's value of key i at `step`: 24 << step bytes, so every
    step outgrows the block the last one was given."""
    return bytes([(idx * 31 + i + step) & 0xFF]) * (24 << step)


def disjoint(fk, idx, writers, n):
    """n keys of this writer's own, each written `rounds` times."""
    for r in range(4):
        for i in range(n):
            fk.rec_insert(FORK, b"w%d:%d" % (idx, i), value(r * n + i))
    return {"wrote": 4 * n}


def ring(fk, idx, writers, n):
    """Every key goes round the writers: writer w takes a key when its
    counter % writers == w, and leaves the counter + 1, until n."""
    keys = [b"ring:%d" % k for k in range(8)]
    took = 0
    t_end = time.monotonic() + 60
    left = set(keys)
    while left and time.monotonic() < t_end:
        for key in list(left):
            c = int.from_bytes(fk.rec_query(FORK, key)[:8], "little")
            if c >= n:
                left.discard(key)
            elif c % writers == idx:
                fk.rec_insert(FORK, key, value(c + 1))
                took += 1
    return {"took": took, "left": len(left)}


def grow(fk, idx, writers, n):
    """Even writers grow their keys' values past each block's capacity,
    step after step; odd ones insert fresh keys meanwhile."""
    for step in range(8):
        for i in range(n):
            if idx % 2 == 0:
                fk.rec_insert(FORK, b"g%d:%d" % (idx, i), grown(idx, i, step))
            else:
                fk.rec_insert(FORK, b"f%d:%d:%d" % (idx, step, i),
                              grown(idx, i, 0))
    return {"steps": 8}


def die(fk, idx, writers, n):
    """Takes the lock, says so, and waits to be killed inside it."""
    fk.lock()
    print(json.dumps({"locked": True, "writer": fk.writer_id,
                      "pid": os.getpid()}), flush=True)
    time.sleep(600)


def survive(fk, idx, writers, n):
    """Attached before the victim died; writes once told to (a line on
    stdin), and reports how that ended and how long it took."""
    print(json.dumps({"attached": True}), flush=True)
    sys.stdin.readline()
    t0 = time.monotonic()
    try:
        fk.rec_insert(FORK, b"after", b"x")
        return {"raised": None, "s": time.monotonic() - t0}
    except FunkLockError as e:
        return {"raised": str(e), "writer": e.writer, "pid": e.pid,
                "s": time.monotonic() - t0}


if __name__ == "__main__":
    what, shm_name = sys.argv[1], sys.argv[2]
    idx, writers, n = (int(a) for a in sys.argv[3:6])
    fk = NativeFunk.attach(shm_name, timeout_s=30)
    out = {"disjoint": disjoint, "ring": ring, "grow": grow, "die": die,
           "survive": survive}[what](fk, idx, writers, n)
    out["writer_id"] = fk.writer_id
    out["lock"] = fk.lock_stats()
    print(json.dumps(out), flush=True)
    fk.close()
