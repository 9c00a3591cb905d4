"""Standalone verify-stage HOST path measurement (VERDICT r3 weak #5):
how many elements/s can the stage assemble into device batches and
drain, independent of any accelerator (precomputed_ok short-circuits
the dispatch)?  Run: python scripts/perf_verify_host.py [n_txns]
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from firedancer_tpu.runtime.benchg import gen_transfer_pool  # noqa: E402
from firedancer_tpu.runtime.verify import VerifyStage  # noqa: E402
from firedancer_tpu.tango import shm  # noqa: E402


def bench_assembly(n=50_000, batch=512, max_msg_len=256):
    """Just the batch-assembly math: elems -> device-shaped arrays."""
    pool = gen_transfer_pool(64, seed=b"hostperf")
    elems = []
    from firedancer_tpu.protocol import txn as ft

    for i in range(n):
        p = pool[i % 64]
        t = ft.txn_parse(p)
        elems.append((t.message(p), t.signatures(p)[0],
                      list(t.signers(p))[0]))
    stage = VerifyStage("v", batch=batch, max_msg_len=max_msg_len,
                        precomputed_ok=False)

    class _A:
        pass

    t0 = time.perf_counter()
    done = 0
    while done < n:
        acc = _A()
        acc.elems = elems[done : done + batch]
        acc.slots = []
        arrays = stage._assemble(acc)
        done += len(acc.elems)
    dt = time.perf_counter() - t0
    print(f"assembly: {n} elems in {dt:.3f}s = {n/dt:,.0f} elems/s "
          f"(batch {batch})")
    return n / dt


def bench_stage_loop(n=20_000, batch=512):
    """Whole stage: frag in -> parse -> dedup -> batch -> emit, with a
    precomputed all-pass mask (no device round trips)."""
    uid = f"{os.getpid()}_{int(time.monotonic_ns() % 1_000_000)}"
    nv = shm.ShmLink.create(f"fdtpu_hpv_{uid}", depth=4096, mtu=1232)
    vo = shm.ShmLink.create(f"fdtpu_hpo_{uid}", depth=4096, mtu=4096)
    try:
        stage = VerifyStage(
            "v", ins=[shm.Consumer(nv, lazy=64)],
            outs=[shm.Producer(vo)], batch=batch, max_msg_len=256,
            precomputed_ok=True, batch_deadline_s=0.005,
        )
        sink = shm.Consumer(vo, lazy=64)
        prod = shm.Producer(nv)
        pool = gen_transfer_pool(256, seed=b"hostloop")
        sent = got = 0
        t0 = time.perf_counter()
        while got < n:
            while sent < n and prod.try_publish(pool[sent % 256]):
                sent += 1
            stage.run_once()
            while isinstance(sink.poll(), tuple):
                got += 1
        stage.flush()
        while got < n and isinstance(sink.poll(), tuple):
            got += 1
        dt = time.perf_counter() - t0
        print(f"stage loop: {got} txns in {dt:.3f}s = {got/dt:,.0f} txn/s "
              f"(host only, batch {batch})")
        return got / dt
    finally:
        for l in (nv, vo):
            l.close()
            l.unlink()


if __name__ == "__main__":
    # neither bench touches a device (precomputed mask): pin the CPU
    # backend so a host-only measurement never takes the chip
    from firedancer_tpu.utils.platform import force_cpu_backend

    force_cpu_backend(device_count=1)
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 50_000
    bench_assembly(n)
    bench_stage_loop(min(n, 50_000))
